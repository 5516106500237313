"""Model adapter of the family `glm_dsa`: GLM-5.2 (`model_type`
`glm_moe_dsa`) through the program's latent-attention decoder with the
sparse-attention indexer (`ray_tpu/models/glm_dsa.py`). Served only:
the program has no loss for it, so the training names are left out
(`models/dense.py` says what an adapter holds).

The file keeps the published `mlp_layer_types` and `indexer_types`
whole; the layers this chip's share holds are the entries
`deployment.layers_held` of them. Of the `deployment.router_width`
experts the router chooses among, the program holds the range
`deployment.experts_held` (first, count), `n_routed_experts` of them.
"""

from __future__ import annotations

import copy
import dataclasses

import jax.numpy as jnp

from ray_tpu.models.glm_dsa import (GlmDsaConfig, forward_with_cache,
                                    init_cache, init_params)


def program_config(config):
    share = config["deployment"]
    kinds = tuple((config["mlp_layer_types"][i], config["indexer_types"][i])
                  for i in share["layers_held"])
    first, count = share["experts_held"]
    assert len(kinds) == config["num_hidden_layers"]
    assert count == config["n_routed_experts"]
    width = config["moe_intermediate_size"]
    return GlmDsaConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_layers=len(kinds), n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], hidden_dim=width,
        dense_hidden_dim=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype={"bfloat16": jnp.bfloat16,
               "float32": jnp.float32}[config["torch_dtype"]],
        n_experts=share["router_width"],
        n_experts_per_token=config["num_experts_per_tok"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        scoring=config["scoring_func"],
        selection_bias=config["topk_method"] == "noaux_tc",
        gate_scale=float(config["routed_scaling_factor"]),
        shared_hidden_dim=config["n_shared_experts"] * width,
        experts_held=(first, count),
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        index_n_heads=config["index_n_heads"],
        index_head_dim=config["index_head_dim"],
        index_topk=config["index_topk"], layer_kinds=kinds)


def with_layers(cfg, n):
    """The first layer and the last n - 1: of this family's shares that
    is a layer of each kind, and a `shared` layer above a `full` one."""
    kinds = cfg.kinds[:1] + cfg.kinds[len(cfg.kinds) - (n - 1):]
    return dataclasses.replace(cfg, n_layers=n, layer_kinds=kinds)


# What the benchmark's weights differ from the program's initialiser
# in, two scales.
#
# Every routed expert's down-projection is `ROUTED_OUT_SCALE` of the
# initialiser's. The runner's comparisons hold the largest error over
# every position under a limit, and a top-k router is not continuous:
# of 256 experts scored by random weights the 8th and the 9th lie
# closer than bfloat16 activations resolve at about one token and layer
# in two thousand, a float32 reference then chooses another expert
# there, and at the initialiser's own scale one routed expert is a
# sixth of a five-layer residual stream: such a position read 16 to
# 23 % of the largest logit on the chip, the program's and every
# fault's alike (PERF.md section 6, PR 32). At this scale it moves a
# logit by less than bfloat16's own error. A comparison by a quantile
# and a share of outliers holds the plain scale:
# `tools/glm_logit_check.py --weights plain` makes it, the runner
# cannot yet.
#
# The router's selection bias is `ROUTER_BIAS_SCALE` of the
# initialiser's (sigma 0.01 of a sigmoid score in place of 0.1). The
# published model's bias is the buffer its training balances the
# experts' load with; a random one of sigma 0.1 does the opposite: near
# the 8th of 256 scores two neighbours lie 0.004 apart, so it chooses
# nearly alone, the same few experts for every token, and whether the
# sixteen held here are among them is the seed's: the share of the
# pairs that fell on them read 4.0 to 7.5 % over twelve seeds, a decode
# step read their weights for 1.7 to 7.2 experts a layer, and the token
# gap followed it seed by seed (30.2 ms under 6.3 %, 30.7 over 6.4 %;
# PERF.md section 6, PR 32). At a tenth it still decides the 8th
# against the 9th, and every seed's router spreads its pairs over the
# experts as a balanced one does: the same work a step.
ROUTED_OUT_SCALE = 1 / 32
ROUTER_BIAS_SCALE = 1 / 10


def init(cfg, key):
    params = init_params(cfg, key)
    return {**params, "runs": [
        {**run, "we2": run["we2"] * ROUTED_OUT_SCALE,
         "router_bias": run["router_bias"] * ROUTER_BIAS_SCALE}
        if "we2" in run else run for run in params["runs"]]}


cached_forward = forward_with_cache


def deployment_args(cfg, params_fn):
    return (cfg, params_fn), {}


def debug(config):
    """Still the family's shape: a dense `full` layer, sparse `shared`
    and `full` ones above it, a quarter of the router's experts held,
    and an `index_topk` that the CPU tests' shortest contexts pass. In
    float32: at these widths a bfloat16 indexer chooses other keys than
    the float32 reference, and each of four keys is a quarter of a
    row's attention."""
    config = copy.deepcopy(config)
    config.update(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=4, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=24, qk_rope_head_dim=8, qk_head_dim=32,
        v_head_dim=16, index_n_heads=4, index_head_dim=16, index_topk=4,
        max_position_embeddings=256, num_hidden_layers=3,
        n_routed_experts=4, num_experts_per_tok=2, torch_dtype="float32")
    config["deployment"].update(layers_held=[2, 3, 6], router_width=16,
                                experts_held=[4, 4])
    return config
