"""Model adapter of the family `nemotron_h`: Nemotron 3 Super through
the program's hybrid decoder (`ray_tpu/models/nemotron_h.py`: Mamba-2
mixers, one attention layer in eleven, LatentMoE expert layers, every
published layer a mixer or an expert layer alone). Served only: the
chunked scan has no backward pass, so the training names are left out
(`models/dense.py` says what an adapter holds).

The file keeps the published `hybrid_override_pattern` whole; the
layers this chip's share holds are the letters `deployment.layers_held`
of it. Of the `deployment.router_width` experts the router chooses
among, the program holds the range `deployment.experts_held` (first,
count), `n_routed_experts` of them.
"""

from __future__ import annotations

import copy
import dataclasses

import jax.numpy as jnp
from jax import lax

from ray_tpu.models import nemotron_h
from ray_tpu.models.nemotron_h import NemotronHConfig
from ray_tpu.serve.llm import prefill_bucket

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def program_config(config):
    share = config["deployment"]
    pattern = "".join(config["hybrid_override_pattern"][i]
                      for i in share["layers_held"])
    first, count = share["experts_held"]
    assert len(pattern) == config["num_hidden_layers"]
    assert count == config["n_routed_experts"]
    assert config["mlp_hidden_act"] == "relu2"
    return NemotronHConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_layers=len(pattern), n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        hidden_dim=config["moe_intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["layer_norm_epsilon"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=_DTYPES[config["torch_dtype"]],
        n_experts=share["router_width"],
        n_experts_per_token=config["num_experts_per_tok"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        scoring="sigmoid", selection_bias=True,
        gate_scale=float(config["routed_scaling_factor"]),
        shared_hidden_dim=config["n_shared_experts"]
        * config["moe_shared_expert_intermediate_size"],
        experts_held=(first, count), expert_kind="relu2",
        latent_dim=config["moe_latent_size"], pattern=pattern,
        ssm_heads=config["mamba_num_heads"],
        ssm_head_dim=config["mamba_head_dim"],
        ssm_groups=config["n_groups"], ssm_state=config["ssm_state_size"],
        conv_kernel=config["conv_kernel"], chunk_size=config["chunk_size"],
        state_dtype=_DTYPES[config["ssm_state_dtype"]])


def with_layers(cfg, n):
    """The last n published layers of the share: of this family's
    shares the last five are a block of each kind, a Mamba-2 mixer with
    an expert layer, a Mamba-2 mixer alone (an attention layer follows
    it), and attention with an expert layer."""
    return dataclasses.replace(cfg, n_layers=n, pattern=cfg.pattern[-n:])


# What the benchmark's weights differ from the program's initialiser
# in, two scales, for the reasons `models/glm_dsa.py` gives at length
# for its own two (the configuration's `assumed` has this family's
# readings).
#
# Every routed expert's down-projection is `ROUTED_OUT_SCALE` of the
# initialiser's. The runner holds the largest logit error over every
# position under a limit, and a top-k router is not continuous: of 512
# experts scored by random weights the 22nd and the 23rd lie closer
# than bfloat16 activations resolve so often that, at the initialiser's
# own scale, one position in ten read 2 to 6.2 % of the largest
# logit on the chip where a float32 reference chose another expert, the
# program's and every fault's alike (PERF.md section 6, PR 34). At this
# scale the one expert moves a logit by less than bfloat16's own error.
# `tools/glm_logit_check.py --config nemotron-3-super-serve --weights
# plain` holds the plain scale by a quantile and a share of outliers.
#
# The router's selection bias is `ROUTER_BIAS_SCALE` of the
# initialiser's (sigma 0.001 of a sigmoid score in place of 0.1). The
# published buffer balances the experts' load; a random one of sigma
# 0.1 does the opposite: near the 22nd of 512 scores two neighbours lie
# 0.006 apart, so it chooses nearly alone, the same experts for every
# token, and how many of the 128 held here are among them is the
# seed's. At a hundredth it still decides the 22nd against the 23rd
# about one time in ten, and every seed's router spreads its pairs over
# the experts as a balanced one does: the same work a step.
ROUTED_OUT_SCALE = 1 / 32
ROUTER_BIAS_SCALE = 1 / 100


def init(cfg, key):
    params = nemotron_h.init_params(cfg, key)
    return {**params, "runs": [
        {**run, "we2": run["we2"] * ROUTED_OUT_SCALE,
         "router_bias": run["router_bias"] * ROUTER_BIAS_SCALE}
        if "we2" in run else run for run in params["runs"]]}


def init_cache(cfg, rows, max_seq):
    """The model's cache and, for the runner's check, what each row was
    prefilled with (`prompt`, [rows, 0] until a prefill) and the
    position its state stands after (`ends`)."""
    return {"model": nemotron_h.init_cache(cfg, rows, max_seq),
            "prompt": jnp.zeros((rows, 0), jnp.int32),
            "ends": jnp.zeros(rows, jnp.int32)}


def cached_forward(params, tokens, cfg, cache, start_pos):
    """Prefill and decode through the cache, the logits of every
    position. A prefill (from position 0) is padded to the engine's
    bucket as the engine pads it, and the state left is that after the
    last real token: the bucket's padding and the chunk's tail are in
    the compared path.

    The runner's check prefills every row with the same number of
    tokens and then has each row decode from its own, shorter length.
    Attention masks the keys past a row's position; a recurrent state
    cannot be rewound. So a decode step whose rows do not stand where
    their state does first prefills the rows' prompts again, padded as
    before, leaving each row's state after its own position: what the
    engine does for a slot whose prompt is that long."""
    t = tokens.shape[1]
    if t > 1:
        padded = jnp.pad(tokens, ((0, 0), (0, prefill_bucket(t) - t)))
        logits, model = nemotron_h.forward_with_cache(
            params, padded, cfg, cache["model"], start_pos, at=t - 1)
        return logits[:, :t], {"model": model, "prompt": padded,
                               "ends": start_pos + t}

    def again(model):
        return nemotron_h.forward_with_cache(
            params, cache["prompt"], cfg, model, jnp.zeros_like(start_pos),
            at=start_pos - 1)[1]

    model = cache["model"]
    if cache["prompt"].shape[1]:
        model = lax.cond((cache["ends"] != start_pos).any(), again,
                         lambda model: model, model)
    logits, model = nemotron_h.forward_with_cache(params, tokens, cfg,
                                                  model, start_pos)
    return logits, {**cache, "model": model, "ends": start_pos + 1}


def deployment_args(cfg, params_fn):
    return (cfg, params_fn), {}


def debug(config):
    """Still the family's shape: every kind of block (`MEMEM*E` of the
    share's letters), a quarter of the router's experts held, relu^2
    experts in a latent narrower than the hidden size, a chunk shorter
    than the CPU tests' prompts. In float32."""
    config = copy.deepcopy(config)
    config.update(
        vocab_size=512, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
        mamba_head_dim=16, n_groups=2, ssm_state_size=16, chunk_size=8,
        moe_intermediate_size=48, moe_latent_size=32,
        moe_shared_expert_intermediate_size=96, intermediate_size=48,
        num_experts_per_tok=3, n_routed_experts=4, num_hidden_layers=7,
        max_position_embeddings=256, torch_dtype="float32")
    config["deployment"].update(
        layers_held=[31, 32, 33, 34, 35, 36, 37], router_width=16,
        experts_held=[4, 4])
    return config
