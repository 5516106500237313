"""Model adapter of the family `kimi_linear`: Kimi-Linear-48B-A3B
through the program's decoder of Kimi Delta Attention and latent
attention over a dense SwiGLU and sigmoid-routed experts
(`ray_tpu/models/kimi_linear.py`). Served only: the chunked delta scan
has no backward pass, so the training names are left out
(`models/dense.py` says what an adapter holds).

The file keeps the published `linear_attn_config` whole, its two lists
of layers counted from 1; the layers this chip's stage holds are
`deployment.layers_held`, counted from 0, and those of them below
`first_k_dense_replace` have the dense FFN. Of the
`deployment.router_width` experts the router chooses among, the
program holds the range `deployment.experts_held` (first, count),
`num_experts` of them.

A program without the family (the parent of the PR that brought it) has
no module to import: the served names are then left out, and a cell
over this family ends at set-up with `manifest.model_adapter`'s line.
"""

from __future__ import annotations

import copy
import dataclasses

import jax.numpy as jnp
from jax import lax

try:
    from ray_tpu.models import kimi_linear
except ImportError:
    kimi_linear = None
from ray_tpu.serve.llm import prefill_bucket

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def with_layers(cfg, n):
    """The first layer held and the last n - 1: of this family's first
    stage three of them are a KDA layer over the dense FFN, a KDA layer
    over experts and a latent layer over experts."""
    kinds = cfg.kinds[:1] + cfg.kinds[len(cfg.kinds) - (n - 1):]
    return dataclasses.replace(cfg, n_layers=n, layer_kinds=kinds)


# What the benchmark's weights differ from the program's initialiser
# in, two scales (the configuration's `assumed` has this family's
# reading of each).
#
# Every routed expert's down-projection is `ROUTED_OUT_SCALE` of the
# initialiser's, for the reason `models/glm_dsa.py` gives at length for
# its own: the runner holds the largest logit error over every position
# under a limit, a top-8 router over 256 experts is not continuous, and
# at the initialiser's scale the one expert a float32 reference chooses
# differently where the 8th and the 9th score lie closer than bfloat16
# activations resolve moves that position's logits by more than lower
# precision moves them. `tools/glm_logit_check.py --config
# kimi-linear-48b-a3b-serve --weights plain` holds the plain weights by
# a median and a high percentile; the routed experts' own faults are
# held in float32 on the CPU at the plain weights
# (`tests/models/test_kimi_linear.py`).
ROUTED_OUT_SCALE = 1 / 32
# The router's selection bias is `ROUTER_BIAS_SCALE` of the
# initialiser's (sigma 0.01 of a sigmoid score in place of 0.1), as
# `models/glm_dsa.py` has it and for its reason (ROADMAP lesson 5): the
# published bias is the buffer training balances the experts' load
# with; a random one of sigma 0.1 beside 256 scores that lie 0.004
# apart near the 8th chooses nearly alone, the same few experts for
# every token, and whether the 32 held here are among them is the
# seed's. At a tenth it still decides the 8th against the 9th, and
# every seed's pairs fall on the held experts as a balanced router's
# do: the same work a step.
ROUTER_BIAS_SCALE = 1 / 10


def debug(config):
    """Still the family's shape: two whole periods, the leading dense
    layer in the first, dk != dv, a head count that is no power of
    two, a chunk of two sub-blocks, a quarter of the router's experts
    held. In float32."""
    config = copy.deepcopy(config)
    config.update(
        vocab_size=512, hidden_size=48, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=3,
        num_key_value_heads=3, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, num_experts=4,
        num_experts_per_token=2, num_hidden_layers=8,
        model_max_length=256, torch_dtype="float32", delta_chunk=32,
        delta_value_dim=16)
    config["linear_attn_config"] = {
        **config["linear_attn_config"], "head_dim": 8, "num_heads": 3}
    config["deployment"].update(layers_held=list(range(8)), router_width=16,
                                experts_held=[4, 4])
    return config


if kimi_linear is not None:
    def program_config(config):
        share, linear = config["deployment"], config["linear_attn_config"]
        held = share["layers_held"]
        kinds = tuple(
            ("dense" if i < config["first_k_dense_replace"] else "sparse",
             "mla" if i + 1 in linear["full_attn_layers"] else "kda")
            for i in held)
        first, count = share["experts_held"]
        assert len(kinds) == config["num_hidden_layers"]
        assert count == config["num_experts"]
        assert config["model_type"] == "kimi_linear"
        assert config["mla_use_nope"] and config["q_lora_rank"] is None
        assert config["num_expert_group"] == config["topk_group"] == 1
        assert config["moe_router_activation_func"] == "sigmoid"
        assert all((i + 1 in linear["kda_layers"])
                   != (i + 1 in linear["full_attn_layers"]) for i in held)
        width = config["moe_intermediate_size"]
        # The program's own, but in `debug`: dk is the published
        # `head_dim`, and so is dv.
        own = {"chunk_size": config["delta_chunk"]} \
            if "delta_chunk" in config else {}
        return kimi_linear.KimiLinearConfig(
            vocab_size=config["vocab_size"], dim=config["hidden_size"],
            n_layers=len(kinds), n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"], hidden_dim=width,
            dense_hidden_dim=config["intermediate_size"],
            max_seq_len=config["model_max_length"],
            norm_eps=float(config["rms_norm_eps"]),
            tie_embeddings=bool(config["tie_word_embeddings"]),
            dtype=_DTYPES[config["torch_dtype"]],
            n_experts=share["router_width"],
            n_experts_per_token=config["num_experts_per_token"],
            norm_topk_prob=bool(config["moe_renormalize"]),
            gate_scale=float(config["routed_scaling_factor"]),
            shared_hidden_dim=config["num_shared_experts"] * width,
            experts_held=(first, count),
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            delta_heads=linear["num_heads"],
            delta_key_dim=linear["head_dim"],
            delta_value_dim=config.get("delta_value_dim",
                                       linear["head_dim"]),
            conv_kernel=linear["short_conv_kernel_size"],
            gate_rank=linear["head_dim"],
            state_dtype=_DTYPES[config["state_dtype"]],
            layer_kinds=kinds, **own)

    def init(cfg, key):
        params = kimi_linear.init_params(cfg, key)
        return {**params, "runs": [
            {**run, "we2": run["we2"] * ROUTED_OUT_SCALE,
             "router_bias": run["router_bias"] * ROUTER_BIAS_SCALE}
            if "we2" in run else run for run in params["runs"]]}

    def init_cache(cfg, rows, max_seq):
        """The model's cache and, for the runner's check, what each row
        was prefilled with (`prompt`, [rows, 0] until a prefill) and the
        position its state stands after (`ends`)."""
        return {"model": kimi_linear.init_cache(cfg, rows, max_seq),
                "prompt": jnp.zeros((rows, 0), jnp.int32),
                "ends": jnp.zeros(rows, jnp.int32)}

    def cached_forward(params, tokens, cfg, cache, start_pos):
        """Prefill and decode through the cache, the logits of every
        position. A prefill (from position 0) is padded to the engine's
        bucket as the engine pads it, and the state left is that after
        the last real token: the bucket's padding and the chunk's tail
        are in the compared path.

        The runner's check prefills every row with the same number of
        tokens and then has each row decode from its own, shorter
        length. Attention masks the keys past a row's position; a delta
        state cannot be rewound. So a decode step whose rows do not
        stand where their state does first prefills the rows' prompts
        again, padded as before, over the state the first prefill left
        (a row that starts at 0 must start from zeros), leaving each
        row's state after its own position: what the engine does for a
        slot whose prompt is that long."""
        t = tokens.shape[1]
        if t > 1:
            padded = jnp.pad(tokens, ((0, 0), (0, prefill_bucket(t) - t)))
            logits, model = kimi_linear.forward_with_cache(
                params, padded, cfg, cache["model"], start_pos, at=t - 1,
                keep=t)
            return logits, {"model": model, "prompt": padded,
                            "ends": start_pos + t}

        def again(model):
            return kimi_linear.forward_with_cache(
                params, cache["prompt"], cfg, model,
                jnp.zeros_like(start_pos), at=start_pos - 1, keep=1)[1]

        model = cache["model"]
        if cache["prompt"].shape[1]:
            model = lax.cond((cache["ends"] != start_pos).any(), again,
                             lambda model: model, model)
        logits, model = kimi_linear.forward_with_cache(
            params, tokens, cfg, model, start_pos)
        return logits, {**cache, "model": model, "ends": start_pos + 1}

    def deployment_args(cfg, params_fn):
        return (cfg, params_fn), {}
