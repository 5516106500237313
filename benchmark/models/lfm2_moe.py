"""Model adapter of the family `lfm2_moe`: LFM2-8B-A1B through the
program's decoder of gated short convolutions and full attention over a
dense SwiGLU and sigmoid-routed experts (`ray_tpu/models/lfm2_moe.py`).
Served only: the program has no loss for it, so the training names are
left out (`models/dense.py` says what an adapter holds).

The file keeps the published `layer_types` whole; the layers this
chip's stage holds are the entries `deployment.layers_held` of it, and
those of them below `num_dense_layers` have the dense FFN.

A program without the family (the parent of the PR that brought it) has
no module to import: the served names are then left out, and a cell
over this family ends at set-up with `manifest.model_adapter`'s line.
"""

from __future__ import annotations

import copy
import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

try:
    from ray_tpu.models import lfm2_moe
except ImportError:
    lfm2_moe = None
from ray_tpu.serve.llm import prefill_bucket

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
_KINDS = {"conv": "conv", "full_attention": "full"}


def with_layers(cfg, n):
    """n layers from the last leading dense layer on: of this family's
    first stage four of them (published layers 1 to 4) are a conv layer
    over the dense FFN, a full layer over experts and two conv layers
    over experts."""
    first = cfg.n_dense_layers - 1
    assert first >= 0 and first + n <= cfg.n_layers
    return dataclasses.replace(
        cfg, n_layers=n, n_dense_layers=1,
        layer_types=cfg.layer_types[first:first + n])


# What the benchmark's weights differ from the program's initialiser
# in: two scales and one vector of signs (the configuration's `assumed`
# has this family's reading of each).
#
# Every routed expert's down-projection is `ROUTED_OUT_SCALE` of the
# initialiser's, for the reason `models/glm_dsa.py` gives at length for
# its own: the runner holds the largest logit error over every position
# under a limit, a top-4 router over 32 experts is not continuous, and
# at the initialiser's scale the one expert a float32 reference chooses
# differently where the 4th and the 5th score lie closer than bfloat16
# activations resolve moves that position's logits by more than lower
# precision moves them. `tools/glm_logit_check.py --config
# lfm2-8b-a1b-serve --weights plain` holds the plain weights by a median
# and a 99.9th percentile; the routed experts' own faults are held in
# float32 on the CPU at the plain weights
# (`tests/models/test_lfm2_moe.py`).
ROUTED_OUT_SCALE = 1 / 32
# The router's selection bias is `ROUTER_BIAS_SCALE` of the
# initialiser's (sigma 0.01 of a sigmoid score in place of 0.1), as
# `models/glm_dsa.py` has it and for its reason. The published model's
# bias is the buffer its training balances the experts' load with; a
# random one of sigma 0.1 beside scores that spread by 0.2 does the
# opposite: a few experts of a layer are all but never chosen, which
# ones is the seed's, and a decode step reads the rest: 90.0 % of the
# experts held in the first traced run, 83.9 % at 32 slots, and the
# token gap followed it seed by seed (15.16 to 15.42 ms over six seeds,
# a spread of 1.09 % against the 1.25 % a new cell is admitted under;
# PERF.md section 6, PR 55). At a tenth it still decides the 4th
# against the 5th, and every seed's 256 pairs a step fall on all 32
# experts as a balanced router's do: the same work a step.
ROUTER_BIAS_SCALE = 1 / 10


def final_norm_signs(cfg, key):
    """The final norm's weight, +1 or -1 a channel by the seed, where
    the initialiser has ones: `models/cohere2_moe.py`'s cure for a tied
    head at random weights. Under ones a token's largest logit is its
    own, greedy decoding repeats a prompt's last token, a request's
    routing never changes and the experts a decode step reads are those
    the seed's few requests happen to hit. Signs keep every operation,
    byte and magnitude of the norm and take the embedding's likeness to
    itself out of the head."""
    flip = jax.random.bernoulli(jax.random.fold_in(key, 55), 0.5, (cfg.dim,))
    return jnp.where(flip, -1, 1).astype(cfg.dtype)


def debug(config):
    """Still the family's shape: both leading dense layers, a period
    and a half behind them, four query heads a key head, more experts
    than a token takes. In float32."""
    config = copy.deepcopy(config)
    config.update(
        vocab_size=512, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=8,
        num_key_value_heads=2, head_dim=8, num_experts=8,
        num_experts_per_tok=3, num_hidden_layers=8,
        max_position_embeddings=256, torch_dtype="float32")
    config["deployment"].update(layers_held=list(range(8)))
    return config


if lfm2_moe is not None:
    def program_config(config):
        held = config["deployment"]["layers_held"]
        kinds = tuple(_KINDS[config["layer_types"][i]] for i in held)
        assert len(kinds) == config["num_hidden_layers"]
        assert config["model_type"] == "lfm2_moe" and not config["conv_bias"]
        assert config["use_expert_bias"] and config["tie_word_embeddings"]
        assert config["head_dim"] * config["num_attention_heads"] \
            == config["hidden_size"]
        return lfm2_moe.Lfm2MoeConfig(
            vocab_size=config["vocab_size"], dim=config["hidden_size"],
            n_layers=len(kinds), n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            hidden_dim=config["moe_intermediate_size"],
            dense_hidden_dim=config["intermediate_size"],
            max_seq_len=config["max_position_embeddings"],
            rope_theta=float(config["rope_theta"]),
            norm_eps=float(config["norm_eps"]),
            dtype=_DTYPES[config["torch_dtype"]],
            n_experts=config["num_experts"],
            n_experts_per_token=config["num_experts_per_tok"],
            norm_topk_prob=bool(config["norm_topk_prob"]),
            gate_scale=float(config["routed_scaling_factor"]),
            layer_types=kinds,
            n_dense_layers=sum(i < config["num_dense_layers"] for i in held),
            conv_kernel=config["conv_L_cache"])

    def init(cfg, key):
        params = lfm2_moe.init_params(cfg, key)
        return {**params, "final_norm": final_norm_signs(cfg, key),
                "runs": [{**run, "we2": run["we2"] * ROUTED_OUT_SCALE,
                          "router_bias": run["router_bias"]
                          * ROUTER_BIAS_SCALE}
                         if "we2" in run else run for run in params["runs"]]}

    def init_cache(cfg, rows, max_seq):
        """The model's cache and, for the runner's check, what each row
        was prefilled with (`prompt`, [rows, 0] until a prefill) and the
        position its carried rows stand after (`ends`)."""
        return {"model": lfm2_moe.init_cache(cfg, rows, max_seq),
                "prompt": jnp.zeros((rows, 0), jnp.int32),
                "ends": jnp.zeros(rows, jnp.int32)}

    def cached_forward(params, tokens, cfg, cache, start_pos):
        """Prefill and decode through the cache, the logits of every
        position. A prefill (from position 0) is padded to the engine's
        bucket as the engine pads it, and the carried rows are those
        that end at the last real token: the bucket's padding is in the
        compared path.

        The runner's check prefills every row with the same number of
        tokens and then has each row decode from its own, shorter
        length. Attention masks the keys past a row's position; a
        convolution's carry cannot be rewound. So a decode step whose
        rows do not stand where their carries do first prefills the
        rows' prompts again, padded as before, over the carries the
        first prefill left (a row that starts at 0 must start from
        zeros), leaving each row's carry after its own position: what
        the engine does for a slot whose prompt is that long."""
        t = tokens.shape[1]
        if t > 1:
            padded = jnp.pad(tokens, ((0, 0), (0, prefill_bucket(t) - t)))
            logits, model = lfm2_moe.forward_with_cache(
                params, padded, cfg, cache["model"], start_pos, at=t - 1,
                keep=t)
            return logits, {"model": model, "prompt": padded,
                            "ends": start_pos + t}

        def again(model):
            return lfm2_moe.forward_with_cache(
                params, cache["prompt"], cfg, model,
                jnp.zeros_like(start_pos), at=start_pos - 1, keep=1)[1]

        model = cache["model"]
        if cache["prompt"].shape[1]:
            model = lax.cond((cache["ends"] != start_pos).any(), again,
                             lambda model: model, model)
        logits, model = lfm2_moe.forward_with_cache(params, tokens, cfg,
                                                    model, start_pos)
        return logits, {**cache, "model": model, "ends": start_pos + 1}

    def deployment_args(cfg, params_fn):
        return (cfg, params_fn), {}
