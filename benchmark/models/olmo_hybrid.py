"""Model adapter of the family `olmo_hybrid`: Olmo-Hybrid-7B through
the program's hybrid decoder (`ray_tpu/models/olmo_hybrid.py`: three
gated delta-rule layers to one of full attention, a SwiGLU in every
layer, OLMo 2's block). Served only: the chunked delta scan has no
backward pass, so the training names are left out (`models/dense.py`
says what an adapter holds).

The file keeps the published `layer_types` whole; the layers this
chip's stage holds are the entries `deployment.layers_held` of it.
"""

from __future__ import annotations

import copy
import dataclasses

import jax.numpy as jnp
from jax import lax

from ray_tpu.models import olmo_hybrid
from ray_tpu.models.olmo_hybrid import OlmoHybridConfig
from ray_tpu.serve.llm import prefill_bucket

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
_KINDS = {"linear_attention": "linear", "full_attention": "full"}


def program_config(config):
    kinds = tuple(_KINDS[config["layer_types"][i]]
                  for i in config["deployment"]["layers_held"])
    assert len(kinds) == config["num_hidden_layers"]
    assert config["hidden_act"] == "silu" and not config["attention_bias"]
    assert config["rope_parameters"]["rope_theta"] is None
    assert config["linear_num_key_heads"] == config["linear_num_value_heads"]
    chunk = {"chunk_size": config["delta_chunk"]} \
        if "delta_chunk" in config else {}  # the program's own, but in `debug`
    return OlmoHybridConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_layers=len(kinds), n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        hidden_dim=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=_DTYPES[config["torch_dtype"]], layer_types=kinds,
        delta_heads=config["linear_num_value_heads"],
        delta_key_dim=config["linear_key_head_dim"],
        delta_value_dim=config["linear_value_head_dim"],
        conv_kernel=config["linear_conv_kernel_dim"],
        allow_neg_eigval=bool(config["linear_allow_neg_eigval"]),
        state_dtype=_DTYPES[config["state_dtype"]], **chunk)


def with_layers(cfg, n):
    """The last n layers held: of this family's stages the last four
    are one whole period, three delta layers and the full one."""
    return dataclasses.replace(cfg, n_layers=n,
                               layer_types=cfg.layer_types[-n:])


init = olmo_hybrid.init_params


def init_cache(cfg, rows, max_seq):
    """The model's cache and, for the runner's check, what each row was
    prefilled with (`prompt`, [rows, 0] until a prefill) and the
    position its state stands after (`ends`)."""
    return {"model": olmo_hybrid.init_cache(cfg, rows, max_seq),
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
    Attention masks the keys past a row's position; a delta state
    cannot be rewound. So a decode step whose rows do not stand where
    their state does first prefills the rows' prompts again, padded as
    before, leaving each row's state after its own position: what the
    engine does for a slot whose prompt is that long."""
    t = tokens.shape[1]
    if t > 1:
        padded = jnp.pad(tokens, ((0, 0), (0, prefill_bucket(t) - t)))
        logits, model = olmo_hybrid.forward_with_cache(
            params, padded, cfg, cache["model"], start_pos, at=t - 1)
        return logits[:, :t], {"model": model, "prompt": padded,
                               "ends": start_pos + t}

    def again(model):
        return olmo_hybrid.forward_with_cache(
            params, cache["prompt"], cfg, model, jnp.zeros_like(start_pos),
            at=start_pos - 1)[1]

    model = cache["model"]
    if cache["prompt"].shape[1]:
        model = lax.cond((cache["ends"] != start_pos).any(), again,
                         lambda model: model, model)
    logits, model = olmo_hybrid.forward_with_cache(params, tokens, cfg,
                                                   model, start_pos)
    return logits, {**cache, "model": model, "ends": start_pos + 1}


def deployment_args(cfg, params_fn):
    return (cfg, params_fn), {}


def debug(config):
    """Still the family's shape: two whole periods, key and value heads
    of different sizes, a head count that is no power of two, a chunk
    shorter than the CPU tests' prompts. In float32."""
    config = copy.deepcopy(config)
    config.update(
        vocab_size=512, hidden_size=60, num_attention_heads=3,
        num_key_value_heads=3, intermediate_size=96,
        linear_num_key_heads=3, linear_num_value_heads=3,
        linear_key_head_dim=8, linear_value_head_dim=16,
        num_hidden_layers=8, max_position_embeddings=256,
        torch_dtype="float32", delta_chunk=8)
    config["deployment"].update(layers_held=list(range(8)))
    return config
