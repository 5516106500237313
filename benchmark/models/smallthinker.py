"""Model adapter of the family `smallthinker`: SmallThinker-21BA3B
through the program's decoder of window and full layers with a router
on the layer's input and ReGLU experts
(`ray_tpu/models/smallthinker.py`). Trained only: the program has no
cached forward pass for it, so the serving names are left out
(`models/dense.py` says what an adapter holds).

The file keeps the published 52-long `sliding_window_layout` and
`rope_layout`; the layers held are their first `num_hidden_layers`
entries. `moe_num_primary_experts` counts the experts held here, from
`experts_held_first` on, and the router's width is the published count
(`published`), where the file cuts it."""

from __future__ import annotations

import copy

import jax.numpy as jnp

from benchmark.models.dense import with_remat  # noqa: F401
from ray_tpu.models.smallthinker import (SmallThinkerConfig,
                                         init_params_sharded, loss_fn)


def program_config(config):
    depth = config["num_hidden_layers"]
    windowed = config["sliding_window_layout"][:depth]
    # The program's two kinds of layer: a window with rotary positions,
    # every key with none.
    assert windowed == config["rope_layout"][:depth], config["rope_layout"]
    assert config["moe_primary_router_apply_softmax"]
    held = config["moe_num_primary_experts"]
    experts = config.get("published", {}).get("moe_num_primary_experts",
                                              held)
    return SmallThinkerConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_layers=depth, n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_size=config["head_dim"],
        hidden_dim=config["moe_ffn_hidden_size"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype={"bfloat16": jnp.bfloat16,
               "float32": jnp.float32}[config["torch_dtype"]],
        n_experts=experts,
        n_experts_per_token=config["moe_num_active_primary_experts"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        aux_loss_coeff=float(config["router_aux_loss_coef"]),
        experts_held=None if held == experts
        else (config.get("experts_held_first", 0), held),
        layer_kinds=tuple("window" if w else "full" for w in windowed),
        sliding_window=config["sliding_window_size"])


init_sharded = init_params_sharded
loss = loss_fn


def debug(config):
    """Still the family's shape: one period of a full and three windowed
    layers, a window shorter than the debug sequence of 32 (so that a
    row's window ends inside it), a head size that is not the hidden
    size over the heads, half of eight experts held."""
    config = copy.deepcopy(config)
    config.update(vocab_size=512, hidden_size=64, head_dim=32,
                  moe_ffn_hidden_size=32, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=256,
                  num_hidden_layers=4, sliding_window_size=12,
                  moe_num_primary_experts=4,
                  moe_num_active_primary_experts=3)
    config["published"] = {**config.get("published", {}),
                           "moe_num_primary_experts": 8}
    return config
