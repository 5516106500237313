"""Model adapter of the family `dense`: the program's grouped-query
decoder (`ray_tpu/models/llama.py`), trained and served.

An adapter is how a configuration file reaches the program's model, and
the only place under `benchmark/` that names one. `configs/<c>.json`
says `"family": "<f>"`; the runners, `sweep.py` and the tests find
`models/<f>.py` by that name (`manifest.model_adapter`) and take from
it, by these names:

- `program_config(config)`: the program's config object, from whatever
  keys of the file this family has. `with_remat(cfg, policy)` and
  `with_layers(cfg, n)` are the two changes a runner makes to it: the
  training plan's rematerialisation, the logit check's shallow copy.
- for a train cell, what `make_train_step` is built over:
  `init_sharded(cfg, mesh, key) -> params` and
  `loss(params, batch, cfg, mesh=) -> (loss, metrics)`.
- for a serve cell: `init(cfg, key) -> params`, unsharded;
  `cached_forward(params, tokens, cfg=, cache=, start_pos=) ->
  (logits, cache)` and `init_cache(cfg, rows, max_seq) -> cache`, which
  the logit check steps through; `deployment_args(cfg, params_fn) ->
  (args, kwargs)`, the model's part of `LLMDeployment.bind`. A family
  that cannot be served yet leaves these out, and a serve cell over it
  fails at set-up saying so.
- `debug(config)`: the configuration cut to widths a CPU test can run.

It holds no timing, no traffic and no check: seeds, batches, windows,
the reference and every comparison stay in the runners.
"""

from __future__ import annotations

import copy
import dataclasses

import jax.numpy as jnp

from ray_tpu.models.llama import (LlamaConfig, forward_with_cache,
                                  init_kv_cache, init_params,
                                  init_params_sharded, loss_fn)


def decoder_fields(config):
    """The fields every decoder of the program shares, from the
    `config.json` keys of the file."""
    return dict(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        hidden_dim=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype={"bfloat16": jnp.bfloat16,
               "float32": jnp.float32}[config["torch_dtype"]])


def program_config(config):
    return LlamaConfig(**decoder_fields(config))


def with_remat(cfg, policy):
    return dataclasses.replace(cfg, remat=policy)


def with_layers(cfg, n):
    return dataclasses.replace(cfg, n_layers=n)


init_sharded = init_params_sharded
loss = loss_fn

init = init_params
cached_forward = forward_with_cache
init_cache = init_kv_cache


def deployment_args(cfg, params_fn):
    return (cfg, params_fn), {}


def debug(config):
    config = copy.deepcopy(config)
    config.update(vocab_size=512, hidden_size=64, intermediate_size=128,
                  num_attention_heads=4, num_key_value_heads=2,
                  max_position_embeddings=256, num_hidden_layers=2)
    return config
