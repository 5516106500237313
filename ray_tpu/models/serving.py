"""What `ray_tpu.serve.llm` runs a model through, found by the type of
its config: the one place that says which architectures have a path
through the slot cache. The engine knows no model; it takes from here

- ``forward(params, tokens, cfg, cache, start_pos, at) -> (logits
  [B, vocab], cache, counts)``: `tokens` [B, T] from per-row offsets
  `start_pos` [B], prefill and decode alike; the logits are those of
  position `at` of `tokens` (an int or an int32 scalar: a prefill wants
  its last real position, a decode step its only one); `counts` is a
  dict of int32 scalars the model counted over the call, which a decode
  block sums and hands to the host with its tokens, {} of a model that
  counts nothing;
- ``init_cache(cfg, n_slots, max_seq) -> cache``: any pytree of
  leaves of two kinds, each [layers_i, slots, ...]. A *row* leaf is
  [layers_i, slots, max_seq, ...], one row a position (keys and values,
  latents): `forward` returns it with the call's [B, T] new rows a
  layer written in and nothing else moved; the engine slices slots,
  reads and writes blocks of rows and sizes its prefix cache by these
  leaves. A *state* leaf has no sequence axis (a recurrent layer's
  state, a vector a channel as Mamba-2's or a matrix a head as the
  delta rule's, and a convolution's last rows, of which a layer may
  carry several: `gated_delta` has three): `forward` rewrites it whole, a row
  that starts at position 0 starts from zeros whatever the leaf held,
  and what is left is the state after position `at` and no later (a
  prefill's bucket padding must not enter it); the engine only slices
  its slots. A *ring* is a state leaf too, though it has an axis of
  rows: [layers_i, slots, window, ...], the keys and values of a
  windowed layer, position p in row p mod window whatever `max_seq`
  is (`cohere2_moe`). Which position a row holds follows from the
  slot's length, which the model is handed as `start_pos`, so the
  engine need not clear a ring between two requests; but a row is no
  position the engine could name, so it may not read, write or copy
  blocks of a ring's rows, nor size anything by them, and what holds
  for a recurrent state holds for a ring: `forward` leaves in it the
  rows up to `at` and none of the padding after. All leaves ride the
  model's layer scan as its carry (`decoder.layers`), so a cache that
  is donated to the program is updated where it lies;
- ``state_leaves(cache) -> the same structure of bools``: True at a
  state leaf; all rows unless the model says otherwise. A model with a
  state leaf is served with no prefix cache: the prefix cache holds
  blocks of rows, and neither a recurrent layer nor a ring can resume
  from a block of rows, only from a snapshot of its state at the
  block's boundary (for a ring: the window's worth of rows that end
  there), which nothing takes yet;
- ``keys_attended(cfg, lengths) -> per row``: of `lengths` cached keys
  (host integers) how many the next token attends: all of them, unless
  the model selects keys;
- ``keys_read(cfg, lengths) -> per row``, or None: the rows a layer's
  attention fetches for a row of `lengths` keys, where a decode step
  reads by the slot's length (`ops.attention.decode_attention`: the
  length rounded up to the kernel's block of rows; the engine holds it
  to the slot's region). None where a step reads the region whole or
  reads something that is no region (a ring, selected keys).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional


def _every_key(cfg, lengths):
    return lengths


def _keys_read(cfg, lengths):
    """`lengths` rounded up to `decode_attention`'s block of rows for
    `cfg`'s key heads."""
    from ray_tpu.ops.attention import decode_block_rows
    rows = decode_block_rows(cfg.n_kv_heads, cfg.head_dim, cfg.dtype)
    return -(-lengths // rows) * rows


def _all_rows(cache):
    import jax
    return jax.tree.map(lambda _: False, cache)


@dataclasses.dataclass(frozen=True)
class ServedModel:
    forward: Callable
    init_cache: Callable
    keys_attended: Callable = _every_key
    state_leaves: Callable = _all_rows
    keys_read: Optional[Callable] = None


def _llama():
    from ray_tpu.models import llama

    def forward(params, tokens, cfg, cache, start_pos, at):
        logits, cache = llama.forward_with_cache(params, tokens, cfg, cache,
                                                 start_pos)
        return logits[:, at], cache, {}

    return ServedModel(forward, llama.init_kv_cache, keys_read=_keys_read)


def _glm_dsa():
    from ray_tpu.models import glm_dsa
    return ServedModel(glm_dsa.forward, glm_dsa.init_cache,
                       glm_dsa.keys_attended)


def _nemotron_h():
    from ray_tpu.models import nemotron_h
    return ServedModel(nemotron_h.forward, nemotron_h.init_cache,
                       state_leaves=nemotron_h.state_leaves)


def _olmo_hybrid():
    from ray_tpu.models import olmo_hybrid
    return ServedModel(olmo_hybrid.forward, olmo_hybrid.init_cache,
                       state_leaves=olmo_hybrid.state_leaves,
                       keys_read=_keys_read)


def _cohere2_moe():
    from ray_tpu.models import cohere2_moe
    return ServedModel(cohere2_moe.forward, cohere2_moe.init_cache,
                       cohere2_moe.keys_attended, cohere2_moe.state_leaves)


# By the config's own type, not its bases: `MoEConfig` is a
# `LlamaConfig` and has no cached forward pass.
_SERVED = {"LlamaConfig": _llama, "GlmDsaConfig": _glm_dsa,
           "NemotronHConfig": _nemotron_h,
           "Cohere2MoeConfig": _cohere2_moe,
           "OlmoHybridConfig": _olmo_hybrid}


def served_model(cfg) -> ServedModel:
    find = _SERVED.get(type(cfg).__name__)
    if find is None:
        raise TypeError(
            f"no served path for {type(cfg).__name__}: ray_tpu/models/"
            f"serving.py names a cached forward pass and a cache "
            f"initialiser for {sorted(_SERVED)}")
    return find()
