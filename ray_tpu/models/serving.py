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
- ``init_cache(cfg, n_slots, max_seq) -> cache``: any pytree whose
  every leaf is [layers_i, slots, max_seq, ...]; the engine slices
  slots, reads and writes blocks of rows and sizes its prefix cache leaf
  by leaf. `forward` returns it with the call's [B, T] new rows a layer
  written in and nothing else moved: the leaves ride the model's layer
  scan as its carry (`decoder.layers`), so a cache that is donated to
  the program is updated where it lies;
- ``keys_attended(cfg, lengths) -> per row``: of `lengths` cached keys
  (host integers) how many the next token attends: all of them, unless
  the model selects keys.
"""

from __future__ import annotations

import dataclasses
from typing import Callable


def _every_key(cfg, lengths):
    return lengths


@dataclasses.dataclass(frozen=True)
class ServedModel:
    forward: Callable
    init_cache: Callable
    keys_attended: Callable = _every_key


def _llama():
    from ray_tpu.models import llama

    def forward(params, tokens, cfg, cache, start_pos, at):
        logits, cache = llama.forward_with_cache(params, tokens, cfg, cache,
                                                 start_pos)
        return logits[:, at], cache, {}

    return ServedModel(forward, llama.init_kv_cache)


def _glm_dsa():
    from ray_tpu.models import glm_dsa
    return ServedModel(glm_dsa.forward, glm_dsa.init_cache,
                       glm_dsa.keys_attended)


# By the config's own type, not its bases: `MoEConfig` is a
# `LlamaConfig` and has no cached forward pass.
_SERVED = {"LlamaConfig": _llama, "GlmDsaConfig": _glm_dsa}


def served_model(cfg) -> ServedModel:
    find = _SERVED.get(type(cfg).__name__)
    if find is None:
        raise TypeError(
            f"no served path for {type(cfg).__name__}: ray_tpu/models/"
            f"serving.py names a cached forward pass and a cache "
            f"initialiser for {sorted(_SERVED)}")
    return find()
