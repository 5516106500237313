"""Ulysses-style (all-to-all) sequence parallelism.

Second context-parallel scheme (complement to ring attention; absent from
the reference — SURVEY.md §5). Activations arrive sequence-sharded
[B, S/P, H, D]; two all-to-alls re-shard to head-sharded [B, S, H/P, D] so
each device runs *full-sequence* attention over a subset of heads, then the
layout is restored. Preferred over ring attention when heads % P == 0 and
the sequence fits HBM after gathering — the all-to-alls move each element
twice total vs. P-1 ppermutes of K/V, and the attention itself needs no
online-softmax bookkeeping.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.parallel.ring_attention import reference_attention


def _ulysses_sharded(q, k, v, axis_name: str, causal: bool,
                     attn_fn: Optional[Callable]):
    # [B, S/P, H, D] -> [B, S, H/P, D]: split heads (axis 2), concat seq (1).
    def scatter_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def scatter_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    attn = attn_fn or functools.partial(reference_attention, causal=causal)
    out = attn(qh, kh, vh)
    return scatter_seq(out)


def ulysses_attention(q, k, v, *, mesh: Optional[Mesh] = None,
                      axis_name: str = "seq", causal: bool = True,
                      attn_fn: Optional[Callable] = None):
    """All-to-all sequence-parallel attention.

    q/k/v: [batch, seq, heads, head_dim] with seq sharded over `axis_name`.
    `attn_fn` lets callers swap in the Pallas flash kernel for the inner
    full-sequence attention. Requires heads % axis_size == 0.
    """
    if mesh is None:
        return _ulysses_sharded(q, k, v, axis_name, causal, attn_fn)
    spec = P(("data", "fsdp"), axis_name, "tensor", None)
    fn = jax.shard_map(
        functools.partial(_ulysses_sharded, axis_name=axis_name,
                          causal=causal, attn_fn=attn_fn),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
