"""Ring attention: context parallelism over a mesh axis.

Absent from the reference (SURVEY.md §5 "Long-context / sequence
parallelism": *not present*; our charter requires it first-class). Design:
the sequence dimension is sharded over the ``seq`` mesh axis; each device
holds one contiguous chunk of Q, K, V. K/V chunks rotate around the ring via
`lax.ppermute` (single-hop ICI neighbours) while each device accumulates
flash-style online-softmax partial results for its resident Q chunk. Compute
on step i overlaps with the DMA of step i+1's K/V — XLA schedules the
ppermute asynchronously, so for chunk sizes that keep the MXU busy the ring
is bandwidth-hidden.

Math (per Q row): maintain running max m, normalizer l, accumulator o.
For each incoming K/V block with scores s:
    m' = max(m, rowmax(s));  p = exp(s - m') (masked entries forced to 0)
    l  = l * exp(m - m') + rowsum(p)
    o  = o * exp(m - m') + p @ V
Final output o / l. Causality is decided per (q_chunk, kv_chunk) pair:
kv_chunk > q_chunk → fully masked (contributes nothing), kv_chunk ==
q_chunk → intra-chunk causal mask, kv_chunk < q_chunk → unmasked.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from ray_tpu.parallel.collectives import axis_size as _axis_size
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def _block_attn(q, k, v, m, l, o, mask):
    """One online-softmax accumulation step.

    q: [B, Sq, H, D]; k/v: [B, Sk, H, D]; mask: [Sq, Sk] bool or None.
    m, l: [B, H, Sq]; o: [B, Sq, H, D]. All accumulation in f32.
    """
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None, :, :], s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # Guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1.
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        p = jnp.where(mask[None, None, :, :], p, 0.0)
    correction = jnp.exp(m - m_new)
    l_new = l * correction + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    o_new = o * correction.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def _ring_attention_sharded(q, k, v, axis_name: str, causal: bool):
    """Body executed per-shard under shard_map. Shapes are local chunks."""
    axis_size = _axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    sk = k.shape[1]

    q32 = q.astype(jnp.float32)
    m0 = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    o0 = jnp.zeros((b, sq, h, d), jnp.float32)

    # Intra-chunk causal mask, used only when kv_chunk == q_chunk. Global
    # positions: q row r is my_idx*sq + r, kv col c is kv_idx*sk + c; with
    # equal chunk sizes the diagonal comparison reduces to r >= c.
    diag_mask = (jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]) \
        if causal else None

    def step(carry, r):
        m, l, o, k_cur, v_cur = carry
        kv_idx = (my_idx - r) % axis_size  # origin chunk of current k/v
        if causal:
            # Select mask regime without data-dependent control flow:
            # full-visible (ones), diagonal, or hidden (zeros).
            full = kv_idx < my_idx
            hidden = kv_idx > my_idx
            mask = jnp.where(
                hidden, False, jnp.where(full, True, diag_mask)
            )
        else:
            mask = None
        m, l, o = _block_attn(q32, k_cur.astype(jnp.float32),
                              v_cur, m, l, o, mask)
        # Rotate k/v to the next device; skip on the last step.
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (m, l, o, k_nxt, v_nxt), None

    (m, l, o, _, _), _ = lax.scan(
        step, (m0, l0, o0, k, v), jnp.arange(axis_size)
    )
    l = jnp.maximum(l, 1e-30)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, *, mesh: Optional[Mesh] = None,
                   axis_name: str = "seq", causal: bool = True):
    """Context-parallel attention. q/k/v: [batch, seq, heads, head_dim],
    sequence dim sharded over `axis_name`.

    Called under an active mesh context (inside shard_map/jit with the axis
    bound) it runs per-shard directly; given a `mesh` it wraps itself in
    shard_map with batch over (data, fsdp), heads over tensor, seq over
    `axis_name`.
    """
    if mesh is None:
        return _ring_attention_sharded(q, k, v, axis_name, causal)
    spec = P(("data", "fsdp"), axis_name, "tensor", None)
    fn = jax.shard_map(
        functools.partial(_ring_attention_sharded, axis_name=axis_name,
                          causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


def reference_attention(q, k, v, causal: bool = True):
    """Unsharded reference implementation (for tests and 1-device paths)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)
