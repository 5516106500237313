"""Flash attention: tiled online-softmax attention as Pallas TPU kernels.

Forward pass is a Pallas kernel (grid over batch × heads × q-blocks with an
inner k-block sweep; scores never hit HBM) that also emits the per-row
logsumexp. Backward is two Pallas kernels recomputing p = exp(s - lse)
per tile: a dk/dv kernel (grid over k-blocks, inner q sweep) and a dq
kernel (grid over q-blocks, inner k sweep) — the [Sq, Sk] score matrix
never materialises in HBM in either direction. Long-context training
memory is additionally handled one level up by ring attention
(`ray_tpu.parallel.ring_attention`), which only ever sees per-chunk blocks.
All three kernels take a sliding window (`window`: a row sees that many
keys, itself the last): tiles wholly outside a block's windows are
neither fetched nor computed, forward and backward. The forward kernel
alone can leave the logsumexp unwritten (`flash_attention_forward`, a
served prefill's).

Layout: public API takes [batch, seq, heads, head_dim] (matching the rest
of the framework); the kernel runs in [batch, heads, seq, head_dim]. GQA is
supported by indexing the KV head as ``h * num_kv_heads // num_heads`` in
the BlockSpec index maps — no KV replication in HBM.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# Grid order of all three kernels: batch, head and the outer block axis
# are independent; the inner sweep accumulates.
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))
# The backward kernels of a windowed call: the second edge's mask is one
# more [block_q, block_k] array among the tile's intermediates, and at
# blocks of 1024 the dk/dv kernel then asks for 18.7 MB of fast memory
# against the compiler's default limit of 16 (of the core's 128).
_WINDOWED_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=32 << 20)


def on_tpu() -> bool:
    """Whether this process's default JAX backend is a TPU — the one
    place the model and the kernels take that decision from. A backend
    that cannot be opened raises here; it never reads as "not a TPU"."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_ref, l_ref, acc_ref, *,
                      sm_scale: float, causal: bool,
                      block_q: int, block_k: int, sk: int,
                      window: Optional[int] = None,
                      block: Optional[int] = None):
    """`window` (with `causal`): a row sees that many keys, itself the
    last of them. `block` (with `causal`, a power of two that divides
    `block_q`): a row sees the keys up to the end of its own block of
    that many positions, both ways inside one and causal between them;
    a tile's edge is a block's edge, so the tiles skipped are
    causality's. `lse_ref` is None where no backward pass will ask for
    the logsumexp (`flash_attention_forward`)."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Blocks fully above the diagonal contribute nothing under causality.
    should_compute = True
    if causal:
        should_compute = (iq + 1) * block_q > ik * block_k
    if window is not None:
        # Nor do blocks whose newest key lies a window or more behind
        # the block's first row.
        should_compute &= iq * block_q - ((ik + 1) * block_k - 1) < window

    # Ragged last k-block (sk % block_k != 0): the padded columns hold
    # undefined memory and must not feed the online softmax. Statically
    # elided when shapes divide evenly.
    pad_cols = sk % block_k != 0

    def compute(apply_mask):
        # Matmul inputs stay in their storage dtype (bf16 on the training
        # path) with float32 accumulation — an f32 upcast before the dot
        # would push the MXU onto its much slower fp32 path. sm_scale is
        # folded into the [bq, d] q tile instead of being spent as a full
        # [bq, bk] pass over the score matrix.
        q = q_ref[0, 0] * jnp.asarray(sm_scale, q_ref.dtype)  # [bq, d]
        k = k_ref[0, 0]                          # [bk, d]
        v = v_ref[0, 0]                          # [bk, d]
        if pad_cols:
            # Padded K/V rows hold undefined memory; a masked p of exactly
            # 0 still yields NaN from 0 * NaN in p @ v — zero them.
            kv_rows = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, v.shape[-1]), 0)
            v = jnp.where(kv_rows < sk, v, jnp.zeros_like(v))
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                         # [bq, bk]
        mask = None
        if apply_mask:
            rows = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            if block is not None:
                # The last position of the row's block.
                rows = rows | (block - 1)
            if causal and pad_cols:
                mask = (rows >= cols) & (cols < sk)
            elif causal:
                mask = rows >= cols
            else:
                mask = cols < sk
            if window is not None:
                mask &= rows - cols < window
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:]                         # [bq, 128], lanes equal
        l_prev = l_ref[:]
        m_cur = jnp.max(s, axis=-1, keepdims=True)          # [bq, 1]
        m_next = jnp.maximum(m_prev, m_cur)                 # [bq, 128]
        p = jnp.exp(s - m_next[:, :1])                      # [bq, bk]
        if mask is not None:
            # Also covers fully-masked rows (m = -inf would give p = 1).
            p = jnp.where(mask, p, 0.0)
        correction = jnp.exp(m_prev[:, :1] - m_next[:, :1])  # [bq, 1]
        l_ref[:] = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:] = m_next
        # p in the storage dtype for the PV matmul (FlashAttention-standard;
        # keeps the MXU on its fast path), accumulate in f32.
        acc_ref[:] = acc_ref[:] * correction + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if not causal and not pad_cols:
        pl.when(should_compute)(lambda: compute(False))
    elif not causal:
        pl.when(should_compute)(lambda: compute(True))
    else:
        # The kernel is VPU-bound, so mask arithmetic is a real cost:
        # only blocks intersecting the diagonal (or the ragged tail) pay
        # for the iota/compare/select passes; blocks fully below the
        # diagonal — most of the sweep for long sequences — skip them.
        needs_mask = iq * block_q < (ik + 1) * block_k - 1
        if window is not None:
            # ... and those the window's far edge crosses.
            needs_mask |= (iq + 1) * block_q - 1 - ik * block_k >= window
        if pad_cols:
            needs_mask = needs_mask | (ik == nk - 1)
        pl.when(should_compute & needs_mask)(lambda: compute(True))
        pl.when(should_compute & jnp.logical_not(needs_mask))(
            lambda: compute(False))

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0, 0] = (acc_ref[:] / l[:, :1]).astype(o_ref.dtype)
        # Per-row logsumexp (lane-broadcast), consumed by the backward
        # kernels to recompute p = exp(s - lse) per tile.
        if lse_ref is not None:
            lse_ref[0, 0] = m_ref[:] + jnp.log(l)


def _flash_fwd(q, k, v, causal: bool, sm_scale: float,
               block_q: int, block_k: int, interpret: bool,
               window: Optional[int] = None, with_lse: bool = True,
               block: Optional[int] = None):
    """q: [B, H, S, D]; k/v: [B, Hkv, Sk, D] (already transposed).

    Returns ``(o, lse)`` where ``lse`` is the per-row logsumexp with shape
    ``[B, H, Sq]`` (float32), needed by the Pallas backward; None without
    `with_lse`, and then the kernel writes none.
    """
    b, h, sq, d = q.shape
    _, h_kv, sk, _ = k.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    grid = (b, h, pl.cdiv(sq, block_q), pl.cdiv(sk, block_k))
    assert block is None or (causal and window is None and block > 0
                             and not block & (block - 1)
                             and not block_q % block), (block, block_q)

    def kv_index(ib, ih, iq, ik):
        if causal:
            # Blocks strictly above the diagonal are skipped by the kernel;
            # clamp their fetch index to the diagonal block so the pipeline
            # doesn't stream K/V tiles that are never read.
            ik = jnp.minimum(ik, ((iq + 1) * block_q - 1) // block_k)
        if window is not None:
            # Nor those wholly behind the window of the block's first row.
            ik = jnp.maximum(ik, (iq * block_q - window + 1) // block_k)
        return (ib, ih * h_kv // h, ik, 0)

    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, sk=sk, window=window, block=block,
    )
    if not with_lse:
        with_out = kernel

        def kernel(q_ref, k_ref, v_ref, o_ref, *scratch):
            with_out(q_ref, k_ref, v_ref, o_ref, None, *scratch)
    scratch_shapes = [
        pltpu.VMEM((block_q, 128), jnp.float32),  # m
        pltpu.VMEM((block_q, 128), jnp.float32),  # l
        pltpu.VMEM((block_q, d), jnp.float32),    # acc
    ]

    n_out = 2 if with_lse else 1
    o, *lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 128),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        ][:n_out],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 128), jnp.float32),
        ][:n_out],
        scratch_shapes=scratch_shapes,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return o, lse[0][..., 0] if with_lse else None


# ---------------------------------------------------------------------------
# Backward kernels
#
# Standard flash backward (reference design: the FlashAttention-2 paper's
# tiling; no code shared with any framework): with lse saved from the
# forward and delta = rowsum(do * o) precomputed,
#   p  = exp(s - lse)          s = scale * q @ k^T
#   dv = p^T @ do
#   dp = do @ v^T
#   ds = p * (dp - delta) * scale
#   dk = ds^T @ q
#   dq = ds @ k
# Split into two kernels so every output is written by exactly one grid
# lane: dk/dv (grid over k-blocks, inner q sweep) and dq (grid over
# q-blocks, inner k sweep).
# ---------------------------------------------------------------------------


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *,
                          sm_scale: float, causal: bool,
                          block_q: int, block_k: int, sq: int, sk: int,
                          window: Optional[int] = None):
    """`window` as the forward kernel's: a block of keys is seen by the
    blocks of queries from its own up to the one a window ahead."""
    ik = pl.program_id(2)
    iq = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    should_compute = True
    if causal:
        should_compute = (iq + 1) * block_q > ik * block_k
    if window is not None:
        # Nor does a block of queries whose first row lies a window or
        # more ahead of the block's newest key.
        should_compute &= iq * block_q - ((ik + 1) * block_k - 1) < window

    pad_rows = sq % block_q != 0

    def compute(apply_mask):
        # Storage-dtype matmul inputs, f32 accumulation; sm_scale folded
        # into the q tile (dk = ds^T @ (scale*q) is the exact gradient —
        # see the math above).
        q = q_ref[0, 0] * jnp.asarray(sm_scale, q_ref.dtype)   # [bq, d]
        k = k_ref[0, 0]                            # [bk, d]
        v = v_ref[0, 0]                            # [bk, d]
        do = do_ref[0, 0]                          # [bq, d]
        lse = lse_ref[0, 0][:, :1]                 # [bq, 1]
        delta = delta_ref[0, 0][:, :1]             # [bq, 1]
        if apply_mask and pad_rows:
            # Ragged last q-block: padded rows hold undefined memory and
            # would pollute the dk/dv column sums — zero their inputs.
            q_rows = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, q.shape[-1]), 0)
            q = jnp.where(q_rows < sq, q, jnp.zeros_like(q))
            do = jnp.where(q_rows < sq, do, jnp.zeros_like(do))

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                          # [bq, bk]
        p = jnp.exp(s - lse)

        mask = None
        if apply_mask:
            rows = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.full((block_q, block_k), True)
            if causal:
                cols = ik * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                mask &= rows >= cols
                if window is not None:
                    mask &= rows - cols < window
            if pad_rows:
                mask &= rows < sq
            p = jnp.where(mask, p, 0.0)

        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                          # [bq, bk]
        ds = p * (dp - delta)
        if mask is not None:
            ds = jnp.where(mask, ds, 0.0)

        # dv += p^T @ do ; dk += ds^T @ q  (contract over the q rows)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # Masking is needed only on diagonal-intersecting blocks and (for a
    # ragged sq) the last q block; padded k columns are column-separable
    # here — their garbage lands in dk/dv rows that are sliced off.
    if not causal and not pad_rows:
        pl.when(should_compute)(lambda: compute(False))
    else:
        needs_mask = False
        if causal:
            needs_mask = iq * block_q < (ik + 1) * block_k - 1
        if window is not None:
            # ... and the tiles the window's far edge crosses.
            needs_mask |= (iq + 1) * block_q - 1 - ik * block_k >= window
        if pad_rows:
            needs_mask = needs_mask | (iq == nq - 1)
        pl.when(should_compute & needs_mask)(lambda: compute(True))
        pl.when(should_compute & jnp.logical_not(needs_mask))(
            lambda: compute(False))

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_acc, *,
                         sm_scale: float, causal: bool,
                         block_q: int, block_k: int, sk: int,
                         window: Optional[int] = None):
    """`window` as the forward kernel's, and the same blocks of keys
    skipped."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    should_compute = True
    if causal:
        should_compute = (iq + 1) * block_q > ik * block_k
    if window is not None:
        should_compute &= iq * block_q - ((ik + 1) * block_k - 1) < window

    pad_cols = sk % block_k != 0

    def compute(apply_mask):
        # Storage-dtype matmul inputs, f32 accumulation; sm_scale folded
        # into the q tile, un-applied to dq in _finalize.
        q = q_ref[0, 0] * jnp.asarray(sm_scale, q_ref.dtype)   # [bq, d]
        k = k_ref[0, 0]                            # [bk, d]
        v = v_ref[0, 0]                            # [bk, d]
        do = do_ref[0, 0]                          # [bq, d]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        if apply_mask and pad_cols:
            # Padded K/V rows hold undefined memory; dq = ds @ k mixes k
            # rows into every dq element, so zero them (ds is masked to 0
            # there, but 0 * NaN would still poison the product).
            kv_rows = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, k.shape[-1]), 0)
            k = jnp.where(kv_rows < sk, k, jnp.zeros_like(k))
            v = jnp.where(kv_rows < sk, v, jnp.zeros_like(v))

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        p = jnp.exp(s - lse)

        mask = None
        if apply_mask:
            rows = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            if causal and pad_cols:
                mask = (rows >= cols) & (cols < sk)
            elif causal:
                mask = rows >= cols
            else:
                mask = cols < sk
            if window is not None:
                mask &= rows - cols < window
            p = jnp.where(mask, p, 0.0)

        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        if mask is not None:
            ds = jnp.where(mask, ds, 0.0)

        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if not causal and not pad_cols:
        pl.when(should_compute)(lambda: compute(False))
    else:
        needs_mask = False
        if causal:
            needs_mask = iq * block_q < (ik + 1) * block_k - 1
        if window is not None:
            needs_mask |= (iq + 1) * block_q - 1 - ik * block_k >= window
        if pad_cols:
            needs_mask = needs_mask | (ik == nk - 1)
        pl.when(should_compute & needs_mask)(lambda: compute(True))
        pl.when(should_compute & jnp.logical_not(needs_mask))(
            lambda: compute(False))

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = (dq_acc[:] * sm_scale).astype(dq_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, causal: bool, sm_scale: float,
               block_q: int, block_k: int, interpret: bool,
               window: Optional[int] = None):
    """All tensors [B, H(kv), S, D]; lse [B, H, Sq] float32. With
    `window` the index maps, like the kernels, leave out the tiles that
    lie wholly outside the windows: the blocks of keys behind a block of
    queries' (dq), the blocks of queries ahead of a block of keys'
    (dk/dv)."""
    b, h, sq, d = q.shape
    _, h_kv, sk, _ = k.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)

    # A kernel without a window is handed none: the call, and so the
    # program lowered from it, is the one it was before there was one.
    windowed = {} if window is None else {"window": window}
    compiler_params = _COMPILER_PARAMS if window is None \
        else _WINDOWED_PARAMS
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse4 = jnp.broadcast_to(lse[..., None], (b, h, sq, 128))
    delta4 = jnp.broadcast_to(delta[..., None], (b, h, sq, 128))

    def kv_index(ib, ih, iq, ik):
        if causal:
            ik = jnp.minimum(ik, ((iq + 1) * block_q - 1) // block_k)
        if window is not None:
            ik = jnp.maximum(ik, (iq * block_q - window + 1) // block_k)
        return (ib, ih * h_kv // h, ik, 0)

    def q_index(ib, ih, iq, ik):
        return (ib, ih, iq, 0)

    def lane_index(ib, ih, iq, ik):
        return (ib, ih, iq, 0)

    # --- dq: grid over q-blocks, inner sweep over k-blocks -----------------
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, sk=sk, **windowed),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            pl.BlockSpec((1, 1, block_q, d), q_index),
            pl.BlockSpec((1, 1, block_q, 128), lane_index),
            pl.BlockSpec((1, 1, block_q, 128), lane_index),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), q_index),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=compiler_params,
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse4, delta4)

    # --- dk/dv: grid over k-blocks, inner sweep over q-blocks --------------
    # For causal masks the head of the q sweep is skipped; clamp the fetch
    # index up to the first contributing q-block.
    # Under a window its tail is skipped too: clamp down to the last.
    def q_index_dkv(ib, ih, ik, iq):
        if causal:
            iq = jnp.maximum(iq, (ik * block_k) // block_q)
        if window is not None:
            iq = jnp.minimum(
                iq, ((ik + 1) * block_k + window - 2) // block_q)
        return (ib, ih, iq, 0)

    lane_index_dkv = q_index_dkv

    def kv_index_dkv(ib, ih, ik, iq):
        return (ib, ih * h_kv // h, ik, 0)

    def dkv_out_index(ib, ih, ik, iq):
        return (ib, ih, ik, 0)

    # dk/dv are produced per *query* head (float32) and group-reduced to the
    # kv heads afterwards — no KV replication in HBM on the way in.
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, sq=sq, sk=sk, **windowed),
        grid=(b, h, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), q_index_dkv),
            pl.BlockSpec((1, 1, block_k, d), kv_index_dkv),
            pl.BlockSpec((1, 1, block_k, d), kv_index_dkv),
            pl.BlockSpec((1, 1, block_q, d), q_index_dkv),
            pl.BlockSpec((1, 1, block_q, 128), lane_index_dkv),
            pl.BlockSpec((1, 1, block_q, 128), lane_index_dkv),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), dkv_out_index),
            pl.BlockSpec((1, 1, block_k, d), dkv_out_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=compiler_params,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse4, delta4)

    if h_kv != h:
        rep = h // h_kv
        dk = dk.reshape(b, h_kv, rep, sk, d).sum(axis=2)
        dv = dv.reshape(b, h_kv, rep, sk, d).sum(axis=2)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Reference math (used on non-TPU backends and as the test oracle)
# ---------------------------------------------------------------------------


def attention_reference(q, k, v, causal: bool, sm_scale: float,
                        window: Optional[int] = None,
                        block: Optional[int] = None):
    """[B, H, S, D] layout. GQA-aware. `window` and `block` as the
    forward kernel's."""
    b, h, sq, d = q.shape
    h_kv = k.shape[1]
    if h_kv != h:
        rep = h // h_kv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        sk = k.shape[2]
        rows = jnp.arange(sq) if block is None \
            else jnp.arange(sq) // block * block + block - 1
        back = rows[:, None] - jnp.arange(sk)[None, :]
        mask = back >= 0 if window is None else (back >= 0) & (back < window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, sm_scale, block_q, block_k, interpret,
           window=None):
    o, _ = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                      window=window)
    return o


def _flash_vjp_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                   window=None):
    o, lse = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                        interpret, window=window)
    # Under layer-level rematerialization, saving these two residuals (and
    # recomputing only the cheap projections for q/k/v) lets the remat
    # policy elide the forward kernel from the backward pass entirely:
    # jax.checkpoint_policies.save_only_these_names("flash_out", "flash_lse").
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    # Optionally saveable (policy decides): skips the qkv-projection +
    # rope recompute in the backward at ~50MB/layer for typical configs.
    q = checkpoint_name(q, "flash_q")
    k = checkpoint_name(k, "flash_k")
    v = checkpoint_name(v, "flash_v")
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, sm_scale, block_q, block_k, interpret, window,
                   residuals, do):
    q, k, v, o, lse = residuals
    return _flash_bwd(q, k, v, o, lse, do, causal, sm_scale,
                      block_q, block_k, interpret, window)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    sm_scale: Optional[float] = None,
                    block_q: int = 1024, block_k: int = 1024,
                    interpret: bool = False):
    """Flash attention over [batch, seq, heads, head_dim] tensors, with
    a backward pass. With `window` (causal only) a row sees that many
    keys and no more, itself the last of them, and the tiles wholly
    outside the windows are neither read nor computed, forward or
    backward.

    KV tensors may have fewer heads (GQA). On a TPU backend this is
    always the compiled kernel: `interpret` never reaches a TPU call,
    and a kernel Mosaic refuses is an error, not a switch to the
    reference. On other backends it is the fused-by-XLA reference unless
    `interpret=True` runs the kernel through the Pallas interpreter
    (used by tests).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    assert window is None or (causal and window > 0), (causal, window)
    if on_tpu() or interpret:
        out = _flash(qt, kt, vt, causal, sm_scale, block_q, block_k,
                     not on_tpu(), window)
    else:
        out = attention_reference(qt, kt, vt, causal, sm_scale, window)
    return out.transpose(0, 2, 1, 3)


def forward_tile(rows: int, most: int = 1024) -> int:
    """The tile `flash_attention_forward` takes along an axis of `rows`
    positions: for a multiple of 128 the largest multiple of 128 that
    divides it and is at most `most`, so that no tile hangs over the
    end (1,024 wherever that divides the rows, the rows themselves up
    to 1,024, 640 for 1,280, 896 for 3,584); for any other count, or a
    `most` under 128, `most` or the rows, whichever is less, and the
    kernel masks a ragged last tile."""
    if rows % 128 or most < 128:
        return min(most, rows)
    return max(t for t in range(128, min(most, rows) + 1, 128)
               if rows % t == 0)


def flash_attention_forward(q, k, v, *, window: Optional[int] = None,
                            block: Optional[int] = None,
                            sm_scale: Optional[float] = None,
                            block_q: int = 1024, block_k: int = 1024,
                            interpret: bool = False):
    """Causal attention with no backward pass, for a served prefill over
    its own call's keys: `flash_attention`'s forward kernel, which here
    writes no logsumexp, and with `window` a row sees that many keys and
    no more, itself the last of them (the blocks of keys wholly behind a
    block of queries' windows are neither read nor computed); with
    `block` (a power of two, and no `window`) attention is causal
    between blocks of that many positions and goes both ways inside
    one: row i sees key j iff j // block <= i // block, what a model
    that generates by diffusion over blocks prefills with. Layout,
    GQA and the choice between kernel, interpreter and reference as
    `flash_attention`'s; `block_q` and `block_k` are the most a tile
    may hold, and `forward_tile` says what it does hold. Head sizes
    served: 128 (a row of lanes a head) and 64 (LFM2's: blocks
    [block_q, 64], half a row, which Mosaic takes as they are; compiled
    for the v5e and run there, PERF.md, PR 55); no other has been
    compiled."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    if on_tpu() or interpret:
        out, _ = _flash_fwd(qt, kt, vt, True, sm_scale,
                            forward_tile(qt.shape[2], block_q),
                            forward_tile(kt.shape[2], block_k),
                            not on_tpu(), window=window, with_lse=False,
                            block=block)
    else:
        out = attention_reference(qt, kt, vt, True, sm_scale, window, block)
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Decode attention: one token a slot over the slot cache where it lies
# ---------------------------------------------------------------------------

# A block of a leaf holds at most this many positions and this many
# bytes. A slot's count of keys is rounded up to a block, so half a
# block a slot and leaf is fetched for nothing, and a grid step costs
# some 0.35 us whether it fetches or not: 256 positions keep both near a
# tenth of what a half-filled region of 1,024 or 2,048 costs to read
# (PERF.md, PR 43). Two blocks of K and two of V are in fast memory at
# once.
_DECODE_ROWS = 256
_DECODE_BLOCK_BYTES = 2 << 20


def decode_block_rows(kv_heads: int, head_dim: int, dtype) -> int:
    """Positions of a block of `decode_attention`, from a leaf's shapes
    alone: the largest power of two that is at most `_DECODE_ROWS` and
    whose keys are at most `_DECODE_BLOCK_BYTES`. A region shorter than
    that is one block."""
    row = kv_heads * head_dim * jnp.dtype(dtype).itemsize
    rows = min(_DECODE_ROWS, max(16, _DECODE_BLOCK_BYTES // row))
    return 1 << (rows.bit_length() - 1)


def _decode_kernel(layer_ref, len_ref, q_ref, own_ref, k_ref, v_ref, o_ref,
                   qs_ref, m_ref, l_ref, acc_ref, *, rows: int, group: int,
                   lane_heads: int, rep: int, sm_scale: float,
                   limits: int = 1):
    """One slot's block of `rows` positions. A block of K or V is
    [rows x group, lane_heads x D] as the leaf stores it: `group` key
    heads a position lie in consecutive rows (the dense leaf's
    [S, Hkv, D], rows and heads merged) or `lane_heads` of them side by
    side in a row (Olmo-Hybrid's [S, Hkv x D]). Either way the scores of
    all query heads are one product with the block where it lies: q is
    [heads, lane_heads x D] with a head's channels under its key head's
    lanes and zeros elsewhere, `own_ref` [heads, rows x group] takes
    the columns of other key heads' rows out (-1e30; zeros where a row
    holds every head), and of the weighted sum [heads,
    lane_heads x D] a head keeps the D lanes of its key head. With
    `limits` over 1 a slot has so many lengths, and the query heads of
    a key head are as many equal groups in order, each seeing the keys
    under its own."""
    del layer_ref  # the K and V blocks' index maps read it
    i = pl.program_id(1)
    d = q_ref.shape[-1]
    if limits == 1:
        n = len_ref[pl.program_id(0)]
    else:
        own = [len_ref[pl.program_id(0) * limits + j] for j in range(limits)]
        n, least = functools.reduce(jnp.maximum, own), \
            functools.reduce(jnp.minimum, own)
        # A row of the scores is a query head: its group's length.
        part = jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[0], 1), 0) % rep // (rep // limits)
        n_of_head = functools.reduce(
            lambda at, j: jnp.where(part == j, own[j], at),
            range(1, limits), jnp.full(part.shape, own[0], jnp.int32))

    @pl.when(i == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        q = q_ref[...]
        if lane_heads > 1:
            wide = jnp.concatenate([q.astype(jnp.float32)] * lane_heads, 1)
            lane = jax.lax.broadcasted_iota(jnp.int32, wide.shape, 1)
            first = jax.lax.broadcasted_iota(
                jnp.int32, wide.shape, 0) // rep * d
            q = jnp.where((lane >= first) & (lane < first + d), wide,
                          0.0).astype(q.dtype)
        qs_ref[...] = q

    def attend(edge: bool):
        k, v = k_ref[...], v_ref[...]
        s = jax.lax.dot_general(
            qs_ref[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale + own_ref[...]
        if edge:
            # The slot's last block: rows past its length hold whatever
            # was there before. Their scores are masked, and they are
            # zeroed in V (0 x NaN is NaN).
            left = (n - i * rows) * group
            s = jnp.where(jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1) < (left if limits == 1 else (
                    n_of_head - i * rows) * group), s, _NEG_INF)
            v = jnp.where(jax.lax.broadcasted_iota(
                jnp.int32, v.shape, 0) < left, v.astype(jnp.float32),
                0.0).astype(v.dtype)
        # Every head sees a key in every block computed (the block's
        # first position lies under the length), so a masked column's
        # exp is 0 and needs no second mask.
        m_prev = m_ref[:]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_next[:, :1])
        correction = jnp.exp(m_prev - m_next)
        l_ref[:] = l_ref[:] * correction + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:] = m_next
        acc_ref[:] = acc_ref[:] * correction[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # A block past the slot's length is neither fetched (its index map
    # names the next slot's first block all along) nor computed.
    # (Every head has seen a key by then: a length is 1 at least, so a
    # later block that lies past a head's own length adds nothing to it.)
    start = i * rows
    whole = n if limits == 1 else least
    pl.when(start + rows <= whole)(lambda: attend(False))
    pl.when((start < n) & (start + rows > whole))(lambda: attend(True))

    @pl.when(i == pl.num_programs(1) - 1)
    def _finalize():
        inv = 1.0 / l_ref[:, :1]
        if lane_heads == 1:
            o_ref[...] = acc_ref[:] * inv
        else:
            o_ref[...] = jnp.zeros_like(o_ref)
            for kv in range(lane_heads):
                at = slice(kv * rep, (kv + 1) * rep)
                o_ref[at, :] = acc_ref[at, kv * d:(kv + 1) * d] * inv[at]


@functools.partial(jax.jit, static_argnames=("rows", "group", "rep",
                                             "interpret", "limits"))
def _decode_call(q, own, k, v, layer, lengths, *, rows: int, group: int,
                 rep: int, interpret: bool, limits: int = 1):
    """The kernel's call: q [B, heads, D], k and v [layers, B,
    S x group, width], lengths [B x limits]. Jitted, so that a
    program's layers trace and lower it once."""
    _, slots, span, width = k.shape
    heads, d = q.shape[1:]
    lane_heads = width // d
    block = rows * group

    def rows_of(b, i, layer, lengths):
        # Past the slot's last needed block: the next slot's first, so
        # that it is on its way while this slot's last is worked on and
        # is not fetched again when its turn comes.
        longest = lengths[b] if limits == 1 else functools.reduce(
            jnp.maximum, [lengths[b * limits + j] for j in range(limits)])
        needed = i * rows < longest
        ahead = jnp.minimum(b + 1, slots - 1)
        return (layer[0], jnp.where(needed, b, ahead),
                jnp.where(needed, i, 0), 0)

    leaf = pl.BlockSpec((None, None, block, width), rows_of)
    a_slot = pl.BlockSpec((None, heads, d), lambda b, i, *_: (b, 0, 0))
    block_bytes = block * width * k.dtype.itemsize
    return pl.pallas_call(
        functools.partial(
            _decode_kernel, rows=rows, group=group, lane_heads=lane_heads,
            rep=rep, sm_scale=d ** -0.5, limits=limits),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots, pl.cdiv(span, block)),
            in_specs=[a_slot,
                      pl.BlockSpec(own.shape, lambda b, i, *_: (0, 0)),
                      leaf, leaf],
            out_specs=a_slot,
            scratch_shapes=[
                pltpu.VMEM((heads, width), q.dtype),       # q, spread
                pltpu.VMEM((heads, 128), jnp.float32),     # m
                pltpu.VMEM((heads, 128), jnp.float32),     # l
                pltpu.VMEM((heads, width), jnp.float32),   # acc
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=6 * block_bytes + (16 << 20)),
        interpret=interpret,
        name="decode_attention",
    )(layer[None], lengths, q, own, k, v)


def decode_attention(q, k_stack, v_stack, layer, lengths, *,
                     interpret: bool = False):
    """One token a slot attends the keys its slot holds, read out of the
    run's stacks where they lie: q [B, H, D] in the stacks' dtype;
    `k_stack` and `v_stack` a run's leaves whole, [layers, B, S, Hkv, D]
    or [layers, B, S, Hkv x D]; `layer` an int32 scalar; `lengths`
    int32 [B], the keys a row sees (its position + 1, this step's row
    written) -> [B, H, D]. `lengths` [B, n] gives a slot n of them: the
    `H // Hkv` query heads of a key head are n equal groups in order,
    and group j sees `lengths[:, j]` keys, the slot's keys read once
    for all of them (a step of n blocks of a model that generates by
    blocks, each block's queries standing as query heads).

    The grid is (slot, block of `decode_block_rows` positions). `layer`
    and `lengths` are scalar-prefetch arguments: the K and V blocks'
    index maps pick (layer, slot, block), and past a slot's length the
    next slot's first block, so nothing slices a stack, no other layer
    is touched, and a block past a slot's length is neither fetched nor
    computed. Online softmax across blocks; scores, mask and both
    accumulations float32, the weights cast to the stacks' dtype for
    the product with V, and the `H // Hkv` query heads of a key head
    contracted together: what `llama._cached_attention` guarantees.

    Head sizes served: 128 (the dense leaf's rows x heads, and merged
    leaves of 4 and 30 heads) and 64 on a merged leaf (LFM2's 8 x 64 =
    512 channels, four query heads a key head: q is spread over slices
    of 64 lanes that start at multiples of 64, and a head keeps such a
    slice of the weighted sum; compiled for the v5e and run there,
    PERF.md, PR 55). A dense leaf [.., Hkv, 64] has not been compiled:
    its view [S x Hkv, 64] is half-filled rows of lanes, and a family
    with such heads merges them.

    On a TPU backend this is always the compiled kernel: `interpret`
    never reaches a TPU call, and a kernel Mosaic refuses is an error.
    On other backends it is `llama._cached_attention` on the sliced
    layer (looked up in its module when traced), unless `interpret=True`
    runs the kernel through the Pallas interpreter (used by tests)."""
    b, h, d = q.shape
    span = k_stack.shape[2]
    kv_heads = math.prod(k_stack.shape[3:]) // d
    lengths = jnp.clip(lengths, 1, span).astype(jnp.int32)
    limits = 1 if lengths.ndim == 1 else lengths.shape[1]
    interpret = interpret and not on_tpu()
    if not (on_tpu() or interpret):
        from ray_tpu.models import decoder, llama
        keys, values = (
            decoder.layer_rows(x, layer, 0, span).reshape(
                b, span, kv_heads, d) for x in (k_stack, v_stack))
        plain = llama._cached_attention  # raylint: disable=R3 -- the plain path is the served model's own, found by the name the benchmark's tests patch; no second copy of its arithmetic lives here
        if limits == 1:
            return plain(None, q[:, None], keys, values,
                         lengths[:, None] - 1)[:, 0]
        # A group of heads at a time, as a call of its own would run
        # it: [B, key heads, n, a group's heads, D].
        groups = q.reshape(b, kv_heads, limits, -1, d)
        return jnp.stack([
            plain(None, groups[:, :, j].reshape(b, 1, -1, d), keys, values,
                  lengths[:, j, None] - 1)[:, 0].reshape(
                      groups[:, :, j].shape)
            for j in range(limits)], 2).reshape(q.shape)
    rows = min(decode_block_rows(kv_heads, d, k_stack.dtype), span)
    group = kv_heads if k_stack.ndim == 5 else 1
    if group > 1:
        # [S, Hkv, D] as [S x Hkv, D]: the same bytes, as the TPU tiles
        # them too (a tile is 8 rows of 128 lanes).
        k_stack, v_stack = (x.reshape(x.shape[:2] + (span * group, d))
                            for x in (k_stack, v_stack))
    # Query heads in whole tiles of the stacks' dtype; the rows added
    # attend like any other and are cut off.
    padded = -(-h // 16) * 16
    q = jnp.pad(q, ((0, 0), (0, padded - h), (0, 0)))
    rep = h // kv_heads
    # A block's row r holds key head r % group; a query head's own is
    # head // rep. (Every head's, where a row holds them all.)
    own = jnp.where(
        jnp.arange(rows * group)[None, :] % group
        == jnp.arange(padded)[:, None] // rep % group, 0.0, _NEG_INF)
    assert limits == 1 or (padded == h and rep % limits == 0), \
        (h, rep, limits)
    out = _decode_call(q, own, k_stack, v_stack,
                       jnp.asarray(layer, jnp.int32), lengths.reshape(-1),
                       rows=rows, group=group, rep=rep, interpret=interpret,
                       limits=limits)
    return out[:, :h].astype(q.dtype)
