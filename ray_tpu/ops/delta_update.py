"""The gated delta rule's one-token recurrence on the state where it
lies: a head's state `[dk, dv]` is read once out of a run's stack
`[layers, slots, heads, dk, dv]`, decayed, corrected and written back to
the place it came from.

    s = (fresh ? 0 : S) * gamma[:, None];   u = beta * (v - sum_i s[i, :] k[i])
    s = s + k (outer) u;                    o = sum_i s[i, :] q[i]

The decay is a number a key channel, `gamma [dk]` a head (Kimi Delta
Attention), or one number a head (Gated DeltaNet), which is the column
that repeats it: one kernel serves both, and a scalar decay costs
`dk` floats a head beside the `dk x dv` state they decay.

Written as `jnp` ops on a layer sliced out of the stack (`reference`,
and `dynamic_update_index_in_dim` to put the layer back) the TPU
compiler makes three passes over the layer's states, one for each sum
and one that adds the correction and writes the layer into the stack,
each at memory speed: a quarter of a served step's device time where
one read and one write are needed (PERF.md, PR 42). The kernel's grid is
(slot, block of heads); the state's block is picked out of the stack by
its index map from scalar-prefetch arguments (`layer`), the stack is
aliased to the output, and Pallas's pipeline has the next block in
flight while this one is worked on. Nothing slices the stack and no
other layer of it is touched.

Float32 wherever the state is touched, whatever the stack stores, and no
matmul unit: the two sums over `dk` are sublane reductions. `k`, `q` and
`gamma` arrive a column a head (`dk` on sublanes, so that their
broadcast over `dv` is a lane broadcast), `v` a row a head, `beta` as a
scalar.

On a TPU backend this is always the compiled kernel; on other backends
it is `reference` unless `interpret=True` runs the kernel through the
Pallas interpreter (used by tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import on_tpu


def reference(s0, q, k, v, gamma, beta):
    """The recurrence for one token, float32 throughout and elementwise
    (no matmul unit rounds the state): s0 [B, H, dk, dv], q and k
    [B, H, dk], v [B, H, dv], gamma [B, H] (a decay a head) or
    [B, H, dk] (a decay a key channel), beta [B, H] -> (o [B, H, dv],
    S [B, H, dk, dv])."""
    s = s0 * gamma.reshape(gamma.shape[:2] + (-1, 1))
    u = beta[..., None] * (v - (s * k[..., None]).sum(-2))
    s = s + k[..., None] * u[..., None, :]
    return (s * q[..., None]).sum(-2), s


# A block of states is at most this many bytes as the TPU stores them
# (one is read and one written while the next two are in flight).
# Blocks of 0.6 to 3 MB move at one speed, 0.3 MB ones 6 % slower
# (PERF.md, PR 42).
_BLOCK_BYTES = 3 << 19


def _stored_bytes(dk: int, dv: int, dtype) -> int:
    """Bytes of one head's [dk, dv] state in the TPU's tiles: (8, 128)
    of 32 bits, twice the sublanes of 16."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * 4 // itemsize
    return -(-dk // sublanes) * sublanes * -(-dv // 128) * 128 * itemsize


def _head_block(heads: int, dk: int, dv: int, dtype) -> int:
    """Heads of a block: the heads dealt evenly over the fewest blocks
    of at most `_BLOCK_BYTES`."""
    most = max(1, _BLOCK_BYTES // _stored_bytes(dk, dv, dtype))
    return -(-heads // -(-heads // most))


def _kernel(layer_ref, fresh_ref, beta_ref, cols_ref, v_ref, s_ref, o_ref,
            new_ref, *, heads: int):
    del layer_ref  # the state block's index map reads it
    slot, hb = pl.program_id(0), s_ref.shape[0]
    kept = fresh_ref[slot] == 0
    first = pl.program_id(1) * hb
    for h in range(hb):
        # A head past the last (the last block's, where the heads are
        # not a multiple of a block) reads the last one's scalar and
        # writes nowhere.
        at = slot * heads + jnp.minimum(first + h, heads - 1)
        k = cols_ref[:, h:h + 1]                                  # [dk, 1]
        q = cols_ref[:, hb + h:hb + h + 1]
        gamma = cols_ref[:, 2 * hb + h:2 * hb + h + 1]
        s = jnp.where(kept, s_ref[h].astype(jnp.float32), 0.0) * gamma
        u = beta_ref[at] * (v_ref[h:h + 1, :] - (s * k).sum(0, keepdims=True))
        s = s + k * u
        o_ref[h:h + 1, :] = (s * q).sum(0, keepdims=True)
        new_ref[h] = s.astype(new_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(stack, layer, fresh, beta, cols, v, *, interpret: bool):
    """The kernel's call. Jitted, so that a program's runs of delta
    layers trace and lower it once."""
    _, slots, heads, dk, dv = stack.shape
    blocks, hb = v.shape[1:3]

    def small(*tail):
        return pl.BlockSpec((None, None) + tail,
                            lambda b, j, *scalars: (b, j, 0, 0))

    state = pl.BlockSpec((None, None, hb, dk, dv),
                         lambda b, j, layer, *scalars: (layer[0], b, j, 0, 0))
    block = hb * _stored_bytes(dk, dv, stack.dtype)
    return pl.pallas_call(
        functools.partial(_kernel, heads=heads),
        out_shape=(jax.ShapeDtypeStruct(v.shape, jnp.float32),
                   jax.ShapeDtypeStruct(stack.shape, stack.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots, blocks),
            in_specs=[small(dk, 3 * hb), small(hb, dv), state],
            out_specs=(small(hb, dv), state)),
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=4 * block + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=8 * slots * heads * dk * dv, transcendentals=0,
            bytes_accessed=2 * slots * heads * dk * dv
            * stack.dtype.itemsize),
        interpret=interpret,
        name="delta_update",
    )(layer[None], fresh, beta, cols, v, stack)


def delta_update(stack, layer, fresh, q, k, v, gamma, beta, *,
                 interpret: bool = False):
    """stack [layers, B, H, dk, dv], a run's state leaf whole; `layer`
    an int32 scalar; `fresh` [B], the rows that start from zeros
    whatever their slot holds; q and k [B, H, dk], v [B, H, dv], gamma
    [B, H] (one decay a head) or [B, H, dk] (one a key channel), beta
    [B, H], float32 -> (o [B, H, dv] float32, the stack with
    `stack[layer]` the new states and every other layer as it was).
    Which decay it is follows from gamma's shape and chooses nothing:
    the kernel takes a column a head either way.

    On a TPU backend that is always the compiled kernel: `interpret`
    never reaches a TPU call, and a kernel Mosaic refuses is an error,
    not a switch to `reference`."""
    interpret = interpret and not on_tpu()
    if not (on_tpu() or interpret):
        s0 = jnp.where(fresh[:, None, None, None], 0.0,
                       lax.dynamic_index_in_dim(stack, layer, 0, False)
                       .astype(jnp.float32))
        o, s = reference(s0, q, k, v, gamma, beta)
        return o, lax.dynamic_update_index_in_dim(
            stack, s.astype(stack.dtype), layer, 0)
    bsz, h, dk, dv = stack.shape[1:]
    hb = _head_block(h, dk, dv, stack.dtype)
    blocks = -(-h // hb)

    def by_block(x):  # [B, H, w] -> [B, blocks, hb, w]
        x = jnp.pad(x, ((0, 0), (0, blocks * hb - h), (0, 0)))
        return x.reshape(bsz, blocks, hb, -1)

    # A block's keys, then its queries, then its decays, a column a
    # head.
    gamma = jnp.broadcast_to(gamma.reshape(bsz, h, -1), k.shape)
    cols = jnp.concatenate([by_block(k), by_block(q), by_block(gamma)],
                           2).swapaxes(2, 3)
    o, stack = _call(
        stack, jnp.asarray(layer, jnp.int32), fresh.astype(jnp.int32),
        beta.reshape(-1), cols, by_block(v), interpret=interpret)
    return o.reshape(bsz, blocks * hb, dv)[:, :h], stack
