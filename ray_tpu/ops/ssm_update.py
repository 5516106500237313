"""The Mamba-2 one-token recurrence on the state where it lies: a head's
state `[P, N]` is read once out of a run's stack `[layers, slots, heads,
P, N]`, decayed, added to and written back to the place it came from.

    s = (fresh ? 0 : S) * decay  +  dtx (outer) B[g(h)]
    y = sum_n s[:, n] C[g(h)][n]

`decay = exp(dt a)` is one number a head, `dtx = dt xs` a column a head
(`P` on sublanes, broadcast over lanes), `B` and `C` a row a *group* of
heads (`N` on lanes, broadcast over sublanes): a slot's `2 G` rows ride
in whole and a head picks its group's two.

Written as `jnp` ops on a layer sliced out of the stack (`reference`,
and `dynamic_update_index_in_dim` to put the layer back) the TPU
compiler moves the layer's states three times, one fusion that reads
them for y and one that reads them, writes them and copies them into
the stack, each at memory speed: three tenths of Nemotron's served step
where one read and one write are needed (PERF.md, PR 58). The kernel is
`ops/delta_update.py`'s in its shape (grid (slot, block of heads), the
state's block picked out of the stack by its index map from the
scalar-prefetch `layer`, the stack aliased to the output, blocks sized
by its `_head_block`) and another in its body: this recurrence
contracts the state over its *last* axis, so y is a sum over lanes
where the delta rule's two are over sublanes.

Float32 wherever the state is touched, whatever the stack stores. The
state written is elementwise, two products and a sum as `reference` has
them, and no matmul unit rounds it. y alone goes through the matmul
unit: C's row against the head's new state, both contracted over their
last axis at `Precision.HIGHEST` (float32 operands split into bfloat16
parts, the parts' products summed in float32), which is float32's
rounding of `reference`'s sum in another order and keeps the kernel at
the speed of its memory traffic, where the vector unit's sum over lanes
took a tenth longer (PERF.md, PR 58).

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
from ray_tpu.ops.delta_update import _head_block, _stored_bytes


def reference(s0, xs, b_mat, c_mat, dt, a):
    """The recurrence for one token, float32 throughout and elementwise
    (no matmul unit rounds the state): s0 [B, H, P, N], xs [B, H, P], b
    and c [B, H, N] (a group's, repeated for its heads), dt [B, H], a
    [H] -> (y [B, H, P], S [B, H, P, N])."""
    s = s0 * jnp.exp(dt * a)[..., None, None] \
        + (dt[..., None] * xs)[..., None] * b_mat[:, :, None, :]
    return (s * c_mat[:, :, None, :]).sum(-1), s


def _kernel(layer_ref, fresh_ref, decay_ref, dtx_ref, bc_ref, s_ref, y_ref,
            new_ref, *, heads: int, groups: int):
    del layer_ref  # the state block's index map reads it
    slot, hb = pl.program_id(0), s_ref.shape[0]
    kept = fresh_ref[slot] == 0
    first = pl.program_id(1) * hb
    for h in range(hb):
        # A head past the last (the last block's, where the heads are
        # not a multiple of a block) reads the last one's scalar and
        # rows and writes nowhere.
        head = jnp.minimum(first + h, heads - 1)
        group = lax.div(head, heads // groups)
        b = bc_ref[pl.ds(group, 1), :]                            # [1, N]
        c = bc_ref[pl.ds(groups + group, 1), :]
        s = jnp.where(kept, s_ref[h].astype(jnp.float32), 0.0) \
            * decay_ref[slot * heads + head] + dtx_ref[:, h:h + 1] * b
        y_ref[h:h + 1, :] = lax.dot_general(
            jnp.broadcast_to(c, (8, c.shape[1])), s,
            (((1,), (1,)), ((), ())), precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)[:1]
        new_ref[h] = s.astype(new_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(stack, layer, fresh, decay, dtx, bc, *, interpret: bool):
    """The kernel's call. Jitted, so that a program's runs of Mamba-2
    layers trace and lower it once."""
    _, slots, heads, p, n = stack.shape
    blocks, hb = dtx.shape[1], dtx.shape[3]
    groups = bc.shape[1] // 2

    def small(*tail):
        return pl.BlockSpec((None, None) + tail,
                            lambda b, j, *scalars: (b, j, 0, 0))

    rows = pl.BlockSpec((None, 2 * groups, n),
                        lambda b, j, *scalars: (b, 0, 0))
    state = pl.BlockSpec((None, None, hb, p, n),
                         lambda b, j, layer, *scalars: (layer[0], b, j, 0, 0))
    block = hb * _stored_bytes(p, n, stack.dtype)
    return pl.pallas_call(
        functools.partial(_kernel, heads=heads, groups=groups),
        out_shape=(jax.ShapeDtypeStruct((slots, blocks, hb, p), jnp.float32),
                   jax.ShapeDtypeStruct(stack.shape, stack.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots, blocks),
            in_specs=[small(p, hb), rows, state],
            out_specs=(small(hb, p), state)),
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=4 * block + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=5 * slots * heads * p * n, transcendentals=0,
            bytes_accessed=2 * slots * heads * p * n
            * stack.dtype.itemsize),
        interpret=interpret,
        name="ssm_update",
    )(layer[None], fresh, decay, dtx, bc, stack)


def ssm_update(stack, layer, fresh, xs, b_mat, c_mat, dt, a, *,
               interpret: bool = False):
    """stack [layers, B, H, P, N], a run's state leaf whole; `layer` an
    int32 scalar; `fresh` [B], the rows that start from zeros whatever
    their slot holds; xs [B, H, P], b_mat and c_mat [B, G, N] (a row a
    group of H / G heads), dt [B, H], a [H], float32 -> (y [B, H, P]
    float32, the stack with `stack[layer]` the new states and every
    other layer as it was).

    On a TPU backend that is always the compiled kernel: `interpret`
    never reaches a TPU call, and a kernel Mosaic refuses is an error,
    not a switch to `reference`."""
    bsz, h, p, n = stack.shape[1:]
    interpret = interpret and not on_tpu()
    if not (on_tpu() or interpret):
        s0 = jnp.where(fresh[:, None, None, None], 0.0,
                       lax.dynamic_index_in_dim(stack, layer, 0, False)
                       .astype(jnp.float32))
        per_group = h // b_mat.shape[1]
        y, s = reference(s0, xs, jnp.repeat(b_mat, per_group, 1),
                         jnp.repeat(c_mat, per_group, 1), dt, a)
        return y, lax.dynamic_update_index_in_dim(
            stack, s.astype(stack.dtype), layer, 0)
    hb = _head_block(h, p, n, stack.dtype)
    blocks = -(-h // hb)
    # A block's `dt xs`, a column a head.
    dtx = jnp.pad(dt[..., None] * xs, ((0, 0), (0, blocks * hb - h), (0, 0)))
    dtx = dtx.reshape(bsz, blocks, hb, p).swapaxes(2, 3)
    y, stack = _call(
        stack, jnp.asarray(layer, jnp.int32), fresh.astype(jnp.int32),
        jnp.exp(dt * a).reshape(-1), dtx,
        jnp.concatenate([b_mat, c_mat], 1), interpret=interpret)
    return y.reshape(bsz, blocks * hb, p)[:, :h], stack
