"""Grouped matrix product for a served share of experts: rows in expert
order times each row's expert's matrix, the matrices read where they lie
in a run's stack `[layers, experts, K, N]` and only where a row fell.

What `lax.ragged_dot(xs, stack.reshape(layers * experts, K, N), sizes)`
computes, the sizes zero outside one layer, as a Pallas TPU kernel tiled
for what a served step gives it: one to three rows an expert in a decode
step, tens to hundreds in a prefill. The kernel the TPU compiler makes
of `lax.ragged_dot` is tiled for training's thousands of rows a group
and reads a few rows' matrices at some two fifths of memory speed
(PERF.md, PR 37). Forward only: the trained expert layer keeps
`lax.ragged_dot`, whose kernel suits its shapes and has a VJP.

The grid is megablox's (jax.experimental.pallas.ops.tpu.megablox): one
step a *visit*, a (row tile, group) pair that share at least one row, in
row order, so that a row tile's visits are consecutive and its output
block stays in fast memory until its last group has written its rows.
The weight block's index map picks (layer, group) out of the stack from
scalar-prefetch arguments: nothing slices the stack, an expert no row
fell on is never fetched, and Pallas's pipeline has the next visit's
matrix in flight while this one's is multiplied. Consecutive visits of
one group (a crowded expert's row tiles) fetch its matrix once. A matrix
too large for one block is taken a band of columns at a time, at all of
K, the visits walked once a band. The grid is static, sized by the most
visits one layer's groups can make (row tiles + groups - 1); the steps
past the visits a call has repeat the last one's blocks and do nothing.

The visits are built once for a layer's two or three products
(`plan`), with a handful of `jnp` ops: megablox builds its own inside
every call, which cost seconds of tracing (PERF.md, PR 27).

On a TPU backend this is always the compiled kernel; on other backends
it is `lax.ragged_dot` unless `interpret=True` runs the kernel through
the Pallas interpreter (used by tests).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import on_tpu


class Groups(NamedTuple):
    """One layer's groups over a buffer of rows, as the products of that
    layer take them. `sizes` [groups] int32, the rows of each group in
    order (rows past their sum belong to none). The rest is the kernel's
    and unset where `lax.ragged_dot` runs: `row_tile` and `interpret`
    (static), `starts` [groups + 1] (the row a group starts at; the
    last, where the last ends), `group_of` and `tile_of` [visits] (which
    group and which row tile a grid step works on), `n_visits` [1]."""
    sizes: jax.Array
    row_tile: int = 0
    interpret: bool = False
    starts: Optional[jax.Array] = None
    group_of: Optional[jax.Array] = None
    tile_of: Optional[jax.Array] = None
    n_visits: Optional[jax.Array] = None


def _row_tile(rows: int, n_groups: int) -> int:
    """Rows of a row tile, from the rows a group has if the buffer is
    dealt evenly. Every visit multiplies a whole tile, so a tile of far
    more rows than a group wastes the MXU on rows that are masked away,
    and a tile of far fewer makes a crowded group many visits."""
    per_group = max(1, rows // n_groups)
    tile = 16  # a bfloat16 tile's sublanes: the least a block can have
    while tile < min(per_group, 256):
        tile *= 2
    return min(tile, rows)  # (a buffer of fewer rows is one block)


def plan(sizes, rows: int, *, interpret: bool = False) -> Groups:
    """The groups of one layer over a buffer of `rows` rows, for every
    product of that layer. `sizes` [groups] int32 sums to `rows` or
    less."""
    if sizes.dtype != jnp.int32:
        sizes = sizes.astype(jnp.int32)
    interpret = interpret and not on_tpu()
    if not (on_tpu() or interpret):
        return Groups(sizes)
    tile = _row_tile(rows, sizes.shape[0])
    return Groups(sizes, tile, interpret,
                  *_visits(sizes, rows=rows, tile=tile))


@functools.partial(jax.jit, static_argnames=("rows", "tile"))
def _visits(sizes, *, rows: int, tile: int):
    """(starts, group_of, tile_of, n_visits) of `Groups`. Jitted like
    the kernel's call, and for its reason: a program's runs of expert
    layers trace it once."""
    n_groups = sizes.shape[0]
    ends = lax.cumsum(sizes)
    starts = ends - sizes
    # A group visits every row tile it has a row in. (`lax.div`
    # truncates, which is the floor of what a group with a row gives.)
    first = lax.div(starts, jnp.int32(tile))
    tiles = jnp.where(sizes > 0,
                      lax.div(ends - 1, jnp.int32(tile)) - first + 1, 0)
    visit_ends = lax.cumsum(tiles)
    n_visits = visit_ends[-1:]
    # Row tiles, and one more visit for every group that starts inside
    # a tile another group began.
    most = -(-rows // tile) + n_groups - 1
    visit = jnp.minimum(lax.iota(jnp.int32, most),
                        jnp.maximum(n_visits - 1, 0))
    group_of = jnp.minimum(
        (visit[:, None] >= visit_ends[None, :]).sum(-1, dtype=jnp.int32),
        n_groups - 1)
    tile_of = (first + tiles - visit_ends)[group_of] + visit
    return (jnp.concatenate([starts, ends[-1:]]), group_of, tile_of,
            n_visits)


# A weight block is at most this many bytes (two are in flight), and a
# matrix that fits is one block. Blocks of half the size read GLM-5.2's
# [6144, 2048] a sixth slower, larger ones no faster (PERF.md, PR 37).
_WEIGHT_BLOCK_BYTES = 16 << 20
_K_CHUNK_MOST = 512


def _k_chunk(k: int) -> int:
    """Rows of a weight block one product inside the kernel takes: the
    most that divide K, are whole lane tiles and are not over
    `_K_CHUNK_MOST`; K itself where none is."""
    for kc in range(min(k, _K_CHUNK_MOST) // 128 * 128, 0, -128):
        if k % kc == 0:
            return kc
    return k


def _columns(k: int, n: int, itemsize: int, block_bytes: int = 0) -> int:
    """Columns of the block of a [K, N] matrix a grid step multiplies:
    the whole matrix where it fits `block_bytes` (this module's
    `_WEIGHT_BLOCK_BYTES` unless given), else one of the fewest even
    bands of its columns that do, at all of K, so that no product is
    summed across steps."""
    most = max(128, (block_bytes or _WEIGHT_BLOCK_BYTES)
               // (k * itemsize) // 128 * 128)
    bands = -(-n // most)
    return n if bands == 1 else -(-n // (bands * 128)) * 128


def _gmm_kernel(layer_ref, starts_ref, group_ref, tile_ref, n_ref,
                x_ref, w_ref, o_ref, *, row_tile: int, kc: int):
    del layer_ref  # the weight block's index map reads it
    visit = pl.program_id(1)
    k = x_ref.shape[1]

    def dot(x, w):
        return lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

    @pl.when(visit < n_ref[0])
    def _visit():
        if kc == k:
            product = dot(x_ref[...], w_ref[...])
        else:
            # A loop the compiler keeps a loop: one product of the
            # whole block unrolls into code of the block's size.
            def chunk(i, acc):
                ks = pl.ds(pl.multiple_of(i * kc, kc), kc)
                return acc + dot(x_ref[:, ks], w_ref[ks, :])
            product = lax.fori_loop(0, k // kc, chunk,
                                    lax.full(o_ref.shape, 0.0, jnp.float32))
        # A tile's rows outside this group are another visit's, or no
        # group's: they keep what they hold.
        group = group_ref[visit]
        row = tile_ref[visit] * row_tile + lax.broadcasted_iota(
            jnp.int32, o_ref.shape, 0)
        mine = (row >= starts_ref[group]) & (row < starts_ref[group + 1])
        o_ref[...] = lax.select(mine, product.astype(o_ref.dtype),
                                o_ref[...])


@functools.partial(jax.jit, static_argnames=("tm", "tn", "kc", "interpret"))
def _gmm(xs, stack, layer, starts, group_of, tile_of, n_visits, *, tm: int,
         tn: int, kc: int, interpret: bool):
    """The kernel's call. Jitted, so that a program's call sites of one
    shape (a layer's first two products, the runs of expert layers) are
    traced and lowered once: a call site costs tens of milliseconds of
    a warm process's set-up, and GLM-5.2 has some 126 of them."""
    rows, k = xs.shape
    n = stack.shape[-1]
    visits = group_of.shape[0]

    def x_block(n_i, visit, layer, starts, group_of, tile_of, n_visits):
        return tile_of[visit], 0

    def w_block(n_i, visit, layer, starts, group_of, tile_of, n_visits):
        return layer[0], group_of[visit], 0, n_i

    def o_block(n_i, visit, layer, starts, group_of, tile_of, n_visits):
        return tile_of[visit], n_i

    blocks = 2 * (tm * k * xs.dtype.itemsize + k * tn * stack.dtype.itemsize
                  + tm * tn * xs.dtype.itemsize) + 3 * tm * tn * 4
    return pl.pallas_call(
        functools.partial(_gmm_kernel, row_tile=tm, kc=kc),
        out_shape=jax.ShapeDtypeStruct((rows, n), xs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(pl.cdiv(n, tn), visits),
            in_specs=[pl.BlockSpec((tm, k), x_block),
                      pl.BlockSpec((None, None, k, tn), w_block)],
            out_specs=pl.BlockSpec((tm, tn), o_block)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=blocks + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=(min(visits, stack.shape[1]) * k * n
                            * stack.dtype.itemsize
                            + rows * (k * pl.cdiv(n, tn) + n)
                            * xs.dtype.itemsize)),
        interpret=interpret,
        name="gmm",
    )(layer[None], starts, group_of, tile_of, n_visits, xs, stack)


def grouped_matmul(xs, stack, layer, groups: Groups):
    """xs [R, K] rows in group order, stack [layers, groups, K, N],
    `layer` an int32 scalar, `groups` from `plan` -> [R, N] in xs's
    dtype: each row of a group times `stack[layer, group]`, accumulated
    in float32. A row past the last group's end holds whatever the
    backend leaves there.

    `groups` says what runs (`plan`). On a TPU backend that is always
    the compiled kernel: `interpret` never reaches a TPU call, and a
    kernel Mosaic refuses is an error, not a switch to
    `lax.ragged_dot`."""
    if groups.row_tile:
        k, n = stack.shape[2:]
        return _gmm(xs, stack, jnp.asarray(layer, jnp.int32), groups.starts,
                    groups.group_of, groups.tile_of, groups.n_visits,
                    tm=groups.row_tile,
                    tn=_columns(k, n, stack.dtype.itemsize), kc=_k_chunk(k),
                    interpret=groups.interpret)
    layers, count = stack.shape[:2]
    # The stack's two leading axes read as one, layers x groups groups,
    # all empty but this layer's: an empty group's matrix is not read.
    sizes = lax.dynamic_update_slice_in_dim(
        jnp.zeros(layers * count, jnp.int32), groups.sizes,
        layer * count, 0)
    return lax.ragged_dot(xs, stack.reshape((layers * count,)
                                            + stack.shape[2:]), sizes)
