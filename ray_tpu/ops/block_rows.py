"""A step's blocks of rows into the slot cache where it lies: what
`decoder.write_rows` does a block of a call at a time, for a model that
generates by blocks (`models/sdar_moe.py`), as one Pallas TPU kernel a
layer for all of a step's blocks and both of its leaves.

`decoder.write_rows` is a scatter of one window a slot, which the TPU
runs a window at a time at some 1.4 us each: a step of two blocks a
slot, K and V, 32 slots and 6 layers is 768 of them, 1.1 ms of a
forward of 20 (PERF.md, PR 51). Here a grid step takes one whole tile
of 16 rows of a slot's region (what a bfloat16 array is tiled by; a
block's rows never straddle one, a block's length dividing 16), puts
the step's rows where they fall in it and writes it back, K and V
together: two grid steps a slot, the tile the first block starts in
and the one behind it, which the last block may reach into.

On a TPU backend this is always the compiled kernel; on other backends
it is `decoder.write_rows`, a block at a time in the call's order,
unless `interpret=True` runs the kernel through the Pallas interpreter
(used by tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import on_tpu

_TILE = 16  # rows of a bfloat16 tile


def _kernel(layer_ref, tile_ref, lo_ref, hi_ref, *refs):
    del layer_ref, tile_ref  # the stacks' index maps read them
    at = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
    n = len(refs) // 3
    row = jax.lax.broadcasted_iota(jnp.int32, refs[0].shape, 0)
    mine = (row >= lo_ref[at]) & (row < hi_ref[at])
    for new, old, out in zip(refs[:n], refs[n:2 * n], refs[2 * n:]):
        # (Chosen in float32, which every dtype of a cache passes
        # through unchanged: the mask is tiled as int32 is.)
        out[...] = jnp.where(mine, new[...].astype(jnp.float32),
                             old[...].astype(jnp.float32)).astype(out.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(stacks, tiles, layer, tile_of, lo, hi, *, interpret: bool):
    """stacks: the leaves, each [layers, B, S, W]; tiles: for each, the
    tiles as they are to stand, [B, 2, 16, W]; tile_of, lo, hi [B x 2]:
    which tile of the slot's region a grid step rewrites and which of
    its rows it takes from `tiles`."""
    n = len(stacks)
    slots, steps, rows, width = tiles[0].shape

    def new(b, j, *_):
        return b, j, 0, 0

    def old(b, j, layer, tile_of, lo, hi):
        return layer[0], b, tile_of[b * steps + j], 0

    block = (None, None, rows, width)
    return pl.pallas_call(
        _kernel,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in stacks],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(slots, steps),
            in_specs=[pl.BlockSpec(block, new)] * n
            + [pl.BlockSpec(block, old)] * n,
            out_specs=[pl.BlockSpec(block, old)] * n),
        # A stack is rewritten where it lies.
        input_output_aliases={4 + n + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="write_blocks",
    )(layer[None], tile_of, lo, hi, *tiles, *stacks)


def write_blocks(stacks, layer, rows, starts, *, interpret: bool = False):
    """`rows` (a sequence, one a stack, each [B, n x L, ...]: n blocks
    of L rows a slot) into `stacks` (each [layers, B, S, ...]) at
    (`layer`, slot, `starts[slot, i]`) for block i, cast to the stack's
    dtype; `starts` int32 [B, n], multiples of L. Blocks are written in
    the call's order, so of two at one start the later one's rows stay;
    no other row of a stack is touched. L divides 16, and a slot's
    blocks start within 16 rows of its first: the engine's step, whose
    blocks of a slot stand at one start or one behind the other.
    Returns the stacks."""
    stacks = tuple(stacks)
    interpret = interpret and not on_tpu()
    n = starts.shape[1]
    length = rows[0].shape[1] // n
    if not (on_tpu() or interpret):
        from ray_tpu.models import decoder
        for i in range(n):
            at = slice(i * length, (i + 1) * length)
            stacks = tuple(decoder.write_rows(x, layer, new[:, at],
                                              starts[:, i])
                           for x, new in zip(stacks, rows))
        return stacks
    span = stacks[0].shape[2]
    assert _TILE % length == 0 and span % _TILE == 0 \
        and n * length <= _TILE, (length, n, span)
    shapes = [x.shape for x in stacks]
    stacks = [x.reshape(x.shape[:3] + (-1,)) for x in stacks]
    rows = [new.reshape(new.shape[:2] + (-1,)).astype(x.dtype)
            for new, x in zip(rows, stacks)]
    # The two tiles a slot's blocks can lie in (the second held to the
    # region: where it would pass the end it is the first again, and
    # both grid steps then write the same tile the same way).
    tile_of = jnp.minimum(starts[:, :1] // _TILE + jnp.arange(2),
                          span // _TILE - 1)                    # [B, 2]
    at = tile_of[..., None] * _TILE + jnp.arange(_TILE)         # [B, 2, 16]
    # Which row of `rows` a position takes: the last block that holds it.
    source = jnp.full(at.shape, -1, jnp.int32)
    for i in range(n):
        offset = at - starts[:, i, None, None]
        source = jnp.where((offset >= 0) & (offset < length),
                           i * length + offset, source)
    held = source >= 0
    lo = jnp.argmax(held, -1)  # (0 of a tile that holds none)
    hi = lo + held.sum(-1)
    pick = jnp.maximum(source, 0).reshape(source.shape[0], -1, 1)
    tiles = [jnp.take_along_axis(new, pick, 1).reshape(
        at.shape + new.shape[2:]) for new in rows]
    out = _call(stacks, tiles, jnp.asarray(layer, jnp.int32),
                tile_of.reshape(-1).astype(jnp.int32),
                lo.reshape(-1).astype(jnp.int32),
                hi.reshape(-1).astype(jnp.int32), interpret=interpret)
    return tuple(x.reshape(shape) for x, shape in zip(out, shapes))
