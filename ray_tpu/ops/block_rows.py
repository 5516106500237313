"""A step's rows into the slot cache where it lies: what
`decoder.write_rows` does a window a slot, as one Pallas TPU kernel a
layer for all of the layer's leaves and all of the step's rows.

`decoder.write_rows` is a scatter of one window a slot, which the TPU
runs a window at a time, and it is three ops, not one: the scatter's
own select and bounds check ride beside it as fusions, 2.5 to 4 us a
window in all. A dense decode step of 32 slots, K and V, 16 layers is
1,024 windows, 2.8 ms of a step of 16.2 for 2 MB written, and 0.14 ms
here; a block-diffusion step of two blocks a slot, 6 layers, 768 of
them (PERF.md, PR 51 and PR 54). Here a grid step takes one whole tile
of 16 rows of a slot's region (what a bfloat16 array is tiled by), puts
the step's rows where they fall in it and writes it back, every leaf of
the layer together:

- `write_tokens`, a decode step of one token a slot (every family that
  generates a token at a time calls it from its mixer): one grid step a
  slot, the tile its row lies in. A leaf with axes between its rows and
  its width, [layers, B, S, Hkv, D], is written in the view attention
  reads it in, [layers, B, S x Hkv, D], where a position is Hkv rows
  that never straddle a tile (Hkv divides 16) and no leaf is laid out
  anew;
- `write_blocks`, a step of two blocks of L rows a slot
  (`models/sdar_moe.py`): two grid steps a slot, the tile the first
  block starts in and the one behind it, which the last block may reach
  into (a block's rows never straddle one, L dividing 16).

On a TPU backend these are always the compiled kernel, where the
shapes allow it; on other backends, and for shapes the tile does not
divide, they are `decoder.write_rows`, which stays the definition,
unless `interpret=True` runs the kernel through the Pallas interpreter
(used by tests).
"""

from __future__ import annotations

import functools
import math

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
    mine = {}  # by a tile's shape: the leaves need not be one width
    for new, old, out in zip(refs[:n], refs[n:2 * n], refs[2 * n:]):
        if out.shape not in mine:
            row = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
            mine[out.shape] = (row >= lo_ref[at]) & (row < hi_ref[at])
        # (Chosen in float32, which every dtype of a cache passes
        # through unchanged: the mask is tiled as int32 is.)
        out[...] = jnp.where(mine[out.shape], new[...].astype(jnp.float32),
                             old[...].astype(jnp.float32)).astype(out.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(stacks, tiles, layer, tile_of, lo, hi, *, interpret: bool):
    """stacks: the leaves, each [layers, B, S, W] (a width a leaf);
    tiles: for each, the tiles as they are to stand, [B, n, 16, W], n
    grid steps a slot; tile_of, lo, hi [B x n]: which tile of the
    slot's region a grid step rewrites and which of its rows it takes
    from `tiles`."""
    n = len(stacks)
    slots, steps = tiles[0].shape[:2]

    def new(b, j, *_):
        return b, j, 0, 0

    def old(b, j, layer, tile_of, lo, hi):
        return layer[0], b, tile_of[b * steps + j], 0

    # A block a leaf: the leaves of one layer need not be one width.
    blocks = [(None, None) + x.shape[2:] for x in tiles]
    return pl.pallas_call(
        _kernel,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in stacks],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(slots, steps),
            in_specs=[pl.BlockSpec(block, new) for block in blocks]
            + [pl.BlockSpec(block, old) for block in blocks],
            out_specs=[pl.BlockSpec(block, old) for block in blocks]),
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


def _fits(stacks, rows):
    """The rows p a position has in the leaves' [layers, B, S x p, W]
    views where `write_tokens`' kernel can write this call, else 0: one
    token a slot of more than one, and leaves of one region S and one
    p, p dividing the tile and the tile the view."""
    if rows[0].shape[1] != 1 or rows[0].shape[0] < 2:
        return 0
    per = {(x.shape[2], math.prod(x.shape[3:-1])) for x in stacks}
    if len(per) != 1:
        return 0
    (span, p), = per
    return p if _TILE % p == 0 and (span * p) % _TILE == 0 else 0


def write_tokens(stacks, layer, rows, start_pos, *, interpret: bool = False):
    """`rows` (a sequence, one a stack, each [B, T, ...]) into `stacks`
    (each [layers, B, S, ...]) at (`layer`, slot, `start_pos[slot]`),
    cast to the stack's dtype, to the bit what `decoder.write_rows`
    writes a stack at a time: a start past S - 1 (a retired slot keeps
    stepping) lands on S - 1 and one below 0 on 0, and no other row of a
    stack is touched. A call of one token a slot of several, on a TPU
    (or with `interpret`), whose leaves' regions are whole tiles
    (`_fits`), is one kernel call for all the leaves, each in the view
    [layers, B, S x p, W] that merges the axes between its rows and its
    width into its rows; every other call (a prefill's one window, any
    call off the TPU, a region the tile does not divide) is
    `decoder.write_rows`. Returns the stacks."""
    stacks = tuple(stacks)
    interpret = interpret and not on_tpu()
    p = _fits(stacks, rows) if on_tpu() or interpret else 0
    if not p:
        from ray_tpu.models import decoder
        return tuple(decoder.write_rows(x, layer, new, start_pos)
                     for x, new in zip(stacks, rows))
    slots, span = stacks[0].shape[1:3]
    # The first of the position's p rows in the view, where
    # `write_rows`' clip puts it.
    at = jnp.clip(start_pos.astype(jnp.int32), 0, span - 1) * p
    views = [x.reshape(x.shape[:2] + (span * p, x.shape[-1]))
             for x in stacks]
    # A tile of the position's rows over and over: its start is a
    # multiple of p, so row r of the tile is to hold row r mod p.
    tiles = [jnp.tile(new.reshape(slots, p, -1).astype(x.dtype),
                      (1, _TILE // p, 1))[:, None]
             for new, x in zip(rows, stacks)]
    out = _call(views, tiles, jnp.asarray(layer, jnp.int32), at // _TILE,
                at % _TILE, at % _TILE + p, interpret=interpret)
    return tuple(x.reshape(old.shape) for x, old in zip(out, stacks))
