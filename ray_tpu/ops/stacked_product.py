"""A served layer's weight product for a decode step: a few rows of
activations times one layer's matrix, the matrix read where it lies in
the run's stack `[layers, ...]`.

What `jnp.einsum` computes on `stack[layer]`, as a Pallas TPU kernel
whose weight block's index map takes the layer from scalar prefetch:
nothing slices the stack. A layer scan hands its body a scanned leaf as
a slice of the stack; where the product that reads it does not take the
slice into its own fusion, the slice is a copy of the layer's matrix,
whole, ahead of the product (PERF.md, PR 40 and PR 60). This is
`grouped_matmul`'s body with one group of all the rows, so no visits
and no plan: every row is resident, the matrix streams through once in
blocks of columns, and Pallas's pipeline has the next block in flight
while this one is multiplied. bf16 in, float32 accumulation, the
activations' dtype out.

A leaf is read *as the TPU stores it*, which for a leaf of three axes
`[K, heads, k]` is not always the order its shape names: the TPU lays
an array out by its shape, so as to spare the padding of its last two
axes to whole tiles of 16 x 128 (`_stored`). These cases, each a view
of the stack that is a bitcast of what lies in memory, so that the
compiler lays nothing out anew in front of the kernel:

- `[K, N]`, and `[K, heads, k]` with whole tiles of heads and of k
  only where it has one head: columns of `[K, N]`;
- `[K, heads, k]` with k whole lanes and heads no whole tile (30 x
  128): stored `[heads, K, k]`, a matrix a head, read some heads a
  block;
- `[K, heads, k]` with k no whole lanes (30 x 96, 30 x 192): stored
  `[heads, k, K]`, the transposed matrix, read in blocks of its rows
  and contracted over the last axis of both operands.

- an output projection `[heads, k, N]` (`contract` 2) with k whole
  tiles of rows: stored as named, columns of `[heads x k, N]`.

- `[K, heads, 128]` with eight heads or whole tiles of them (8, 32,
  128 x 128): stored as named, 16 heads x 128 lanes of one hidden
  channel a tile, so the contracted axis lies outside the tiles and no
  view with it inside one is a bitcast. Read a block of hidden channels
  a step, a head's matrix gathered out of the block by strided loads
  (`_rows_kernel`), the products summed over the steps.

A leaf stored with the contracted axis outside the tiles that the
strided loads cannot take (k of 256 lanes; `[K, heads, 64]` with 128
heads, stored `[K, k, heads]`; fewer than eight heads) stays a slice
(`fits` says no).

On a TPU backend this is always the compiled kernel for the shapes
`fits` takes, of a call of a few rows; every other call, and any call
off the TPU, is `jnp.einsum` on `lax.dynamic_index_in_dim(stack,
layer)`, what the scan did, unless `interpret=True` runs the kernel
through the Pallas interpreter (used by tests).
"""

from __future__ import annotations

import contextlib
import functools
import math
import threading

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import on_tpu
from ray_tpu.ops.grouped_matmul import _columns, _k_chunk

# Rows above which a call is no decode step's: the kernel keeps every
# row resident and multiplies them against each block as one tile.
_ROWS_MOST = 256
_TILE = 16    # rows of a bfloat16 tile
_LANES = 128
# A weight block is at most this many bytes (two are in flight). One
# matrix streams through once, so a block is only as large as hides a
# grid step's overhead: at 16 MB (`grouped_matmul`'s, whose visits
# revisit a block) the first block's fetch, which nothing overlaps, is
# a sixth to a half of these matrices; 2 to 4 MB read them 2 to 20 %
# faster (PERF.md, PR 60).
_BLOCK_BYTES = 4 << 20


# What `weights_read` collects in this thread, or nothing.
_reading = threading.local()


@contextlib.contextmanager
def weights_read():
    """While a program is traced in this thread (an engine's compile
    of its decode program): the bytes of the layers' parameters a run of
    the program reads, {"in_place": through this kernel where they lie
    in their stacks, "sliced": as the layer scan's slices}, as
    `stacked_product` and `decoder.layers` note them (`note`)."""
    _reading.bytes = read = {"in_place": 0, "sliced": 0}
    try:
        yield read
    finally:
        del _reading.bytes


def note(how: str, *leaves) -> None:
    """Count `leaves` (stacks, every layer of which one run of the
    program being traced reads once) as read `how`: "in_place", or
    "sliced" (`decoder.layers`' scanned leaves, and a leaf handed whole
    that this module sliced after all)."""
    read = getattr(_reading, "bytes", None)
    if read is not None:
        read[how] += sum(x.size * x.dtype.itemsize for x in leaves)


def engages(tokens: int) -> bool:
    """Whether the halves of a call of `tokens` tokens a slot name the
    leaves they read in place: a decode step, on a TPU. What a family's
    `halves` asks once a call."""
    return tokens == 1 and on_tpu()


def _stored(shape, contract: int = 1):
    """How the TPU stores one layer `shape` of a stack whose first
    `contract` axes the product sums over, as the module's docstring
    lists the cases: "columns", "heads", "transposed", "rows", or None
    of a leaf the kernel cannot read as it lies."""
    if len(shape) == 2 and contract == 1:
        return "columns"
    if len(shape) != 3:
        return None
    if contract == 2:
        # [heads, k, N], stored as named: its view [heads x k, N] is a
        # bitcast where k is whole tiles of rows.
        return "columns" if shape[1] % _TILE == 0 and shape[2] % _LANES == 0 \
            else None
    d, heads, k = shape
    if k % _LANES == 0:
        if heads == 1:
            return "columns"
        if heads % 8 and heads > 8:
            return "heads"
        # Whole tiles of heads, and the eight of a grouped query's
        # keys, stay where they are named, under the contracted axis:
        # a head's matrix is every `heads`-th row of [D x heads, k].
        return "rows" if heads % 8 == 0 and k == _LANES \
            and _rows_chunk(d, heads * k * 2) else None
    # [heads, k, K] where neither k nor heads is whole lanes and K is.
    return "transposed" if k % _TILE == 0 and heads % _LANES \
        and d % _LANES == 0 else None


def fits(x, stack, contract: int = 1) -> bool:
    """Whether the kernel takes the product of x [..., K] (or [...,
    heads, k], `contract` 2) with a layer of `stack` [layers, ...]: a
    decode step's rows, both bfloat16, the leaf stored in one of the
    orders the kernel reads."""
    return (0 < math.prod(x.shape[:-contract]) <= _ROWS_MOST
            and x.dtype == stack.dtype == jnp.bfloat16
            and _stored(stack.shape[1:], contract) is not None)


def _dot(x, w, transposed: bool):
    return lax.dot_general(x, w, (((1,), (1 if transposed else 0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _kernel(layer_ref, x_ref, w_ref, o_ref, *, kc: int, transposed: bool):
    """x [rows, K] times the block's matrices: w [G, K, tn], or
    [G, tn, K] where `transposed`, into o [rows, G x tn]."""
    del layer_ref  # the weight block's index map reads it
    k = x_ref.shape[1]
    group, tn = w_ref.shape[0], o_ref.shape[1] // w_ref.shape[0]
    for g in range(group):
        if kc == k:
            product = _dot(x_ref[...], w_ref[g], transposed)
        else:
            # A loop the compiler keeps a loop (`grouped_matmul`).
            def chunk(i, acc, g=g):
                ks = pl.ds(pl.multiple_of(i * kc, kc), kc)
                w = w_ref[g, :, ks] if transposed else w_ref[g, ks, :]
                return acc + _dot(x_ref[:, ks], w, transposed)
            product = lax.fori_loop(
                0, k // kc, chunk,
                lax.full((o_ref.shape[0], tn), 0.0, jnp.float32))
        o_ref[:, g * tn:(g + 1) * tn] = product.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("group", "tn", "transposed",
                                             "interpret"))
def _call(x, stack, layer, *, group: int, tn: int, transposed: bool,
          interpret: bool):
    """The kernel's call over `stack` [layers, G_all, K, N] (or
    [layers, G_all, N, K]): a grid step takes `group` of the G_all
    matrices at `tn` of their N columns. Jitted, so that a program's
    call sites of one shape are traced and lowered once."""
    rows, k = x.shape
    count = stack.shape[1]
    n = stack.shape[2 if transposed else 3]
    # A matrix a head is read whole, some heads a step; one matrix a
    # block of its columns (of its rows, transposed) a step.
    by_heads = count > 1
    assert not by_heads or (tn == n and not transposed)
    steps = count // group if by_heads else pl.cdiv(n, tn)

    def x_block(i, layer):
        return 0, 0

    def w_block(i, layer):
        if by_heads:
            return layer[0], i, 0, 0
        return (layer[0], 0, i, 0) if transposed else (layer[0], 0, 0, i)

    def o_block(i, layer):
        return 0, i

    w_shape = (None, group) + ((tn, k) if transposed else (k, tn))
    itemsize = stack.dtype.itemsize
    blocks = 2 * (rows * k * itemsize + group * k * tn * itemsize
                  + rows * group * tn * itemsize) + 3 * rows * tn * 4
    return pl.pallas_call(
        functools.partial(_kernel, kc=_k_chunk(k), transposed=transposed),
        out_shape=jax.ShapeDtypeStruct((rows, count * n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,),
            in_specs=[pl.BlockSpec((rows, k), x_block),
                      pl.BlockSpec(w_shape, w_block)],
            out_specs=pl.BlockSpec((rows, group * tn), o_block)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=blocks + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * count * n, transcendentals=0,
            bytes_accessed=(count * k * n * itemsize
                            + rows * (k + count * n) * x.dtype.itemsize)),
        interpret=interpret,
        name="stacked_product",
    )(layer[None], x, stack)


def _rows_chunk(k: int, row_bytes: int) -> int:
    """Hidden channels of a block of a leaf stored [K, heads, 128], a
    row of `row_bytes` a channel: the most that are whole lanes of x,
    divide K and keep the block under `_BLOCK_BYTES`; 0 where none."""
    most = min(k, _BLOCK_BYTES // row_bytes) // _LANES * _LANES
    return next((kc for kc in range(most, 0, -_LANES) if k % kc == 0), 0)


def _rows_kernel(layer_ref, x_ref, w_ref, o_ref, acc_ref, *, heads: int):
    """x [rows, kc] times a block of kc hidden channels of a leaf that
    lies [K, heads, 128], summed over the grid's steps into o [rows,
    heads x 128]. The contracted axis is outside the tiles (16 heads x
    128 lanes of one channel a tile), so a head's [kc, 128] matrix is
    gathered by the load unit: every `heads`-th row of the block seen
    as [kc x heads, 128]. A bfloat16 tile packs rows 2 i and 2 i + 1
    into one 32-bit row, low half first, and a strided load takes
    32-bit rows: the block is read as uint32, a pair of heads a load,
    and each half is widened back to the bfloat16 it holds."""
    del layer_ref  # the weight block's index map reads it
    step = pl.program_id(0)
    lanes, kc = w_ref.shape[-1], x_ref.shape[1]

    @pl.when(step == 0)
    def _start():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pairs = w_ref.bitcast(jnp.uint32)            # [1, kc x heads / 2, 128]
    x = x_ref[...]
    for pair in range(heads // 2):
        packed = pairs[0, pl.ds(pair, kc, stride=heads // 2), :]
        halves = (packed << 16, packed & jnp.uint32(0xFFFF0000))
        for head, bits in zip((2 * pair, 2 * pair + 1), halves):
            w = lax.bitcast_convert_type(bits, jnp.float32).astype(x.dtype)
            acc_ref[:, head * lanes:(head + 1) * lanes] += _dot(x, w, False)

    @pl.when(step == pl.num_programs(0) - 1)
    def _end():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kc", "interpret"))
def _rows_call(x, stack, layer, *, kc: int, interpret: bool):
    """`_rows_kernel` over `stack` [layers, K, heads, 128], `kc`
    hidden channels a grid step."""
    rows, k = x.shape
    layers, _, heads, lanes = stack.shape
    itemsize = stack.dtype.itemsize
    blocks = 2 * (rows * kc + kc * heads * lanes) * itemsize \
        + rows * heads * lanes * (4 + 2 * itemsize) + 4 * kc * lanes * 4
    return pl.pallas_call(
        functools.partial(_rows_kernel, heads=heads),
        out_shape=jax.ShapeDtypeStruct((rows, heads * lanes), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(k // kc,),
            in_specs=[pl.BlockSpec((rows, kc), lambda i, layer: (0, i)),
                      pl.BlockSpec((1, kc * heads, lanes),
                                   lambda i, layer: (layer[0], i, 0))],
            out_specs=pl.BlockSpec((rows, heads * lanes),
                                   lambda i, layer: (0, 0)),
            scratch_shapes=[pltpu.VMEM((rows, heads * lanes), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=blocks + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * heads * lanes, transcendentals=0,
            bytes_accessed=(k * heads * lanes * itemsize
                            + rows * (k + heads * lanes) * itemsize)),
        interpret=interpret,
        name="stacked_product",
    )(layer[None], x, stack.reshape(layers, k * heads, lanes))


def _heads_a_block(heads: int, k_rows: int, width: int, itemsize: int) -> int:
    """Heads of a block of a leaf stored a matrix a head, [heads,
    k_rows, width]: the most that divide `heads` and keep the block
    under `_BLOCK_BYTES`."""
    most = max(1, _BLOCK_BYTES // (k_rows * width * itemsize))
    return max(g for g in range(1, most + 1) if heads % g == 0)


def stacked_product(x, stack, layer, *, contract: int = 1,
                    interpret: bool = False):
    """x [..., K] times `stack[layer]`, `stack` [layers, K, N] or
    [layers, K, heads, k], `layer` an int32 scalar -> [..., N] or
    [..., heads, k] in x's dtype, accumulated in float32: what
    `jnp.einsum("...d,df->...f", x, stack[layer])` gives. With
    `contract` 2, x [..., heads, k] times a layer [heads, k, N], summed
    over both (an output projection). On a TPU (or with `interpret`),
    for the calls `fits` takes, one kernel call that reads the layer
    where it lies; else the einsum on the layer's slice."""
    lead, out = x.shape[:-contract], stack.shape[1 + contract:]
    rows, k = math.prod(lead), math.prod(stack.shape[1:1 + contract])
    xs = x.reshape(rows, k)
    interpret = interpret and not on_tpu()
    if not ((on_tpu() or interpret) and fits(x, stack, contract)):
        note("sliced", stack)
        w = lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)
        return jnp.einsum("rd,d...->r...", xs,
                          w.reshape((k,) + out)).reshape(lead + out)
    note("in_place", stack)
    layers, n, itemsize = stack.shape[0], math.prod(out), stack.dtype.itemsize
    stored = _stored(stack.shape[1:], contract)
    if stored == "rows":
        y = _rows_call(xs, stack, jnp.asarray(layer, jnp.int32),
                       kc=_rows_chunk(k, n * itemsize), interpret=interpret)
        return y.reshape(lead + out)
    group, tn, transposed = 1, _columns(k, n, itemsize, _BLOCK_BYTES), False
    if stored == "columns":
        view = stack.reshape(layers, 1, k, n)
    elif stored == "heads":                           # [layers, heads, K, k]
        view = stack.transpose(0, 2, 1, 3)
        group, tn = _heads_a_block(out[0], k, out[1], itemsize), out[1]
    else:                                             # [layers, heads, k, K]
        view = stack.transpose(0, 2, 3, 1).reshape(layers, 1, n, k)
        transposed = True
    y = _call(xs, view, jnp.asarray(layer, jnp.int32), group=group, tn=tn,
              transposed=transposed, interpret=interpret)
    return y.reshape(lead + out)


def leaf_product(spec: str, x, name: str, lp, stacks=None):
    """What a half writes for x times its leaf `name`:
    `jnp.einsum(spec, x, lp[name])` on the scan's slice, or, where
    `decoder.layers` handed the half that leaf whole (`stacks`: (the
    leaves it named, the layer)), `stacked_product` on the stack."""
    if stacks is not None and name in stacks[0]:
        # (What the leaf's first axes sum over is what `spec` says.)
        ins, out = spec.split("->")
        contract = len(set(ins.split(",")[1]) - set(out))
        return stacked_product(x, stacks[0][name], stacks[1],
                               contract=contract)
    return jnp.einsum(spec, x, lp[name])
