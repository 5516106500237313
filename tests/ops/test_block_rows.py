"""A step's rows into the slot cache (`ops.block_rows`): the Pallas
kernel through the interpreter against `decoder.write_rows`, which is
what it is off the TPU, to the bit. A step of two blocks a slot
(`write_blocks`): blocks of a slot one behind the other and at one
start (the later one's rows stay), in one tile and across two, at a
region's first and last rows. A step of one token a slot
(`write_tokens`): a row at a region's first row, inside a tile, at a
tile's last row, at the region's last and past it (where `write_rows`'
clip puts it), leaves of unlike widths, leaves with heads between rows
and width, a ring, and the calls that keep the scatter. Nothing else of
a stack is touched."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decoder
from ray_tpu.ops import block_rows

LAYERS, SPAN, WIDTH, LENGTH = 3, 64, 256, 4
# A slot each: the starts of its two blocks.
STARTS = {
    "behind each other in a tile": [[0, 4], [16, 20], [36, 40]],
    "across two tiles": [[12, 16], [28, 32], [44, 48]],
    "at one start": [[0, 0], [12, 12], [60, 60]],
    "the region's last rows": [[56, 60], [60, 60], [52, 56]],
    "mixed": [[8, 8], [12, 16], [60, 60], [56, 60], [0, 4], [20, 20]],
}


@pytest.mark.parametrize("starts", STARTS.values(), ids=STARTS.keys())
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_kernel_writes_what_write_rows_writes(starts, dtype):
    starts = jnp.asarray(starts, jnp.int32)
    slots = starts.shape[0]
    rng = np.random.default_rng(slots)
    k, v = (jnp.asarray(rng.normal(size=(LAYERS, slots, SPAN, WIDTH)), dtype)
            for _ in range(2))
    new = [jnp.asarray(rng.normal(size=(slots, 2 * LENGTH, WIDTH)),
                       jnp.float32) for _ in range(2)]
    layer = jnp.int32(1)
    want = [k, v]
    for i in range(2):
        at = slice(i * LENGTH, (i + 1) * LENGTH)
        want = [decoder.write_rows(x, layer, rows[:, at], starts[:, i])
                for x, rows in zip(want, new)]
    plain = block_rows.write_blocks((k, v), layer, new, starts)
    got = jax.jit(block_rows.write_blocks, static_argnames="interpret")(
        (k, v), layer, new, starts, interpret=True)
    for x, y, z, old in zip(got, plain, want, (k, v)):
        assert x.dtype == dtype and x.shape == old.shape
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(z, np.float32))
        np.testing.assert_array_equal(np.asarray(y, np.float32),
                                      np.asarray(z, np.float32))
        assert (np.asarray(x[1], np.float32)
                != np.asarray(old[1], np.float32)).any()
        np.testing.assert_array_equal(np.asarray(x[0::2], np.float32),
                                      np.asarray(old[0::2], np.float32))


def _leaves(rng, shapes, dtype):
    return [jnp.asarray(rng.normal(size=shape), dtype) for shape in shapes]


def _same(x, y):
    np.testing.assert_array_equal(np.asarray(x, np.float32),
                                  np.asarray(y, np.float32))


# One token a slot. The engine clamps a decode step's start to S - 2
# (a retired slot keeps stepping there) and a ring's is `start %
# window`, so what it hands on lies in [0, S - 1]; a start past that is
# `write_rows`' clip, which the kernel keeps all the same.
SPAN_1 = 64
TOKENS = {
    # name: (a leaf's shape behind [layers, slots], the starts)
    "first, in a tile, a tile's last, S - 2, S - 1": (
        [(SPAN_1, 256)] * 2, [0, 5, 15, 31, 32, SPAN_1 - 2, SPAN_1 - 1]),
    "past the region and below it (the clip)": (
        [(SPAN_1, 256)] * 2, [SPAN_1, SPAN_1 + 9, 4 * SPAN_1, -3]),
    "unlike widths (512, 64, 128)": (
        [(SPAN_1, 512), (SPAN_1, 64), (SPAN_1, 128)], [0, 17, 47, SPAN_1 - 1]),
    "eight heads between rows and width": (
        [(SPAN_1, 8, 128)] * 2, [0, 1, 15, 16, SPAN_1 - 1, SPAN_1 + 2]),
    "two heads between rows and width": (
        [(SPAN_1, 2, 128)] * 2, [0, 7, 8, 15, SPAN_1 - 1, SPAN_1 + 2]),
    "a ring (start % window)": (
        [(32, 8, 128)] * 2, [p % 32 for p in (0, 31, 32, 33, 95, 4096)]),
    "a single leaf": ([(SPAN_1, 128)], [3, 60]),
}


@pytest.mark.parametrize("case", TOKENS.values(), ids=TOKENS.keys())
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_one_token_a_slot_is_written_as_write_rows_writes_it(case, dtype):
    shapes, starts = case
    starts = jnp.asarray(starts, jnp.int32)
    slots = starts.shape[0]
    rng = np.random.default_rng(slots)
    stacks = _leaves(rng, [(LAYERS, slots) + shape for shape in shapes],
                     dtype)
    new = _leaves(rng, [(slots, 1) + shape[1:] for shape in shapes],
                  jnp.float32)
    layer = jnp.int32(1)
    want = [decoder.write_rows(x, layer, rows, starts)
            for x, rows in zip(stacks, new)]
    plain = block_rows.write_tokens(stacks, layer, new, starts)
    lowered = jax.jit(block_rows.write_tokens,
                      static_argnames="interpret").lower(
        stacks, layer, new, starts, interpret=True)
    assert "scatter" not in lowered.as_text()  # the kernel took the call
    got = lowered.compile()(stacks, layer, new, starts)
    assert len(got) == len(plain) == len(stacks)
    for x, y, z, old in zip(got, plain, want, stacks):
        assert x.dtype == dtype and x.shape == old.shape
        _same(x, z)
        _same(y, z)
        # One row a slot moved, and no other layer's.
        moved = (np.asarray(x, np.float32)
                 != np.asarray(old, np.float32)).reshape(
            LAYERS, slots, old.shape[2], -1).any(-1)
        assert moved[1].sum(-1).tolist() == [1] * slots
        assert not moved[0::2].any()


SCATTERED = {
    # name: (a leaf's shape behind [layers], the new rows')
    "a region the tile does not divide": ((4, 40, 128), (4, 1, 128)),
    "heads that do not divide the tile": ((4, 64, 3, 128), (4, 1, 3, 128)),
    "one slot (a prefill of one token)": ((1, 64, 128), (1, 1, 128)),
    "more tokens than one a slot": ((4, 64, 128), (4, 2, 128)),
}


@pytest.mark.parametrize("case", SCATTERED.values(), ids=SCATTERED.keys())
def test_what_the_tile_does_not_fit_keeps_the_scatter(case):
    leaf, rows = case
    rng = np.random.default_rng(7)
    stacks = _leaves(rng, [(LAYERS,) + leaf] * 2, jnp.bfloat16)
    new = _leaves(rng, [rows] * 2, jnp.float32)
    starts = jnp.arange(leaf[0], dtype=jnp.int32) * 9 + 2
    layer = jnp.int32(2)
    lowered = jax.jit(block_rows.write_tokens,
                      static_argnames="interpret").lower(
        stacks, layer, new, starts, interpret=True)
    assert lowered.as_text().count("scatter") >= 2
    for x, old, rows in zip(lowered.compile()(stacks, layer, new, starts),
                            stacks, new):
        _same(x, decoder.write_rows(old, layer, rows, starts))


def test_off_the_tpu_a_call_is_the_scatter():
    rng = np.random.default_rng(3)
    stacks = _leaves(rng, [(LAYERS, 4, 64, 128)] * 2, jnp.bfloat16)
    new = _leaves(rng, [(4, 1, 128)] * 2, jnp.float32)
    starts = jnp.asarray([0, 9, 63, 70], jnp.int32)
    text = jax.jit(block_rows.write_tokens).lower(
        stacks, jnp.int32(0), new, starts).as_text()
    assert text.count("scatter") >= 2 and "write_blocks" not in text
