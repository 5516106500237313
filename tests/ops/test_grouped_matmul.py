"""The held experts' grouped product (`ray_tpu/ops/grouped_matmul.py`):
the Pallas kernel through the interpreter against `lax.ragged_dot` over
the stack read as layers x groups groups."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.ops import grouped_matmul as gm


def _sizes(count, **at):
    sizes = np.zeros(count, np.int32)
    for group, n in at.items():
        sizes[int(group[1:])] = n
    return sizes


# rows, K, N, sizes, layers, layer, dtype
CASES = {
    # A decode step: one to three rows an expert, most experts empty.
    "decode_few_rows_most_groups_empty": (
        64, 256, 256, _sizes(16, g1=2, g5=1, g6=3, g11=1), 2, 0,
        jnp.bfloat16),
    # A prefill: hundreds of rows an expert, every expert touched.
    "prefill_hundreds_of_rows": (
        1024, 256, 256, np.array([300, 150, 260, 290], np.int32), 1, 0,
        jnp.bfloat16),
    # One expert takes nearly every row: its rows span four row tiles of
    # 16, and its neighbours share a tile with its edges.
    "one_crowded_group_over_several_tiles": (
        80, 128, 128, _sizes(8, g2=3, g3=60, g4=1, g7=5), 1, 0,
        jnp.float32),
    # Rows past the last group's end hold NaN: their output is never
    # read, and nothing of it reaches a group's rows.
    "rows_past_the_last_group": (
        96, 128, 256, _sizes(8, g0=7, g3=20, g6=2), 1, 0, jnp.bfloat16),
    "last_layer_of_a_stack": (
        64, 128, 256, _sizes(8, g0=4, g1=9, g5=17, g7=2), 3, 2,
        jnp.bfloat16),
    "wide_matrix": (
        48, 128, 512, _sizes(4, g0=5, g2=30), 2, 1, jnp.bfloat16),
    "tall_matrix": (
        48, 512, 128, _sizes(4, g0=5, g2=30), 2, 1, jnp.bfloat16),
    # Neither the rows, nor K, nor N, nor a group's edge is a multiple
    # of a tile.
    "sizes_off_the_tiles": (
        100, 96, 200, _sizes(8, g1=70, g4=3, g7=1), 2, 1, jnp.float32),
    "no_row_in_any_group": (
        32, 128, 128, _sizes(4), 2, 1, jnp.bfloat16),
}


@pytest.mark.parametrize("blocks", ["whole", "pieces"])
@pytest.mark.parametrize("case", CASES)
def test_kernel_matches_ragged_dot_over_the_stack(case, blocks, monkeypatch):
    """Every case with the matrix as one block multiplied at once, and
    again in pieces: bands of 128 columns (what a matrix larger than a
    block gets) and, inside the kernel, 128 rows of the block at a time
    (what a block of more than `_K_CHUNK_MOST` rows gets)."""
    rows, k, n, sizes, layers, layer, dtype = CASES[case]
    if blocks == "pieces":
        monkeypatch.setattr(gm, "_WEIGHT_BLOCK_BYTES", k * 128
                            * jnp.dtype(dtype).itemsize)
        monkeypatch.setattr(gm, "_K_CHUNK_MOST", 128)
        assert gm._columns(k, n, jnp.dtype(dtype).itemsize) == 128
        assert gm._k_chunk(k) == (128 if k % 128 == 0 else k)
    count, held = len(sizes), int(sizes.sum())
    kx, kw = jax.random.split(jax.random.PRNGKey(rows + k))
    xs = jax.random.normal(kx, (rows, k), dtype).at[held:].set(jnp.nan)
    stack = jax.random.normal(kw, (layers, count, k, n), dtype) * k ** -0.5

    groups = gm.plan(jnp.asarray(sizes), rows, interpret=True)
    got = jax.jit(lambda xs, stack, layer: gm.grouped_matmul(
        xs, stack, layer, groups))(xs, stack, layer)
    assert got.shape == (rows, n) and got.dtype == dtype

    all_sizes = np.zeros(layers * count, np.int32)
    all_sizes[layer * count:][:count] = sizes
    want = lax.ragged_dot(xs, stack.reshape(layers * count, k, n),
                          jnp.asarray(all_sizes))
    # The same off the TPU with no interpreter asked for.
    plain = gm.grouped_matmul(xs, stack, layer, gm.plan(sizes, rows))
    np.testing.assert_array_equal(np.asarray(plain[:held], np.float32),
                                  np.asarray(want[:held], np.float32))
    # float32 accumulation in another order: a rounding of the result's
    # dtype apart at most.
    tol = 2.0 ** -7 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got[:held], np.float32),
                               np.asarray(want[:held], np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("rows,count,k,n,tile,columns", [
    (704, 128, 1024, 2688, 16, 2688),    # Nemotron 3 Super, decode
    (704, 128, 2688, 1024, 16, 1024),
    (128, 16, 6144, 2048, 16, 1024),     # GLM-5.2, decode
    (128, 16, 2048, 6144, 16, 3072),
    (11264, 128, 1024, 2688, 128, 2688),  # Nemotron, the 1,024 bucket
    (7168, 16, 6144, 2048, 256, 1024),   # GLM, the 7,168 bucket
])
def test_tiles_follow_the_static_shapes(rows, count, k, n, tile, columns):
    """The served cells' shapes: a row tile of about the rows a group
    has, a matrix whole where it fits a block and else in even bands,
    multiplied some 512 rows at a time."""
    assert gm._row_tile(rows, count) == tile
    assert gm._columns(k, n, 2) == columns
    assert n % columns == 0
    assert gm._k_chunk(k) == (384 if k == 2688 else 512)


def test_a_layers_visits_are_planned_once_and_cover_every_group_tile():
    """`plan`: each (row tile, group) pair that shares a row is one
    visit, in row order; the grid's steps past them repeat the last."""
    sizes = np.array([3, 0, 40, 0, 0, 5, 16, 0], np.int32)
    groups = gm.plan(jnp.asarray(sizes), 96, interpret=True)
    tile = groups.row_tile
    assert tile == 16 and groups.group_of.shape == (96 // 16 + 8 - 1,)
    starts = np.concatenate([[0], np.cumsum(sizes)])
    want = [(row // tile, g) for g in range(8) for row in
            range(starts[g] // tile * tile, starts[g + 1], tile)
            if sizes[g]]
    n = int(groups.n_visits[0])
    got = list(zip(np.asarray(groups.tile_of), np.asarray(groups.group_of)))
    assert got[:n] == want and set(got[n:]) <= {want[-1]}
    np.testing.assert_array_equal(groups.starts, starts)
