"""A decode step's attention over the slot cache where it lies
(`ops.attention.decode_attention`): the Pallas kernel through the
interpreter against `llama._cached_attention` on the layer sliced out of
the run's stack, at a head size of 128 so that the blocks are those the
TPU would see (256 rows a block)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.ops import attention

D = 128
# (query heads, key heads, whether a row holds the heads in one axis):
# the dense leaf [layers, slots, S, 8, 128] under 32 query heads, and
# Olmo-Hybrid's [layers, slots, S, 30 x 128] with a key head a query
# head, cut in heads to keep the interpreter quick.
LAYOUTS = {"dense": (32, 8, False), "merged": (6, 6, True)}
BLOCK = 256
LENGTHS = {
    "one": [1],
    "a block's edge": [BLOCK],
    "one past it": [BLOCK + 1],
    "the whole region": [3 * BLOCK],
    "mixed": [1, BLOCK, BLOCK + 1, 3 * BLOCK, 300, 2 * BLOCK - 1],
}
# Largest difference from the plain path, as a share of the largest
# |output|: float32 differs by the order of its sums; in bfloat16 the
# kernel rounds the weights before their sum divides them, the plain
# path after.
LIMIT = {jnp.float32: 2e-6, jnp.bfloat16: 1.5e-2}


def _case(layout, dtype, lengths, seed, layers=3, layer=1):
    heads, kv_heads, merged = LAYOUTS[layout]
    b, s = len(lengths), 3 * BLOCK
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, heads, D)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(layers, b, s, kv_heads, D)), dtype)
            for _ in range(2))
    lengths = jnp.asarray(lengths, jnp.int32)
    want = llama._cached_attention(None, q[:, None], k[layer], v[layer],
                                   lengths[:, None] - 1)[:, 0]
    # Nothing but the layer's rows under a slot's length may be read:
    # every other element is NaN.
    unread = (jnp.arange(layers)[:, None, None] != layer) | (
        jnp.arange(s)[None, None, :] >= lengths[None, :, None])
    k, v = (jnp.where(unread[..., None, None], jnp.nan, x) for x in (k, v))
    if merged:
        k, v = (x.reshape(layers, b, s, kv_heads * D) for x in (k, v))
    return q, k, v, jnp.int32(layer), lengths, want


@pytest.mark.parametrize("lengths", LENGTHS.values(), ids=LENGTHS.keys())
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_kernel_reads_what_a_slot_holds_and_no_more(layout, dtype,
                                                        lengths):
    assert attention.decode_block_rows(
        LAYOUTS[layout][1], D, dtype) == BLOCK
    *args, want = _case(layout, dtype, lengths, seed=len(lengths))
    got = jax.jit(attention.decode_attention, static_argnames="interpret")(
        *args, interpret=True)
    assert got.shape == want.shape and got.dtype == dtype
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= LIMIT[dtype] * np.abs(want).max()


# A slot's two lengths: the first half of a key head's query heads sees
# the keys under the first, the second half those under the second.
TWO_LENGTHS = {
    "one and a few": [[1, 9]],
    "either side of a block's edge": [[BLOCK - 3, BLOCK + 1]],
    "a block apart": [[BLOCK, 2 * BLOCK]],
    "more than a block apart": [[5, 3 * BLOCK]],
    "the same": [[300, 300]],
    "mixed": [[1, 2 * BLOCK + 7], [BLOCK + 4, BLOCK + 8], [3 * BLOCK - 4,
                                                          3 * BLOCK],
              [1, 1], [2 * BLOCK - 4, 2 * BLOCK], [700, 9]],
}


@pytest.mark.parametrize("lengths", TWO_LENGTHS.values(),
                         ids=TWO_LENGTHS.keys())
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("merged", [False, True], ids=["dense", "merged"])
def test_two_lengths_a_slot_are_two_groups_of_its_query_heads(merged, dtype,
                                                              lengths):
    """32 query heads on 4 key heads, 8 a key head: the first 4 of each
    see the slot's first length and the last 4 its second, as two calls
    of 16 heads with a length each would; nothing past the longer one
    is read."""
    heads, kv_heads, layers, layer = 32, 4, 2, 1
    b, s = len(lengths), 3 * BLOCK
    rng = np.random.default_rng(b)
    q = jnp.asarray(rng.normal(size=(b, heads, D)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(layers, b, s, kv_heads, D)), dtype)
            for _ in range(2))
    lengths = jnp.asarray(lengths, jnp.int32)
    by_group = q.reshape(b, kv_heads, 2, 4, D)
    want = jnp.stack([llama._cached_attention(
        None, by_group[:, :, j].reshape(b, 1, 16, D), k[layer], v[layer],
        lengths[:, j, None] - 1)[:, 0].reshape(b, kv_heads, 4, D)
        for j in range(2)], 2).reshape(q.shape)
    unread = (jnp.arange(layers)[:, None, None] != layer) | (
        jnp.arange(s)[None, None, :] >= lengths.max(-1)[None, :, None])
    k, v = (jnp.where(unread[..., None, None], jnp.nan, x) for x in (k, v))
    if merged:
        k, v = (x.reshape(layers, b, s, kv_heads * D) for x in (k, v))
    got = jax.jit(attention.decode_attention, static_argnames="interpret")(
        q, k, v, jnp.int32(layer), lengths, interpret=True)
    assert got.shape == want.shape and got.dtype == dtype
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= LIMIT[dtype] * np.abs(want).max()
    # Off the TPU the plain path, a group of heads a query position.
    k, v = (jnp.nan_to_num(x) for x in (k, v))
    plain = attention.decode_attention(q, k, v, jnp.int32(layer), lengths)
    assert np.abs(np.asarray(plain, np.float32) - want).max() \
        <= LIMIT[dtype] * np.abs(want).max()


def test_off_the_tpu_it_is_the_plain_path_on_the_sliced_layer():
    """No interpreter asked for: `llama._cached_attention` itself, to
    the bit, on both layouts (finite stacks: the plain path reads the
    region whole)."""
    for layout in LAYOUTS:
        q, k, v, layer, lengths, want = _case(layout, jnp.bfloat16,
                                              [5, BLOCK + 7], seed=9)
        k, v = (jnp.nan_to_num(x) for x in (k, v))
        by_head = k.shape[:3] + (LAYOUTS[layout][1], D)
        want = llama._cached_attention(
            None, q[:, None], k.reshape(by_head)[layer],
            v.reshape(by_head)[layer], lengths[:, None] - 1)[:, 0]
        got = attention.decode_attention(q, k, v, layer, lengths)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


@pytest.mark.parametrize("kv_heads,dtype,rows", [
    (8, jnp.bfloat16, 256),     # Mistral-7B: 2 KB a row, 0.5 MB a block
    (30, jnp.bfloat16, 256),    # Olmo-Hybrid: 7.5 KB a row, 1.97 MB
    (30, jnp.float32, 128),     # the same in float32: 2 MB holds 136
    (128, jnp.bfloat16, 64),    # 32 KB a row
])
def test_a_block_is_worked_out_from_the_shapes(kv_heads, dtype, rows):
    assert attention.decode_block_rows(kv_heads, D, dtype) == rows
