"""The two served kernels of `ops/attention.py` at a head size of 64,
half a row of lanes (LFM2's heads), through the Pallas interpreter
against the plain paths: `decode_attention` over a merged leaf of
8 x 64 = 512 channels, four query heads a key head, where a head keeps
a slice of 64 lanes that starts at a multiple of 64; and
`flash_attention_forward` over blocks [block_q, 64]."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.ops import attention

D, HEADS, KV_HEADS = 64, 32, 8
BLOCK = 256
LENGTHS = {
    "one": [1],
    "either side of a block's edge": [BLOCK, BLOCK + 1],
    "mixed": [1, BLOCK - 1, 3 * BLOCK, 300, 2 * BLOCK + 9],
}
# As `test_decode_attention.py`'s: float32 differs by the order of its
# sums, bfloat16 by where the weights are rounded.
LIMIT = {jnp.float32: 2e-6, jnp.bfloat16: 1.5e-2}


@pytest.mark.parametrize("lengths", LENGTHS.values(), ids=LENGTHS.keys())
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_decode_attention_on_a_merged_leaf_of_eight_heads_of_64(dtype,
                                                                lengths):
    layers, layer = 2, 1
    b, s = len(lengths), 3 * BLOCK
    assert attention.decode_block_rows(KV_HEADS, D, dtype) == BLOCK
    rng = np.random.default_rng(len(lengths))
    q = jnp.asarray(rng.normal(size=(b, HEADS, D)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(layers, b, s, KV_HEADS, D)), dtype)
            for _ in range(2))
    lengths = jnp.asarray(lengths, jnp.int32)
    want = llama._cached_attention(None, q[:, None], k[layer], v[layer],
                                   lengths[:, None] - 1)[:, 0]
    # Nothing but the layer's rows under a slot's length may be read.
    unread = (jnp.arange(layers)[:, None, None] != layer) | (
        jnp.arange(s)[None, None, :] >= lengths[None, :, None])
    k, v = (jnp.where(unread[..., None, None], jnp.nan, x).reshape(
        layers, b, s, KV_HEADS * D) for x in (k, v))
    got = jax.jit(attention.decode_attention, static_argnames="interpret")(
        q, k, v, jnp.int32(layer), lengths, interpret=True)
    assert got.shape == want.shape and got.dtype == dtype
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= LIMIT[dtype] * np.abs(want).max()
    # A head's output is its own key head's: heads 4i .. 4i + 3 differ
    # from the next four (the slices of lanes were not mixed up).
    assert np.abs(got[:, :4] - got[:, 4:8]).max() > 0.01


@pytest.mark.parametrize("rows,block", [(256, 128), (384, 128), (200, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_forward_flash_kernel_at_heads_of_64(dtype, rows, block):
    """A served prefill's attention over its own keys, grouped-query,
    a ragged last block among the cases."""
    rng = np.random.default_rng(rows)
    q = jnp.asarray(rng.normal(size=(1, rows, HEADS, D)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(1, rows, KV_HEADS, D)), dtype)
            for _ in range(2))
    got = attention.flash_attention_forward(
        q, k, v, block_q=block, block_k=block, interpret=True)
    want = attention.flash_attention_forward(q, k, v)  # the reference
    assert got.shape == want.shape == q.shape
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.abs(got - want).max() <= LIMIT[dtype] * np.abs(want).max()
