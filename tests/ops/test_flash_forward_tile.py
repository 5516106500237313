"""The tile `flash_attention_forward` takes for a prefill's rows
(`attention.forward_tile`): multiples of 128 rows between two powers of
two (the engine's buckets 384, 768 and 1,536 are such:
`serve.llm.prefill_bucket`; here 640, 896, 1,280 and 1,792, whose tiles
are no power of two either) go through the kernel in tiles that divide
them, through the Pallas interpreter against `attention_reference`;
every row count served or trained before those buckets came keeps its
tile."""

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention


@pytest.mark.parametrize("rows", [128, 256, 512, 1024, 2048, 4096, 5120,
                                  9216, 16384])
def test_a_row_count_of_before_keeps_its_tile(rows):
    assert attention.forward_tile(rows) == min(1024, rows)


@pytest.mark.parametrize("rows, tile, under_512", [
    (384, 384, 384), (640, 640, 128), (768, 768, 384), (896, 896, 128),
    (1280, 640, 256), (1536, 768, 512), (1792, 896, 256), (2560, 640, 512),
    (3072, 1024, 512), (3584, 896, 512),
    (200, 200, 200), (1100, 1024, 512), (640 + 64, 640 + 64, 512)])
def test_the_tile_divides_a_multiple_of_128(rows, tile, under_512):
    """... is a multiple of 128 itself and at most 1,024, or what the
    caller says; rows that are no multiple of 128 keep that or
    themselves, the last tile ragged."""
    assert attention.forward_tile(rows) == tile
    assert attention.forward_tile(rows, 512) == under_512


CALLS = {"plain": (128, None), "block=4": (128, 4), "a head of 64": (64, None)}


@pytest.mark.parametrize("d, block", CALLS.values(), ids=CALLS.keys())
@pytest.mark.parametrize("rows", [640, 896, 1280, 1792])
def test_the_forward_kernel_at_a_bucket_between_two_powers_of_two(
        rows, d, block, monkeypatch):
    heads, kv_heads = 2, 1
    rng = np.random.default_rng(rows + d)
    q = jnp.asarray(rng.normal(size=(1, rows, heads, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, rows, kv_heads, d)), jnp.float32)
            for _ in range(2))
    tiles = []
    flash_fwd = attention._flash_fwd

    def seen(q, k, v, causal, sm_scale, block_q, block_k, *args, **kwargs):
        tiles.append((block_q, block_k))
        return flash_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                         *args, **kwargs)

    monkeypatch.setattr(attention, "_flash_fwd", seen)
    got = attention.flash_attention_forward(q, k, v, block=block,
                                            interpret=True)
    tile = attention.forward_tile(rows)
    assert tiles == [(tile, tile)] and rows % tile == 0 and tile % 128 == 0
    want = attention.attention_reference(
        *(x.transpose(0, 2, 1, 3) for x in (q, k, v)), True, d ** -0.5,
        None, block).transpose(0, 2, 1, 3)
    assert got.shape == want.shape == q.shape
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() <= 2e-6 * np.abs(want).max()
    if block is not None:  # the block mask is not ignored
        causal = attention.flash_attention_forward(q, k, v, interpret=True)
        assert np.abs(np.asarray(causal) - want).max() > 1e-2
