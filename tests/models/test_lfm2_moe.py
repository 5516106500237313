"""What is LFM2's alone, at debug widths on the CPU, in float32, seeded
random weights: the file building the published model, the short
convolution being Mamba-2's with no activation, a row that starts at
position 0 starting from zeros, and a prefill through the flash kernel
at heads of 64 channels. What every served family's tests hold is in
`test_served_contract.py`, over this family's row in `families.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import lfm2_moe, mamba2
from tests.models import families

NAME = "Lfm2MoeConfig"
FILE, ADAPTER = families.file(NAME), families.adapter(NAME)
CFG = families.cfg(NAME)
forward_with_cache = families.forward_with_cache(NAME)


@pytest.fixture
def params():
    return families.params(NAME)


def _tokens(shape, seed=1):
    return families.tokens(NAME, shape, seed)


def _state(cache):
    return families.state(NAME, cache)


def _cache(rows=2, max_seq=32):
    return lfm2_moe.init_cache(CFG, rows, max_seq)


def test_the_file_builds_the_published_model():
    cfg = ADAPTER.program_config(FILE)
    assert cfg.layer_types == lfm2_moe.PUBLISHED_LAYER_TYPES[:14]
    assert cfg.runs() == [(("dense", "conv"), 2)] + [
        (("sparse", "full"), 1), (("sparse", "conv"), 3)] * 3
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.hidden_dim, cfg.dense_hidden_dim, cfg.vocab_size) == (
                2048, 32, 8, 64, 1792, 7168, 65536)
    assert (cfg.n_experts, cfg.n_experts_per_token, cfg.scoring,
            cfg.selection_bias, cfg.norm_topk_prob, cfg.gate_scale,
            cfg.shared_hidden_dim, cfg.experts_held) == (
                32, 4, "sigmoid", True, True, 1.0, 0, None)
    assert (cfg.conv_kernel, cfg.n_dense_layers, cfg.tie_embeddings,
            cfg.rope_theta, cfg.norm_eps) == (3, 2, True, 1e6, 1e-5)
    assert cfg.dtype == jnp.bfloat16
    # The defaults are the published model's.
    assert dataclasses.replace(
        cfg, n_layers=24, layer_types=lfm2_moe.PUBLISHED_LAYER_TYPES) \
        == lfm2_moe.Lfm2MoeConfig()
    assert lfm2_moe.PUBLISHED_LAYER_TYPES == tuple(
        {"conv": "conv", "full_attention": "full"}[kind]
        for kind in FILE["layer_types"])
    # The compared stack: published layers 1 to 4, every kind of layer.
    assert ADAPTER.with_layers(cfg, 4).runs() == [
        (("dense", "conv"), 1), (("sparse", "full"), 1),
        (("sparse", "conv"), 2)]
    assert CFG == lfm2_moe.Lfm2MoeConfig.debug_lfm2()
    assert CFG.n_heads // CFG.n_kv_heads == cfg.n_heads // cfg.n_kv_heads


def test_the_convolution_is_mamba2s_with_no_activation():
    """`mamba2._conv` told `activation=None` hands back the sum itself;
    with its default it is what it was, silu of that sum."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 7, 6)), jnp.float32)
    carry = jnp.asarray(rng.normal(size=(2, 2, 6)), jnp.float32)
    lp = {"conv_w": jnp.asarray(rng.normal(size=(6, 3)), jnp.float32)}
    at = jnp.asarray([6, 3], jnp.int32)
    plain, rows = mamba2._conv(CFG, lp, carry, x, at, bias=False,
                               activation=None)
    window = np.concatenate([carry, x], 1)
    want = sum(window[:, j:j + 7] * np.asarray(lp["conv_w"])[:, j]
               for j in range(3))
    np.testing.assert_allclose(plain, want, atol=1e-6)
    np.testing.assert_array_equal(rows[0], x[0, 5:7])
    np.testing.assert_array_equal(rows[1], x[1, 2:4])
    activated, same_rows = mamba2._conv(CFG, lp, carry, x, at, bias=False)
    np.testing.assert_allclose(activated, jax.nn.silu(plain), atol=1e-6)
    np.testing.assert_array_equal(same_rows, rows)


def test_a_row_that_starts_at_zero_starts_from_zeros(params):
    """A cache whose carried rows hold another request's: a prefill from
    position 0 reads none of them, one from a later position does."""
    tokens = _tokens((2, 9), seed=5)
    start = jnp.zeros(2, jnp.int32)
    want, _ = forward_with_cache(params, tokens, CFG, _cache(), start)
    _, used = forward_with_cache(params, _tokens((2, 12), seed=6), CFG,
                                 _cache(), start)
    assert all(float(jnp.abs(x).max()) > 0 for x in _state(used))
    got, _ = forward_with_cache(params, tokens, CFG, used, start)
    np.testing.assert_allclose(got, want, atol=1e-6)
    later, _ = forward_with_cache(params, tokens, CFG, used, start + 12)
    fresh, _ = forward_with_cache(params, tokens, CFG, _cache(), start + 12)
    assert float(jnp.abs(later - fresh).max()) > 1e-3


# 128 rows in two tiles of 64; 640, a multiple of 128 between two powers
# of two as `serve.llm.prefill_bucket`'s 384, 768 and 1,536 are, in the
# tile the kernel chooses.
@pytest.mark.parametrize("rows, tiles", [
    (128, {"block_q": 64, "block_k": 64}), (640, {})], ids=["128", "640"])
def test_a_prefill_through_the_flash_kernel_equals_the_plain_path(
        params, monkeypatch, rows, tiles):
    """A prefill from position 0 at a bucket the kernel tiles (any
    multiple of 128 rows) takes `flash_attention_forward` over the
    call's own keys on a TPU (interpreted here); one from a later
    position takes the plain path on the same program."""
    import types

    from jax import lax

    from ray_tpu.ops import attention

    tokens = _tokens((1, rows), seed=8)
    start = jnp.zeros(1, jnp.int32)
    want, plain_cache = forward_with_cache(
        params, tokens, CFG, _cache(1, 2 * rows), start, at=rows - 28)
    calls = []

    def flash(q, k, v):
        calls.append(q.shape)
        return attention.flash_attention_forward(
            q, k, v, interpret=True, **tiles)

    monkeypatch.setattr(lfm2_moe, "attention", types.SimpleNamespace(
        on_tpu=lambda: False, flash_attention_forward=flash))
    # `serving.own_keys` as it is on a TPU.
    monkeypatch.setattr(
        lfm2_moe, "own_keys", lambda tiled, start_pos, flash, plain:
        lax.cond(start_pos.max() == 0, flash, plain) if tiled else plain())
    # (A jit of its own: it is traced under the patches.)
    patched = jax.jit(lambda *args, **at: lfm2_moe.forward_with_cache(
        *args, **at), static_argnums=2)
    got, cache = patched(
        params, tokens, CFG, _cache(1, 2 * rows), start, at=rows - 28)
    assert calls == [(1, rows, 8, 8)] * 2  # a trace a run of full layers
    np.testing.assert_allclose(got, want, atol=3e-6 * np.abs(want).max())
    assert not np.array_equal(got, want)
    for x, y in zip(jax.tree.leaves(cache), jax.tree.leaves(plain_cache)):
        np.testing.assert_allclose(x, y, atol=3e-6 * np.abs(y).max())
    later, _ = patched(
        params, tokens, CFG, cache, start + rows, at=rows - 28)
    assert later.shape == want.shape and bool(jnp.isfinite(later).all())
