"""`_cached_attention` against the plain repeat-and-float32 form it
replaced (kept here as the reference), and a walk over a decode step's
jaxpr that keeps the repeat and the up-cast of the cache from coming
back unnoticed."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import llama
from ray_tpu.models.llama import (
    LlamaConfig,
    forward_with_cache,
    init_kv_cache,
    init_params,
)

S = 96
HEAD_DIM = 64
KV_HEADS = 2


def reference_attention(cfg, q, k_cache, v_cache, q_positions):
    """The form `_cached_attention` had up to PR 24: K and V repeated
    over the GQA group and multiplied in float32."""
    d = q.shape[-1]
    rep = cfg.n_heads // cfg.n_kv_heads
    k = jnp.repeat(k_cache, rep, axis=2)
    v = jnp.repeat(v_cache, rep, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                        k.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * (d ** -0.5)
    mask = jnp.arange(k.shape[1])[None, None, :] <= q_positions[:, :, None]
    scores = jnp.where(mask[:, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _starts(b, t):
    """Rows at different positions: the first at 0, the second ending at
    S - 1, the others in between. One row alone takes each in turn."""
    rows = [0, S - t, 5, 17][:b]
    return [rows] if b > 1 else [[0], [S - t], [11]]


@pytest.mark.parametrize("b,t", [(4, 1), (1, 64), (3, 16)])
@pytest.mark.parametrize("rep", [1, 4, 8])
def test_matches_repeat_and_float32_reference(rep, b, t):
    cfg = LlamaConfig(n_heads=KV_HEADS * rep, n_kv_heads=KV_HEADS,
                      dim=KV_HEADS * rep * HEAD_DIM)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(rep * 100 + b), 3)
    q = jax.random.normal(kq, (b, t, cfg.n_heads, HEAD_DIM), jnp.bfloat16)
    # Every position of every row holds keys and values, as a reused
    # slot's region does beyond the row's own length.
    shape = (b, S, KV_HEADS, HEAD_DIM)
    k_cache = jax.random.normal(kk, shape, jnp.bfloat16)
    v_cache = jax.random.normal(kv, shape, jnp.bfloat16)
    for starts in _starts(b, t):
        positions = (jnp.asarray(starts, jnp.int32)[:, None]
                     + jnp.arange(t)[None, :])
        got = llama._cached_attention(cfg, q, k_cache, v_cache, positions)
        want = reference_attention(cfg, q, k_cache, v_cache, positions)
        assert got.shape == want.shape and got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=1e-2, atol=1e-2, err_msg=f"starts {starts}")
        # A stale key must weigh nothing: the row at position 0 returns
        # its first value whatever lies behind it.
        if starts[0] == 0:
            np.testing.assert_array_equal(
                np.asarray(got[0, 0], np.float32),
                np.asarray(jnp.repeat(v_cache[0, 0], rep, axis=0),
                           np.float32))


def _intermediates(jaxpr):
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            if hasattr(var.aval, "shape"):
                yield eqn.primitive.name, var.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _intermediates(sub)


def _decode_step_offenders():
    """Intermediates of one decode step (`T = 1`, `rep = 4`) that are
    larger than one layer's K cache, or as large and float32. One layer,
    so that the stacked cache the step returns is of that size too."""
    slots, max_seq = 4, 256
    cfg = LlamaConfig(vocab_size=64, dim=128, n_layers=1, n_heads=8,
                      n_kv_heads=2, hidden_dim=64, max_seq_len=max_seq,
                      dtype=jnp.bfloat16)
    layer_cache = slots * max_seq * cfg.n_kv_heads * cfg.head_dim
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, slots, max_seq))
    closed = jax.make_jaxpr(
        lambda p, c, tok, pos: forward_with_cache(p, tok, cfg, c, pos))(
            params, cache, jax.ShapeDtypeStruct((slots, 1), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32))
    seen = list(_intermediates(closed.jaxpr))
    assert len(seen) > 50  # the walk went into the layer scan
    return [(name, aval.shape, aval.dtype.name) for name, aval in seen
            if aval.size > layer_cache
            or (aval.size == layer_cache and aval.dtype == jnp.float32)]


def test_decode_step_holds_nothing_larger_than_a_layers_cache():
    assert _decode_step_offenders() == []


def test_the_walk_finds_the_repeat_and_the_upcast(monkeypatch):
    monkeypatch.setattr(llama, "_cached_attention", reference_attention)
    found = _decode_step_offenders()
    # rep x the cache (bf16, then float32) for K and for V.
    assert len(found) >= 4
    assert any(dtype == "float32" for _, _, dtype in found)
    assert any(dtype == "bfloat16" for _, _, dtype in found)
