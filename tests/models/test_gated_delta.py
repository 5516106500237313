"""The gated delta-rule mixer on the CPU in float32: the chunked form
against the recurrence it was derived from, at lengths that are and
are not multiples of the chunk, with beta near 2 and gamma near 0 and
near 1; the inverse of a chunk's unit lower triangular matrix by halves
against the row-by-row one; padding that neither writes nor decays;
and `mamba2._conv` without a bias, bit for bit with one of zeros."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gated_delta, mamba2

H, DK, DV = 3, 8, 16  # dk != dv, a head count that is no power of two


def _inputs(t, seed, *, beta="any", gamma="any", batch=2):
    """q, k (unit length, q scaled), v, log gamma and beta for `t`
    positions, and a carried state."""
    rng = np.random.default_rng(seed)
    q = gated_delta._queries(jnp.asarray(
        rng.normal(size=(batch, t, H, DK)), jnp.float32))
    k = gated_delta._keys(jnp.asarray(
        rng.normal(size=(batch, t, H, DK)), jnp.float32))
    if beta == "near 2" and t > 1:
        # The same key twice in a row, written with beta = 2: the
        # transition's eigenvalue along it is -1.
        k = k.at[:, 1::2].set(k[:, :-1:2][:, :k[:, 1::2].shape[1]])
    v = jnp.asarray(rng.normal(size=(batch, t, H, DV)), jnp.float32)
    lo, hi = {"any": (0.2, 0.999), "near 0": (1e-30, 1e-6),
              "near 1": (0.9999, 1.0)}[gamma]
    log_gamma = jnp.log(jnp.asarray(
        rng.uniform(lo, hi, (batch, t, H)), jnp.float32))
    betas = {"any": rng.uniform(0, 2, (batch, t, H)),
             "near 2": rng.uniform(1.99, 2.0, (batch, t, H))}[beta]
    s0 = jnp.asarray(rng.normal(size=(batch, H, DK, DV)), jnp.float32)
    return s0, q, k, v, log_gamma, jnp.asarray(betas, jnp.float32)


def _recurrence(s0, q, k, v, log_gamma, beta):
    """`_update` position by position: (o [B, T, H, dv], the last S)."""
    def position(s, now):
        q_t, k_t, v_t, g_t, b_t = now
        o, s = gated_delta._update(s, q_t, k_t, v_t, jnp.exp(g_t), b_t)
        return s, o

    last, o = jax.lax.scan(position, s0, tuple(
        x.swapaxes(0, 1) for x in (q, k, v, log_gamma, beta)))
    return o.swapaxes(0, 1), last


def _cfg(chunk):
    return types.SimpleNamespace(chunk_size=chunk)


@pytest.mark.parametrize("t, chunk", [(16, 8), (19, 8), (5, 8), (64, 64),
                                      (150, 64), (40, 32)])
@pytest.mark.parametrize("beta, gamma", [("any", "any"), ("near 2", "any"),
                                         ("any", "near 0"),
                                         ("near 2", "near 1")])
def test_the_chunked_form_is_the_recurrence(t, chunk, beta, gamma):
    args = _inputs(t, seed=t + chunk, beta=beta, gamma=gamma)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = _recurrence(*args)
        got_o, got_s = gated_delta._scan(_cfg(chunk), *args)
    scale = float(jnp.abs(want_o).max())
    np.testing.assert_allclose(got_o, want_o, atol=2e-5 * scale)
    np.testing.assert_allclose(got_s, want_s,
                               atol=2e-5 * float(jnp.abs(want_s).max()))


def test_beta_2_on_a_repeated_key_turns_the_state_over():
    """gamma = 1, beta = 2, S = 0: the write of v leaves S^T k = 2 v -
    0; writing v again along the same key reads 2 v back and leaves
    2 (v - 2 v) + 2 v = 0: the eigenvalue -1 that `allow_neg_eigval`
    allows, in one chunk."""
    k = gated_delta._keys(jnp.ones((1, 1, 1, DK)))
    q = k
    v = jnp.arange(1.0, DV + 1).reshape(1, 1, 1, DV)
    twice = [jnp.repeat(x, 2, 1) for x in (q, k, v)]
    o, s = gated_delta._scan(
        _cfg(8), jnp.zeros((1, 1, DK, DV)), *twice,
        jnp.zeros((1, 2, 1)), jnp.full((1, 2, 1), 2.0))
    np.testing.assert_allclose(o[0, 0, 0], 2 * v[0, 0, 0], rtol=1e-5)
    np.testing.assert_allclose(o[0, 1, 0], 0 * v[0, 0, 0], atol=1e-4)
    np.testing.assert_allclose(s, 0 * s, atol=1e-4)


@pytest.mark.parametrize("c", [8, 16, 64])
def test_the_inverse_by_halves_is_the_inverse(c):
    rng = np.random.default_rng(c)
    a = jnp.tril(jnp.asarray(rng.normal(size=(2, 3, c, c)) * 0.5,
                             jnp.float32), -1)
    with jax.default_matmul_precision("highest"):
        got = gated_delta._unit_lower_inverse(a)
        np.testing.assert_allclose(
            jnp.matmul(got, jnp.eye(c) + a),
            jnp.broadcast_to(jnp.eye(c), a.shape), atol=2e-4)
    np.testing.assert_allclose(got, gated_delta._by_rows(a), rtol=2e-3,
                               atol=2e-4)
    assert float(jnp.abs(jnp.triu(got, 1)).max()) == 0.0


def test_padding_neither_writes_nor_decays():
    """Positions with beta = 0 and gamma = 1 after the real ones, and
    the chunk's own tail: the state is that after the last real one."""
    s0, q, k, v, log_gamma, beta = _inputs(13, seed=2)
    _, want = gated_delta._scan(_cfg(8), s0, q, k, v, log_gamma, beta)
    rng = np.random.default_rng(9)

    def padded(x, fill):
        tail = jnp.asarray(rng.normal(size=(x.shape[0], 7) + x.shape[2:]),
                           jnp.float32) if fill is None \
            else jnp.full((x.shape[0], 7) + x.shape[2:], fill)
        return jnp.concatenate([x, tail], 1)

    o, got = gated_delta._scan(
        _cfg(8), s0, padded(q, None), padded(k, None), padded(v, None),
        padded(log_gamma, 0.0), padded(beta, 0.0))
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert bool(jnp.isfinite(o).all())


def test_mamba2s_convolution_without_a_bias():
    cfg = types.SimpleNamespace(conv_kernel=4)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 9, 12)), jnp.float32)
    carry = jnp.asarray(rng.normal(size=(2, 3, 12)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(12, 4)), jnp.float32)
    at = jnp.asarray([8, 5], jnp.int32)
    none, rows = mamba2._conv(cfg, {"conv_w": w}, carry, x, at, bias=False)
    zero, rows_b = mamba2._conv(
        cfg, {"conv_w": w, "conv_b": jnp.zeros(12)}, carry, x, at)
    assert np.array_equal(none, zero) and np.array_equal(rows, rows_b)
    # Written out: the sum over the window, then silu; the carry ends
    # at each row's own `at`.
    window = jnp.concatenate([carry, x], 1)
    want = jax.nn.silu(sum(window[:, j:j + 9] * w[:, j] for j in range(4)))
    np.testing.assert_allclose(none, want, atol=1e-6)
    np.testing.assert_array_equal(rows[1], window[1, 6:9])
