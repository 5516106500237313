"""Op-level tests: Pallas kernels (interpret mode on CPU) vs reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import (
    apply_rope,
    flash_attention,
    layer_norm,
    rope_frequencies,
    softmax_cross_entropy,
)
from ray_tpu.ops.attention import attention_reference
from ray_tpu.ops.cross_entropy import softmax_cross_entropy_reference


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_interpret_matches_reference(causal):
    b, s, h, d = 2, 128, 4, 32
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, h, d), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    expected = attention_reference(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal, d ** -0.5,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-6)


def test_flash_attention_gqa():
    b, s, h, h_kv, d = 1, 64, 8, 2, 16
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, h_kv, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, h_kv, d), jnp.float32)
    got = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    expected = attention_reference(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), True, d ** -0.5,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-6)


def test_flash_attention_grad():
    b, s, h, d = 1, 64, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, d))
    k = jax.random.normal(jax.random.PRNGKey(3), (b, s, h, d))
    v = jax.random.normal(jax.random.PRNGKey(4), (b, s, h, d))

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, block_q=32, block_k=32,
                               interpret=True).sum()

    def loss_ref(q, k, v):
        return attention_reference(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), True, d ** -0.5).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [
    (1, 64, 64, 4, 2, 16),     # GQA, even blocks
    (2, 96, 96, 4, 4, 16),     # ragged q and k blocks (96 % 32 != 0 w/ 64)
    (1, 100, 100, 2, 2, 16),   # ragged both
])
def test_flash_attention_grad_pallas_bwd(causal, shape):
    """Pallas dq/dk/dv kernels vs reference autodiff, incl. GQA + ragged."""
    b, sq, sk, h, h_kv, d = shape
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(keys[0], (b, sq, h, d))
    k = jax.random.normal(keys[1], (b, sk, h_kv, d))
    v = jax.random.normal(keys[2], (b, sk, h_kv, d))
    do = jax.random.normal(keys[3], (b, sq, h, d))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=64,
                               block_k=64, interpret=True)

    def ref(q, k, v):
        return attention_reference(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal, d ** -0.5,
        ).transpose(0, 2, 1, 3)

    _, vjp1 = jax.vjp(flash, q, k, v)
    _, vjp2 = jax.vjp(ref, q, k, v)
    for a, b_ in zip(vjp1(do), vjp2(do)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


def test_layer_norm():
    x = jax.random.normal(jax.random.PRNGKey(7), (8, 64), jnp.float32)
    w = jnp.ones(64)
    b = jnp.zeros(64)
    out = layer_norm(x, w, b)
    np.testing.assert_allclose(np.asarray(out).mean(-1), 0.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out).std(-1), 1.0, atol=1e-2)


def test_rope_rotation_preserves_norm():
    cos, sin = rope_frequencies(64, 128)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 100, 4, 64))
    out = apply_rope(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    # position 0 is identity
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(x[:, 0]),
                               atol=1e-6)


def test_rope_positions_arg():
    cos, sin = rope_frequencies(32, 64)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 8, 2, 32))
    pos = jnp.arange(8)[None, :]
    a = apply_rope(x, cos, sin)
    b = apply_rope(x, cos, sin, positions=pos)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_cross_entropy_blockwise_matches_reference():
    n, v = 32, 1000
    logits = jax.random.normal(jax.random.PRNGKey(10), (n, v)) * 3
    labels = jax.random.randint(jax.random.PRNGKey(11), (n,), 0, v)
    got = softmax_cross_entropy(logits, labels, 256)
    expected = softmax_cross_entropy_reference(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_cross_entropy_grad_matches_reference():
    n, v = 16, 500
    logits = jax.random.normal(jax.random.PRNGKey(12), (n, v))
    labels = jax.random.randint(jax.random.PRNGKey(13), (n,), 0, v)

    g1 = jax.grad(lambda l: softmax_cross_entropy(l, labels, 128).mean())(
        logits)
    g2 = jax.grad(
        lambda l: softmax_cross_entropy_reference(l, labels).mean())(logits)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("v,block", [(500, 128), (384, 128), (1000, 1000)])
def test_fused_linear_cross_entropy_matches_unfused(v, block):
    from ray_tpu.ops.cross_entropy import fused_linear_cross_entropy

    n, d = 24, 32
    x = jax.random.normal(jax.random.PRNGKey(20), (n, d))
    w = jax.random.normal(jax.random.PRNGKey(21), (d, v)) * 0.1
    labels = jax.random.randint(jax.random.PRNGKey(22), (n,), 0, v)

    got = fused_linear_cross_entropy(x, w, labels, block)
    expected = softmax_cross_entropy_reference(x @ w, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-4, atol=1e-4)

    # Gradients wrt both x and w match the unfused composition.
    gx1, gw1 = jax.grad(
        lambda x, w: fused_linear_cross_entropy(x, w, labels, block).mean(),
        argnums=(0, 1))(x, w)
    gx2, gw2 = jax.grad(
        lambda x, w: softmax_cross_entropy_reference(x @ w, labels).mean(),
        argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw1), np.asarray(gw2),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("s,h,h_kv,window,block", [
    (256, 4, 4, 64, 64),     # the window a block long
    (256, 4, 2, 100, 64),    # its far edge inside a block; grouped heads
    (256, 2, 1, 8, 64),      # shorter than a block
    (192, 2, 2, 1000, 64),   # longer than the sequence: plain causal
    (128, 2, 2, 37, 128),    # one block of queries and of keys
    (256, 2, 1, None, 64),   # no window
])
def test_flash_attention_forward_window(s, h, h_kv, window, block):
    """The forward kernel with a window (a row sees `window` keys, itself
    the last) and no logsumexp written, through the interpreter, against
    the dense masked softmax; and the path a backend that is no TPU takes
    without the interpreter, which is that reference."""
    from ray_tpu.ops.attention import flash_attention_forward

    d = 32
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(s + (window or 0)), 3)
    q = jax.random.normal(kq, (2, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (2, s, h_kv, d), jnp.float32)
    v = jax.random.normal(kv, (2, s, h_kv, d), jnp.float32)
    back = np.arange(s)[:, None] - np.arange(s)[None, :]
    seen = (back >= 0) & (back < (window or s))
    scores = np.einsum("bqgrd,bkgd->bgrqk",
                       np.asarray(q).reshape(2, s, h_kv, h // h_kv, d),
                       np.asarray(k)) * d ** -0.5
    scores = np.where(seen, scores, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.einsum("bgrqk,bkgd->bqgrd", probs,
                     np.asarray(v)).reshape(2, s, h, d)
    got = flash_attention_forward(q, k, v, window=window, block_q=block,
                                  block_k=block, interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)
    plain = flash_attention_forward(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(plain), want, rtol=2e-5, atol=2e-6)
    if window is not None and window < s:  # the window is not ignored
        causal = flash_attention(q, k, v, block_q=block, block_k=block,
                                 interpret=True)
        assert np.abs(np.asarray(causal) - want).max() > 1e-2
