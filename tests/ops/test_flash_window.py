"""The sliding window of `flash_attention`, forward and backward: the
three Pallas kernels in interpret mode against `attention_reference`,
the output and the three gradients, over windows that end inside a
tile, on a tile's edge and beyond the sequence, grouped heads 7 to 1,
ragged last blocks, and a window of None, which has to give what the
kernels gave before they had one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention
from ray_tpu.ops.attention import attention_reference, flash_attention


def seeded(b, s, h, h_kv, d, seed=0):
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(kq, (b, s, h, d), jnp.float32),
            jax.random.normal(kk, (b, s, h_kv, d), jnp.float32),
            jax.random.normal(kv, (b, s, h_kv, d), jnp.float32),
            jax.random.normal(kw, (b, s, h, d), jnp.float32))


def reference(q, k, v, window):
    return attention_reference(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), True, q.shape[-1] ** -0.5,
        window).transpose(0, 2, 1, 3)


def both(q, k, v, weight, window, block_q, block_k):
    """(output, (dq, dk, dv)) of the kernels and of the reference, for
    the scalar sum(out * weight)."""
    def kernels(q, k, v):
        out = flash_attention(q, k, v, window=window, block_q=block_q,
                              block_k=block_k, interpret=True)
        return (out * weight).sum(), out

    def plain(q, k, v):
        out = reference(q, k, v, window)
        return (out * weight).sum(), out

    (_, got), got_grads = jax.value_and_grad(
        kernels, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, want), want_grads = jax.value_and_grad(
        plain, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return (got, got_grads), (want, want_grads)


@pytest.mark.parametrize("s,h,h_kv,window,block_q,block_k", [
    (128, 4, 2, 40, 32, 32),     # the far edge inside a tile
    (128, 4, 2, 64, 32, 32),     # on a tile's edge
    (128, 4, 4, 33, 32, 32),     # one key past a tile's edge
    (128, 2, 2, 1, 32, 32),      # a row sees itself alone
    (96, 2, 1, 500, 32, 32),     # beyond the sequence: plain causal
    (128, 7, 1, 48, 32, 32),     # grouped heads 7 to 1
    (128, 4, 2, 40, 64, 32),     # blocks of queries twice the keys'
    (128, 4, 2, 40, 32, 64),     # and of keys twice the queries'
    (100, 4, 2, 37, 32, 32),     # ragged last blocks
    (100, 2, 2, 64, 64, 32),     # ragged, unequal blocks
])
def test_windowed_kernels_match_the_reference(s, h, h_kv, window, block_q,
                                              block_k):
    q, k, v, weight = seeded(2, s, h, h_kv, 16, seed=s + window)
    (got, got_grads), (want, want_grads) = both(q, k, v, weight, window,
                                                block_q, block_k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    for name, a, b in zip(("dq", "dk", "dv"), got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5, err_msg=name)


def test_a_window_one_key_short_is_told_apart():
    """What the comparison above has to catch: 39 keys where 40 are
    meant moves the output and every gradient."""
    q, k, v, weight = seeded(1, 128, 4, 2, 16, seed=3)
    (got, got_grads), _ = both(q, k, v, weight, 39, 32, 32)
    _, (want, want_grads) = both(q, k, v, weight, 40, 32, 32)
    assert float(jnp.abs(got - want).max()) > 1e-3
    for a, b in zip(got_grads, want_grads):
        assert float(jnp.abs(a - b).max()) > 1e-3


@pytest.mark.parametrize("s,block", [(100, 32), (64, 64)])
def test_no_window_is_what_it_was(s, block):
    """`window=None` hands the kernels no window at all (their calls
    carry the arguments they carried before), and its numbers are, bit
    for bit, those of a window that reaches every key and so masks
    nothing, which goes through the windowed code."""
    q, k, v, weight = seeded(1, s, 4, 2, 16, seed=s)

    def run(window):
        def f(q, k, v):
            out = flash_attention(q, k, v, window=window, block_q=block,
                                  block_k=block, interpret=True)
            return (out * weight).sum(), out
        (_, out), grads = jax.value_and_grad(
            f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out, *grads)

    default = jax.value_and_grad(
        lambda q, k, v: (flash_attention(
            q, k, v, block_q=block, block_k=block, interpret=True)
            * weight).sum(), argnums=(0, 1, 2))(q, k, v)[1]
    for a, b, c in zip(run(None)[1:], default, run(s)[1:]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(np.asarray(a), np.asarray(c))
    assert np.array_equal(np.asarray(run(None)[0]), np.asarray(run(s)[0]))


def test_no_window_reaches_no_kernel(monkeypatch):
    """The three calls of a step without a window name no `window`: the
    program lowered from them is the one the train cells had."""
    seen = []
    real = attention.pl.pallas_call

    def spy(kernel, *args, **kwargs):
        seen.append((kwargs["name"], dict(kernel.keywords)))
        return real(kernel, *args, **kwargs)

    monkeypatch.setattr(attention.pl, "pallas_call", spy)
    q, k, v, weight = seeded(1, 64, 2, 2, 16)
    for window in (None, 24):
        seen.clear()
        jax.grad(lambda q: (flash_attention(
            q, k, v, window=window, block_q=32, block_k=32, interpret=True)
            * weight).sum())(q)
        windows = {name: kw.get("window", "absent") for name, kw in seen}
        if window is None:
            assert windows == {"flash_fwd": None, "flash_bwd_dq": "absent",
                               "flash_bwd_dkv": "absent"}
        else:
            assert set(windows.values()) == {24}


def test_tiles_outside_the_windows_are_not_fetched():
    """The index maps of all three kernels, as the kernels' own
    conditions have it: a tile that is not computed names the block of
    a tile that is, so the pipeline fetches nothing new for it."""
    s, bq, bk, window = 256, 32, 32, 70
    q, k, v, weight = seeded(1, s, 2, 1, 16)
    specs = {}
    real = attention.pl.pallas_call

    def spy(kernel, *args, **kwargs):
        specs[kwargs["name"]] = kwargs["in_specs"]
        return real(kernel, *args, **kwargs)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(attention.pl, "pallas_call", spy)
        jax.grad(lambda q: (flash_attention(
            q, k, v, window=window, block_q=bq, block_k=bk, interpret=True)
            * weight).sum())(q)
    n = s // bq

    def seen(iq, ik):  # some row of the block sees some key of the block
        return (iq + 1) * bq > ik * bk \
            and iq * bq - ((ik + 1) * bk - 1) < window

    for name in ("flash_fwd", "flash_bwd_dq"):
        kv_index = specs[name][1].index_map
        for iq in range(n):
            fetched = {int(kv_index(0, 0, iq, ik)[2]) for ik in range(n)}
            assert fetched == {ik for ik in range(n) if seen(iq, ik)}
    q_index = specs["flash_bwd_dkv"][0].index_map
    for ik in range(n):
        fetched = {int(q_index(0, 0, ik, iq)[2]) for iq in range(n)}
        assert fetched == {iq for iq in range(n) if seen(iq, ik)}
    # Far fewer than the causal half: 3 or 4 tiles a row of 8.
    assert sum(seen(iq, ik) for iq in range(n) for ik in range(n)) < 30
