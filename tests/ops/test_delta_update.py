"""The delta rule's one-token recurrence on the state where it lies
(`ray_tpu/ops/delta_update.py`): the Pallas kernel through the
interpreter against `gated_delta._update` on the layer sliced out of the
stack."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gated_delta
from ray_tpu.ops import delta_update as du

# The cell's state leaf cut in slots: a run of 3 layers, 30 heads of
# [96, 192], which the kernel takes 15 heads a block.
CELL = (3, 4, 30, 96, 192)


def _unit(x):
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


def _inputs(shape, seed, gamma=(0.05, 0.999), beta=(0.0, 2.0)):
    """(stack float32, q, k, v, gamma, beta), drawn as a decode step
    hands them: k of unit length, q of 1 / sqrt(dk)."""
    _, b, h, dk, dv = shape
    rng = np.random.default_rng(seed)
    return (rng.normal(size=shape).astype(np.float32),
            (_unit(rng.normal(size=(b, h, dk))) * dk ** -0.5).astype(
                np.float32),
            _unit(rng.normal(size=(b, h, dk))).astype(np.float32),
            rng.normal(size=(b, h, dv)).astype(np.float32),
            rng.uniform(*gamma, size=(b, h)).astype(np.float32),
            rng.uniform(*beta, size=(b, h)).astype(np.float32))


def _plain():
    return _inputs(CELL, 0), 0, [False] * 4, jnp.float32


def _last_layer():
    return _inputs(CELL, 1), 2, [False] * 4, jnp.float32


def _fresh_over_garbage():
    """Rows 1 and 3 start at position 0 in slots whose last tenant left
    huge numbers, infinities and NaNs in every layer."""
    stack, *rest = _inputs(CELL, 2)
    stack[:, 1] = 3e38
    stack[:, 1, ::2, ::3] = np.nan
    stack[:, 3] = -np.inf
    return (stack, *rest), 1, [False, True, False, True], jnp.float32


def _beta_two_on_a_repeated_key():
    """The state already holds k (outer) v0 and the same k comes again
    with beta 2: (I - 2 k k^T) turns the written value's sign."""
    stack, q, k, v, gamma, beta = _inputs(CELL, 3)
    stack[1] = k[..., None] * _inputs(CELL, 4)[3][..., None, :]
    return (stack, q, k, v, np.ones_like(gamma), np.full_like(beta, 2.0)), \
        1, [False] * 4, jnp.float32


def _gamma_at_its_ends():
    """Half the heads forget everything (gamma 1e-30, under float32's
    rounding of anything it multiplies), half nothing (1 - 2^-24)."""
    stack, q, k, v, gamma, beta = _inputs(CELL, 5)
    gamma[:, ::2], gamma[:, 1::2] = 1e-30, np.float32(1) - np.float32(2 ** -24)
    return (stack, q, k, v, gamma, beta), 0, [False] * 4, jnp.float32


def _bfloat16_stack():
    return _inputs(CELL, 6), 1, [False, False, True, False], jnp.bfloat16


def _heads_no_block_divides():
    """17 heads of [96, 192] are two blocks of 9: the second block's
    last head is no head."""
    shape = (2, 2, 17, 96, 192)
    assert 17 % du._head_block(*shape[2:], jnp.float32)
    return _inputs(shape, 7), 1, [True, False], jnp.float32


CASES = {
    "the_cells_shape_cut_in_slots": _plain,
    "last_layer_of_the_stack": _last_layer,
    "fresh_rows_over_garbage": _fresh_over_garbage,
    "beta_two_on_a_repeated_key": _beta_two_on_a_repeated_key,
    "gamma_near_0_and_near_1": _gamma_at_its_ends,
    "bfloat16_stack": _bfloat16_stack,
    "heads_no_block_divides": _heads_no_block_divides,
}


def _want(stack, layer, fresh, *rest):
    """(o, S) of `gated_delta._update` on the layer sliced out of the
    stack, the fresh rows from zeros."""
    s0 = np.where(fresh[:, None, None, None], 0.0,
                  stack[layer].astype(np.float32))
    return gated_delta._update(*map(jnp.asarray, (s0, *rest)))


@pytest.mark.parametrize("case", CASES)
def test_kernel_matches_the_recurrence_on_the_sliced_layer(case):
    (stack, q, k, v, gamma, beta), layer, fresh, dtype = CASES[case]()
    stack = np.asarray(jnp.asarray(stack, dtype))
    fresh = np.asarray(fresh)
    want_o, want_s = _want(stack, layer, fresh, q, k, v, gamma, beta)
    o, new = jax.jit(du.delta_update, static_argnames="interpret")(
        *map(jnp.asarray, (stack, np.int32(layer), fresh, q, k, v, gamma,
                           beta)), interpret=True)
    assert o.dtype == jnp.float32 and o.shape == want_o.shape
    assert new.dtype == dtype and new.shape == stack.shape

    def close(got, want, step):
        want = np.asarray(want, np.float32)
        np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=0,
                                   atol=step * np.abs(want).max())

    close(o, want_o, 1e-6)
    # A bfloat16 stack holds the float32 result rounded: a sum in
    # another order may round to the neighbouring value.
    close(new[layer], want_s.astype(dtype),
          1e-6 if dtype == jnp.float32 else 2.0 ** -8)
    if case == "beta_two_on_a_repeated_key":
        written = np.einsum("bhkv,bhk->bhv", new[layer], k)
        was = np.einsum("bhkv,bhk->bhv", stack[layer], k)
        close(written, 2 * v - was, 1e-5)
    # Every other layer is what went in, bit for bit (the garbage too).
    others = [i for i in range(stack.shape[0]) if i != layer]
    bits = np.uint16 if dtype == jnp.bfloat16 else np.uint32
    np.testing.assert_array_equal(np.asarray(new)[others].view(bits),
                                  stack[others].view(bits))


def test_off_the_tpu_it_is_the_recurrence_as_written():
    """What a CPU run of the model takes: `_update` on the sliced layer
    and the layer put back, to the bit."""
    (stack, q, k, v, gamma, beta), layer, fresh, _ = _fresh_over_garbage()
    fresh = np.asarray(fresh)
    want_o, want_s = _want(stack, layer, fresh, q, k, v, gamma, beta)
    o, new = du.delta_update(*map(jnp.asarray, (
        stack, np.int32(layer), fresh, q, k, v, gamma, beta)))
    np.testing.assert_array_equal(o, want_o)
    np.testing.assert_array_equal(new[layer], want_s)
    np.testing.assert_array_equal(
        np.asarray(new)[[0, 2]].view(np.uint32), stack[[0, 2]].view(np.uint32))
