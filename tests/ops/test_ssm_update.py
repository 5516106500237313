"""The Mamba-2 one-token recurrence on the state where it lies
(`ray_tpu/ops/ssm_update.py`): the Pallas kernel through the interpreter
against `mamba2._update` on the layer sliced out of the stack."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import mamba2
from ray_tpu.ops import ssm_update as su

# Nemotron 3 Super's state leaf cut in layers, slots and heads: a head
# is [64, 128], 16 heads share a group's B and C; 32 heads are one
# block.
CELL = (3, 3, 32, 64, 128)


def _inputs(shape, groups, seed, dt=(1e-3, 1e-1)):
    """(stack float32, xs, B, C, dt, a), drawn as a decode step hands
    them (`mamba2.init`: dt log-uniform, A uniform in [1, 16])."""
    _, b, h, p, n = shape
    rng = np.random.default_rng(seed)
    return (rng.normal(size=shape).astype(np.float32),
            rng.normal(size=(b, h, p)).astype(np.float32),
            rng.normal(size=(b, groups, n)).astype(np.float32),
            rng.normal(size=(b, groups, n)).astype(np.float32),
            np.exp(rng.uniform(*np.log(dt), size=(b, h))).astype(np.float32),
            -rng.uniform(1.0, 16.0, size=h).astype(np.float32))


def _plain():
    return _inputs(CELL, 2, 0), 0, [False] * 3, jnp.float32


def _heads_no_block_divides():
    """49 heads of [64, 128] are two blocks of 25: the second block's
    last head is no head, and a block's heads are of four groups of
    seven."""
    shape = (2, 2, 49, 64, 128)
    assert 49 % su._head_block(*shape[2:], jnp.float32)
    return _inputs(shape, 7, 1), 1, [True, False], jnp.float32


def _middle_layer_of_five():
    return _inputs((5, 2, 8, 64, 128), 2, 2), 2, [False] * 2, jnp.float32


def _fresh_over_garbage():
    """Rows 0 and 2 start at position 0 in slots whose last tenant left
    huge numbers, infinities and NaNs in every layer."""
    stack, *rest = _inputs(CELL, 2, 3)
    stack[:, 0] = 3e38
    stack[:, 0, ::2, ::3] = np.nan
    stack[:, 2] = -np.inf
    return (stack, *rest), 1, [True, False, True], jnp.float32


def _bfloat16_stack():
    return _inputs(CELL, 2, 4), 1, [False, True, False], jnp.bfloat16


def _one_group():
    return _inputs((2, 2, 12, 64, 128), 1, 5), 0, [False] * 2, jnp.float32


def _eight_groups():
    """Eight groups of three heads: a head's group is not a shift of
    its number."""
    return _inputs((2, 2, 24, 64, 128), 8, 6), 1, [False, True], jnp.float32


def _dt_zero_keeps_the_state():
    """dt = 0 (a position that is not to count) neither decays the
    state nor adds to it: the layer comes back bit for bit."""
    stack, xs, b, c, dt, a = _inputs(CELL, 2, 7)
    return (stack, xs, b, c, np.zeros_like(dt), a), 2, [False] * 3, \
        jnp.float32


CASES = {
    "nemotrons_head_at_few_slots": _plain,
    "heads_no_block_divides": _heads_no_block_divides,
    "middle_layer_of_five": _middle_layer_of_five,
    "fresh_rows_over_garbage": _fresh_over_garbage,
    "bfloat16_stack": _bfloat16_stack,
    "one_group": _one_group,
    "eight_groups": _eight_groups,
    "dt_zero_keeps_the_state": _dt_zero_keeps_the_state,
}


def _want(stack, layer, fresh, xs, b, c, dt, a):
    """(y, S) of `mamba2._update` on the layer sliced out of the stack,
    the fresh rows from zeros, a group's rows repeated for its heads."""
    s0 = np.where(fresh[:, None, None, None], 0.0,
                  stack[layer].astype(np.float32))
    r = xs.shape[1] // b.shape[1]
    return mamba2._update(*map(jnp.asarray, (
        s0, xs, np.repeat(b, r, 1), np.repeat(c, r, 1), dt, a)))


@pytest.mark.parametrize("case", CASES)
def test_kernel_matches_the_recurrence_on_the_sliced_layer(case):
    args, layer, fresh, dtype = CASES[case]()
    stack = np.asarray(jnp.asarray(args[0], dtype))
    fresh = np.asarray(fresh)
    want_y, want_s = _want(stack, layer, fresh, *args[1:])
    y, new = jax.jit(su.ssm_update, static_argnames="interpret")(
        *map(jnp.asarray, (stack, np.int32(layer), fresh, *args[1:])),
        interpret=True)
    assert y.dtype == jnp.float32 and y.shape == want_y.shape
    assert new.dtype == dtype and new.shape == stack.shape

    def close(got, want, step):
        want = np.asarray(want, np.float32)
        np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=0,
                                   atol=step * np.abs(want).max())

    close(y, want_y, 1e-6)
    # A bfloat16 stack holds the float32 result rounded: a product and
    # a sum fused may round to the neighbouring value.
    close(new[layer], want_s.astype(dtype),
          1e-6 if dtype == jnp.float32 else 2.0 ** -8)
    bits = np.uint16 if dtype == jnp.bfloat16 else np.uint32
    if case == "dt_zero_keeps_the_state":
        np.testing.assert_array_equal(np.asarray(new)[layer].view(bits),
                                      stack[layer].view(bits))
    # Every other layer is what went in, bit for bit (the garbage too).
    others = [i for i in range(stack.shape[0]) if i != layer]
    np.testing.assert_array_equal(np.asarray(new)[others].view(bits),
                                  stack[others].view(bits))


def test_off_the_tpu_it_is_the_recurrence_as_written():
    """What a CPU run of the model takes: `_update` on the sliced layer
    and the layer put back, to the bit."""
    args, layer, fresh, _ = _fresh_over_garbage()
    fresh = np.asarray(fresh)
    want_y, want_s = _want(args[0], layer, fresh, *args[1:])
    y, new = su.ssm_update(*map(jnp.asarray, (
        args[0], np.int32(layer), fresh, *args[1:])))
    np.testing.assert_array_equal(y, want_y)
    np.testing.assert_array_equal(new[layer], want_s)
    np.testing.assert_array_equal(
        np.asarray(new)[[0, 2]].view(np.uint32),
        args[0][[0, 2]].view(np.uint32))
