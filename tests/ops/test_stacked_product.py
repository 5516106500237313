"""`ops.stacked_product`: the kernel through the Pallas interpreter
against `jnp.einsum` on `stack[layer]`, at the served shapes' forms (a
tenth of their sizes along K and N: the interpreter is slow, the tiles,
the bands of columns and the three stored orders are the real ones'),
every layer of a stack of three, and the fallback off the TPU and for a
call of many rows giving the same answer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import stacked_product as sp
from ray_tpu.ops.stacked_product import stacked_product

LAYERS = 3
# One layer's shape, and the served leaf it stands for.
SHAPES = {
    "swiglu_up_3840x11008": (384, 11008),      # Olmo-Hybrid's w1, w3
    "swiglu_down_11008x3840": (1152, 3840),    # its w2
    "dense_up_4096x14336": (512, 14336),       # Mistral-7B's w1, w3
    "dense_down_14336x4096": (1792, 4096),     # its w2
    "delta_value_3840x30x192": (384, 30, 192),  # gated delta's wv, wg
    "delta_key_3840x30x96": (384, 30, 96),     # its wq, wk
    "full_3840x30x128": (384, 30, 128),        # a full layer's, k = 128
    "one_head_2048x1x2048": (256, 1, 2048),
    "dense_q_4096x32x128": (512, 32, 128),     # Mistral-7B's wq
    "dense_kv_4096x8x128": (1024, 8, 128),     # its wk, wv
    "kda_2304x32x128": (384, 32, 128),         # Kimi Linear's, K / 128 odd
}


def _operands(rows, shape, seed=0):
    k_w, k_x = jax.random.split(jax.random.PRNGKey(seed))
    stack = (jax.random.normal(k_w, (LAYERS,) + shape, jnp.float32)
             * 0.05).astype(jnp.bfloat16)
    x = jax.random.normal(k_x, (rows, 1, shape[0]),
                          jnp.float32).astype(jnp.bfloat16)
    return x, stack


def _reference(x, stack, layer):
    return jnp.einsum("bsd,d...->bs...", x, stack[layer],
                      preferred_element_type=jnp.float32)


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("rows", [16, 32, 64])
@pytest.mark.parametrize("name", SHAPES)
def test_the_kernel_is_the_einsum_on_the_layer(name, rows, layer):
    x, stack = _operands(rows, SHAPES[name])
    assert sp.fits(x, stack)
    got = stacked_product(x, stack, jnp.int32(layer), interpret=True)
    want = _reference(x, stack, layer)
    assert got.shape == want.shape and got.dtype == jnp.bfloat16
    # float32 sums rounded once to bfloat16, as the einsum's are.
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=2 ** -7, atol=2 ** -7)
    # Another layer's matrix gives another answer: the index map read
    # the layer it was handed.
    other = _reference(x, stack, (layer + 1) % LAYERS)
    assert np.abs(np.asarray(got, np.float32) - np.asarray(other)).max() > 0.5


@pytest.mark.parametrize("name", SHAPES)
def test_off_the_tpu_it_is_the_einsum_on_the_slice(name):
    x, stack = _operands(32, SHAPES[name], seed=1)
    got = stacked_product(x, stack, jnp.int32(1))
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda x, s: stacked_product(x, s, jnp.int32(1)))(x, stack))
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(jnp.einsum("bsd,d...->bs...", x, stack[1]), np.float32))
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(stacked_product(x, stack, jnp.int32(1), interpret=True),
                   np.float32), rtol=2 ** -6, atol=2 ** -6)


@pytest.mark.parametrize("case", ["many_rows", "float32", "whole_tiles"])
def test_a_call_the_kernel_does_not_take_is_the_einsum(case):
    rows, shape = {"many_rows": (512, (256, 384)),
                   "float32": (16, (256, 384)),
                   # Stored as named, the contracted axis outside the
                   # tiles, and two tiles of lanes a head: no strided
                   # load gathers a head's matrix.
                   "whole_tiles": (16, (256, 16, 256))}[case]
    x, stack = _operands(rows, shape, seed=2)
    if case == "float32":
        x, stack = x.astype(jnp.float32), stack.astype(jnp.float32)
    assert not sp.fits(x, stack)
    run = lambda x, s: stacked_product(x, s, jnp.int32(2), interpret=True)
    assert "pallas_call" not in str(jax.make_jaxpr(run)(x, stack))
    np.testing.assert_array_equal(
        np.asarray(run(x, stack), np.float32),
        np.asarray(jnp.einsum("bsd,d...->bs...", x, stack[2]), np.float32))


def test_a_decode_step_on_a_tpu_engages_and_nothing_else(monkeypatch):
    assert not sp.engages(1)  # this process's backend is no TPU
    monkeypatch.setattr(sp, "on_tpu", lambda: True)
    assert sp.engages(1) and not sp.engages(256)


def test_leaf_product_reads_the_stack_only_where_it_was_handed():
    x, stack = _operands(16, (256, 384), seed=3)
    lp = {"w": stack[1]}
    sliced = sp.leaf_product("bsd,df->bsf", x, "w", lp)
    handed = sp.leaf_product("bsd,df->bsf", x, "w", {}, ({"w": stack}, 1))
    other = sp.leaf_product("bsd,df->bsf", x, "w", lp, ({"v": stack}, 1))
    np.testing.assert_array_equal(np.asarray(sliced, np.float32),
                                  np.asarray(handed, np.float32))
    np.testing.assert_array_equal(np.asarray(sliced, np.float32),
                                  np.asarray(other, np.float32))
