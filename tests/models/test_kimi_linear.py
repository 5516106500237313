"""What is Kimi Linear's alone, at debug widths on the CPU, in float32:
the file building the published model; the chunked scan for a decay a
key channel against the recurrence token by token, decays near 0 and
near 1 in one head; and the state kernel through the Pallas interpreter
for a decay a head and a decay a channel. What every served family's
tests hold is in `test_served_contract.py`, over this family's row in
`families.py`."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gated_delta, kimi_linear
from ray_tpu.ops import delta_update as du
from tests.models import families

NAME = "KimiLinearConfig"
FILE, ADAPTER = families.file(NAME), families.adapter(NAME)
CFG = families.cfg(NAME)


def test_the_file_builds_the_published_model():
    cfg = ADAPTER.program_config(FILE)
    period = [("sparse", "kda")] * 3 + [("sparse", "mla")]
    assert list(cfg.kinds) == [("dense", "kda")] + period[1:] + period * 2
    assert cfg.kinds == kimi_linear.published_kinds()[:12]
    assert (cfg.dim, cfg.n_heads, cfg.hidden_dim, cfg.dense_hidden_dim,
            cfg.vocab_size) == (2304, 32, 1024, 9216, 20480)
    assert (cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim,
            cfg.conv_kernel, cfg.gate_rank, cfg.allow_neg_eigval) \
        == (32, 128, 128, 4, 128, False)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert (cfg.n_experts, cfg.experts_held, cfg.n_experts_per_token,
            cfg.scoring, cfg.selection_bias, cfg.norm_topk_prob,
            cfg.gate_scale, cfg.shared_hidden_dim) \
        == (256, (0, 32), 8, "sigmoid", True, True, 2.446, 1024)
    assert cfg.dtype == jnp.bfloat16 and cfg.state_dtype == jnp.float32
    # The defaults are the published model's, uncut.
    whole = kimi_linear.KimiLinearConfig()
    assert dataclasses.replace(
        cfg, n_layers=27, vocab_size=163840, experts_held=None,
        layer_kinds=()) == whole
    kinds = whole.kinds
    assert [i + 1 for i, (_, mixer) in enumerate(kinds) if mixer == "mla"] \
        == FILE["linear_attn_config"]["full_attn_layers"]
    assert [i + 1 for i, (_, mixer) in enumerate(kinds) if mixer == "kda"] \
        == FILE["linear_attn_config"]["kda_layers"]
    assert [ffn for ffn, _ in kinds] == ["dense"] + ["sparse"] * 26
    assert ADAPTER.with_layers(cfg, 3).kinds == (
        ("dense", "kda"), ("sparse", "kda"), ("sparse", "mla"))
    debug = kimi_linear.KimiLinearConfig.debug_kimi_linear()
    assert debug.delta_key_dim != debug.delta_value_dim
    assert debug.delta_heads & (debug.delta_heads - 1)  # no power of two
    assert debug.chunk_size > gated_delta._SUB_BLOCK
    assert CFG == debug


# -- the chunked scan and the kernel for a decay a key channel ---------------

H, DK, DV = 3, 8, 16


def _scan_inputs(t, seed, gamma, batch=2):
    """q, k (unit length, q scaled), v, a log decay a key channel and
    beta in (0, 1) for `t` positions, and a carried state. "both ends":
    in every head, every other channel forgets all but 1e-30 to 1e-6 of
    its row a step and the rest keep 0.9999 of theirs, so that
    exp(-g_j) of the one overflows float32 within a chunk while
    exp(g_i) of the other stays 1."""
    rng = np.random.default_rng(seed)
    q = gated_delta._queries(jnp.asarray(
        rng.normal(size=(batch, t, H, DK)), jnp.float32))
    k = gated_delta._keys(jnp.asarray(
        rng.normal(size=(batch, t, H, DK)), jnp.float32))
    v = jnp.asarray(rng.normal(size=(batch, t, H, DV)), jnp.float32)
    if gamma == "any":
        decay = rng.uniform(0.2, 0.999, (batch, t, H, DK))
    else:
        decay = rng.uniform(0.9999, 1.0, (batch, t, H, DK))
        decay[..., ::2] = rng.uniform(1e-30, 1e-6, (batch, t, H, DK // 2))
    beta = jnp.asarray(rng.uniform(0, 1, (batch, t, H)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(batch, H, DK, DV)), jnp.float32)
    return s0, q, k, v, jnp.log(jnp.asarray(decay, jnp.float32)), beta


def _recurrence(s0, q, k, v, log_gamma, beta):
    """`_update` position by position: (o [B, T, H, dv], the last S)."""
    def position(s, now):
        q_t, k_t, v_t, g_t, b_t = now
        o, s = gated_delta._update(s, q_t, k_t, v_t, jnp.exp(g_t), b_t)
        return s, o

    last, o = jax.lax.scan(position, s0, tuple(
        x.swapaxes(0, 1) for x in (q, k, v, log_gamma, beta)))
    return o.swapaxes(0, 1), last


@pytest.mark.parametrize("t, chunk", [(19, 8), (150, 64), (70, 48)])
@pytest.mark.parametrize("gamma", ["any", "both ends"])
def test_the_chunked_form_for_a_decay_a_channel_is_the_recurrence(
        t, chunk, gamma):
    """Chunks of one sub-block, of several, and of three of 16 rows
    (48); every exponent stays non-positive, so the result is finite
    and right where a channel forgets everything beside one that
    forgets nothing."""
    args = _scan_inputs(t, seed=t + chunk, gamma=gamma)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = _recurrence(*args)
        got_o, got_s = gated_delta._scan(
            types.SimpleNamespace(chunk_size=chunk), *args)
    assert bool(jnp.isfinite(got_o).all() & jnp.isfinite(got_s).all())
    np.testing.assert_allclose(got_o, want_o,
                               atol=2e-5 * float(jnp.abs(want_o).max()))
    np.testing.assert_allclose(got_s, want_s,
                               atol=2e-5 * float(jnp.abs(want_s).max()))


def test_a_decay_that_every_channel_shares_is_the_scalar_one():
    """A decay a channel that repeats one number a head gives what the
    decay a head gives: the two shapes are one recurrence."""
    s0, q, k, v, log_gamma, beta = _scan_inputs(40, 3, "any")
    cfg = types.SimpleNamespace(chunk_size=32)
    with jax.default_matmul_precision("highest"):
        want = gated_delta._scan(cfg, s0, q, k, v, log_gamma[..., 0], beta)
        got = gated_delta._scan(
            cfg, s0, q, k, v,
            jnp.broadcast_to(log_gamma[..., :1], log_gamma.shape), beta)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()))


def _kernel_inputs(shape, seed, by_channel):
    _, b, h, dk, dv = shape
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    gamma = rng.uniform(0.05, 0.999, (b, h, dk) if by_channel else (b, h))
    if by_channel:  # both ends in one head
        gamma[..., ::4], gamma[..., 1::4] = 1e-30, 1 - 2.0 ** -24
    return [jnp.asarray(x, jnp.float32) for x in (
        rng.normal(size=shape), unit(rng.normal(size=(b, h, dk))) * dk ** -0.5,
        unit(rng.normal(size=(b, h, dk))), rng.normal(size=(b, h, dv)),
        gamma, rng.uniform(0, 1, (b, h)))]


@pytest.mark.parametrize("by_channel", [False, True],
                         ids=["a-decay-a-head", "a-decay-a-channel"])
@pytest.mark.parametrize("shape", [(2, 3, 32, 128, 128), (3, 2, 17, 96, 192)],
                         ids=["kimi-linear", "heads-no-block-divides"])
def test_the_kernel_takes_either_decay(shape, by_channel):
    """The one kernel through the Pallas interpreter against
    `reference` on the layer sliced out of the stack, at this model's
    state of 32 heads of [128, 128] (two blocks of 16) and at a head
    count no block divides, a row starting from zeros over what its
    slot held."""
    stack, q, k, v, gamma, beta = _kernel_inputs(shape, sum(shape),
                                                 by_channel)
    layer = shape[0] - 1
    fresh = np.arange(shape[1]) == 1
    s0 = jnp.where(fresh[:, None, None, None], 0.0, stack[layer])
    want_o, want_s = du.reference(s0, q, k, v, gamma, beta)
    o, new = jax.jit(du.delta_update, static_argnames="interpret")(
        stack, np.int32(layer), jnp.asarray(fresh), q, k, v, gamma, beta,
        interpret=True)
    np.testing.assert_allclose(o, want_o,
                               atol=1e-6 * float(jnp.abs(want_o).max()))
    np.testing.assert_allclose(new[layer], want_s,
                               atol=1e-6 * float(jnp.abs(want_s).max()))
    np.testing.assert_array_equal(new[:layer], stack[:layer])
    # Off the TPU and uninterpreted it is `reference` itself.
    plain_o, plain = du.delta_update(stack, np.int32(layer),
                                     jnp.asarray(fresh), q, k, v, gamma, beta)
    np.testing.assert_array_equal(plain_o, want_o)
    np.testing.assert_array_equal(plain[layer], want_s)
