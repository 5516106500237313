"""What is Command A+'s alone (`ray_tpu/models/cohere2_moe.py`), in
float32 at debug widths on the CPU, against the plain reference's full
forward pass: a window of 8 keys, shorter than every prompt but one, so
the rings wrap several times, a prefill is longer than the ring, a
prefill is padded to its bucket, and slots of unlike lengths (one
shorter than the ring) decode beside each other; the decode step's
seam; and the fused shared expert. What every served family's tests
hold is in `test_served_contract.py`, over this family's row in
`families.py`."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.models import cohere2_moe, moe, serving
from tests.models import families

NAME = "Cohere2MoeConfig"
CFG = families.cfg(NAME)
reference = families.reference(NAME)
HP = reference.hyper(families.config(NAME))
# Every compared logit error is under this (`families.py` has the
# faults' factors over it).
LIMIT = 1e-5


@pytest.fixture
def params():
    return families.params(NAME)


def _tokens(shape, seed=1):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, CFG.vocab_size, shape), jnp.int32)


forward_with_cache = families.forward_with_cache(NAME)
full_forward = jax.jit(lambda params, tokens: reference.forward(
    params, tokens, HP))


def _traced_anew():
    """The cached forward pass under a jit of its own, for a test that
    has it traced under a patch."""
    return jax.jit(lambda *args, **at: cohere2_moe.forward_with_cache(
        *args, **at), static_argnums=2)


def test_the_config_is_the_published_model_cut_for_the_tests():
    full = cohere2_moe.Cohere2MoeConfig()
    assert (full.dim, full.n_heads, full.n_kv_heads, full.head_dim) == (
        4096, 128, 8, 128)
    assert full.runs() == [("sliding", 3), ("full", 1)] * 8
    assert (full.parallel_block, full.norm_kind) == (True, "layer")
    assert (CFG.sliding_window, CFG.n_layers, CFG.experts_held) == (
        8, 4, (4, 4))
    assert CFG.runs() == [("sliding", 3), ("full", 1)]


def _flash_as_on_a_tpu(monkeypatch, rows, block):
    """The prefill's choice as a TPU makes it, for calls of `rows` rows
    or a multiple: the kernel through the interpreter, tiles of `block`."""
    from ray_tpu.ops import attention
    kernel, used = attention._flash_fwd, []

    def interpreted(q, k, v, causal, scale, block_q, block_k, _, **kw):
        used.append(kw["window"])
        return kernel(q, k, v, causal, scale, block, block, True, **kw)

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(attention, "_flash_fwd", interpreted)
    monkeypatch.setattr(cohere2_moe, "_FLASH_ROWS", rows)
    return used


@pytest.mark.parametrize("query_block,key_block,flash", [
    (256, 1024, False), (16, 4, False), (16, 4, True)],
    ids=["one block", "blocks of queries and keys", "the flash kernel"])
def test_prefill_then_ring_decode_against_the_full_forward(
        params, monkeypatch, query_block, key_block, flash):
    """Four slots whose rings hold another request's keys: prompts of
    45, 39, 26 and 5 tokens in a bucket of 64 (the last shorter than
    the ring of 8), each slot left after its own last token, then 12
    decode steps side by side, the rings wrapping once more. With
    `flash` the prefill's rows attend through the flash kernel, tiles
    of 16 and a window of 8, and the decode steps by blocks as ever."""
    monkeypatch.setattr(serving, "QUERY_BLOCK", query_block)
    monkeypatch.setattr(cohere2_moe, "_KEY_BLOCK", key_block)
    used = _flash_as_on_a_tpu(monkeypatch, 16, 16) if flash else []
    step = _traced_anew()
    lens = np.array([45, 39, 26, 5])
    tokens = _tokens((4, 45 + 12))
    want = full_forward(params, tokens)
    cache = jax.tree.map(lambda x: x + 3.0,
                         cohere2_moe.init_cache(CFG, 4, 64))
    padded = jnp.pad(tokens[:, :45], ((0, 0), (0, 19)))
    logits, cache = step(
        params, padded, CFG, cache, jnp.zeros(4, jnp.int32),
        at=jnp.asarray(lens - 1))
    for row, n in enumerate(lens):
        np.testing.assert_allclose(logits[row, :n], want[row, :n],
                                   atol=LIMIT)
    rows = np.arange(4)
    for i in range(12):
        logits, cache = step(
            params, tokens[rows, lens + i][:, None], CFG, cache,
            jnp.asarray(lens + i, jnp.int32))
        np.testing.assert_allclose(logits[:, 0], want[rows, lens + i],
                                   atol=LIMIT)
    # Three sliding layers in one scan and the full layer in another.
    assert used == ([CFG.sliding_window, None] if flash else [])


def test_the_rings_hold_the_last_window_of_real_rows_and_no_padding(params):
    """After a padded prefill the ring's row p mod 8 is position p's
    key for the last 8 real positions; a one-token call writes one row
    and leaves the rest where they lie."""
    tokens = _tokens((1, 29))
    cache = cohere2_moe.init_cache(CFG, 1, 32)
    _, exact = forward_with_cache(
        params, tokens, CFG, cache, jnp.zeros(1, jnp.int32))
    _, padded = forward_with_cache(
        params, jnp.pad(tokens, ((0, 0), (0, 3))), CFG, cache,
        jnp.zeros(1, jnp.int32), at=28)
    for a, b in zip(jax.tree.leaves(exact), jax.tree.leaves(padded)):
        if a.shape[2] == CFG.sliding_window:
            np.testing.assert_allclose(a, b, atol=1e-6)
    ring = exact["runs"][0]["ring_k"]
    assert ring.shape == (3, 1, 8, 2, 16)
    _, stepped = forward_with_cache(
        params, _tokens((1, 1), 2), CFG, exact, jnp.full(1, 29, jnp.int32))
    changed = np.abs(np.asarray(stepped["runs"][0]["ring_k"] - ring)).max(
        (0, 1, 3, 4)) > 0
    assert changed.tolist() == [r == 29 % 8 for r in range(8)]


def test_a_call_from_a_later_position_attends_the_ring_it_found(params):
    """A prefill in two calls equals one: the second call's rows see
    the first call's last keys in the ring, and their own beside them."""
    tokens = _tokens((2, 27))
    zeros = jnp.zeros(2, jnp.int32)
    whole, _ = forward_with_cache(
        params, tokens, CFG, cohere2_moe.init_cache(CFG, 2, 32), zeros)
    _, cache = forward_with_cache(
        params, tokens[:, :13], CFG, cohere2_moe.init_cache(CFG, 2, 32),
        zeros)
    rest, _ = forward_with_cache(
        params, tokens[:, 13:], CFG, cache, zeros + 13)
    np.testing.assert_allclose(rest, whole[:, 13:], atol=LIMIT)


def test_a_later_call_of_flash_size_still_attends_the_cache(
        params, monkeypatch):
    """A program that holds the flash kernel takes it only from
    position 0: a second call of the kernel's size, or one whose rows
    start at unlike positions, reads the ring and the rows it found."""
    _flash_as_on_a_tpu(monkeypatch, 8, 8)
    step = _traced_anew()
    tokens = _tokens((2, 32))
    zeros = jnp.zeros(2, jnp.int32)
    want = full_forward(params, tokens)
    first, cache = step(
        params, tokens[:, :16], CFG, cohere2_moe.init_cache(CFG, 2, 32),
        zeros)
    rest, _ = step(
        params, tokens[:, 16:], CFG, cache, zeros + 16)
    np.testing.assert_allclose(first, want[:, :16], atol=LIMIT)
    np.testing.assert_allclose(rest, want[:, 16:], atol=LIMIT)


@pytest.mark.parametrize("t", [1, 16], ids=["decode", "prefill"])
def test_the_decode_steps_seam_moves_no_number(params, monkeypatch, t):
    """A call of one row a slot goes through the seam, a barrier ahead
    of the rotary turn on each `sliding` layer; a call of more is the
    program it was. Both give what the parent's program gives, which is
    this one with the barrier the identity: logits, rings, rows and
    counts, from slots of unlike lengths whose rings another request
    filled."""
    lens = np.array([24, 13, 5])
    tokens = _tokens((3, 24 + t))
    cache = jax.tree.map(lambda x: x + 3.0,
                         cohere2_moe.init_cache(CFG, 3, 64))
    _, cache = forward_with_cache(
        params, tokens[:, :24], CFG, cache, jnp.zeros(3, jnp.int32),
        at=jnp.asarray(lens - 1))

    def call():
        return cohere2_moe.forward(params, tokens[:, 24:], CFG, cache,
                                   jnp.asarray(lens, jnp.int32), t - 1)

    def barriers():  # (a function of its own: a trace is kept by function)
        return str(jax.make_jaxpr(lambda: call())()).count(
            "optimization_barrier")

    got, seams = call(), barriers()
    assert seams == (1 if t == 1 else 0)  # one scan body of sliding layers
    monkeypatch.setattr(lax, "optimization_barrier", lambda x: x)
    want = call()
    assert barriers() == 0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=0)


def _expert_layer(seed=3):
    cfg = dataclasses.replace(CFG, experts_held=None)
    lp = cohere2_moe._init_layer(cfg, jax.random.PRNGKey(seed))
    y = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 24, cfg.dim))
    return cfg, lp, y


def test_the_fused_shared_expert_is_four_averaged_ones():
    cfg, lp, y = _expert_layer(5)
    fused = moe._add_shared_expert(cfg, lp, y, jnp.zeros_like(y))
    width = cfg.shared_hidden_dim // 4
    apart = [reference.swiglu(
        y, lp["ws1"][:, j * width:(j + 1) * width],
        lp["ws3"][:, j * width:(j + 1) * width],
        lp["ws2"][j * width:(j + 1) * width]) for j in range(4)]
    np.testing.assert_allclose(fused, sum(apart) / 4, atol=2e-6)
    summed = moe._add_shared_expert(
        dataclasses.replace(cfg, shared_combination="sum"), lp, y,
        jnp.zeros_like(y))
    np.testing.assert_allclose(summed, sum(apart), atol=5e-6)


def test_the_other_families_blocks_are_what_they_were():
    """The two fields of the block's form default to the sequential
    RMSNorm block: a dense decoder's program has no mean taken off and
    reads both of its norms."""
    from ray_tpu.models import llama
    dense = llama.LlamaConfig.debug()
    assert (dense.norm_kind, dense.parallel_block) == ("rms", False)
    p = llama.init_params(dense, jax.random.PRNGKey(0))
    x = _tokens((1, 8)) % dense.vocab_size
    want = llama.forward(p, x, dense)
    other = {**p, "layers": {**p["layers"], "mlp_norm":
                             p["layers"]["mlp_norm"] * 2}}
    assert np.abs(np.asarray(llama.forward(other, x, dense) - want)).max() > 0
