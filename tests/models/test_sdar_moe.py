"""What is SDAR's alone, at debug widths on the CPU, in float32, seeded
random weights at the program's own initialiser: block steps through
the cache, each block as a denoising pass and as its commit, rows at
different positions and phases in one call; the engine's step of two
blocks a row, a start each, with and without a block that awaits its
commit, against the plain reference; what such a step writes and what
it leaves; a slot used before; the flash kernel's block mask; and what
the registry says of a model that generates by blocks. The served path
against the reference and each named fault failing are cases of
`test_served_contract.py`, over this family's row in `families.py`,
which has the faults."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, sdar_moe, serving
from ray_tpu.ops import attention
from tests.models import families
# (`tests/benchmark/test_sdar_moe.py` takes the faults from this module
# by this name.)
from tests.models.families import sdar_faults as _faults  # noqa: F401

NAME = "SdarMoeConfig"
FILE, ADAPTER = families.file(NAME), families.adapter(NAME)
CFG = families.cfg(NAME)
reference = families.reference(NAME)
HP = reference.hyper(families.config(NAME))


def test_the_file_builds_the_published_model():
    cfg = ADAPTER.program_config(FILE)
    assert cfg == dataclasses.replace(
        sdar_moe.SdarMoeConfig(), n_layers=6, denoising_steps=2)
    assert (cfg.block_length, cfg.mask_token_id) == (4, 151669)
    assert cfg.runs() == [("full", 6)]
    served = serving.served_model(cfg)
    assert served.block_length == 4
    # The five that yield a token a step say nothing.
    assert serving.served_model(llama.LlamaConfig.debug()).block_length \
        is None


@pytest.fixture
def params():
    return families.params(NAME)


def _tokens(shape, seed=1):
    return jnp.asarray(np.random.default_rng(seed).integers(
        1, CFG.vocab_size - 1, shape, dtype=np.int32))


def _want(params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.forward(params, tokens, HP))


def test_rows_at_different_positions_and_phases_in_one_call(params):
    """Row 0 commits its second block while row 1 denoises its fourth:
    one call, two positions, two phases, each against the reference's
    pass of that very input."""
    tokens = _tokens((2, 16))
    cache = sdar_moe.init_cache(CFG, 2, 32)
    _, cache = sdar_moe.forward_with_cache(
        params, tokens[:, :12], CFG, cache, jnp.zeros(2, jnp.int32))
    mask = CFG.mask_token_id
    noised = np.asarray(tokens[1, 12:16]).copy()
    noised[[0, 3]] = mask
    fed = jnp.stack([tokens[0, 4:8], jnp.asarray(noised)])
    logits, _ = sdar_moe.forward_with_cache(
        params, fed, CFG, cache, jnp.asarray([4, 12], jnp.int32))
    want = _want(params, tokens)
    with jax.default_matmul_precision("highest"):
        denoised = reference.denoise_logits(
            params, np.asarray(tokens[1, :12]), noised, HP)
    np.testing.assert_allclose(logits[0], want[0, 4:8], atol=1e-5)
    np.testing.assert_allclose(logits[1], denoised, atol=1e-5)


def _prefilled(params, tokens, upto, max_seq=32):
    cache = sdar_moe.init_cache(CFG, tokens.shape[0], max_seq)
    return sdar_moe.forward_with_cache(
        params, tokens[:, :upto], CFG, cache,
        jnp.zeros(tokens.shape[0], jnp.int32))[1]


def _noised(block, open_):
    block = np.asarray(block).copy()
    block[list(open_)] = CFG.mask_token_id
    return block


# Of the call's two rows, which carry a whole block that awaits its
# commit in front of the block being denoised; the other carries its
# own block twice, at one start.
@pytest.mark.parametrize("awaiting", [(True, True), (False, False),
                                      (True, False), (False, True)],
                         ids=["both", "neither", "first", "second"])
def test_a_step_of_two_blocks_a_row(params, awaiting):
    """The engine's step: two blocks a row, a start each. Row 0 stands
    at length 4 and row 1 at 12; a row that awaits a commit feeds the
    whole block at its length and the noised one behind it, the other
    its noised block twice at its length. Every block's logits are the
    reference's pass of that very input, but for the first of two at
    one start, which is written over and is nobody's; the head's
    (`forward` with `at` None) are the last block's."""
    tokens = _tokens((2, 24))
    lengths = [4, 12]
    cache = _prefilled(params, tokens, 12)
    fed, starts, want = [], [], []
    for row, (length, waits, open_) in enumerate(zip(
            lengths, awaiting, [(1, 2), (0, 3)])):
        first = length + 4 * waits  # of the block being denoised
        noised = _noised(tokens[row, first:first + 4], open_)
        with jax.default_matmul_precision("highest"):
            denoised = reference.denoise_logits(
                params, np.asarray(tokens[row, :first]), noised, HP)
        if waits:
            fed.append(np.concatenate([tokens[row, length:first], noised]))
            want.append(np.concatenate(
                [_want(params, tokens[row:row + 1, :first])[0, length:],
                 denoised]))
        else:
            fed.append(np.concatenate([noised, noised]))
            want.append(np.concatenate([denoised, denoised]))
        starts.append([length, first])
    fed, starts = jnp.asarray(np.stack(fed)), jnp.asarray(starts, jnp.int32)
    logits, _ = sdar_moe.forward_with_cache(params, fed, CFG, cache, starts)
    np.testing.assert_allclose(logits[:, 4:], np.stack(want)[:, 4:],
                               atol=1e-5)
    waits = np.asarray(awaiting)
    np.testing.assert_allclose(logits[waits, :4], np.stack(want)[waits, :4],
                               atol=1e-5)
    head, _, _ = serving.served_model(CFG).forward(
        params, fed, CFG, cache, starts, None)
    np.testing.assert_array_equal(head, logits[:, 4:])


def test_a_commit_in_a_step_of_two_blocks_writes_what_a_commit_writes(params):
    """The rows a two-block step leaves for the block it commits, and
    for the block behind it, are to the bit those of a block step with
    the whole block's tokens and one with the noised block's behind it;
    so are the noised block's logits, which see the committed rows as
    the cache stores them."""
    tokens = _tokens((2, 24), seed=6)
    cache = _prefilled(params, tokens, 12)
    lengths = jnp.asarray([4, 12], jnp.int32)
    whole = jnp.stack([tokens[0, 4:8], tokens[1, 12:16]])
    noised = jnp.asarray(np.stack([_noised(tokens[0, 8:12], (0, 1, 2, 3)),
                                   _noised(tokens[1, 16:20], (2,))]))
    _, stepped = sdar_moe.forward_with_cache(params, whole, CFG, cache,
                                             lengths)
    want, stepped = sdar_moe.forward_with_cache(params, noised, CFG, stepped,
                                                lengths + 4)
    got, fused = sdar_moe.forward_with_cache(
        params, jnp.concatenate([whole, noised], 1), CFG, cache,
        jnp.stack([lengths, lengths + 4], -1))
    np.testing.assert_array_equal(got[:, 4:], want)
    for a, b in zip(jax.tree.leaves(fused), jax.tree.leaves(stepped)):
        np.testing.assert_array_equal(a, b)
    # ... and not the rows a denoising pass of the block had left there.
    _, denoised = sdar_moe.forward_with_cache(
        params, jnp.asarray(np.stack([_noised(whole[0], (1, 3)),
                                      _noised(whole[1], (0,))])),
        CFG, cache, lengths)
    assert np.abs(np.asarray(denoised["runs"][0]["k"])
                  - np.asarray(fused["runs"][0]["k"])).max() > 1e-3


def test_a_committed_row_is_not_written_again(params):
    """Steps of a row with nothing awaiting commit (its block twice, at
    its length) write that block's rows and no other: what the cache
    holds before the length, a committed block's rows among them (the
    prefix cache may have hashed them), stays to the bit, and so does
    what lies behind the block."""
    tokens = _tokens((2, 24), seed=8)
    cache = _prefilled(params, tokens, 12)
    lengths = np.asarray([4, 12])
    starts = jnp.asarray(np.stack([lengths, lengths], -1), jnp.int32)
    after = cache
    for open_ in [(0, 1, 2, 3), (1, 2)]:
        noised = jnp.asarray(np.stack([
            _noised(tokens[row, at:at + 4], open_)
            for row, at in enumerate(lengths)]))
        _, after = sdar_moe.forward_with_cache(
            params, jnp.concatenate([noised, noised], 1), CFG, after, starts)
    for old, new in zip(jax.tree.leaves(cache), jax.tree.leaves(after)):
        old, new = np.asarray(old), np.asarray(new)
        for row, at in enumerate(lengths):
            np.testing.assert_array_equal(new[:, row, :at], old[:, row, :at])
            np.testing.assert_array_equal(new[:, row, at + 4:],
                                          old[:, row, at + 4:])
            assert (new[:, row, at:at + 4] != old[:, row, at:at + 4]).any()


def test_a_slot_used_by_a_longer_request_before_serves_a_shorter_one(params):
    long, short = _tokens((1, 32), 3), _tokens((1, 12), 4)
    cache = sdar_moe.init_cache(CFG, 1, 32)
    _, used = sdar_moe.forward_with_cache(
        params, long, CFG, cache, jnp.zeros(1, jnp.int32))
    padded = jnp.pad(short, ((0, 0), (0, 4)))
    logits, used = sdar_moe.forward_with_cache(
        params, padded, CFG, used, jnp.zeros(1, jnp.int32), keep=12)
    np.testing.assert_allclose(logits, _want(params, short), atol=1e-5)
    # ... and its next block, which stands on rows the long one wrote.
    block = _tokens((1, 4), 5)
    logits, _ = sdar_moe.forward_with_cache(
        params, block, CFG, used, jnp.full(1, 12, jnp.int32))
    np.testing.assert_allclose(
        logits, _want(params, jnp.concatenate([short, block], 1))[:, 12:],
        atol=1e-5)


def test_a_block_step_returns_every_positions_logits(params):
    """`forward` with `at` None: the engine's block step."""
    served = serving.served_model(CFG)
    tokens = _tokens((2, 4))
    cache = served.init_cache(CFG, 2, 16)
    start = jnp.zeros(2, jnp.int32)
    logits, _, counts = served.forward(params, tokens, CFG, cache, start,
                                       None)
    assert logits.shape == (2, 4, CFG.vocab_size)
    np.testing.assert_allclose(logits, _want(params, tokens), atol=1e-5)
    # Every layer holds all its experts and reads them where they lie.
    assert int(counts["pairs_held"]) == int(counts["pairs_routed"]) \
        == CFG.n_layers * 2 * 4 * CFG.n_experts_per_token
    assert int(counts["experts_held_steps"]) == CFG.n_layers * CFG.n_experts


def test_a_prefill_of_640_rows_goes_through_the_flash_kernel(
        params, monkeypatch):
    """A bucket between two powers of two (`serve.llm.prefill_bucket`:
    384, 768, 1,536) is a multiple of 128 rows, as 640 is, which is all
    `own_keys` asks of a prefill from position 0 on a TPU (interpreted
    here, in the tile the kernel chooses); the plain path gives the
    same logits and rows."""
    import types

    from jax import lax

    tokens = _tokens((1, 640), seed=8)
    start = jnp.zeros(1, jnp.int32)
    want, plain_cache = sdar_moe.forward_with_cache(
        params, tokens, CFG, sdar_moe.init_cache(CFG, 1, 1024), start)
    calls = []

    def flash(q, k, v, block):
        calls.append((q.shape[1], block))
        return attention.flash_attention_forward(q, k, v, block=block,
                                                 interpret=True)

    monkeypatch.setattr(sdar_moe, "attention", types.SimpleNamespace(
        on_tpu=lambda: False, flash_attention_forward=flash))
    # `serving.own_keys` as it is on a TPU.
    monkeypatch.setattr(
        sdar_moe, "own_keys", lambda tiled, start_pos, flash, plain:
        lax.cond(start_pos.max() == 0, flash, plain) if tiled else plain())
    got, cache = sdar_moe.forward_with_cache(
        params, tokens, CFG, sdar_moe.init_cache(CFG, 1, 1024), start)
    assert calls == [(640, CFG.block_length)]  # one run of full layers
    np.testing.assert_allclose(got, want, atol=3e-6 * np.abs(want).max())
    assert not np.array_equal(got, want)
    for x, y in zip(jax.tree.leaves(cache), jax.tree.leaves(plain_cache)):
        np.testing.assert_allclose(x, y, atol=3e-6 * np.abs(y).max())


@pytest.mark.parametrize("rows,block_q", [(64, 1024), (256, 128)],
                         ids=["one-tile", "two-tiles"])
def test_the_flash_kernels_block_mask(rows, block_q):
    """The forward kernel through the Pallas interpreter against a
    dense softmax under the mask written out: key j is seen from row i
    iff j // 4 <= i // 4. Tiles above the diagonal are skipped as
    causality's are."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, rows, 4, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, rows, 2, 16)), jnp.float32)
            for _ in range(2))
    got = attention.flash_attention_forward(
        q, k, v, block=4, block_q=block_q, block_k=block_q, interpret=True)
    at = np.arange(rows)
    seen = at[None, :] // 4 <= at[:, None] // 4
    scores = np.einsum("bqhd,bkhd->bhqk", q, np.repeat(k, 2, 2)) / 4.0
    probs = jax.nn.softmax(jnp.where(seen, scores, -np.inf), -1)
    want = np.einsum("bhqk,bkhd->bqhd", probs, np.repeat(v, 2, 2))
    np.testing.assert_allclose(got, want, atol=2e-5)
    # Off the TPU the same call is the reference, with the same mask.
    np.testing.assert_allclose(
        attention.flash_attention_forward(q, k, v, block=4), want, atol=2e-5)
    causal = attention.flash_attention_forward(q, k, v, interpret=True)
    assert np.abs(np.asarray(causal) - want).max() > 1e-2
