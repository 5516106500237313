"""The engine of a model that generates by blocks (`_BlockEngine`), on
the CPU at debug widths in float32: tokens and the step each was fixed
at equal the plain reference's `generate` for prompts of every length
modulo the block and one shorter than a block, answers that end inside
a block, a stop id inside a block, 1, 2 and 4 denoising steps, several
requests at different phases in their slots, one forward a dispatch and
several; a slot used before and a region's last rows; the step's
program by hand (a commit rides in the next block's first forward, and
a committed row is never written again); what the host counts; the
prefix cache; and the stream's events."""

import dataclasses
import threading

import jax
import numpy as np
import pytest

from benchmark.harness.manifest import ROOT, load_json, model_adapter
from benchmark.references import sdar_moe as reference
from ray_tpu.serve.llm import (LLMDeployment, LLMEngine, SamplingParams,
                               _BlockEngine)

FILE = load_json(ROOT, "benchmark", "configs", "sdar-30b-a3b-serve.json")
ADAPTER = model_adapter(FILE)
CONFIG = ADAPTER.debug(FILE)
CFG = ADAPTER.program_config(CONFIG)
HP = reference.hyper(CONFIG)


@pytest.fixture(scope="module")
def params():
    # The benchmark's weights: under them open positions differ.
    return ADAPTER.init(CFG, jax.random.PRNGKey(3))


def _engine(params, decode_steps=1, slots=4, **kw):
    engine = LLMEngine(CFG, params, max_batch_size=slots, max_seq_len=64,
                       decode_steps=decode_steps, **kw)
    assert type(engine) is _BlockEngine
    return engine


def _prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(
        1, CFG.vocab_size - 1, n).tolist()


def _want(params, prompt, max_tokens, steps=None, stop=()):
    with jax.default_matmul_precision("highest"):
        tokens, fixed_at = reference.generate(
            params, prompt, max_tokens, HP, denoising_steps=steps, stop=stop)
    return list(zip(tokens, fixed_at))


@pytest.fixture(scope="module", params=[1, 3], ids=["one-forward", "three"])
def engine(request, params):
    engine = _engine(params, decode_steps=request.param)
    yield engine
    engine.stop()


# Prompts of 0, 1, 2 and 3 tokens over whole blocks, and one shorter
# than a block (no prefill at all); answers of 0, 1, 2 and 3 over.
@pytest.mark.parametrize("n_prompt,max_tokens", [
    (8, 8), (9, 5), (10, 6), (11, 7), (3, 9), (12, 1)])
def test_tokens_and_steps_equal_the_references(engine, params, n_prompt,
                                               max_tokens):
    prompt = _prompt(n_prompt)
    got = engine.generate(prompt, SamplingParams(max_tokens=max_tokens),
                          with_steps=True)
    assert got == _want(params, prompt, max_tokens)
    assert engine.generate(prompt, SamplingParams(max_tokens=max_tokens)) \
        == [token for token, _ in got]


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_a_requests_own_denoising_steps(engine, params, steps):
    prompt = _prompt(6, seed=steps)
    got = engine.generate(
        prompt, SamplingParams(max_tokens=10, denoising_steps=steps),
        with_steps=True)
    assert got == _want(params, prompt, 10, steps=steps)
    # A block of 4 is fixed in `steps` steps, 4 / steps positions each
    # (the first block opens with 2 known, so it takes what is left).
    assert {step for _, step in got[2:]} == set(range(steps))


def test_denoising_steps_that_do_not_divide_the_block_are_refused(engine):
    with pytest.raises(ValueError, match="does not divide"):
        engine.generate(_prompt(5), SamplingParams(denoising_steps=3))


def test_a_stop_id_inside_a_block_ends_the_request_there(engine, params):
    prompt = _prompt(8, seed=7)
    whole = _want(params, prompt, 12)
    # The second token of the second block, unless the answer held it
    # before.
    stop = whole[5][0]
    first = [token for token, _ in whole].index(stop)
    got = engine.generate(
        prompt, SamplingParams(max_tokens=12, stop_token_ids=(stop,)),
        with_steps=True)
    assert got == whole[:first + 1] == _want(params, prompt, 12,
                                             stop=(stop,))


def test_requests_at_different_phases_beside_each_other(engine, params):
    """Five requests on four slots, of different prompt lengths and
    schedules, started together: slots denoise and commit beside each
    other, the fifth is admitted into a slot that was used before."""
    asked = [(_prompt(n, seed=11), m, steps) for n, m, steps in [
        (9, 11, 2), (4, 6, 4), (14, 9, 1), (7, 13, 2), (21, 7, 2)]]
    got = [None] * len(asked)

    def ask(i):
        prompt, max_tokens, steps = asked[i]
        got[i] = engine.generate(
            prompt, SamplingParams(max_tokens=max_tokens,
                                   denoising_steps=steps), with_steps=True)

    threads = [threading.Thread(target=ask, args=(i,))
               for i in range(len(asked))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    for (prompt, max_tokens, steps), answer in zip(asked, got):
        assert answer == _want(params, prompt, max_tokens, steps=steps)


def test_what_the_host_counts(params):
    """One request alone on an engine, two forwards a dispatch: the
    totals are arithmetic of the schedule
    (`tests/serve/test_engine_spans.py` holds the spans' sums to
    them)."""
    engine = _engine(params, decode_steps=2, slots=2)
    try:
        got = engine.generate(_prompt(8), SamplingParams(max_tokens=12))
        assert len(got) == 12
        engine.stop()
        engine._flush_pending()
        totals = engine.metrics()["totals"]
    finally:
        engine.stop()
    # Three blocks handed over: 2 denoising forwards each, 2 positions
    # fixed a forward; the first forward of the second and of the third
    # block commits the block before it, and no forward only commits
    # (the last block's request ended with it: the block in flight then
    # is nobody's).
    assert totals["blocks_emitted"] == 3 and totals["tokens_kept"] == 12
    assert totals["slot_forwards_denoise"] == 6
    assert totals["tokens_fixed"] == 12
    assert totals["slot_forwards_commit"] == 0
    assert totals["slot_forwards_fused"] == 2
    assert totals["tokens_kept"] + totals["tokens_discarded"] \
        >= 4 * totals["blocks_emitted"]


def test_the_program_never_writes_a_committed_row_again(params):
    """The step's program driven by hand, a forward a dispatch: a slot
    prefilled with 8 tokens, its first block seeded with 2 known. What
    the cache holds below the slot's length after a forward is there to
    the bit after every later one (the prefix cache may have hashed and
    copied it), the length grows by a block in the very forward that
    fixes the next block's first positions, and no forward of the slot
    fixes nothing."""
    engine = _engine(params, slots=2)
    b, prompt = CFG.block_length, _prompt(10)
    tokens = np.zeros((1, 16), np.int32)
    tokens[0, :8] = prompt[:8]
    engine.cache, _ = engine._run_prefill(tokens, 1, 8, 0, 16)
    engine._temps_arr = np.zeros(2, np.float32)
    blocks, opens = np.zeros((2, b), np.int32), np.ones((2, b), bool)
    blocks[0, :2], opens[0, :2] = prompt[8:], False
    engine._set_carries(engine._run_seed(
        np.asarray([1, 2], np.int32), blocks, opens,
        np.asarray([8, 0], np.int32)))
    seen, answer = [], []
    for _ in range(7):
        out, _ = engine._dispatch_decode()
        row = np.asarray(out)[1, 0]
        emitted, committed, fixed = row[2 * b:]
        if emitted:
            answer += zip(row[:b].tolist(), row[b:2 * b].tolist())
        seen.append((int(engine._dev_lengths[1]), int(committed), int(fixed),
                     [np.asarray(x)[:, 1] for x in
                      jax.tree.leaves(engine.cache)]))
    assert [(length, committed, fixed) for length, committed, fixed, _
            in seen] == [(8, 0, 2), (12, 1, 2), (12, 0, 2), (16, 1, 2),
                         (16, 0, 2), (20, 1, 2), (20, 0, 2)]
    for i, (length, _, _, rows) in enumerate(seen):
        for _, _, _, later in seen[i + 1:]:
            for old, new in zip(rows, later):
                np.testing.assert_array_equal(new[:, :length],
                                              old[:, :length])
    assert answer[2:] == _want(params, prompt, 14)


def test_a_slot_a_longer_request_used_serves_a_shorter_one(params):
    """One slot: a request of 21 prompt tokens and 20 of answer leaves
    its rows and its last block, which awaited a commit, behind; the
    request admitted into the slot next has a prompt shorter than a
    block (nothing to prefill) and answers as the reference does; so
    does the one after it, whose rows end where the region does."""
    engine = _engine(params, slots=1, decode_steps=2)
    try:
        for n_prompt, max_tokens, kept in [(21, 20, 20), (2, 11, 11),
                                           (50, 40, 14)]:
            prompt = _prompt(n_prompt, seed=5)
            got = engine.generate(prompt, SamplingParams(
                max_tokens=max_tokens), with_steps=True)
            # (The third runs out of rows: blocks at 48, 52, 56 and 60
            # of 64 are the last that fit.)
            assert got == _want(params, prompt, max_tokens)[:kept]
            assert len(got) == kept
    finally:
        engine.stop()


def test_a_prompt_seen_before_is_served_from_the_prefix_cache(params):
    """The cache is rows only, so the prefix cache stays on: the second
    request of a prompt copies its whole blocks of rows in and prefills
    the tail, and answers what the first did."""
    engine = _engine(params, slots=2)
    try:
        assert engine.prefix_cache is not None
        assert engine.block_tokens % CFG.block_length == 0
        prompt = _prompt(41)
        first = engine.generate(prompt, SamplingParams(max_tokens=8),
                                with_steps=True)
        before = engine.metrics()["totals"]["prefill_tokens_real"]
        again = engine.generate(prompt, SamplingParams(max_tokens=8),
                                with_steps=True)
        prefilled = engine.metrics()["totals"]["prefill_tokens_real"] - before
    finally:
        engine.stop()
    assert first == again == _want(params, prompt, 8)
    assert before == 40 and prefilled == 40 - 32  # two blocks of 16 rows hit


def test_the_stream_carries_a_blocks_tokens_with_their_steps(params):
    deployment = LLMDeployment(
        dataclasses.replace(CFG), lambda: params, max_batch_size=2,
        max_seq_len=64, warmup=False)
    try:
        prompt = _prompt(10)
        events = list(deployment({"prompt_ids": prompt, "max_tokens": 6,
                                  "stream": True}))
        whole = deployment({"prompt_ids": prompt, "max_tokens": 6,
                            "denoising_steps": 4})
    finally:
        deployment.engine.stop()
    want = _want(params, prompt, 6)
    assert [(e["token"], e["step"]) for e in events] == want
    assert [e["index"] for e in events] == list(range(6))
    assert list(zip(whole["tokens"], whole["steps"])) \
        == _want(params, prompt, 6, steps=4)
