"""LLM engine tests: KV-cache correctness + continuous batching +
prefix-cache reuse + admission behavior under slot pressure."""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu._private.config import ray_config
from ray_tpu.models.llama import (
    LlamaConfig,
    forward,
    forward_with_cache,
    init_kv_cache,
    init_params,
)
from ray_tpu.serve import llm
from ray_tpu.serve.llm import (
    LLMEngine,
    PromptTooLongError,
    SamplingParams,
)

# Multi-process / soak tests: excluded from the quick
# tier (pytest -m 'not slow').
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.debug()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def naive_greedy(cfg, params, prompt, n_tokens):
    """Generate by re-running the full forward each step (ground truth)."""
    tokens = list(prompt)
    for _ in range(n_tokens):
        logits = forward(params, jnp.asarray([tokens]), cfg)
        tokens.append(int(logits[0, -1].argmax()))
    return tokens[len(prompt):]


def test_cache_prefill_matches_full_forward(model):
    cfg, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    full = forward(params, tokens, cfg)
    cache = init_kv_cache(cfg, 2, 32)
    cached, _ = forward_with_cache(params, tokens, cfg, cache,
                                   jnp.zeros(2, jnp.int32))
    np.testing.assert_allclose(np.asarray(cached), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


def test_cache_incremental_matches_full(model):
    cfg, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 12), 0,
                                cfg.vocab_size)
    full = forward(params, tokens, cfg)
    cache = init_kv_cache(cfg, 1, 32)
    # Prefill 8, then decode 4 one at a time.
    _, cache = forward_with_cache(params, tokens[:, :8], cfg, cache,
                                  jnp.zeros(1, jnp.int32))
    outs = []
    for i in range(8, 12):
        logits, cache = forward_with_cache(
            params, tokens[:, i:i + 1], cfg, cache,
            jnp.asarray([i], jnp.int32))
        outs.append(logits[:, 0])
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(full[:, 8:12]),
                               rtol=2e-4, atol=2e-4)


def test_engine_greedy_matches_naive(model):
    cfg, params = model
    prompt = [3, 17, 42, 8]
    expected = naive_greedy(cfg, params, prompt, 8)
    engine = LLMEngine(cfg, params, max_batch_size=2, max_seq_len=64)
    got = engine.generate(prompt, SamplingParams(max_tokens=8))
    engine.stop()
    assert got == expected


def test_engine_concurrent_requests(model):
    cfg, params = model
    engine = LLMEngine(cfg, params, max_batch_size=4, max_seq_len=64)
    prompts = [[1, 2, 3], [9, 8], [5, 5, 5, 5], [7], [11, 13], [2, 4, 6]]
    expected = [naive_greedy(cfg, params, p, 6) for p in prompts]

    import threading

    results = [None] * len(prompts)

    def worker(i):
        results[i] = engine.generate(prompts[i],
                                     SamplingParams(max_tokens=6))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    engine.stop()
    for got, exp in zip(results, expected):
        assert got == exp


def test_engine_streaming_and_metrics(model):
    cfg, params = model
    engine = LLMEngine(cfg, params, max_batch_size=2, max_seq_len=64)
    stream = engine.generate([4, 2], SamplingParams(max_tokens=5),
                             stream=True)
    tokens = list(stream)
    assert len(tokens) == 5
    m = engine.metrics()
    assert m["active_slots"] == 0 and m["free_slots"] == 2
    engine.stop()


# -- PR 16: prefix/KV cache + admission behavior -------------------------


def test_prompt_longer_than_cap_rejected_typed(model):
    """The old behavior silently truncated the prompt HEAD (corrupting
    answers); now an over-cap prompt fails loudly with a typed error
    before any slot/queue resource is touched."""
    cfg, params = model
    engine = LLMEngine(cfg, params, max_batch_size=2, max_seq_len=16)
    with pytest.raises(PromptTooLongError) as ei:
        engine.generate(list(range(1, 30)), SamplingParams(max_tokens=2))
    assert ei.value.n_tokens == 29 and ei.value.cap == 15
    m = engine.metrics()
    assert m["queued"] == 0 and m["active_slots"] == 0
    engine.stop()


def test_slot_exhaustion_parks_then_admits(model):
    """More concurrent requests than slots: the overflow request parks
    in the queue (never dropped, never doubly assigned) and admits as
    soon as a retirement frees a slot — continuous batching's core
    contract."""
    cfg, params = model
    engine = LLMEngine(cfg, params, max_batch_size=2, max_seq_len=64)
    prompts = [[1, 2], [3, 4], [5, 6], [7, 8], [9, 10]]
    expected = [naive_greedy(cfg, params, p, 4) for p in prompts]
    results = [None] * len(prompts)

    def worker(i):
        results[i] = engine.generate(prompts[i],
                                     SamplingParams(max_tokens=4))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    # With 2 slots and 5 requests, at least one must park mid-flight.
    deadline = time.monotonic() + 30
    saw_queued = False
    while time.monotonic() < deadline and not saw_queued:
        if engine.metrics()["queued"] > 0:
            saw_queued = True
        time.sleep(0.001)
    for t in threads:
        t.join(timeout=120)
    engine.stop()
    assert saw_queued, "5 requests over 2 slots never queued"
    for got, exp in zip(results, expected):
        assert got == exp


def test_retired_slot_reuse_never_leaks_prior_tokens(model):
    """A slot retired by request A and re-admitted for request B must
    produce exactly B's tokens: stale KV from A beyond B's length can
    never be attended (positions are overwritten before any query
    reaches them). Run a LONG request then a SHORT one through a
    1-slot engine — same slot, different lengths — and cross-check
    the short one against ground truth."""
    cfg, params = model
    engine = LLMEngine(cfg, params, max_batch_size=1, max_seq_len=64)
    long_prompt = list(range(1, 25))
    short_prompt = [42, 7]
    exp_long = naive_greedy(cfg, params, long_prompt, 6)
    exp_short = naive_greedy(cfg, params, short_prompt, 6)
    assert engine.generate(long_prompt,
                           SamplingParams(max_tokens=6)) == exp_long
    assert engine.generate(short_prompt,
                           SamplingParams(max_tokens=6)) == exp_short
    engine.stop()


# -- PR 31: a wave only dispatches, behind the decode block in flight -------
#
# The block in flight at a wave was dispatched for the slots' requests
# of that moment; a request admitted into a retired slot must get none
# of its tokens, its own first token comes from the device when the
# prefill is done, and the decode carries are fed on the device.


class _SlowDecode(LLMEngine):
    """A decode dispatch that takes what a small model's step takes on
    a chip, so that clients enqueue while a block is in flight."""

    def _run_decode(self, last, lengths, temps, topks):
        time.sleep(0.004)
        return super()._run_decode(last, lengths, temps, topks)


@pytest.mark.parametrize("decode_steps", [1, 4])
def test_admitted_behind_a_block_in_flight_matches_naive(model,
                                                         decode_steps):
    """Requests admitted while other slots decode, into slots retired a
    block earlier, answer token for token what the full forward pass
    does; answers that end in the middle of a block of 4 included."""
    cfg, params = model
    engine = _SlowDecode(cfg, params, max_batch_size=3, max_seq_len=64,
                         decode_steps=decode_steps)
    prompts = [[(5 * i + j) % 97 + 1 for j in range(2 + i % 5)]
               for i in range(9)]
    lengths = [9, 14, 6, 11, 5, 7, 10, 3, 6]
    expected = [naive_greedy(cfg, params, p, n)
                for p, n in zip(prompts, lengths)]
    results = [None] * len(prompts)

    def worker(i):
        results[i] = engine.generate(prompts[i],
                                     SamplingParams(max_tokens=lengths[i]))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
        time.sleep(0.006)  # arrivals spread over the others' decoding
    for t in threads:
        t.join(timeout=120)
    totals = engine.metrics()["totals"]
    engine.stop()
    assert results == expected
    # The mechanism did engage: waves found a block in flight and left
    # it there, and that block's steps for the slots they admitted into
    # were dropped, not delivered.
    assert totals["admit_waves_behind_block"] >= 3
    assert totals["slot_steps_stale"] >= 3 * decode_steps
    assert totals["slot_steps_stale"] <= totals["tokens_discarded"]
    assert totals["tokens_kept"] == sum(lengths)


def test_block_dispatched_before_an_admission_gives_it_no_token(model):
    """`test_retired_slot_reuse_never_leaks_prior_tokens` with the
    second request waiting while the first decodes: it is admitted into
    the one slot behind a block that was dispatched for the first, and
    every token of that block is counted as stale, none delivered."""
    cfg, params = model
    engine = _SlowDecode(cfg, params, max_batch_size=1, max_seq_len=64)
    long_prompt, short_prompt = list(range(1, 25)), [42, 7]
    exp_long = naive_greedy(cfg, params, long_prompt, 6)
    exp_short = naive_greedy(cfg, params, short_prompt, 6)
    got = {}
    first = threading.Thread(target=lambda: got.update(
        long=engine.generate(long_prompt, SamplingParams(max_tokens=6))))
    first.start()
    while engine.metrics()["active_slots"] == 0:
        time.sleep(0.001)
    got["short"] = engine.generate(short_prompt,
                                   SamplingParams(max_tokens=6))
    first.join(timeout=60)
    totals = engine.metrics()["totals"]
    engine.stop()
    assert got == {"long": exp_long, "short": exp_short}
    assert totals["admit_waves"] == 2
    assert totals["admit_waves_behind_block"] == 1
    assert totals["slot_steps_stale"] == 1
    assert totals["tokens_kept"] == 12


@pytest.mark.parametrize("how", ["max_tokens", "stop_id"])
def test_request_that_ends_on_its_first_token(model, how):
    """One token, the end of the stream, the slot free again, and the
    next request in that slot exact: the decode block already
    dispatched for the ended request is dropped."""
    cfg, params = model
    prompt, follower = [3, 17, 42, 8], [9, 8, 7]
    first = naive_greedy(cfg, params, prompt, 1)
    exp_follower = naive_greedy(cfg, params, follower, 5)
    one = SamplingParams(max_tokens=1) if how == "max_tokens" else \
        SamplingParams(max_tokens=8, stop_token_ids=(first[0],))
    engine = _SlowDecode(cfg, params, max_batch_size=1, max_seq_len=64)
    assert engine.generate(prompt, one) == first
    m = engine.metrics()
    assert m["active_slots"] == 0 and m["free_slots"] == 1
    assert engine.generate(follower,
                           SamplingParams(max_tokens=5)) == exp_follower
    # Again with the follower waiting for the slot while the one-token
    # request holds it.
    got = {}
    waiter = threading.Thread(target=lambda: got.update(
        follower=engine.generate(follower, SamplingParams(max_tokens=5))))
    stream = engine.generate(prompt, one, stream=True)
    waiter.start()
    assert list(stream) == first
    waiter.join(timeout=60)
    totals = engine.metrics()["totals"]
    engine.stop()
    assert got["follower"] == exp_follower
    assert totals["admissions"] == 4
    assert totals["tokens_kept"] == 2 * 1 + 2 * 5


def test_first_token_is_streamed_before_any_decode_block_is_fetched(model):
    """The first token reaches the stream when its prefill is done: with
    every decode block's fetch held back it is there all the same, and
    the rest follow it in order once the blocks are let through."""
    cfg, params = model
    gate = threading.Event()

    class Gated(LLMEngine):
        def _fetch_tokens(self, block):
            assert gate.wait(timeout=60)
            return super()._fetch_tokens(block)

    prompt = [4, 2, 11]
    expected = naive_greedy(cfg, params, prompt, 5)
    engine = Gated(cfg, params, max_batch_size=2, max_seq_len=64)
    stream = engine.generate(prompt, SamplingParams(max_tokens=5),
                             stream=True)
    assert next(stream) == expected[0]
    assert engine.metrics()["totals"]["tokens_kept"] == 1
    gate.set()
    assert list(stream) == expected[1:]
    engine.stop()


def test_prefix_cache_greedy_identical_and_hits(model, monkeypatch):
    """The tentpole's correctness bar: greedy output is TOKEN-IDENTICAL
    with the prefix cache on vs off (copied-in KV blocks are
    byte-equivalent to recomputed prefill), and the shared-head
    workload actually HITS the cache (the perf claim isn't vacuous)."""
    cfg, params = model
    monkeypatch.setattr(ray_config, "llm_kv_block_tokens", 4)
    monkeypatch.setattr(ray_config, "llm_prefix_shm_tier", False)
    shared = list(range(1, 18))  # 17 tokens = 4 full blocks + tail
    prompts = [shared + [50 + i] for i in range(4)]

    def run(cache_on):
        monkeypatch.setattr(ray_config, "llm_prefix_cache", cache_on)
        engine = LLMEngine(cfg, params, max_batch_size=2,
                           max_seq_len=64, model="m")
        outs = [engine.generate(p, SamplingParams(max_tokens=6))
                for p in prompts]
        stats = engine.prefix_cache.stats() if engine.prefix_cache \
            else None
        engine.stop()
        return outs, stats

    off, off_stats = run(False)
    on, on_stats = run(True)
    assert off_stats is None
    assert on == off, "prefix cache changed greedy output"
    assert on_stats["hits"] >= 3 * 4, on_stats  # 4 shared blocks x 3 reqs
    assert on_stats["blocks"] > 0 and on_stats["bytes"] > 0


def test_multi_model_chain_seeds_never_cross_hit(model):
    """Two models on one replica must never share prefix-cache keys:
    the chain seed commits to the model identity, so identical prompts
    under different models produce disjoint chains."""
    from ray_tpu._private.kv_cache import chain_keys

    cfg, params = model
    engine_a = LLMEngine(cfg, params, max_batch_size=1, model="a")
    engine_b = LLMEngine(cfg, params, max_batch_size=1, model="b")
    toks = list(range(32))
    ka = chain_keys(toks, 16, engine_a._chain_seed)
    kb = chain_keys(toks, 16, engine_b._chain_seed)
    assert ka and kb and not (set(ka) & set(kb))
    engine_a.stop()
    engine_b.stop()


# -- the prefix cache's read-back, in two halves (PR 28) --------------------
#
# A wave only dispatches a request's read-back (one gather, a copy to
# the host started); the loop finishes it in the shadow of a decode
# block. These cases hold a read-back pending by patching the readiness
# check, and look at what has to happen when its payload is needed early.


def _never_ready(monkeypatch):
    monkeypatch.setattr(LLMEngine, "_readback_ready",
                        staticmethod(lambda rb: False))


def test_hit_on_a_block_whose_readback_is_pending(model, monkeypatch):
    """A prompt repeated while its first read-back is still on its way
    is served from the cache all the same: the payload is waited for
    (one forced read-back), the match is whole, and the greedy tokens
    are those of a run without the cache."""
    cfg, params = model
    monkeypatch.setattr(ray_config, "llm_kv_block_tokens", 4)
    monkeypatch.setattr(ray_config, "llm_prefix_shm_tier", False)
    prompt = list(range(1, 18))  # 4 full blocks and a tail of one
    monkeypatch.setattr(ray_config, "llm_prefix_cache", False)
    plain = LLMEngine(cfg, params, max_batch_size=2, max_seq_len=64)
    expected = plain.generate(prompt, SamplingParams(max_tokens=6))
    plain.stop()

    monkeypatch.setattr(ray_config, "llm_prefix_cache", True)
    _never_ready(monkeypatch)
    matched = []

    class Engine(LLMEngine):
        def _prefix_copy_in(self, req, slot, prompt):
            m_tok, chain = super()._prefix_copy_in(req, slot, prompt)
            matched.append(m_tok)
            return m_tok, chain

    engine = Engine(cfg, params, max_batch_size=2, max_seq_len=64)
    first = engine.generate(prompt, SamplingParams(max_tokens=6))
    assert len(engine._readbacks) == 1 and not engine._kv_store
    second = engine.generate(prompt, SamplingParams(max_tokens=6))
    totals = engine.metrics()["totals"]
    engine.stop()
    assert first == second == expected
    assert matched == [0, 16]
    assert totals["kv_readbacks_deferred"] == 1
    assert totals["kv_readbacks_forced"] == 1
    assert totals["kv_blocks_read_back"] == 4 == len(engine._kv_store)


def test_evicting_a_pending_block_offloads_its_bytes(model, monkeypatch):
    """With the shm tier on, a block evicted while its read-back is
    pending is waited for and offloaded with the bytes a finished
    read-back stores."""
    from ray_tpu._private.kv_cache import chain_keys

    cfg, params = model
    monkeypatch.setattr(ray_config, "llm_kv_block_tokens", 4)
    monkeypatch.setattr(ray_config, "llm_prefix_shm_tier", False)
    first, second = list(range(1, 10)), list(range(20, 29))  # 2 blocks each

    # The reference: the same prompt through an engine that finishes
    # its read-backs by itself.
    ref = LLMEngine(cfg, params, max_batch_size=1, max_seq_len=64)
    ref.generate(first, SamplingParams(max_tokens=2))
    ref.stop()
    keys = chain_keys(first, 4, ref._chain_seed)
    want = {LLMEngine._shm_object_id(key):
            ref._kv_store[ref.prefix_cache._blocks[key].block_id]
            for key in keys}

    class Plane:
        def __init__(self):
            self.put = {}

        def maybe_put(self, oid, payload, timeout):
            self.put[oid] = payload
            return True

    plane = Plane()
    monkeypatch.setattr(ray_config, "llm_prefix_cache_bytes",
                        2 * ref._block_nbytes)
    _never_ready(monkeypatch)
    # This engine's gather hands its rows over a layer an array (the
    # reference's in one), as a long prompt's does at real widths.
    monkeypatch.setattr(llm, "_D2H_ARRAY_BYTES", 1)
    engine = LLMEngine(cfg, params, max_batch_size=1, max_seq_len=64)
    monkeypatch.setattr(engine, "_shm_plane", lambda: plane)
    engine.generate(first, SamplingParams(max_tokens=2))
    assert len(engine._readbacks) == 1 and not plane.put
    assert len(engine._readbacks[0].arrays) == 2 * cfg.n_layers
    engine.generate(second, SamplingParams(max_tokens=2))  # evicts `first`
    totals = engine.metrics()["totals"]
    assert totals["kv_readbacks_forced"] == 1
    assert len(engine._readbacks) == 1  # `second`'s own, still pending
    engine.stop()
    assert set(plane.put) == set(want)
    for oid, (k, v) in want.items():
        np.testing.assert_array_equal(plane.put[oid][0], k)
        np.testing.assert_array_equal(plane.put[oid][1], v)
    # The host store holds `second`'s blocks and none of the evicted.
    assert len(engine._kv_store) == 2 == engine.prefix_cache.stats()["blocks"]


def test_pending_readback_bytes_stay_under_the_cap(model, monkeypatch):
    """A burst of admissions whose read-backs never finish by
    themselves: the engine waits for the oldest before the pending
    arrays pass the cap, and no payload is lost."""
    cfg, params = model
    monkeypatch.setattr(ray_config, "llm_kv_block_tokens", 4)
    monkeypatch.setattr(ray_config, "llm_prefix_shm_tier", False)
    _never_ready(monkeypatch)
    pending = []

    class Engine(LLMEngine):
        def _start_readback(self, slot, created):
            super()._start_readback(slot, created)
            pending.append(self._readback_bytes)

    engine = Engine(cfg, params, max_batch_size=16, max_seq_len=64)
    cap = engine._readback_cap
    assert cap == 2 * 64 * engine._block_nbytes // 4  # two slots' rows
    prompts = [[(11 * i + j) % 500 + 1 for j in range(18 + i)]
               for i in range(16)]  # 4 to 8 blocks each, unshared
    threads = [threading.Thread(
        target=engine.generate, args=(p, SamplingParams(max_tokens=3)))
        for p in prompts]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    totals = engine.metrics()["totals"]
    assert totals["kv_readbacks_deferred"] == len(pending) == 16
    assert max(pending) <= cap
    assert max(pending) > cap // 2  # several were pending together
    assert 1 <= totals["kv_readbacks_forced"] < 16
    engine.stop()
    assert engine._readback_bytes == 0 and not engine._readbacks
    assert len(engine._kv_store) == totals["kv_blocks_read_back"] \
        == sum(len(p) // 4 for p in prompts)


def test_wave_readback_time_does_not_grow_with_blocks(model, monkeypatch):
    """What the change is for: with a copy to the host that costs
    10 ms a block, a prompt of 25 blocks holds its admission wave no
    longer than a prompt of one; the copy's time lies under the decode
    loop's spans."""
    from ray_tpu._private import critical_path, flight_recorder

    cfg, params = model
    monkeypatch.setattr(ray_config, "llm_kv_block_tokens", 4)
    monkeypatch.setattr(ray_config, "llm_prefix_shm_tier", False)
    monkeypatch.setattr(ray_config, "flight_ring_size", 2048)
    critical_path.reset()
    flight_recorder.reset()

    class SlowCopy(LLMEngine):
        def _complete_readback(self):
            time.sleep(0.010 * len(self._readbacks[0].handles))
            return super()._complete_readback()

    engine = SlowCopy(cfg, params, max_batch_size=1, max_seq_len=128)
    engine.warmup(128)  # nothing compiles inside a wave
    short, long = list(range(1, 6)), list(range(100, 201))  # 1, 25 blocks
    engine.generate(short, SamplingParams(max_tokens=8))
    engine.generate(long, SamplingParams(max_tokens=8))
    engine.stop()
    spans = [s for s in flight_recorder.local_snapshot()["spans"]
             if s["stage"].startswith("engine.")]
    critical_path.reset()
    flight_recorder.reset()
    waves = {s["id"] for s in spans if s["stage"] == "engine.admit_wave"}
    # The wave's half lies in its closing stretch (PR 53).
    closing = {s["id"] for s in spans if s["stage"] == "engine.prefix_admit"
               and s["parent"] in waves}
    readbacks = [s for s in spans if s["stage"] == "engine.prefix_readback"]
    in_wave = {s["attrs"]["blocks"]: s["dur_s"] for s in readbacks
               if s["parent"] in closing}
    outside = {s["attrs"]["blocks"]: s["dur_s"] for s in readbacks
               if s["parent"] not in closing}
    assert set(in_wave) == set(outside) == {1, 25}
    assert outside[25] >= 0.25 and outside[1] >= 0.01
    # The wave's half is a dispatch: far under the copy's 250 ms, and
    # within a few milliseconds of the short prompt's.
    assert in_wave[25] < 0.05 and in_wave[25] < in_wave[1] + 0.02
