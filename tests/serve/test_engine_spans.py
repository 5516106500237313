"""The engine loop's spans and counters (PR 24; PR 31: a wave only
dispatches): the spans tile the
loop, the totals of `metrics()` add up to what the clients got, a
profiler's trace carries the spans under their own names on the ring's
clock, and the proxy's envelope gives `front.ttft_self`. PR 36: the gap
between two blocks handed over one behind the other is a thin record
by kind (`engine.block_gap.*`), a stream's hand-over lag is sampled
(`stream.wake`), and nothing is recorded a token. PR 53: every pass of
the loop that did work says what it cost the host (`engine.loop_host`),
a long one names the tile it was in (`engine.loop_stall.*`), the wave's
closing stretch has its span (`engine.prefix_admit`), and the recorder's
own thread says whether the process let it run (`process.wake_late`).

Kept tier-1-sized: one tiny model, a few dozen requests.
"""

import glob
import http.client
import json
import os
import re
import threading
import time

import pytest

import numpy as np

import jax
import jax.numpy as jnp

import ray_tpu
from ray_tpu import serve
from ray_tpu._private import critical_path, flight_recorder, perf_stats
from ray_tpu._private.config import ray_config
from ray_tpu.models.llama import LlamaConfig, init_params
from ray_tpu.serve import llm
from ray_tpu.serve.llm import LLMDeployment, LLMEngine, SamplingParams

_TINY = LlamaConfig(vocab_size=64, dim=16, n_layers=1, n_heads=2,
                    n_kv_heads=2, hidden_dim=32, max_seq_len=64,
                    dtype=jnp.float32, remat=False)

# Every span name the loop emits.
_LOOP_SPANS = {"engine.admit_wave", "engine.decode_dispatch",
               "engine.token_fetch", "engine.consume_block",
               "engine.idle_wait"}
_WAVE_SPANS = {"engine.flush_pending", "engine.prefix_copy_in",
               "engine.prefill_dispatch", "engine.sample_dispatch",
               "engine.prefix_admit"}
# A wave's first tokens reach their clients behind the next decode
# dispatch (PR 31): the wait is an `engine.token_fetch` of its own and
# the delivery its child.
_FIRST_TOKENS = "engine.first_tokens"
# The read-back's two halves (PR 28): the dispatch in a wave, the
# completion under the loop's span that shadows it.
_READBACK = "engine.prefix_readback"
# The thin records between two blocks (PR 36): durations, not spans of
# the loop's tiling (each lies over a whole turn of the loop).
_GAP_WAVE, _GAP_PLAIN = "engine.block_gap.wave", "engine.block_gap.plain"
# A pass's host time and a long pass's second record (PR 53): thin
# records too, made at the end of a pass under no span.
_LOOP_HOST, _STALL = "engine.loop_host", "engine.loop_stall."
_WAKE_LATE = "process.wake_late"


@pytest.fixture(scope="module")
def params():
    return init_params(_TINY, jax.random.PRNGKey(0))


@pytest.fixture
def ring(monkeypatch):
    """An empty recorder whose snapshots hold the whole ring."""
    critical_path.reset()
    flight_recorder.reset()
    monkeypatch.setattr(ray_config, "flight_ring_size", 2048)
    monkeypatch.setattr(ray_config, "llm_kv_block_tokens", 4)
    monkeypatch.setattr(ray_config, "llm_prefix_shm_tier", False)
    yield
    critical_path.reset()
    flight_recorder.reset()


class _SteppedEngine(LLMEngine):
    """LLMEngine whose decode step takes what a small model's takes on
    a chip (4 ms; the tiny model's own 0.5 ms on the CPU would make the
    interpreter's few dozen microseconds between two spans a tenth of
    the loop)."""

    def _run_decode(self, last, lengths, temps, topks):
        time.sleep(0.004)
        return super()._run_decode(last, lengths, temps, topks)


def _generate_all(engine, prompts, max_tokens):
    results = [None] * len(prompts)

    def worker(i):
        results[i] = engine.generate(prompts[i],
                                     SamplingParams(max_tokens=max_tokens))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    return results


def _ring(*names):
    return sorted((s for s in flight_recorder.local_snapshot()["spans"]
                   if s["stage"] in names), key=lambda s: s["t1"])


def _engine_spans():
    return [s for s in flight_recorder.local_snapshot()["spans"]
            if s["stage"].startswith("engine.")
            and s["stage"] not in (_GAP_WAVE, _GAP_PLAIN, _LOOP_HOST)
            and not s["stage"].startswith(_STALL)]


def _union(intervals):
    covered, edge = 0.0, float("-inf")
    for a, b in sorted(intervals):
        a = max(a, edge)
        if b > a:
            covered += b - a
            edge = b
    return covered


def _wait_idle(engine, admissions):
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        m = engine.metrics()
        if m["totals"]["admissions"] == admissions \
                and not m["active_slots"] and not m["queued"]:
            # The loop flushes the block in flight, then idles.
            time.sleep(0.1)
            return
        time.sleep(0.02)
    raise AssertionError("the engine never went idle")


def test_spans_tile_the_loop_and_totals_add_up(params, ring):
    engine = _SteppedEngine(_TINY, params, max_batch_size=2,
                            max_seq_len=64)
    engine.warmup(16)
    # Ten requests of 50 tokens on two slots: five waves, some 250
    # decode steps. Prompts of 5 and 9 tokens pay for buckets of 8 and
    # 16, and hold one and two whole 4-token blocks for the read-back.
    # The streams are drained afterwards, so that no client thread
    # wakes for every token and takes the interpreter from the loop.
    prompts = [[(7 * i + j) % 60 + 1 for j in range(5 + 4 * (i % 2))]
               for i in range(10)]
    streams = [engine.generate(p, SamplingParams(max_tokens=50),
                               stream=True) for p in prompts]
    _wait_idle(engine, 10)
    engine.stop()
    answers = [list(s) for s in streams]
    assert [len(a) for a in answers] == [50] * 10
    totals = engine.metrics()["totals"]
    spans = _engine_spans()
    by_name = {}
    for s in spans:
        by_name.setdefault(s["stage"], []).append(s)
    assert set(by_name) >= (_LOOP_SPANS | _WAVE_SPANS
                            | {_READBACK, _FIRST_TOKENS}) - \
        {"engine.idle_wait"}, sorted(by_name)

    # The loop's own spans have no parent; a wave's parts name the wave,
    # and a delivery of first tokens the fetch that waited for them.
    waves = {s["id"] for s in by_name["engine.admit_wave"]}
    for name in _WAVE_SPANS:
        assert all(s["parent"] in waves for s in by_name[name]), name
    top = [s for s in spans if s["parent"] == 0]
    assert {s["stage"] for s in top} <= _LOOP_SPANS
    fetches = {s["id"] for s in by_name["engine.token_fetch"]}
    deliveries = by_name[_FIRST_TOKENS]
    assert all(s["parent"] in fetches for s in deliveries)
    # One delivery a wave, after the wave and before the next one.
    admitting = sorted((s for s in by_name["engine.admit_wave"]
                        if s["attrs"]["admitted"]), key=lambda s: s["t0"])
    deliveries.sort(key=lambda s: s["t0"])
    assert len(deliveries) == len(admitting)
    for wave, delivery, nxt in zip(admitting, deliveries,
                                   admitting[1:] + [None]):
        assert wave["t1"] <= delivery["t0"]
        assert nxt is None or delivery["t1"] <= nxt["t0"]
        assert delivery["attrs"]["admitted"] == wave["attrs"]["admitted"]

    # A wave waits for nothing: what it still does about the block in
    # flight is look at it, and with a 4 ms step all waves but the
    # first land behind one.
    assert max(s["dur_s"] for s in by_name["engine.flush_pending"]) < 1e-3
    behind = sum(s["attrs"]["behind_block"] for s in admitting)
    assert totals["admit_waves_behind_block"] == behind >= 4
    # A read-back is dispatched in a wave's closing stretch and finished
    # under a decode dispatch (or the idle wait), so the loop's tiling
    # covers both.
    shadows = {s["id"] for name in ("engine.decode_dispatch",
                                    "engine.idle_wait")
               for s in by_name.get(name, ())}
    closing = {s["id"]: s for s in by_name["engine.prefix_admit"]}
    assert len(closing) == len(admitting)
    dispatched = [s for s in by_name[_READBACK] if s["parent"] in closing]
    finished = [s for s in by_name[_READBACK] if s["parent"] in shadows]
    assert len(dispatched) + len(finished) == len(by_name[_READBACK])
    assert len(dispatched) == 10 and finished
    assert not any("forced" in s["attrs"] for s in by_name[_READBACK])

    # Tiling: from the first decode step to the end of the last block
    # the loop was inside one of its spans at least 95 % of the time.
    steps = by_name["engine.decode_dispatch"]
    assert len(steps) >= 200
    t_first = min(s["t0"] for s in steps)
    t_last = max(s["t1"] for s in by_name["engine.consume_block"])
    inside = _union((max(s["t0"], t_first), min(s["t1"], t_last))
                    for s in top)
    assert inside / (t_last - t_first) >= 0.95, inside / (t_last - t_first)

    # The totals add up, among themselves and with the spans' counts.
    assert totals["tokens_kept"] == sum(len(a) for a in answers)
    assert totals["admissions"] == 10
    assert totals["admit_waves"] == len(
        [s for s in by_name["engine.admit_wave"]
         if s["attrs"]["admitted"]])
    assert totals["decode_steps"] == len(steps)
    assert totals["active_slot_steps"] == sum(
        s["attrs"]["active"] for s in steps)
    assert 0 < totals["active_slot_steps"] <= totals["decode_steps"] * 2
    blocks = by_name["engine.consume_block"]
    kept = sum(s["attrs"]["kept"] for s in blocks)
    discarded = sum(s["attrs"]["discarded"] for s in blocks)
    stale = sum(s["attrs"]["stale"] for s in blocks)
    # First tokens are counted where they are delivered.
    delivered = sum(s["attrs"]["admitted"] for s in deliveries)
    assert delivered == totals["admissions"]
    assert kept + delivered == totals["tokens_kept"]
    assert discarded == totals["tokens_discarded"]
    assert kept + discarded == 2 * len(blocks)
    # The denominators ride the spans (PR 36): a block's slot-steps, and
    # the keys the slots reserve beside those they hold.
    assert sum(s["attrs"]["slot_steps"] for s in blocks) == 2 * len(blocks)
    assert all(0 <= s["attrs"]["keys_cached"] <= s["attrs"]["keys_reserved"]
               == 2 * 64 for s in steps)
    # And the rows a layer's attention fetches (PR 43): an active slot's
    # keys with the fed token's, rounded up to the kernel's block, here
    # the slot's whole region of 64.
    assert all(s["attrs"]["keys_cached"] < s["attrs"]["keys_read"]
               == 64 * s["attrs"]["active"] for s in steps)
    assert totals["keys_read"] == sum(s["attrs"]["keys_read"] for s in steps)
    # Every decode step's block was consumed, but the last one in
    # flight when the engine stopped.
    assert kept + discarded in (2 * len(steps), 2 * (len(steps) - 1))
    # A wave behind a block admits into slots the block ran for the
    # requests before: one stale slot-step an admission, part of the
    # discarded; none ended on its first token.
    assert stale == totals["slot_steps_stale"] <= discarded
    assert stale == sum(s["attrs"]["admitted"] for s in admitting
                        if s["attrs"]["behind_block"])
    assert not any(s["attrs"]["ended"] for s in deliveries)
    prefills = by_name["engine.prefill_dispatch"]
    assert totals["prefill_tokens_real"] == sum(
        s["attrs"]["real"] for s in prefills) == sum(map(len, prompts))
    assert totals["prefill_tokens_bucketed"] == sum(
        s["attrs"]["bucket"] for s in prefills) == 5 * 8 + 5 * 16
    assert totals["prefill_tokens_real"] <= \
        totals["prefill_tokens_bucketed"]
    # Every created block is counted when its read-back is dispatched,
    # and again by the span that stores its payload.
    blocks = sum(s["attrs"]["blocks"] for s in dispatched)
    assert totals["kv_blocks_read_back"] == blocks == 5 * 1 + 5 * 2
    assert sum(s["attrs"]["blocks"] for s in finished) == blocks
    assert totals["kv_bytes_read_back"] == sum(
        s["attrs"]["bytes"] for s in dispatched) \
        == blocks * engine._block_nbytes
    assert totals["kv_readbacks_deferred"] == 10
    assert totals["kv_readbacks_forced"] == 0
    # The closing stretch says what it did: the blocks it created are
    # its read-backs', and a cache of 256 MB evicts nothing.
    for s in closing.values():
        assert s["attrs"]["created"] == sum(
            d["attrs"]["blocks"] for d in dispatched
            if d["parent"] == s["id"])
        assert not any(s["attrs"][k] for k in (
            "evicted", "offloaded", "forced", "backpressure_waits",
            "put_us")), s
    assert totals["kv_blocks_evicted"] == 0
    # One `engine.loop_host` a pass that did work, all of them far
    # under the threshold of a stall.
    passes = _ring(_LOOP_HOST)
    assert totals["loop_passes"] == len(passes) >= len(steps)
    assert abs(totals["loop_host_us"]
               - 1e6 * sum(s["dur_s"] for s in passes)) <= len(passes)
    assert totals["loop_stalls"] == totals["loop_stall_us"] == 0
    assert not [s for s in flight_recorder.local_snapshot()["spans"]
                if s["stage"].startswith(_STALL)]
    # After stop() nothing is on its way: every created block's payload
    # is in the host store, in the shape the copy-in program takes.
    assert not engine._readbacks and not engine._readback_of
    assert engine._readback_bytes == 0
    assert len(engine._kv_store) == blocks
    k = engine.cache["k"]
    assert all(kb.shape == vb.shape == (k.shape[0], 4) + k.shape[3:]
               for kb, vb in engine._kv_store.values())


def test_a_block_engines_spans_carry_its_sums(ring):
    """A model that generates by blocks, under the loop's span names as
    they are: `engine.consume_block` carries the forwards of owners'
    slots by phase, the positions fixed and the blocks handed over,
    which add up to the totals; `slot_steps` counts the token places of
    emitted rows alone, so kept over it stays a share; a request's
    first block closes its `llm.prefill`, and from one block to the
    next one gap in four is recorded."""
    from benchmark.harness.manifest import ROOT, load_json, model_adapter

    file = load_json(ROOT, "benchmark", "configs", "sdar-30b-a3b-serve.json")
    adapter = model_adapter(file)
    cfg = adapter.program_config(adapter.debug(file))
    engine = LLMEngine(cfg, adapter.init(cfg, jax.random.PRNGKey(0)),
                       max_batch_size=2, max_seq_len=64, decode_steps=2)
    prompts = [[(5 * i + j) % 400 + 1 for j in range(6 + 3 * i)]
               for i in range(4)]
    answers = _generate_all(engine, prompts, 22)
    engine.stop()
    engine._flush_pending()
    assert [len(a) for a in answers] == [22] * 4
    totals = engine.metrics()["totals"]
    blocks = _ring("engine.consume_block")
    summed = {name: sum(s["attrs"].get(name, 0) for s in blocks)
              for name in ("slot_forwards_denoise", "slot_forwards_commit",
                           "slot_forwards_fused", "slot_forwards",
                           "tokens_fixed", "blocks_emitted", "kept",
                           "discarded", "slot_steps")}
    for name in ("slot_forwards_denoise", "slot_forwards_fused",
                 "tokens_fixed", "blocks_emitted"):
        assert summed[name] == totals[name] > 0, name
    # A block's commit rides in the next block's first forward: no
    # forward of a request's slot fixes nothing.
    assert summed["slot_forwards_commit"] \
        == totals["slot_forwards_commit"] == 0
    assert summed["slot_forwards"] == summed["slot_forwards_denoise"] \
        + summed["slot_forwards_commit"]
    # Every block but a request's first is committed in such a forward,
    # but for a last block, whose commit is nobody's.
    assert summed["blocks_emitted"] - 2 * 4 \
        <= summed["slot_forwards_fused"] <= summed["blocks_emitted"] - 4
    assert summed["kept"] == totals["tokens_kept"] == 4 * 22
    assert summed["kept"] + summed["discarded"] == summed["slot_steps"]
    assert summed["slot_steps"] % cfg.block_length == 0
    # 2 of a block's 4 positions a forward, but for a first block that
    # opens with known positions.
    assert 1.8 < summed["tokens_fixed"] / summed["slot_forwards"] <= 2
    assert len(_ring("llm.prefill")) == 4
    assert _ring("engine.emitted_block_gap")
    steps = _ring("engine.decode_dispatch")
    assert all(s["attrs"]["keys_read"] >= s["attrs"]["keys_cached"]
               for s in steps)


def test_block_gaps_tile_a_busy_stretch_by_kind(params, ring):
    """Between two blocks handed over one behind the other lies one
    thin record (PR 36): the gaps of a busy stretch run from its first
    block's hand-over to its last one's with nothing between them, a
    block that a wave's prefills were dispatched in front of is `wave`
    and its neighbours `plain`, and no gap spans an idle loop."""
    engine = _SteppedEngine(_TINY, params, max_batch_size=3,
                            max_seq_len=64)
    engine.warmup(16)
    streams = [engine.generate([3 + i, 1, 4, 1, 5],
                               SamplingParams(max_tokens=40), stream=True)
               for i in range(2)]
    while engine.metrics()["totals"]["decode_steps"] < 10:
        time.sleep(0.002)
    # A third request, into the free slot: a wave in mid-stretch.
    streams.append(engine.generate([9, 2, 6], SamplingParams(max_tokens=12),
                                   stream=True))
    _wait_idle(engine, 3)
    # A second busy stretch, behind an idle loop.
    streams.append(engine.generate([2, 7, 1, 8], SamplingParams(max_tokens=9),
                                   stream=True))
    _wait_idle(engine, 4)
    engine.stop()
    assert [len(list(s)) for s in streams] == [40, 40, 12, 9]
    totals = engine.metrics()["totals"]

    blocks = _ring("engine.consume_block")
    gaps = _ring(_GAP_WAVE, _GAP_PLAIN)
    dispatches = _ring("engine.decode_dispatch")
    idles = _ring("engine.idle_wait")
    waves = [w for w in _ring("engine.admit_wave")
             if w["attrs"]["admitted"]]
    assert len(blocks) == len(dispatches) == totals["decode_steps"]
    assert not any(g["trace_id"] or g.get("attrs") for g in gaps)

    # `behind_wave` says that a wave's prefills went to the device
    # between the dispatch of the block before and the block's own.
    for k, (step, block) in enumerate(zip(dispatches, blocks)):
        since = dispatches[k - 1]["t0"] if k else float("-inf")
        behind = any(since <= w["t0"] and w["t1"] <= step["t0"]
                     for w in waves)
        assert block["attrs"]["behind_wave"] == int(behind), k

    # Busy stretches: blocks with no idle loop between them.
    stretches = [[blocks[0]]]
    for before, block in zip(blocks, blocks[1:]):
        if any(before["t1"] <= i["t0"] <= block["t0"] for i in idles):
            stretches.append([])
        stretches[-1].append(block)
    assert len(stretches) == 2 and len(stretches[0]) > 40
    assert len(gaps) == len(blocks) - 2  # one fewer than a stretch's blocks
    at = 0
    for stretch in stretches:
        mine = gaps[at:at + len(stretch) - 1]
        at += len(mine)
        # Each gap ends where its block's hand-over does and starts
        # where the one before ended: they tile the stretch.
        for gap, before, block in zip(mine, stretch, stretch[1:]):
            assert abs(gap["t1"] - block["t1"]) < 1e-3
            assert abs(gap["dur_s"] - (block["t1"] - before["t1"])) < 1e-3
            assert gap["stage"] == (
                _GAP_WAVE if block["attrs"]["behind_wave"] else _GAP_PLAIN)
        assert abs(sum(g["dur_s"] for g in mine)
                   - (stretch[-1]["t1"] - stretch[0]["t1"])) < 1e-3
    # The third request's wave: one `wave` gap between `plain` ones.
    kinds = [g["stage"] for g in gaps[:len(stretches[0]) - 1]]
    assert _GAP_WAVE in kinds[5:]
    i = kinds.index(_GAP_WAVE, 5)
    assert kinds[i - 1] == kinds[i + 1] == _GAP_PLAIN
    # The totals count the same blocks.
    n_wave = sum(g["stage"] == _GAP_WAVE for g in gaps)
    assert totals["blocks_behind_wave"] == n_wave
    assert totals["blocks_behind_wave"] + totals["blocks_plain"] == len(gaps)
    # A block behind a wave waits for the wave's first tokens too; the
    # fetch that does says so.
    fetches = _ring("engine.token_fetch")
    firsts = {s["parent"] for s in _ring(_FIRST_TOKENS)}
    assert all((f.get("attrs") == {"first_tokens": 1}) == (f["id"] in firsts)
               for f in fetches)
    assert len(firsts) == len(waves)


def test_stream_wake_is_sampled_and_no_record_is_a_tokens(params, ring):
    """64 streams (PR 36): a request's reader records `stream.wake` for
    each 16th token and nothing else, with no trace id, and the ring
    takes far fewer records a block than the block has tokens."""
    n, asked = 64, 50
    engine = LLMEngine(_TINY, params, max_batch_size=n, max_seq_len=64)
    engine.warmup(8)
    prompts = [[(5 * i + j) % 60 + 1 for j in range(3 + i % 4)]
               for i in range(n)]
    answers = _generate_all(engine, prompts, asked)
    engine.stop()
    assert [len(a) for a in answers] == [asked] * n
    spans = flight_recorder.local_snapshot()["spans"]
    assert len(spans) < 2048  # the ring lost none
    wakes = [s for s in spans if s["stage"] == "stream.wake"]
    assert len(wakes) == n * (asked // 16)
    assert not any(s["trace_id"] for s in wakes)
    assert all(0 <= s["dur_s"] < 5.0 for s in wakes)
    blocks = [s for s in spans if s["stage"] == "engine.consume_block"]
    kept = sum(s["attrs"]["kept"] for s in blocks)
    assert kept == n * (asked - 1)
    # A record a token would be `kept` and more. Stated: what is
    # recorded a block or a sampled token (the block's three spans, its
    # gap, a wake for each 16th token of a slot) stays under 8 a block
    # with 64 slots, and the rest, a request's stages and a wave's
    # spans, under 12 a request.
    steady = [s for s in spans if s["stage"] in (
        "engine.decode_dispatch", "engine.token_fetch",
        "engine.consume_block", _GAP_WAVE, _GAP_PLAIN, "stream.wake")]
    assert len(steady) / len(blocks) < 8, (len(steady), len(blocks))
    assert len(spans) - len(steady) < 12 * n
    assert len(spans) < kept / 2


def test_every_name_has_a_row_and_every_reader_a_name(params):
    """PERF.md section 3 lists every `engine.*`, `stream.*` and `llm.*`
    name and every total the program records, with its reader; and what
    a metric's file reads under such a name is a name the program
    records (PR 36)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    read = lambda *parts: open(os.path.join(root, *parts)).read()  # noqa: E731
    source = read("ray_tpu", "serve", "llm.py") \
        + read("ray_tpu", "serve", "streaming.py")
    # Every name is written out as a string where it is recorded.
    emitted = set(re.findall(r'"((?:engine|stream|llm)\.[a-z_.]+)"', source))
    assert {"engine.block_gap.wave", "engine.block_gap.plain", "stream.wake",
            "stream.channel", "llm.prefill", "engine.first_tokens"} \
        <= emitted and len(emitted) >= 20, sorted(emitted)
    perf = read("PERF.md")
    layers = perf[perf.index("## 3. Layers"):perf.index("## 4. Cells")]
    rows = set(re.findall(r"`([a-z_.]+)`", layers))
    assert emitted <= rows, sorted(emitted - rows)
    engine = LLMEngine(_TINY, params, max_batch_size=2, max_seq_len=64)
    totals = set(engine.metrics()["totals"])
    assert {"blocks_behind_wave", "blocks_plain"} <= totals <= rows, \
        sorted(totals - rows)
    # The readers: every stage or span a metric's file names.
    metrics = os.path.join(root, "benchmark", "metrics")
    programs = source + "".join(
        read("ray_tpu", "models", f)
        for f in os.listdir(os.path.join(root, "ray_tpu", "models"))
        if f.endswith(".py"))
    for f in sorted(os.listdir(metrics)):
        args = json.loads(read(metrics, f))["args"]
        named = [v for value in args.values()
                 for v in (value if isinstance(value, list) else [value])
                 if isinstance(v, str)
                 and re.fullmatch(r"(engine|stream|llm)\.[a-z_.]*[a-z]", v)]
        assert set(named) <= emitted, (f, named)
        if named and "num" in args:  # a ratio of two attributes
            assert all(re.search(rf"\b{args[k]}\b", programs)
                       for k in ("num", "den")), (f, args)


def test_trace_carries_the_spans_on_the_rings_clock(params, ring,
                                                    tmp_path):
    """With a `jax.profiler` trace being taken every engine span is in
    the xplane's host plane under its own name, with its attributes as
    stats, and starts where the ring's record says (to 1 ms): the host
    plane's clock is time.time's, counted from the `profile_start_time`
    of the trace's `Task Environment` plane."""
    from jax.profiler import ProfileData

    engine = LLMEngine(_TINY, params, max_batch_size=2, max_seq_len=64)
    engine.warmup(16)
    engine.generate([1, 2, 3], SamplingParams(max_tokens=2))  # loop is up
    critical_path.flush()
    flight_recorder.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _generate_all(engine, [[5, 6, 7, 8, 9], [9, 8, 7]], 6)
        time.sleep(0.12)  # the loop idles: engine.idle_wait
    finally:
        jax.profiler.stop_trace()
    engine.stop()
    ring_spans = _engine_spans()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    start_ns, seen = None, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            start_ns = dict(plane.stats)["profile_start_time"]
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                seen += [(e.name, e.start_ns, dict(e.stats))
                         for e in line.events
                         if e.name.startswith("engine.")]
    assert {name for name, _, _ in seen} == \
        _LOOP_SPANS | _WAVE_SPANS | {_READBACK, _FIRST_TOKENS}
    # Every annotation is some ring record's twin: same name, same
    # attributes, starts within 1 ms of the record's t0.
    for name, rel_ns, stats in seen:
        t0 = (start_ns + rel_ns) / 1e9
        twins = [s for s in ring_spans if s["stage"] == name
                 and abs(s["t0"] - t0) < 1e-3]
        assert twins, (name, t0)
        assert any((s.get("attrs") or {}) == stats for s in twins), \
            (name, stats, twins)
    dispatch = [st for name, _, st in seen
                if name == "engine.decode_dispatch"]
    assert all(st["n_slots"] == 2 and 0 < st["active"] <= 2
               for st in dispatch)


def _sse_tokens(resp):
    events = [e[len(b"data: "):] for e in resp.read().split(b"\n\n")
              if e.startswith(b"data: ")]
    return [json.loads(e)["token"] for e in events if e != b"[DONE]"]


def test_front_ttft_self_from_the_proxys_envelope(params, ring):
    """A streamed request through proxy, replica and engine: its
    waterfall holds the envelope `proxy.first_byte` beside the stages
    it encloses, and `front.ttft_self`, the front's own share, is there
    and no longer than the envelope."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    try:
        serve.run(
            serve.deployment(LLMDeployment).bind(
                _TINY, lambda: params, max_batch_size=2, max_seq_len=64,
                warmup_max_prompt_len=16),
            route_prefix="/llm")
        proxy = serve.start_http_proxy()
        conn = http.client.HTTPConnection(proxy.host, proxy.port,
                                          timeout=60)
        for trace_id in ("warm", "front-1", "front-2"):
            conn.request(
                "POST", "/llm",
                body=json.dumps({"prompt_ids": [3, 1, 4, 1, 5],
                                 "max_tokens": 4, "stream": True}),
                headers={"Content-Type": "application/json",
                         "X-Trace-Id": trace_id})
            resp = conn.getresponse()
            assert resp.status == 200
            assert len(_sse_tokens(resp)) == 4
        conn.close()
        deadline = time.monotonic() + 10
        rows = {}
        while time.monotonic() < deadline and "front-2" not in rows:
            rows = {r["trace_id"]: r
                    for r in critical_path.slow_requests(n=10)}
            time.sleep(0.05)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    for trace_id in ("front-1", "front-2"):
        row = rows[trace_id]
        stages = {s["stage"]: s for s in row["stages"]}
        assert {"proxy.first_byte", "llm.prefill"} <= set(stages), stages
        envelope = stages["proxy.first_byte"]
        assert 0.0 <= row["front_ttft_self_s"] <= envelope["dur_s"]
        # The engine's stages lie inside the envelope.
        assert envelope["t0"] <= stages["llm.prefill"]["t0"]
        assert stages["llm.prefill"]["t1"] <= envelope["t1"] + 1e-3
    derived = [s for s in flight_recorder.local_snapshot()["spans"]
               if s["stage"] == "front.ttft_self"]
    assert {s["trace_id"] for s in derived} >= {"front-1", "front-2"}
    vec = critical_path.attribution_vectors()["/llm"]
    assert vec["front.ttft_self"]["count"] >= 2


# -- what a pass cost the host (PR 53) ------------------------------------


class _SlowTokens:
    """A decode block's tokens whose copy to the host takes 20 ms: the
    wait for the device, on a backend that has none."""

    def __init__(self, tokens):
        self.tokens = tokens

    def is_ready(self):
        return False

    def __array__(self, dtype=None, copy=None):
        time.sleep(0.02)
        return np.asarray(self.tokens)


class _FakePlane:
    """A warm tier whose `maybe_put` takes `waits[i]` seconds for the
    i-th block (0 from there on), counting a back-pressure wait for
    every 10 ms as `shm_plane.maybe_put` does, and takes the block
    unless it waited."""

    def __init__(self, waits):
        self.waits = list(waits)
        self.put = 0
        self.counter = perf_stats.counter("object_create_backpressure_waits")

    def maybe_put(self, object_id, value, timeout):
        wait = self.waits.pop(0) if self.waits else 0.0
        self.put += 1
        if wait:
            self.counter.inc(round(wait / 0.01))
            time.sleep(wait)
        return not wait

    def get(self, object_id):
        return False, None


def _evicting(monkeypatch, engine_cls, params, waits, **overrides):
    """An engine of two slots whose prefix cache holds two 4-token
    blocks, so that every prompt of 9 tokens evicts the two before it
    into a `_FakePlane`; `overrides` replace methods of the engine."""
    monkeypatch.setattr(ray_config, "llm_prefix_cache_bytes", 2 * 512)
    monkeypatch.setattr(llm, "_STALL_S", 0.25)  # whatever the chip's is
    engine = type("_Engine", (engine_cls,), overrides)(
        _TINY, params, max_batch_size=2, max_seq_len=64)
    assert engine._block_nbytes == 512
    plane = _FakePlane(waits)
    engine._shm_plane = lambda: plane
    engine.warmup(16)
    return engine, plane


def _serve(engine, n, max_tokens=12):
    """`n` requests of 9 distinct tokens, one after the other, so that
    each is a wave of its own."""
    for i in range(n):
        prompt = [(11 * i + j) % 60 + 1 for j in range(9)]
        assert len(engine.generate(
            prompt, SamplingParams(max_tokens=max_tokens))) == max_tokens
    _wait_idle(engine, n)
    engine.stop()
    return engine.metrics()["totals"]


def _stalls():
    return [s for s in flight_recorder.local_snapshot()["spans"]
            if s["stage"].startswith(_STALL)]


def test_a_pass_leaves_out_the_wait_for_the_device_and_keeps_the_hosts(
        params, ring, monkeypatch):
    """`engine.loop_host` is the pass less its waits: a fetch that waits
    20 ms for its block is not in it, a `maybe_put` that sleeps 40 ms
    is. One record a pass that did work, and the totals count them."""

    def slow_fetch(self):
        tokens, counts = LLMEngine._dispatch_decode(self)
        return _SlowTokens(tokens), counts

    engine, plane = _evicting(monkeypatch, LLMEngine, params,
                              [0.0, 0.0, 0.04],
                              _dispatch_decode=slow_fetch)
    totals = _serve(engine, 4)
    passes = _ring(_LOOP_HOST)
    fetches = [s for s in _ring("engine.token_fetch") if not s.get("attrs")]
    assert len(fetches) >= 30
    assert min(s["dur_s"] for s in fetches) >= 0.02
    # The waits are left out: a pass whose fetch took 20 ms cost the
    # host a fraction of that.
    durs = sorted(s["dur_s"] for s in passes)
    assert durs[len(durs) // 2] < 0.01, durs
    # The sleep is kept: the pass of the wave that offloaded the third
    # block, and no other, is 40 ms long.
    assert 0.04 <= durs[-1] < 0.25 and durs[-2] < 0.04, durs[-3:]
    slow = max(passes, key=lambda s: s["dur_s"])
    closing = [s for s in _ring("engine.prefix_admit")
               if s["attrs"]["put_us"] >= 40_000]
    assert len(closing) == 1
    # It lies in that pass: behind the end of the pass before it and
    # ahead of its own. (By the passes' ends, which are read off the
    # clock; a thin record's start is its end less the host's time, so a
    # fetch's wait behind the admission moves it past the admission's.)
    before = max((s["t1"] for s in passes if s["t1"] < slow["t1"]),
                 default=0.0)
    assert before <= closing[0]["t0"] and closing[0]["t1"] <= slow["t1"]
    assert closing[0]["attrs"]["backpressure_waits"] == 4
    # Three waves evicted two blocks each; the one waited for was not
    # taken.
    assert plane.put == totals["kv_blocks_evicted"] == 6
    assert totals["kv_blocks_offloaded"] == 5
    assert totals["kv_offload_backpressure_waits"] == 4
    assert totals["kv_offload_us"] >= 40_000
    assert totals["loop_passes"] == len(passes)
    assert abs(totals["loop_host_us"] - 1e6 * sum(durs)) <= len(durs)
    assert totals["loop_stalls"] == 0 and not _stalls()


def test_a_wave_that_waits_for_the_warm_tier_is_a_stall_of_prefix_admit(
        params, ring, monkeypatch):
    """One `maybe_put` of 0.3 s: exactly one `engine.loop_stall.*`
    record, of that length and under `prefix_admit`'s name, beside the
    `engine.loop_host` of the same pass, and the `engine.prefix_admit`
    of its wave says what it waited for."""
    engine, _plane = _evicting(monkeypatch, LLMEngine, params, [0.3])
    totals = _serve(engine, 3)
    (stall,) = _stalls()
    assert stall["stage"] == "engine.loop_stall.prefix_admit"
    assert 0.3 <= stall["dur_s"] < 0.6
    assert not stall["trace_id"] and not stall.get("attrs")
    twin = max(_ring(_LOOP_HOST), key=lambda s: s["dur_s"])
    assert twin["dur_s"] == stall["dur_s"]
    closing = _ring("engine.prefix_admit")
    assert len(closing) == 3
    (waited,) = [s for s in closing if s["attrs"]["backpressure_waits"]]
    assert waited["attrs"]["backpressure_waits"] == 30
    assert 300_000 <= waited["attrs"]["put_us"] < 600_000
    assert waited["attrs"]["evicted"] == 2 and waited["attrs"]["offloaded"] == 1
    assert waited["attrs"]["created"] == 2
    assert stall["t0"] <= waited["t0"] and waited["t1"] <= stall["t1"]
    for attr, total in llm._PREFIX_ADMIT.items():
        assert sum(s["attrs"][attr] for s in closing) == totals[total], attr
    assert totals["loop_stalls"] == 1
    assert abs(totals["loop_stall_us"] - 1e6 * stall["dur_s"]) <= 1


class _Once:
    """Called, it sleeps 0.3 s: the first time after `arm()`."""

    def __init__(self):
        self.left = 0

    def arm(self):
        self.left = 1

    def __call__(self):
        if self.left:
            self.left = 0
            time.sleep(0.3)


def _slow(name, sleep, n_tokens=None):
    """`LLMEngine`'s method `name` behind `sleep()`; `_finished` only
    for a request of `n_tokens` tokens (its first is judged in
    `engine.first_tokens`, the others in `engine.consume_block`)."""

    def call(self, *args):
        if n_tokens is None or len(args[0].tokens) == n_tokens:
            sleep()
        return getattr(LLMEngine, name)(self, *args)
    return {name: call}


@pytest.mark.parametrize("tile, method, n_tokens", [
    ("consume_block", "_finished", 5),
    ("first_tokens", "_finished", 1),
    ("decode_dispatch", "_run_decode", None),
    ("admit_wave", "_run_prefill", None),
    ("other", "_record_hand_over", None),
])
def test_a_long_pass_names_the_tile_it_was_in(params, ring, monkeypatch,
                                              tile, method, n_tokens):
    sleep = _Once()
    engine, _plane = _evicting(monkeypatch, LLMEngine, params, [],
                               **_slow(method, sleep, n_tokens))
    sleep.arm()  # the warm-up is over
    totals = _serve(engine, 2)
    (stall,) = _stalls()
    assert stall["stage"] == _STALL + tile
    assert 0.3 <= stall["dur_s"] < 0.6
    assert totals["loop_stalls"] == 1


def test_with_the_recorder_off_a_pass_leaves_nothing(params, ring,
                                                    monkeypatch):
    monkeypatch.setattr(ray_config, "stage_spans_enabled", False)
    engine, _plane = _evicting(monkeypatch, LLMEngine, params, [0.3])
    totals = _serve(engine, 3)
    time.sleep(0.25)  # two beats of the folder, if it runs
    assert not flight_recorder.local_snapshot()["spans"]
    assert not any(totals[k] for k in (
        "loop_passes", "loop_host_us", "loop_stalls", "loop_stall_us"))
    assert totals["kv_blocks_evicted"] == 4  # counted, not recorded


def test_a_block_engine_records_the_same_names(ring, monkeypatch):
    """`_BlockEngine` has a `_consume_block` of its own and the loop,
    the wave and the pass's end are `LLMEngine`'s: the same records
    under the same names, and its own consume as a stall's tile."""
    from benchmark.harness.manifest import ROOT, load_json, model_adapter

    file = load_json(ROOT, "benchmark", "configs", "sdar-30b-a3b-serve.json")
    adapter = model_adapter(file)
    cfg = adapter.program_config(adapter.debug(file))
    sleep = _Once()
    sleep.arm()
    monkeypatch.setattr(llm, "_STALL_S", 0.25)
    monkeypatch.setattr(llm._BlockEngine, "_finished",
                        _slow("_finished", sleep, 6)["_finished"])
    engine = LLMEngine(cfg, adapter.init(cfg, jax.random.PRNGKey(0)),
                       max_batch_size=2, max_seq_len=64, decode_steps=2)
    assert isinstance(engine, llm._BlockEngine)
    prompts = [[(5 * i + j) % 400 + 1 for j in range(6 + 3 * i)]
               for i in range(3)]
    answers = _generate_all(engine, prompts, 14)
    engine.stop()
    assert [len(a) for a in answers] == [14] * 3
    totals = engine.metrics()["totals"]
    passes = _ring(_LOOP_HOST)
    assert totals["loop_passes"] == len(passes) \
        >= len(_ring("engine.decode_dispatch")) > 0
    closing = _ring("engine.prefix_admit")
    assert closing and all(
        set(s["attrs"]) == set(llm._PREFIX_ADMIT) for s in closing)
    assert sum(s["attrs"]["created"] for s in closing) \
        == totals["kv_blocks_read_back"] > 0
    # No warm-up here, so the passes that compiled are stalls too, of
    # the tiles that dispatch.
    stalls = [s["stage"] for s in _stalls()]
    assert stalls.count("engine.loop_stall.consume_block") == 1
    assert set(stalls) <= {"engine.loop_stall.consume_block",
                           "engine.loop_stall.admit_wave",
                           "engine.loop_stall.decode_dispatch"}, stalls
    assert totals["loop_stalls"] == len(stalls)


def _folder_beats(seconds):
    critical_path.record_stage(None, "test.tick", 0.0)  # the folder is up
    time.sleep(seconds)
    return [s["dur_s"] for s in _ring(_WAKE_LATE)]


def test_the_folders_beat_says_whether_the_process_stood_still(
        ring, monkeypatch):
    """`process.wake_late`: every beat of the recorder's thread, what
    it took beyond the sleeps it asked for. Near 0 on a quiet process
    (the median: the box is shared); the length of the hold where
    something kept the thread from being done, here its own fold."""
    quiet = _folder_beats(0.65)
    assert len(quiet) >= 4 and min(quiet) >= 0.0
    assert sorted(quiet)[len(quiet) // 2] < 0.05, quiet
    flush, sleep = critical_path.flush, _Once()
    sleep.arm()

    def held(max_n=None):
        if threading.current_thread().name == "critical-path-folder":
            sleep()
        return flush(max_n)

    monkeypatch.setattr(critical_path, "flush", held)
    beats = _folder_beats(0.65)
    assert max(beats) >= 0.2, beats
    assert sum(b >= 0.2 for b in beats) == 1


def test_the_decode_compile_says_how_the_step_reads_its_weights(params, ring):
    """PR 60: the engine's compile of its decode program is a span,
    `setup.compile_decode`, that carries what the trace counted: the
    bytes of the layers' parameters a step reads where they lie in
    their stacks (`ops.stacked_product`: none off the TPU) and as the
    layer scan's slices (here every leaf of the one run)."""
    engine = LLMEngine(_TINY, params, max_batch_size=2, max_seq_len=64)
    engine.warmup(16)
    engine.stop()
    compiles = _ring("setup.compile_decode")
    assert len(compiles) == 1 and compiles[0]["parent"] == 0
    layers = sum(x.size * x.dtype.itemsize
                 for x in jax.tree.leaves(params["layers"]))
    assert compiles[0]["attrs"] == {"weights_in_place_bytes": 0,
                                    "weights_sliced_bytes": layers}
    assert compiles[0]["dur_s"] > 0
