"""Serve streaming: generator deployments stream chunks to Python callers
and over HTTP as server-sent events, with the first chunk arriving before
the last is produced.
"""

import http.client
import json
import time

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_up():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def test_python_caller_iter_stream(serve_up):
    @serve.deployment
    class Streamer:
        def __call__(self, request):
            def gen():
                for i in range(5):
                    yield {"i": i}
            return gen()

    handle = serve.run(Streamer.bind(), route_prefix="/s1")
    result = ray_tpu.get(handle.remote({"n": 5}), timeout=60)
    assert serve.is_stream(result)
    chunks = list(serve.iter_stream(result))
    assert [c["i"] for c in chunks] == [0, 1, 2, 3, 4]


def test_stream_error_propagates(serve_up):
    @serve.deployment
    class Bad:
        def __call__(self, request):
            def gen():
                yield {"ok": 1}
                raise ValueError("mid-stream boom")
            return gen()

    handle = serve.run(Bad.bind(), route_prefix="/s2")
    result = ray_tpu.get(handle.remote({}), timeout=60)
    it = serve.iter_stream(result)
    assert next(it)["ok"] == 1
    with pytest.raises(RuntimeError, match="mid-stream boom"):
        list(it)


def test_async_deployment_unary_and_stream(serve_up):
    """Async deployments run on the replica's persistent loop: an async
    unary method resolves normally, an async-generator result streams
    like a sync generator — to Python callers and over HTTP SSE."""

    @serve.deployment
    class AsyncMixed:
        async def __call__(self, request):
            if isinstance(request, dict) and request.get("stream"):
                async def agen():
                    for i in range(4):
                        yield {"i": i}
                return agen()
            return {"unary": request}

    handle = serve.run(AsyncMixed.bind(), route_prefix="/amixed")

    out = ray_tpu.get(handle.remote({"x": 1}), timeout=60)
    assert out == {"unary": {"x": 1}}

    result = ray_tpu.get(handle.remote({"stream": True}), timeout=60)
    assert serve.is_stream(result)
    chunks = list(serve.iter_stream(result))
    assert [c["i"] for c in chunks] == [0, 1, 2, 3]

    proxy = serve.start_http_proxy()
    conn = http.client.HTTPConnection(proxy.host, proxy.port, timeout=30)
    conn.request("POST", "/amixed", body=json.dumps({"stream": True}),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.headers.get("Content-Type") == "text/event-stream"
    body = b""
    while True:
        chunk = resp.read1(65536)
        if not chunk:
            break
        body += chunk
        if b"[DONE]" in body:
            break
    conn.close()
    assert body.count(b"data: ") == 5  # 4 chunks + [DONE]


def test_aiter_stream_async_consumer(serve_up):
    """serve.aiter_stream: the event-loop counterpart of iter_stream
    (what the asyncio proxy uses) yields the same chunks."""
    import asyncio

    @serve.deployment
    class Streamer:
        def __call__(self, request):
            def gen():
                for i in range(5):
                    yield {"i": i}
            return gen()

    handle = serve.run(Streamer.bind(), route_prefix="/as1")
    result = ray_tpu.get(handle.remote({}), timeout=60)
    assert serve.is_stream(result)

    async def consume():
        return [c async for c in serve.aiter_stream(result)]

    chunks = asyncio.run(consume())
    assert [c["i"] for c in chunks] == [0, 1, 2, 3, 4]


@pytest.mark.parametrize("reader", ["iter_stream", "aiter_stream"])
def test_waiting_chunks_restart_the_readers_timeout(serve_up, reader):
    """A generator that has nothing to send yet but says it is alive
    (`STREAM_WAITING_KEY`) outlasts the reader's timeout, and the reader
    passes none of those chunks on; one that goes silent for as long is
    ended by it."""
    import asyncio

    from ray_tpu.serve.streaming import STREAM_WAITING_KEY
    from ray_tpu.util.queue import Empty

    @serve.deployment
    class Slow:
        def __call__(self, request):
            def gen():
                for _ in range(4):
                    time.sleep(0.5)
                    if request["alive"]:
                        yield {STREAM_WAITING_KEY: True}
                yield {"i": 0}
            return gen()

    handle = serve.run(Slow.bind(), route_prefix="/wait-" + reader)

    def read(alive):
        result = ray_tpu.get(handle.remote({"alive": alive}), timeout=60)
        if reader == "iter_stream":
            return list(serve.iter_stream(result, timeout=1.0))

        async def consume():
            return [c async for c in serve.aiter_stream(result, 1.0)]
        return asyncio.run(consume())

    assert read(True) == [{"i": 0}]
    with pytest.raises((Empty, TimeoutError)):
        read(False)


def test_http_sse_streams_incrementally(serve_up):
    """Chunks arrive over HTTP while the generator is still producing —
    the first data line lands well before the slow tail finishes."""

    @serve.deployment
    class SlowStreamer:
        def __call__(self, request):
            def gen():
                for i in range(4):
                    yield {"i": i}
                    time.sleep(0.4)
            return gen()

    serve.run(SlowStreamer.bind(), route_prefix="/slow")
    proxy = serve.start_http_proxy()
    conn = http.client.HTTPConnection(proxy.host, proxy.port, timeout=30)
    t0 = time.perf_counter()
    conn.request("POST", "/slow", body=json.dumps({}),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.headers.get("Content-Type") == "text/event-stream"

    first_at = None
    items = []
    buf = b""
    while True:
        chunk = resp.read1(65536)
        if not chunk:
            break
        buf += chunk
        while b"\n\n" in buf:
            line, buf = buf.split(b"\n\n", 1)
            if not line.startswith(b"data: "):
                continue
            payload = line[len(b"data: "):]
            if payload == b"[DONE]":
                break
            if first_at is None:
                first_at = time.perf_counter() - t0
            items.append(json.loads(payload))
    conn.close()
    assert [c["i"] for c in items] == [0, 1, 2, 3]
    # 4 chunks at 0.4s spacing = ~1.6s total; the first arrived early.
    assert first_at is not None and first_at < 1.0, first_at


# -- the stream's channel where reader and replica share a process -----------


def test_a_stream_in_one_process_rides_no_actor(serve_up):
    """Under the in-process backend a replica pumps into a
    `LocalChannel`: the stream starts no queue actor."""
    from ray_tpu.serve.streaming import STREAM_KEY, LocalChannel
    from ray_tpu.util import queue as actor_queue

    @serve.deployment
    class Streamer:
        def __call__(self, request):
            return ({"i": i} for i in range(3))

    handle = serve.run(Streamer.bind(), route_prefix="/local")
    started = []
    real = actor_queue._QueueActor.options
    actor_queue._QueueActor.options = lambda **kw: started.append(kw) or real(**kw)
    try:
        result = ray_tpu.get(handle.remote({}), timeout=60)
        assert isinstance(result[STREAM_KEY], LocalChannel)
        assert list(serve.iter_stream(result)) == [{"i": 0}, {"i": 1},
                                                   {"i": 2}]
    finally:
        actor_queue._QueueActor.options = real
    assert started == []


def test_local_channel_is_bounded_ordered_and_lets_go():
    import asyncio
    import threading

    from ray_tpu.serve.streaming import LocalChannel
    from ray_tpu.util.queue import Empty, Full

    ch = LocalChannel(maxsize=2)
    ch.put(0, timeout=1.0)
    ch.put(1, timeout=1.0)
    t0 = time.perf_counter()
    with pytest.raises(Full):  # no room, and none comes
        ch.put(2, timeout=0.2)
    assert time.perf_counter() - t0 >= 0.2
    assert ch.get(timeout=1.0) == 0
    ch.put(2, timeout=1.0)  # room again
    assert [ch.get(timeout=1.0), ch.get(timeout=1.0)] == [1, 2]
    with pytest.raises(Empty):
        ch.get(timeout=0.1)

    async def read(n):
        got = [await ch.get_async(5.0) for _ in range(n)]
        return got, await ch.get_async(0.1)

    # A reader that waits on its event loop is woken by a put from
    # another thread, a chunk at a time and in order; past the last it
    # times out with (False, None).
    writer = threading.Thread(
        target=lambda: [(time.sleep(0.05), ch.put(i, timeout=5.0))
                        for i in range(5)])
    writer.start()
    got, after = asyncio.run(read(5))
    writer.join(timeout=10)
    assert not writer.is_alive()
    assert got == [(True, i) for i in range(5)] and after == (False, None)

    # A writer that waits for room is released when the reader leaves.
    ch.put("a", timeout=1.0)
    ch.put("b", timeout=1.0)
    outcome = []

    def blocked():
        try:
            ch.put("c", timeout=30.0)
            outcome.append("put")
        except Full:
            outcome.append("full")

    writer = threading.Thread(target=blocked)
    writer.start()
    time.sleep(0.1)
    ch.shutdown()
    writer.join(timeout=10)
    assert not writer.is_alive() and outcome == ["full"]
    with pytest.raises(Full):
        ch.put("d", timeout=1.0)


def test_an_abandoned_local_stream_closes_its_generator(serve_up):
    """The reader of a stream in one process leaves after a chunk: the
    replica's pump, waiting for room behind it, lets go and closes the
    deployment's generator."""
    closed = []

    @serve.deployment
    class Endless:
        def __call__(self, request):
            def gen():
                try:
                    i = 0
                    while True:
                        yield {"i": i}
                        i += 1
                finally:
                    closed.append(True)
            return gen()

    handle = serve.run(Endless.bind(), route_prefix="/endless")
    result = ray_tpu.get(handle.remote({}), timeout=60)
    reader = serve.iter_stream(result)
    assert next(reader) == {"i": 0}
    reader.close()  # the finally of iter_stream shuts the channel down
    deadline = time.time() + 10
    while not closed and time.time() < deadline:
        time.sleep(0.05)
    assert closed == [True]


def test_a_local_channel_that_leaves_the_process_becomes_a_queue(serve_up):
    """Pickled (a client process fetched the stream's handle), the
    channel hands its reader an actor-backed queue and forwards what
    the pump puts, to the end marker."""
    import cloudpickle  # what carries a result between processes

    from ray_tpu.serve.streaming import (STREAM_END_KEY, STREAM_KEY,
                                         LocalChannel)
    from ray_tpu.util.queue import Queue

    ch = LocalChannel(maxsize=4)
    ch.put({"i": 0}, timeout=1.0)
    remote = cloudpickle.loads(cloudpickle.dumps({STREAM_KEY: ch}))
    assert isinstance(remote[STREAM_KEY], Queue)
    ch.put({"i": 1}, timeout=1.0)
    ch.put({STREAM_END_KEY: True}, timeout=1.0)
    assert list(serve.iter_stream(remote, timeout=10.0)) == [{"i": 0},
                                                             {"i": 1}]


# -- the channel's hand-over lag, sampled (PR 36) -----------------------------


@pytest.fixture
def ring(monkeypatch):
    """An empty recorder whose snapshots hold the whole ring."""
    from ray_tpu._private import critical_path, flight_recorder
    from ray_tpu._private.config import ray_config

    critical_path.reset()
    flight_recorder.reset()
    monkeypatch.setattr(ray_config, "flight_ring_size", 2048)
    yield lambda: [s for s in flight_recorder.local_snapshot()["spans"]
                   if s["stage"] == "stream.channel"]
    critical_path.reset()
    flight_recorder.reset()


def test_local_channel_records_a_lag_of_20us_as_20us(ring, monkeypatch):
    """Every 16th chunk lies in the channel beside the time it was put,
    and its taker records how long it lay there as `stream.channel`: a
    thin record with no trace id and no floor (the request stages' 50 us
    would drop it). The reader gets the very object that was put."""
    from ray_tpu._private import critical_path
    from ray_tpu.serve.streaming import LAG_SAMPLE_EVERY, LocalChannel

    assert LAG_SAMPLE_EVERY == 16
    ticks = iter([100.0, 100.00002, 200.0, 200.5])
    monkeypatch.setattr(critical_path, "clock", lambda: next(ticks))
    ch = LocalChannel(maxsize=64)
    chunks = [{"token": i, "index": i} for i in range(33)]
    for c in chunks[:20]:
        ch.put(c, timeout=1.0)
    got = [ch.get(timeout=1.0) for _ in range(20)]
    assert all(a is b for a, b in zip(got, chunks))
    (rec,) = ring()  # the 16th chunk's, and no other's
    assert rec["dur_s"] == pytest.approx(2e-5, abs=1e-9)
    assert rec["dur_s"] < critical_path.MIN_SPAN_S
    assert rec["trace_id"] == "" and not rec.get("attrs")
    for c in chunks[20:]:
        ch.put(c, timeout=1.0)
    assert [ch.get(timeout=1.0) for _ in range(13)] == chunks[20:]
    assert [round(r["dur_s"], 6) for r in ring()] == [2e-5, 0.5]
    # A request's own stage keeps the floor.
    critical_path.record_stage("req-1", "llm.kv_lookup", 2e-5)
    critical_path.record_stage("", "stream.wake", 2e-5)
    from ray_tpu._private import flight_recorder
    names = [s["stage"] for s in flight_recorder.local_snapshot()["spans"]]
    assert "llm.kv_lookup" not in names and "stream.wake" in names


def test_a_served_stream_samples_its_channel_and_changes_no_chunk(
        serve_up, ring):
    """Through a replica's pump and `iter_stream`: the chunks arrive as
    the deployment yielded them, and of the 40 and the end marker two
    were timed (the 16th and the 32nd put)."""
    @serve.deployment
    class Streamer:
        def __call__(self, request):
            return ({"token": 7 * i, "index": i} for i in range(40))

    handle = serve.run(Streamer.bind(), route_prefix="/lag")
    result = ray_tpu.get(handle.remote({}), timeout=60)
    assert list(serve.iter_stream(result)) == [
        {"token": 7 * i, "index": i} for i in range(40)]
    lags = ring()
    assert len(lags) == 2
    assert all(r["trace_id"] == "" and 0 <= r["dur_s"] < 5.0 for r in lags)
