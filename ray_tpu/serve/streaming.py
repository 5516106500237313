"""Streaming responses: incremental chunks from a deployment.

A deployment method that returns a *generator* streams automatically: the
replica pumps chunks through a bounded queue (`replica._start_stream`),
the HTTP proxy renders them as server-sent-events chunks, and Python
callers unwrap with ``serve.iter_stream``. Reference role: ASGI
StreamingResponse through the uvicorn proxy
(`serve/_private/http_proxy.py:425`); the transport here is a queue
(`channel`: an actor-backed one on the object plane, which any process
can read, or, where the reader shares the replica's process, one in
that process), the contract — incremental chunks over one request,
first token before the last is computed — is the same.
"""

from __future__ import annotations

import asyncio
import collections
import threading
import time
from typing import Any, Iterator

from ray_tpu._private import critical_path

STREAM_KEY = "__ray_tpu_stream__"
STREAM_END_KEY = "__ray_tpu_stream_end__"
# A chunk a deployment's generator may yield while it has nothing to
# send yet and is not hung (`LLMDeployment`: the request waits for a
# slot and the engine is decoding for others). The readers below start
# their wait for a chunk anew and pass nothing on: their timeout stays
# the contract for a deployment that has gone silent.
STREAM_WAITING_KEY = "__ray_tpu_stream_waiting__"
# How long such a generator lets pass between two of them: a third of
# the readers' default timeout.
WAITING_BEAT_S = 20.0
# A stream's hand-over lag is timed for one chunk in this many, by the
# chunk's index: `stream.wake` (the engine's queue to the replica's
# pump, `serve/llm.py`) and `stream.channel` (`LocalChannel`, below).
LAG_SAMPLE_EVERY = 16


class LocalChannel:
    """The stream's queue where its reader shares the replica's process
    (the in-process backend: proxy, router and replica are threads of
    one interpreter): `util.queue.Queue`'s part that a stream uses,
    with no actor behind it. A chunk then costs its reader one wake-up
    and not two actor tasks, which under one interpreter lock were
    what a front of 64 streams passed tokens at (PERF.md, PR 35).
    Bounded like the actor's queue: `put` waits for room, `timeout`
    seconds at the most, and a channel that was shut down takes
    nothing more, so a pump whose reader left lets go.

    Every `LAG_SAMPLE_EVERY`-th chunk lies in the deque beside the time
    it was put, and its taker records `stream.channel`, the time it lay
    there: a thin record with no trace id (`critical_path`)."""

    def __init__(self, maxsize: int):
        self._items = collections.deque()  # (chunk, its stamp or 0.0)
        self._puts = 0
        self._maxsize = maxsize
        self._cond = threading.Condition()
        self._waiter = None  # (loop, future) of a reader in get_async
        self._closed = False

    def put(self, item, timeout: float) -> None:
        from ray_tpu.util.queue import Full

        deadline = time.monotonic() + timeout
        with self._cond:
            while len(self._items) >= self._maxsize and not self._closed:
                left = deadline - time.monotonic()
                if left <= 0 or not self._cond.wait(left):
                    raise Full()
            if self._closed:
                raise Full()
            self._puts += 1
            self._items.append((
                item, 0.0 if self._puts % LAG_SAMPLE_EVERY
                else critical_path.clock()))
            waiter, self._waiter = self._waiter, None
            self._cond.notify_all()
        if waiter is not None:
            loop, future = waiter
            try:
                loop.call_soon_threadsafe(self._wake, future)
            except RuntimeError:  # the reader's loop is closed: it left
                pass

    @staticmethod
    def _wake(future):
        if not future.done():
            future.set_result(None)

    def _take(self):
        item, stamp = self._items.popleft()
        self._cond.notify_all()
        if stamp:
            critical_path.record_stage(None, "stream.channel",
                                       critical_path.clock() - stamp)
        return item

    def get(self, timeout: float):
        from ray_tpu.util.queue import Empty

        with self._cond:
            if not self._cond.wait_for(lambda: self._items, timeout):
                raise Empty()
            return self._take()

    def _take_or_wait(self, loop):
        """(True, the next item), or (False, a future of `loop` that
        the next `put` resolves). The lock is held for this look alone,
        never across a wait, so the loop's thread does not block on it."""
        with self._cond:
            if self._items:
                return True, self._take()
            future = loop.create_future()
            self._waiter = (loop, future)
            return False, future

    def _forget(self, waiter) -> None:
        with self._cond:
            if self._waiter == waiter:
                self._waiter = None

    async def get_async(self, timeout: float):
        """(True, the next item), or (False, None) after `timeout`
        seconds without one; one reader."""
        loop = asyncio.get_running_loop()
        deadline = time.monotonic() + timeout
        while True:
            ready, got = self._take_or_wait(loop)
            if ready:
                return True, got
            try:
                await asyncio.wait_for(got, deadline - time.monotonic())
            except asyncio.TimeoutError:
                return False, None
            finally:  # a reader that left, timed out or not, waits no more
                self._forget((loop, got))

    def shutdown(self, block: bool = True) -> None:
        with self._cond:
            self._closed = True
            self._items.clear()
            self._cond.notify_all()

    def __reduce__(self):
        """Pickled, the result is leaving the process after all (a
        client process fetched it): its reader gets an actor-backed
        queue, and a thread moves into it what the pump puts here."""
        from ray_tpu.util.queue import Queue

        remote = Queue(maxsize=self._maxsize)
        threading.Thread(target=self._forward, args=(remote,), daemon=True,
                         name="serve-stream-forward").start()
        return _unpickled, (remote,)

    def _forward(self, remote) -> None:
        try:
            while True:
                item = self.get(timeout=60.0)
                remote.put(item, timeout=60.0)
                if isinstance(item, dict) and item.get(STREAM_END_KEY):
                    return
        except Exception:  # noqa: BLE001 - the reader or the pump is gone
            self.shutdown()


def _unpickled(queue):
    return queue


def channel(maxsize: int):
    """What a replica pumps a stream's chunks into: `LocalChannel`
    under the in-process backend, where the readers of this process's
    results are its own threads; an actor-backed `util.queue.Queue`,
    which any process can read, on a cluster (its driver's backend
    routes to nodes, and a node's own in-process runtime answers to
    readers elsewhere)."""
    from ray_tpu._private.local_backend import LocalBackend
    from ray_tpu._private.worker import global_worker
    from ray_tpu.util.queue import Queue

    worker = global_worker()
    if isinstance(worker.backend, LocalBackend) \
            and not getattr(worker, "is_cluster_node", False):
        return LocalChannel(maxsize)
    return Queue(maxsize=maxsize)


def is_stream(result: Any) -> bool:
    return isinstance(result, dict) and STREAM_KEY in result


def _is_waiting(item: Any) -> bool:
    return isinstance(item, dict) and STREAM_WAITING_KEY in item


def iter_stream(result: Any, timeout: float = 60.0) -> Iterator[Any]:
    """Iterate a streaming deployment response (pass-through for
    non-streaming results: yields the single value). The backing queue
    actor is torn down when the stream ends, errors, or the consumer
    abandons the iterator — the replica-side pump then unblocks on its
    put timeout and closes the generator."""
    if not is_stream(result):
        yield result
        return
    queue = result[STREAM_KEY]
    try:
        while True:
            item = queue.get(timeout=timeout)
            if _is_waiting(item):
                continue
            if isinstance(item, dict) and item.get(STREAM_END_KEY):
                error = item.get("error")
                if error:
                    raise RuntimeError(
                        f"stream failed in deployment: {error}")
                return
            yield item
    finally:
        try:
            queue.shutdown()
        except Exception:
            pass


async def aiter_stream(result: Any, timeout: float = 60.0):
    """Async counterpart of :func:`iter_stream` for event-loop consumers
    (the asyncio HTTP proxy): each chunk is awaited through the queue
    actor's ObjectRef, so a slow generator never blocks the loop other
    requests are running on. Same contract — pass-through for
    non-streaming results, queue torn down on exit."""
    if not is_stream(result):
        yield result
        return
    queue = result[STREAM_KEY]
    try:
        while True:
            ok, item = await queue.get_async(timeout)
            if not ok:
                raise TimeoutError(
                    f"no stream chunk within {timeout}s")
            if _is_waiting(item):
                continue
            if isinstance(item, dict) and item.get(STREAM_END_KEY):
                error = item.get("error")
                if error:
                    raise RuntimeError(
                        f"stream failed in deployment: {error}")
                return
            yield item
    finally:
        # Non-blocking teardown: the kill is a synchronous control
        # RPC, and this finally runs ON the proxy's event loop — the
        # blocking form would stall every other in-flight request
        # until the round-trip finished.
        try:
            queue.shutdown(block=False)
        except Exception:
            pass
