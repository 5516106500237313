"""Batch iterators with background prefetch and host→device staging.

The TPU ingest hot path (SURVEY.md §5 "object/data plane": *add an
HBM-aware path*): blocks stream out of the object store on a prefetch
thread, get re-batched to a fixed batch size (static shapes for XLA), and
`jax.device_put` runs one batch ahead of the consumer so the transfer
overlaps the train step. Double-buffering is enough on TPU-VMs because
device_put is async — the consumer only blocks if compute outruns ingest.

The path is timed by `critical_path` spans (in the flight ring, and in
a profiler's trace while one is taken): `data.batch_wait` is the
consumer's wait for the next prefetched batch, `data.block_fetch` and
`data.to_device` the two halves of the device path's producer.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Dict, Iterator, Optional

import numpy as np

import ray_tpu
from ray_tpu._private import critical_path
from ray_tpu.data.block import BlockAccessor

_SENTINEL = object()


def _rebatch(block_iter: Iterator[Any], batch_size: Optional[int],
             formatter, drop_last: bool) -> Iterator[Any]:
    """Accumulate blocks, emit fixed-size batches."""
    if batch_size is None:
        for block in block_iter:
            yield formatter(BlockAccessor(block))
        return
    buf = []
    buf_rows = 0
    for block in block_iter:
        buf.append(block)
        buf_rows += BlockAccessor(block).num_rows()
        while buf_rows >= batch_size:
            merged = BlockAccessor.concat(buf)
            acc = BlockAccessor(merged)
            yield formatter(BlockAccessor(acc.slice(0, batch_size)))
            rest = acc.slice(batch_size, acc.num_rows())
            buf = [rest]
            buf_rows = BlockAccessor(rest).num_rows()
    if buf_rows > 0 and not drop_last:
        merged = BlockAccessor.concat(buf)
        yield formatter(BlockAccessor(merged))


def _prefetch_iter(it: Iterator[Any], depth: int) -> Iterator[Any]:
    """Run `it` on a background thread with a bounded queue; the
    consumer's waits for it are `data.batch_wait` spans."""
    q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
    err: list = []

    def worker():
        try:
            for item in it:
                q.put(item)
        except BaseException as e:  # noqa: BLE001 - propagate to consumer
            err.append(e)
        finally:
            q.put(_SENTINEL)

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    while True:
        with critical_path.span("data.batch_wait"):
            item = q.get()
        if item is _SENTINEL:
            if err:
                raise err[0]
            return
        yield item


def iter_batches_from_refs(ref_iter, *, batch_size: Optional[int],
                           batch_format: str = "default",
                           drop_last: bool = False,
                           prefetch: int = 1) -> Iterator[Any]:
    from ray_tpu.data.dataset import _batch_formatter

    formatter = _batch_formatter(batch_format)

    def blocks():
        for ref in ref_iter:
            yield ray_tpu.get(ref)

    it = _rebatch(blocks(), batch_size, formatter, drop_last)
    if prefetch > 0:
        it = _prefetch_iter(it, prefetch)
    return it


def iter_device_batches(ref_iter, *, batch_size: Optional[int],
                        dtypes: Optional[Dict[str, Any]] = None,
                        device=None, sharding=None,
                        prefetch: int = 2,
                        drop_last: bool = True) -> Iterator[Any]:
    """Numpy batches → jax arrays on device/sharding, double-buffered."""
    import jax

    target = sharding if sharding is not None else device

    def to_device(batch: Dict[str, np.ndarray]):
        out = {}
        with critical_path.span("data.to_device"):
            for k, v in batch.items():
                if dtypes and k in dtypes:
                    v = v.astype(dtypes[k])
                out[k] = jax.device_put(v, target) if target is not None \
                    else jax.device_put(v)
        return out

    def blocks():
        for ref in ref_iter:
            with critical_path.span("data.block_fetch"):
                block = ray_tpu.get(ref)
            yield block

    host_iter = _rebatch(blocks(), batch_size,
                         lambda acc: acc.to_numpy(), drop_last)
    staged = (to_device(b) for b in host_iter)
    # The prefetch queue holds device arrays whose transfers are already
    # enqueued — consuming one batch ahead hides H2D latency.
    return _prefetch_iter(staged, prefetch)
