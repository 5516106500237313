"""Leak sanitizer: per-test resource accounting with teardown diffing.

The dynamic half of raylint R4 (resource-lifecycle): R4 proves a
teardown *exists*; this proves it *ran*. Before each test the sanitizer
snapshots the process's resource census — live threads, open file
descriptors (sockets, sqlite/database files, pipes — read straight
from ``/proc/self/fd``), registered actors, and ``memory_store``
entries — and diffs it after every fixture finalizer has completed.
Anything the test created and nobody released is a finding.

Thread findings get a grace window first (daemon threads legitimately
take a few scheduler ticks to observe a shutdown flag); fd findings run
after the grace so a retiring thread's socket close counts. New fds
belonging to the process-lifetime ``RpcClient`` connection pool are
attributed by name so the default policy can suppress them with a
justification instead of the report showing anonymous socket inodes.

Store/actor diffs only fire when the *same* store/backend instance
survived the test (a test that inits and shuts down its own runtime
has nothing to leak into).
"""

from __future__ import annotations

import gc
import os
import threading
import time
from typing import Dict, List, Optional, Set, Tuple

from tools.raysan.core import Finding, Sanitizer

_FD_DIR = "/proc/self/fd"


def scan_fds() -> Dict[int, str]:
    """fd -> readlink target ("socket:[123]", "/path/to/file", ...).
    fds that vanish mid-scan (the scan's own directory handle, a racing
    close) are skipped."""
    out: Dict[int, str] = {}
    try:
        names = os.listdir(_FD_DIR)
    except OSError:
        return out
    for name in names:
        try:
            out[int(name)] = os.readlink(os.path.join(_FD_DIR, name))
        except (OSError, ValueError):
            continue
    return out


def _classify(target: str) -> str:
    if target.startswith("socket:"):
        return "socket"
    if target.endswith((".db", ".sqlite", ".sqlite3")) \
            or "gcs" in target:
        return "sqlite/db file"
    if target.startswith(("pipe:", "anon_inode:")):
        return "pipe/eventfd"
    return "file"


def _target_name(thread) -> str:
    """A thread's target by module and qualified name: a function's
    `repr` carries its address, which differs by run, and the report
    this lands in is committed."""
    target = getattr(thread, "_target", None)
    name = getattr(target, "__qualname__", None)
    if name is None:
        return type(target).__name__
    return f"{getattr(target, '__module__', '?')}.{name}"


def _pooled_rpc_filenos() -> Dict[int, str]:
    """fileno -> label for sockets owned by the process-lifetime
    RpcClient pool (kept across tests by design)."""
    out: Dict[int, str] = {}
    try:
        from ray_tpu._private.rpc import RpcClient
    except Exception:
        return out
    with RpcClient._pools_lock:
        clients = list(RpcClient._pools.items())
    for addr, client in clients:
        sock = client._sock
        if sock is not None:
            try:
                out[sock.fileno()] = f"pooled RpcClient to {addr}"
            except OSError:
                continue
    return out


def _store_census() -> Optional[Tuple[int, int]]:
    """(id(store), entry count) for the live worker's memory store."""
    try:
        from ray_tpu._private.worker import global_worker_or_none
    except Exception:
        return None
    w = global_worker_or_none()
    store = getattr(w, "memory_store", None) if w is not None else None
    if store is None:
        return None
    return id(store), store.num_objects()


def _actor_census() -> Optional[Tuple[int, Set]]:
    """(id(backend), live actor ids) for the live worker's local
    backend."""
    try:
        from ray_tpu._private.worker import global_worker_or_none
    except Exception:
        return None
    w = global_worker_or_none()
    backend = getattr(w, "backend", None) if w is not None else None
    backend = getattr(backend, "local_backend", backend)
    actors = getattr(backend, "_actors", None)
    if actors is None:
        return None
    return id(backend), set(actors.keys())


class LeakSanitizer(Sanitizer):
    name = "leaks"

    def __init__(self, grace_s: float = 1.5):
        self.grace_s = grace_s
        self._threads: Dict[int, str] = {}
        self._fds: Dict[int, str] = {}
        self._store: Optional[Tuple[int, int]] = None
        self._actors: Optional[Tuple[int, Set]] = None

    def before_test(self, test_id: str) -> None:
        self._threads = {t.ident: t.name
                         for t in threading.enumerate() if t.is_alive()}
        self._fds = scan_fds()
        self._store = _store_census()
        self._actors = _actor_census()

    def after_test(self, test_id: str) -> List[Finding]:
        findings: List[Finding] = []
        # Failed tests keep their frames (and every local ref in them)
        # alive in the traceback; collect cycles so only genuinely
        # reachable resources count.
        gc.collect()

        # -- threads, with a grace window --------------------------------
        deadline = time.monotonic() + self.grace_s
        new_threads = self._new_threads()
        while new_threads and time.monotonic() < deadline:
            time.sleep(0.02)
            new_threads = self._new_threads()
        for t in new_threads:
            findings.append(Finding(
                sanitizer=self.name, test=test_id,
                message=f"thread leaked: {t.name!r} "
                        f"(daemon={t.daemon}) still alive "
                        f"{self.grace_s:.1f}s after teardown",
                detail=f"target={_target_name(t)}"))

        # -- fds (after the thread grace, so closes-in-progress land) ----
        pooled = _pooled_rpc_filenos()
        for fd, target in sorted(scan_fds().items()):
            if self._fds.get(fd) == target:
                continue
            label = pooled.get(fd)
            kind = _classify(target)
            findings.append(Finding(
                sanitizer=self.name, test=test_id,
                message=f"fd leaked: {label or kind} fd={fd} "
                        f"({target}) open after teardown"))

        # -- actors ------------------------------------------------------
        after_actors = _actor_census()
        if self._actors is not None and after_actors is not None \
                and after_actors[0] == self._actors[0]:
            for actor_id in sorted(after_actors[1] - self._actors[1],
                                   key=repr):
                findings.append(Finding(
                    sanitizer=self.name, test=test_id,
                    message=f"actor leaked: {actor_id!r} still "
                            f"registered after teardown"))

        # -- memory_store entries ---------------------------------------
        after_store = _store_census()
        if self._store is not None and after_store is not None \
                and after_store[0] == self._store[0] \
                and after_store[1] > self._store[1]:
            findings.append(Finding(
                sanitizer=self.name, test=test_id,
                message=f"memory_store leaked "
                        f"{after_store[1] - self._store[1]} entry(ies) "
                        f"({self._store[1]} -> {after_store[1]}) "
                        f"after teardown"))
        return findings

    def _new_threads(self) -> List[threading.Thread]:
        return [t for t in threading.enumerate()
                if t.is_alive() and t.ident not in self._threads
                and t is not threading.current_thread()]
