"""raymc built-in scenarios: the checked protocol property catalog.

Each scenario drives REAL product objects; fakes are limited to the
environment around them (a replica that records dispatches, a
controller handle that dies on demand) — the same stand-ins the
concurrency regression tests use. Properties:

================== ==========================================================
scenario           property
================== ==========================================================
router_cap         a replica never holds more outstanding dispatches than
                   ``max_concurrent_queries`` (reserved-slot handoff)
pipelined_close    a clean ``PipelinedClient.close(flush_timeout=...)``
                   never orphan-sweeps an about-to-be-acked request
gcs_durability     sqlite group commit: acked (flushed) writes survive a
                   crash at either commit boundary; writes no COMMIT ever
                   covered never resurrect after restart
exactly_once       a submit frame resubmitted under its rid after a
                   connection death executes exactly once (server dedupe)
longpoll_recovery  long-poll membership converges after a controller
                   kill/restart with listeners parked mid-poll
================== ==========================================================
"""

from __future__ import annotations

import os
import socket
import tempfile
import threading
import time
from types import SimpleNamespace
from typing import List, Tuple

from ray_tpu._private import sanitize_hooks

from tools.raymc.props import Invariant, Liveness
from tools.raymc.scenario import Scenario


# -- shared fakes ------------------------------------------------------------


class _FakeMethod:
    def __init__(self, fn):
        self._fn = fn

    def remote(self, *args, **kwargs):
        return self._fn(*args, **kwargs)


class _Replica:
    """Hashable (router keys replicas into dicts) dispatch recorder."""

    def __init__(self, fn):
        self.handle_request = _FakeMethod(fn)


class _FakeController:
    """Enough controller surface for a Router: metrics reports are
    swallowed, long-poll listens fail fast (no membership churn in the
    scenario — the replica set is pinned at setup)."""

    def __init__(self):
        self.listen = _FakeMethod(self._listen)
        self.record_handle_metrics = _FakeMethod(lambda dep, total: None)

    def _listen(self, *a, **k):
        raise RuntimeError("no controller in this scenario")


def _pending_ref():
    """An ObjectRef that never resolves, so dispatched requests stay
    in-flight for the whole execution and an oversubscription cannot
    self-heal before the invariant looks."""
    from ray_tpu._private.ids import ObjectID
    from ray_tpu.object_ref import ObjectRef

    return ObjectRef(ObjectID.from_random(), _register=False)


# -- router reserved-slot cap ------------------------------------------------


class RouterCapScenario(Scenario):
    name = "router_cap"
    description = ("concurrent dispatchers against a cap-1 replica: "
                   "outstanding dispatches never exceed the cap")
    points = ("router.handoff", "router.buggy_gap")
    max_steps = 16
    needs_ray = True

    def __init__(self, dispatchers: int = 2, cap: int = 1):
        self.n_dispatchers = dispatchers
        self.cap = cap

    def setup(self) -> None:
        from ray_tpu.serve._private.router import Router

        self.dispatched = 0
        self._dlock = threading.Lock()

        def handle(method, args, kwargs):
            with self._dlock:
                self.dispatched += 1
            return _pending_ref()

        self.replica = _Replica(handle)
        self.router = Router(_FakeController(), "dep",
                             max_concurrent_queries=self.cap)
        self.router._update_replicas([self.replica])
        self.results: List = []

    def actions(self):
        def dispatch():
            self.results.append(
                self.router.try_assign_request("__call__", (), {}))
        return [(f"dispatch-{chr(ord('a') + i)}", dispatch)
                for i in range(self.n_dispatchers)]

    def invariants(self):
        return [Invariant(
            "router-cap",
            lambda s: (s.dispatched <= s.cap
                       or f"{s.dispatched} requests dispatched to a "
                          f"cap-{s.cap} replica"),
            description="per-replica in-flight cap holds mid-handoff")]

    def teardown(self) -> None:
        self.router.shutdown()


# -- pipelined close vs reader sweep ----------------------------------------


class PipelinedCloseScenario(Scenario):
    name = "pipelined_close"
    description = ("clean close with an in-flight, about-to-be-acked "
                   "request: the reader must never orphan-sweep it")
    points = ("rpc.pipeline.reader_edge", "rpc.pipeline.reply_handled",
              "rpc.pipeline.closed_set")
    max_steps = 24
    block_grace_s = 0.04

    def setup(self) -> None:
        from ray_tpu._private.rpc import PipelinedClient, RpcServer

        self.release = threading.Event()
        self.errors: List[Tuple] = []

        def fast(**kwargs):
            return "ok"

        def slow(**kwargs):
            self.release.wait(5.0)
            return "ok"

        self.server = RpcServer({"fast": fast, "slow": slow})
        self.client = PipelinedClient(
            self.server.address,
            on_error=lambda tag, msg, rid, lost: self.errors.append(
                (tag, lost)))

    def actions(self):
        def driver():
            self.client.send("fast", tag="req1")
            self.client.flush(3.0)
            self.client.send("slow", tag="req2")
            self.release.set()  # the peer acks while close() flushes
            self.client.close(flush_timeout=3.0)
        return [("driver", driver)]

    def invariants(self):
        return [Invariant(
            "close-no-orphan",
            lambda s: (not s.errors
                       or f"clean close produced orphan errors: "
                          f"{s.errors}"),
            description="close(flush_timeout) never sweeps an "
                        "about-to-be-acked request into the orphan "
                        "path")]

    def liveness(self):
        return [Liveness(
            "close-acks-all",
            lambda s: s.client._acked == 2, timeout_s=3.0,
            description="both requests acknowledged by close")]

    def teardown(self) -> None:
        self.release.set()
        try:
            self.client.close()
        except Exception:
            pass
        self.server.shutdown()


# -- sqlite group-commit durability under crash ------------------------------


class GroupCommitDurabilityScenario(Scenario):
    name = "gcs_durability"
    description = ("writers vs group commit vs injected crash: acked "
                   "writes survive, uncommitted writes never resurrect")
    points = ("gcs.put",)
    crash_points = ("gcs.commit.before", "gcs.commit.after")
    crash_budget = 1
    max_steps = 24
    # Writers block on the store lock whenever the committer is parked
    # inside the commit window — a certain, immediate block, so a
    # short grace keeps per-step cost down.
    block_grace_s = 0.02

    def __init__(self, writers: int = 1):
        # One writer is the exhaustive small scope (the property is
        # about put-vs-commit-vs-crash ordering, which one writer
        # fully exercises across the two commit windows); more writers
        # widen coverage but grow the space factorially — use bounded
        # budgets there.
        self.n_writers = writers

    def setup(self) -> None:
        from ray_tpu._private.gcs_storage import SqliteStoreClient

        fd, self.path = tempfile.mkstemp(prefix="raymc-gcs-",
                                         suffix=".db")
        os.close(fd)
        os.unlink(self.path)
        # Group-commit mode WITHOUT the background flusher: construct
        # synchronous (interval 0 starts no thread), then widen the
        # interval so puts defer their COMMIT to the scenario's
        # explicit committer action — the checker owns every commit
        # boundary instead of racing a timer.
        self.store = SqliteStoreClient(self.path, commit_interval_s=0)
        self.store._interval = 3600.0
        self.accepted: List[bytes] = []
        self.acked: set = set()
        self.durable: set = set()
        self.present: set = set()
        self.crashed: str = ""

    def actions(self):
        def writer(key):
            def body():
                try:
                    self.store.put("t", key, b"v")
                except Exception:
                    return  # store died under us: the write never took
                self.accepted.append(key)
            return body

        def committer():
            # TWO commit windows: a crash inside the first flush can
            # only lose never-acked writes (vacuous for durability —
            # flush() hasn't returned, nothing was promised). The
            # placement that bites is a death AFTER a completed, acked
            # flush: the second window provides it, with writers free
            # to interleave around both.
            for window in range(2):
                snap = list(self.accepted)
                self.store.flush()
                self.acked.update(snap)
                if window == 0:
                    # Sync gate OUTSIDE the store lock: without it the
                    # committer can barge straight from window 1 into
                    # window 2's lock hold, and whether a lock-blocked
                    # writer squeezes through between the windows is OS
                    # lock-queue luck — exactly the sub-yield-point
                    # nondeterminism that makes explorations diverge.
                    # Parked here, the lock handoff is a decision.
                    sanitize_hooks.sched_point("mc.sync.commit_gap")

        acts = [(f"writer-{chr(ord('a') + i)}",
                 writer(b"k%d" % i)) for i in range(self.n_writers)]
        acts.append(("committer", committer))
        return acts

    def independent(self, a, b) -> bool:
        # Scenario-specific structure that makes the two-writer config
        # tractable to exhaust (argued from the code, not vibes):
        # - two writers' puts commute: each writes its OWN key;
        # - a writer's start transition is PURE — the segment between
        #   its start gate and its put gate executes nothing (the
        #   gcs.put crossing is the first statement of put()) — so it
        #   commutes with every other thread's transition. The
        #   committer's start is NOT pure (it snapshots `accepted`)
        #   and keeps full conflicts.
        if a[0] == b[0] or a[3] or b[3]:
            return False
        if a[1] == "gcs.put" and b[1] == "gcs.put":
            return True
        if a[1].startswith("mc.start.writer") \
                or b[1].startswith("mc.start.writer"):
            return True
        return super().independent(a, b)

    def on_point(self, point: str, role: str) -> None:
        if point == "gcs.commit.after":
            # Crossed INSIDE the store lock right after COMMIT: exactly
            # the accepted-so-far writes are durable now (a writer
            # mid-put is blocked on the same lock and not yet in
            # `accepted`).
            self.durable.update(self.accepted)

    def on_crash(self, point: str) -> None:
        from ray_tpu._private.gcs_storage import SqliteStoreClient

        try:
            # Process death: the connection drops with the pending
            # transaction uncommitted (sqlite rolls it back). UNDER the
            # store lock: closing a sqlite connection while another
            # thread is inside conn.execute() on it is a C-level
            # use-after-free (segfaulted under full-suite load when a
            # lock-blocked writer woke the instant the crashing flush
            # released the lock). The lock sequences the close after
            # any in-flight statement; later puts hit a clean
            # ProgrammingError on the closed connection, which the
            # writer action treats as "the store died under us".
            with self.store._lock:
                self.store._conn.close()
        except Exception:
            pass
        survivor = SqliteStoreClient(self.path, commit_interval_s=0)
        try:
            self.present = {k for k, _ in survivor.get_all("t")}
        finally:
            survivor.close()
        self.crashed = point  # LAST: invariants key off it

    def invariants(self):
        def durability(s):
            if not s.crashed:
                return True
            lost = s.acked - s.present
            return (not lost
                    or f"acked writes lost across crash at "
                       f"{s.crashed}: {sorted(lost)}")

        def no_resurrection(s):
            if not s.crashed:
                return True
            ghosts = s.present - s.durable
            return (not ghosts
                    or f"uncommitted writes resurrected after crash "
                       f"at {s.crashed}: {sorted(ghosts)}")

        return [
            Invariant("gcs-durability", durability,
                      description="flushed writes survive crash"),
            Invariant("gcs-no-resurrection", no_resurrection,
                      description="unflushed writes stay dead"),
        ]

    def teardown(self) -> None:
        try:
            if not self.crashed:
                self.store.close()
        except Exception:
            pass
        for suffix in ("", "-wal", "-shm"):
            try:
                os.unlink(self.path + suffix)
            except OSError:
                pass


# -- multi-process head: cross-shard routing + per-shard commit windows ------


class CrossShardScenario(Scenario):
    name = "cross_shard"
    description = ("two head shards, writers on both key ranges, a "
                   "committer flushing per-shard windows, one shard "
                   "crashing at a commit boundary: rows never land on "
                   "a foreign shard, a cap-1 lease key is never "
                   "double-granted (even from the other shard's "
                   "writer), and a neighbor's acked rows survive the "
                   "victim's crash")
    # Route crossings only: the apply body runs under the shard lock
    # right after its route decision, so route-level interleavings
    # already cover every observable order while keeping the space
    # drainable inside the tier-1 leg.
    points = ("headshard.route",)
    # Per-shard group commit reuses the store's commit crossings: a
    # crash there is one shard PROCESS dying mid-window, the other
    # shard's window untouched.
    crash_points = ("gcs.commit.before", "gcs.commit.after")
    crash_budget = 1
    max_steps = 30
    # Measured exhaustive sweep: 463 schedules (~5.5s standalone); the
    # floor leaves headroom so the tier-1 `exhausted` claim stays
    # honest.
    max_schedules = 2000
    block_grace_s = 0.02

    def setup(self) -> None:
        from ray_tpu._private.gcs_storage import SqliteStoreClient
        from ray_tpu._private.head_shards import (HeadShardState,
                                                  InprocRouter, shard_of)

        self._store_cls = SqliteStoreClient
        self.paths = []
        states = []
        for i in range(2):
            fd, path = tempfile.mkstemp(prefix=f"raymc-shard{i}-",
                                        suffix=".db")
            os.close(fd)
            os.unlink(path)
            self.paths.append(path)
            state = HeadShardState(i, 2, db_path=path,
                                   commit_interval_s=0)
            # Group-commit mode without the background flusher: the
            # committer ACTION owns every commit boundary (same trick
            # as gcs_durability).
            state.store._interval = 3600.0
            states.append(state)
        self.router = InprocRouter(2, states=states)

        def key_for(shard: int, prefix: bytes) -> bytes:
            i = 0
            while True:
                k = prefix + b"-%d" % i
                if shard_of(k, 2) == shard:
                    return k
                i += 1

        self.obj_key = {i: key_for(i, b"obj") for i in range(2)}
        self.lease_key = key_for(0, b"lease")  # shard 0 owns the cap
        self.accepted = {0: [], 1: []}
        self.acked = {0: set(), 1: set()}
        self.durable = {0: set(), 1: set()}
        self.present = {0: set(), 1: set()}
        self.grant_results: List[bool] = []
        self.flushing = -1
        self.crashed = ""
        self.victim = -1
        # Both directory rows are seeded here, NOT concurrently: the
        # put-vs-commit interleaving is gcs_durability's (per-store)
        # property, already exhausted there — re-exploring it per shard
        # multiplies this space past the tier-1 budget. What THIS
        # scenario owns is the cross-shard surface: the routed cap-1
        # grant race and a crash placement inside either shard's commit
        # window while the neighbor's rows sit acked or open.
        for i in range(2):
            self.router.put("objects", self.obj_key[i],
                            ("10.0.0.%d" % i, i))
            self.accepted[i].append(self.obj_key[i])
        # The NEIGHBOR's window commits deterministically up front: its
        # row is acked before any explored crash, which is exactly the
        # precondition the neighbor-durability invariant needs. Only
        # the victim shard's window stays open for the explorer.
        self.flushing = 1
        self.router.shards[1].store.flush()
        self.acked[1].update(self.accepted[1])
        self.flushing = -1

    def actions(self):
        def grantor(node):
            # Writers on BOTH shards' ranges contend for the SAME cap-1
            # key: writer-b's attempt must cross shards to shard 0's
            # single authority — the admission decision the tentpole
            # moved OUT of the coordinator's memory.
            def body():
                try:
                    ok = self.router.lease_register(self.lease_key,
                                                    node, cap=1)
                except Exception:
                    ok = False
                self.grant_results.append(ok)
            return body

        def committer():
            # The victim shard's group-commit window: a crash at either
            # commit crossing is shard 0's process dying mid-window —
            # shard 1's acked rows (committed in setup) must survive it.
            self.flushing = 0
            snap = list(self.accepted[0])
            try:
                self.router.shards[0].store.flush()
            except Exception:
                return  # the shard crashed mid-window
            self.acked[0].update(snap)

        return [("writer-a", grantor("node-a")),
                ("writer-b", grantor("node-b")),
                ("committer", committer)]

    def independent(self, a, b) -> bool:
        if a[0] == b[0] or a[3] or b[3]:
            return False
        # A writer's start transition is PURE — the segment before its
        # first route crossing executes nothing (router.put's crossing
        # is its first statement), so it commutes with every other
        # thread (same argument as gcs_durability's writers). Route
        # crossings themselves keep full conflicts: the two writers
        # share shard 0's lease authority.
        if a[1].startswith("mc.start.writer") \
                or b[1].startswith("mc.start.writer"):
            return True
        return super().independent(a, b)

    def on_point(self, point: str, role: str) -> None:
        if point == "gcs.commit.after" and self.flushing >= 0:
            self.durable[self.flushing] = set(
                self.accepted[self.flushing])

    def on_crash(self, point: str) -> None:
        victim = self.flushing if self.flushing >= 0 else 0
        store = self.router.shards[victim].store
        try:
            # One shard process dies: its connection drops with the
            # window open (sqlite rolls back). Under the store lock —
            # same use-after-free discipline as gcs_durability.
            with store._lock:
                store._conn.close()
        except Exception:
            pass
        # Read BOTH shards' dbs through fresh connections: what a
        # restarted shard (and the untouched neighbor) would reload.
        for i in range(2):
            survivor = self._store_cls(self.paths[i],
                                       commit_interval_s=0)
            try:
                self.present[i] = {k for k, _ in
                                   survivor.get_all("objects")}
            finally:
                survivor.close()
        self.victim = victim
        self.crashed = point  # LAST: invariants key off it

    def invariants(self):
        def ownership(s):
            for state in s.router.shards:
                for table in ("objects", "lease"):
                    for key in state.tables[table]:
                        if not state.owns(key):
                            return (f"shard {state.index} holds "
                                    f"foreign key {key!r} in {table}")
            return True

        def single_grant(s):
            wins = sum(1 for ok in s.grant_results if ok)
            if wins > 1:
                return (f"cap-1 lease key granted {wins} times across "
                        f"shards")
            if not s.crashed:
                grants = [n for state in s.router.shards
                          for n in state.tables["lease"].get(
                              s.lease_key, ())]
                if len(grants) > 1:
                    return f"duplicate grant rows: {grants}"
            return True

        def neighbor_durability(s):
            if not s.crashed:
                return True
            other = 1 - s.victim
            lost = s.acked[other] - s.present[other]
            return (not lost
                    or f"neighbor shard {other} lost acked rows "
                       f"{sorted(lost)} to shard {s.victim}'s crash")

        def victim_loss_bound(s):
            if not s.crashed:
                return True
            lost = s.acked[s.victim] - s.present[s.victim]
            ghosts = s.present[s.victim] - s.durable[s.victim]
            if lost:
                return (f"victim shard {s.victim} lost ACKED rows "
                        f"{sorted(lost)} (loss must stay inside the "
                        f"open window)")
            return (not ghosts
                    or f"unflushed rows resurrected on shard "
                       f"{s.victim}: {sorted(ghosts)}")

        return [
            Invariant("shard-single-ownership", ownership,
                      description="rows live only on the owning shard"),
            Invariant("shard-single-grant", single_grant,
                      description="cap-1 key never double-granted "
                                  "across shards"),
            Invariant("shard-neighbor-durability", neighbor_durability,
                      description="one shard's crash never loses a "
                                  "neighbor's acked rows"),
            Invariant("shard-victim-loss-bound", victim_loss_bound,
                      description="victim loses at most its open "
                                  "commit window, nothing acked"),
        ]

    def teardown(self) -> None:
        try:
            for i, state in enumerate(self.router.shards):
                if not (self.crashed and i == self.victim):
                    state.close()
        except Exception:
            pass
        for path in self.paths:
            for suffix in ("", "-wal", "-shm"):
                try:
                    os.unlink(path + suffix)
                except OSError:
                    pass


# -- exactly-once resubmit across connection death ---------------------------


class ExactlyOnceResubmitScenario(Scenario):
    name = "exactly_once"
    description = ("connection killed around a submit frame: the rid "
                   "resubmit (cluster_utils lost-frame path) executes "
                   "the frame exactly once")
    points = ("rpc.pipeline.send", "rpc.pipeline.reader_edge",
              "rpc.server.dispatch", "rpc.server.reply")
    crash_points = ("mc.env.conn_kill",)
    crash_budget = 1
    max_steps = 24
    block_grace_s = 0.04

    def setup(self) -> None:
        from ray_tpu._private.rpc import PipelinedClient, RpcServer

        self.executed = {}
        self._xlock = threading.Lock()
        self.resubmits = 0
        self.tids = ["t1"]
        self.server = RpcServer({"apply": self._apply},
                                dedupe_methods=frozenset({"apply"}))
        self.client = PipelinedClient(self.server.address,
                                      on_error=self._pipe_error)

    def _apply(self, task_ids=()):
        with self._xlock:
            for t in task_ids:
                self.executed[t] = self.executed.get(t, 0) + 1
        return True

    def _pipe_error(self, tag, message, rid, lost):
        """The driver-side recovery contract, verbatim from
        ``cluster_utils._batch_pipe_error``'s lost branch: a frame that
        died un-acked is resubmitted under the SAME request id so the
        node's dedupe cache makes it exactly-once."""
        if not lost:
            return
        from ray_tpu._private.rpc import RpcClient

        self.resubmits += 1
        try:
            RpcClient.to(self.server.address).call_with_rid(
                rid, "apply", task_ids=self.tids)
        except Exception:
            pass  # node truly dead → the death-sweep path owns recovery

    def actions(self):
        def driver():
            self.rid = self.client.send("apply", tag="frame",
                                        task_ids=self.tids)
            # The injected fault: the checker may kill the submit
            # connection at any point relative to the server's
            # dispatch/reply and the reader's drain.
            sanitize_hooks.crash_point("mc.env.conn_kill")

        def awaiter():
            # Keeps the execution (and so the explorer's control over
            # server/reader crossings) alive until the protocol
            # settles; must finish well inside the explorer's
            # blocked-threads grace (_wait_for_park) so a settled-but-
            # polling awaiter is never mistaken for a deadlock.
            deadline = time.monotonic() + 2.5
            while time.monotonic() < deadline:
                with self._xlock:
                    done = self.executed.get("t1", 0) >= 1
                if done and self.client.in_flight == 0:
                    return
                time.sleep(0.01)

        return [("driver", driver), ("awaiter", awaiter)]

    def on_crash(self, point: str) -> None:
        sock = self.client._sock
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass

    def invariants(self):
        return [Invariant(
            "exactly-once",
            lambda s: (s.executed.get("t1", 0) <= 1
                       or f"frame executed "
                          f"{s.executed['t1']} times"),
            description="a resubmitted frame never double-executes")]

    def liveness(self):
        return [Liveness(
            "frame-executes",
            lambda s: s.executed.get("t1", 0) == 1, timeout_s=4.0,
            description="the frame executes despite the kill")]

    def teardown(self) -> None:
        from ray_tpu._private.rpc import RpcClient

        try:
            self.client.close()
        except Exception:
            pass
        self.server.shutdown()
        addr = tuple(self.server.address)
        with RpcClient._pools_lock:
            pooled = RpcClient._pools.pop(addr, None)
        if pooled is not None:
            pooled.close()


# -- long-poll convergence across controller restart -------------------------


class LongPollRecoveryScenario(Scenario):
    name = "longpoll_recovery"
    description = ("controller killed with a listener parked mid-poll: "
                   "membership converges after the restart")
    points = ("longpoll.listen", "longpoll.notify",
              "longpoll.client.loop")
    crash_points = ("mc.env.controller_kill",)
    crash_budget = 1
    # The product client polls in an unbounded loop, so executions
    # truncate at the step bound by design: this scenario is a bounded
    # heuristic check, never an exhaustive one.
    max_steps = 18
    needs_ray = True
    block_grace_s = 0.06

    def setup(self) -> None:
        from ray_tpu.serve._private.long_poll import (LongPollClient,
                                                      LongPollHost)

        self.key = "replicas::dep"
        self.gen = 0
        self.host = LongPollHost()
        self.host.notify_changed(self.key, ("r1",))
        self.observed: List = []
        self.client = LongPollClient(
            self._make_handle(), self.key,
            lambda snap: self.observed.append(tuple(snap or ())),
            reresolve=self._make_handle)

    def _make_handle(self):
        """A controller handle bound to the CURRENT incarnation: calls
        against a superseded one raise ActorDiedError, exactly like a
        handle to a killed actor."""
        import ray_tpu
        from ray_tpu.exceptions import ActorDiedError

        scenario = self
        gen = self.gen

        def listen(key, known):
            if scenario.gen != gen:
                raise ActorDiedError("controller incarnation "
                                     f"{gen} is dead")
            result = scenario.host.listen(key, known, timeout=0.4)
            if scenario.gen != gen:
                # Died while we were parked: the poisoned answer of a
                # dead controller surfaces as the actor-death the real
                # transport would raise.
                raise ActorDiedError("controller died mid-listen")
            return ray_tpu.put(result)

        return SimpleNamespace(listen=_FakeMethod(listen))

    def actions(self):
        def env():
            self.host.notify_changed(self.key, ("r1", "r2"))
            sanitize_hooks.crash_point("mc.env.controller_kill")
        return [("env", env)]

    def on_crash(self, point: str) -> None:
        from ray_tpu.serve._private.long_poll import LongPollHost

        old = self.host
        replacement = LongPollHost()
        # The recovered controller re-broadcasts its checkpointed
        # state; clients resume from version -1 via reresolve.
        replacement.notify_changed(self.key, ("r1", "r2"))
        self.gen += 1
        self.host = replacement
        old.shutdown()  # poison: parked listeners wake NOW

    def invariants(self):
        valid = {("r1",), ("r1", "r2")}
        return [Invariant(
            "membership-sane",
            lambda s: (all(o in valid for o in s.observed)
                       or f"client observed garbage membership: "
                          f"{s.observed}"),
            description="observed snapshots are real memberships")]

    def liveness(self):
        return [Liveness(
            "membership-converges",
            lambda s: bool(s.observed)
            and s.observed[-1] == ("r1", "r2"),
            timeout_s=5.0,
            description="client converges to the post-restart "
                        "membership")]

    def teardown(self) -> None:
        self.client.stop()
        self.host.shutdown()
        self.client._thread.join(2.0)


# -- spill pipeline vs ref release vs restore --------------------------------


class SpillRaceScenario(Scenario):
    name = "spill_race"
    description = ("disk spill racing ref release and transparent "
                   "restore: an acked object is never lost, a freed "
                   "object never resurrects")
    points = ("spill.mark", "spill.restore")
    crash_points = ("spill.write.after",)
    crash_budget = 1
    max_steps = 24
    # Exhaustive sweep of this space is ~1.5k schedules (≈2s): above
    # the CLI default cap, well inside the tier-1 wall budget.
    max_schedules = 2500
    block_grace_s = 0.04

    def setup(self) -> None:
        from ray_tpu._private.config import ray_config
        from ray_tpu._private.ids import ObjectID
        from ray_tpu._private.memory_store import MemoryStore
        from ray_tpu._private.spilling import SpillManager

        # Small objects must be spill-eligible for the race to be
        # reachable at model-checking scale; restored in teardown.
        self._saved_min = ray_config.min_spilling_size_bytes
        ray_config.min_spilling_size_bytes = 1
        self.store = MemoryStore()
        # Seed while the budget is huge (no spill during setup) …
        self.manager = self.store.spill_manager = SpillManager(
            self.store, budget_bytes=10 ** 12)
        self.a_oid = ObjectID.from_random()
        self.b_oid = ObjectID.from_random()
        self.a_value = b"A" * 4096
        self.store.put(self.a_oid, self.a_value)
        self.store.put(self.b_oid, b"B" * 4096)
        # … then shrink it so the spiller action must sweep both.
        self.manager.budget = 1
        self.b_freed = False
        self.crashed = None
        self.spill_done = False
        self.a_reads: List = []

    def actions(self):
        def spiller():
            self.manager.maybe_spill()
            self.spill_done = True

        def releaser():
            self.store.free([self.b_oid])
            self.b_freed = True

        def reader():
            ready, value, error = self.store.peek(self.a_oid)
            self.a_reads.append((ready, bytes(value) if value else None,
                                 error))

        return [("spiller", spiller), ("releaser", releaser),
                ("reader", reader)]

    def _spill_path(self, url) -> str:
        return url[len("file://"):] if url else ""

    def invariants(self):
        def a_never_lost(s):
            entry = s.store._entries.get(s.a_oid)
            if entry is None or not entry.ready or entry.error is not None:
                return "acked object A lost its store entry"
            if entry.value is not None:
                return True
            path = s._spill_path(entry.spilled_url)
            return (path and os.path.exists(path)) or \
                "A is value-less with no durable spilled copy"

        def b_never_resurrects(s):
            if not s.b_freed:
                return True
            entry = s.store._entries.get(s.b_oid)
            if entry is None or entry.error is None or \
                    entry.value is not None:
                return "freed object B resurrected with a live value"
            if entry.spilled_url is not None:
                return ("freed object B still carries a restorable "
                        f"spill URL: {entry.spilled_url}")
            if s.crashed or not s.spill_done:
                # A crashed spiller may orphan its in-flight file —
                # disk garbage a dead process's storage dir reclaims,
                # unreachable by any entry; and a mid-sweep file (write
                # done, mark/delete pending) is legal in-flight state.
                return True
            # Once the sweep completed crash-free, the mark-fails→
            # delete path must have left no ghost copy behind (spill
            # files are <oid.hex()>-<token>, unique per write).
            try:
                ghosts = [n for n in os.listdir(
                    s.manager.storage.directory)
                    if n.startswith(s.b_oid.hex())]
            except OSError:
                ghosts = []
            return (not ghosts) or \
                f"freed object B left readable spill ghost(s): {ghosts}"

        return [
            Invariant("spill-no-loss", a_never_lost,
                      description="an acked object survives spill/"
                                  "restore/crash interleavings"),
            Invariant("spill-no-resurrection", b_never_resurrects,
                      description="a freed object never comes back"),
        ]

    def liveness(self):
        def a_reads_correct(s):
            # The reader ran to completion in every non-crashed
            # execution; whatever it observed must be A's real bytes.
            return all(ready and err is None and value == s.a_value
                       for ready, value, err in s.a_reads)

        return [Liveness("reader-sees-acked-value", a_reads_correct,
                         timeout_s=1.0,
                         description="peek(A) returns the acked bytes "
                                     "through any spill state")]

    def on_crash(self, point: str) -> None:
        self.crashed = point  # the spiller thread dies; nothing to kill

    def teardown(self) -> None:
        from ray_tpu._private.config import ray_config

        ray_config.min_spilling_size_bytes = self._saved_min
        try:
            self.manager.storage.destroy()
        except Exception:
            pass


# -- lineage reconstruction vs node death ------------------------------------


class LineageReconstructionScenario(Scenario):
    name = "lineage_reconstruction"
    description = ("node crash between publish and consume: a get on a "
                   "lost object returns the re-executed (or spill-"
                   "restored) value or a bounded error — never a hang, "
                   "never a stale/partial value")
    points = ("recon.request", "recon.resubmit", "recon.restore",
              "store.put", "mc.sync.get_loop")
    max_steps = 40
    # The getter's bounded poll loop widens the space past the CLI
    # default; the exhaustive sweep is still small (two threads).
    max_schedules = 6000
    block_grace_s = 0.04

    def setup(self) -> None:
        from types import SimpleNamespace

        from ray_tpu._private.config import ray_config
        from ray_tpu._private.ids import TaskID
        from ray_tpu._private.memory_store import MemoryStore
        from ray_tpu._private.spilling import FileSystemStorage
        from ray_tpu._private.task_spec import TaskKind, TaskSpec
        from ray_tpu.cluster_utils import ClusterHead, _NodeRecord

        # No health-checker thread: node liveness is scenario-driven.
        self._saved_period = ray_config.health_check_period_s
        ray_config.health_check_period_s = 0
        self.reexec = {"x": 0, "y": 0}
        worker = SimpleNamespace(memory_store=MemoryStore(),
                                 shm_plane=None, gcs=None, backend=None)
        self.head = head = ClusterHead(worker, start_server=False)
        self.store = worker.memory_store

        def execute(spec):
            # The re-execution environment: runs the creating task on
            # the head and reports the output — the real node-side
            # store_task_outputs/report path condensed to its effect.
            key = spec.name
            self.reexec[key] += 1
            value = spec.func()
            self.store.put(spec.return_ids[0], value)
            head._report_objects([spec.return_ids[0].binary()],
                                 head.server.address)

        worker.backend = SimpleNamespace(submit=execute)
        self.node_addr = ("127.0.0.1", 7091)
        head.nodes["n1"] = _NodeRecord("n1", self.node_addr, {"CPU": 1})
        # X: lost copy must be re-created by re-executing its task.
        spec_x = TaskSpec(task_id=TaskID.from_random(),
                          kind=TaskKind.NORMAL_TASK,
                          func=lambda: 42, args=(), kwargs={}, name="x")
        spec_x.assign_return_ids()
        self.x = spec_x.return_ids[0]
        head.record_lineage(spec_x)
        head._report_objects([self.x.binary()], self.node_addr,
                             sizes=[8])
        # Y: lost copy has a surviving spilled payload — restore must
        # win over re-execution (reexec["y"] stays 0).
        spec_y = TaskSpec(task_id=TaskID.from_random(),
                          kind=TaskKind.NORMAL_TASK,
                          func=lambda: "never", args=(), kwargs={},
                          name="y")
        spec_y.assign_return_ids()
        self.y = spec_y.return_ids[0]
        head.record_lineage(spec_y)
        head._report_objects([self.y.binary()], self.node_addr,
                             sizes=[16])
        self.spill_store = FileSystemStorage()
        import cloudpickle as _cp

        url = self.spill_store.spill(self.y, _cp.dumps("from-disk"))
        head._report_spilled([self.y.binary()], [url], node_id="n1")
        self.results = {}

    def _bounded_get(self, key, oid):
        head, store = self.head, self.store
        for _ in range(8):
            sanitize_hooks.sched_point("mc.sync.get_loop")
            ready, value, error = store.peek(oid)
            if ready:
                self.results[key] = ("err", error) if error else value
                return
            info = head._locate2(oid.binary())
            if info is not None:
                record = head.nodes.get("n1")
                if tuple(info["address"]) == self.node_addr:
                    # Remote fetch from the owner: succeeds only while
                    # the owner process is alive (env-controlled).
                    if record is not None and record.alive:
                        self.results[key] = \
                            42 if key == "x" else "from-disk"
                        return
                    # process gone mid-fetch: retry (next locate sees
                    # the dropped location and reconstructs)
        self.results[key] = ("err", "fetch deadline")

    def actions(self):
        def getter():
            self._bounded_get("x", self.x)
            self._bounded_get("y", self.y)

        def env():
            self.head.mark_node_dead("n1", reason="chaos kill")

        return [("getter", getter), ("env", env)]

    def invariants(self):
        def values_sane(s):
            for key, want in (("x", 42), ("y", "from-disk")):
                got = s.results.get(key, "<pending>")
                if got not in (want, "<pending>") and \
                        not (isinstance(got, tuple) and got[0] == "err"):
                    return (f"get({key}) returned stale/partial "
                            f"{got!r} (want {want!r} or bounded error)")
            return True

        def attempts_bounded(s):
            from ray_tpu._private.config import ray_config

            cap = ray_config.max_reconstruction_attempts
            over = {k.hex()[:8]: v
                    for k, v in s.head._recon_attempts.items()
                    if v > cap}
            return (not over) or f"reconstruction attempts over " \
                                 f"max_reconstruction_attempts: {over}"

        def spill_wins(s):
            return s.reexec["y"] == 0 or (
                f"spill-backed object re-executed its task "
                f"{s.reexec['y']} times instead of restoring")

        return [
            Invariant("recon-no-stale-value", values_sane,
                      description="a get never observes a wrong value"),
            Invariant("recon-attempts-bounded", attempts_bounded,
                      description="per-object attempt charge holds"),
            Invariant("recon-spill-short-circuit", spill_wins,
                      description="a durable spilled copy restores "
                                  "instead of re-executing"),
        ]

    def liveness(self):
        def completes_correctly(s):
            # The getter is a bounded loop (never hangs, by
            # construction); with reconstruction enabled it must also
            # CONVERGE: both gets return the real values.
            return s.results.get("x") == 42 and \
                s.results.get("y") == "from-disk"

        return [Liveness(
            "recon-converges", completes_correctly, timeout_s=3.0,
            description="gets on lost objects return the re-executed/"
                        "restored values, not errors")]

    def conformance(self):
        # rayspec refinement over the head's lock-partitioned object
        # directory (ShardedTable): under the publish/death/
        # reconstruct churn, the directory must stay a refinement of
        # ONE flat dict per key — the catalog's sharded_table spec
        # checked against a REAL head under exploration.
        return [("sharded_table", lambda: self.head.object_locations)]

    def teardown(self) -> None:
        from ray_tpu._private.config import ray_config

        ray_config.health_check_period_s = self._saved_period
        self.head.stop()
        try:
            self.spill_store.destroy()
        except Exception:
            pass


# -- actor restart: replay-or-reject over every death placement --------------


class ActorRestartScenario(Scenario):
    name = "actor_restart"
    description = ("node death across mailbox-submit/dispatch/restart: "
                   "<=1 execution per call always, exactly-1 for calls "
                   "with retry budget, rejects name the budget")
    # The scope tier-1 can drain (PR 59). With the gate's two other
    # seams scheduled too (`actor.restart.begin`, `actor.replay`) and
    # a second crossing a beat of node1 the sweep is 17,284 schedules,
    # 78 s alone: the tier-1 leg cut it at 45 s and 12,760, never
    # exhausted. Left unscheduled here: the windows between the kill
    # and the restart decision and between the decision and a call's
    # replay (env_kill runs from the kill to its first route in one
    # step; `test_fault_semantics` pins the sweep deterministically and
    # the rayspec refinement still checks every quiescent state). A
    # caller's route still lands before the kill, mid-restart with the
    # budgeted call parked or not yet, and after the actor is ready.
    points = ("actor.route", "actor.restart.ready", "mc.sync.exec1")
    max_steps = 36
    # Measured exhaustive sweep: 2,252 schedules (~9s alone); the
    # floor leaves headroom so the tier-1 `exhausted` claim stays
    # honest.
    max_schedules = 5000
    block_grace_s = 0.04

    # The model around the REAL ActorRestartGate mirrors the head's
    # choreography (ClusterBackendMixin.submit / ClusterHead.
    # mark_node_dead) the way exactly_once mirrors _batch_pipe_error:
    # dispatch appends to the hosting node's mailbox (+ the inflight
    # table), node death sweeps the inflight snapshot through
    # gate.recover_call, and the restarted actor's location release
    # drains parked calls. Execution and its inflight-clear are one
    # atomic segment — the model's analog of the output report; the
    # report-in-flight window is out of scope here (closed by the
    # caller-side dedupe in ClusterHead.recover_actor_call — ROADMAP
    # FT gap (a) — with the rayspec exactly_once_call spec as the
    # mechanical witness, see test_rayspec.py's pre-fix history test).

    def setup(self) -> None:
        from types import SimpleNamespace

        from ray_tpu._private.actor_gate import (ActorRestartGate,
                                                 ActorRestartState)
        self._alive = ActorRestartState.ALIVE
        self._restarting = ActorRestartState.RESTARTING
        self._dead = ActorRestartState.DEAD
        self.aid = b"actor-1"

        aid = self.aid

        class _Call:  # hashable (rides set-typed inflight tables)
            def __init__(self, name, retries):
                self.name = name
                self.max_retries = retries
                self.actor_id = SimpleNamespace(
                    binary=lambda: aid, hex=lambda: "61637430")

            def describe(self):
                return self.name

        def call(name, retries):
            return _Call(name, retries)

        self.gate = ActorRestartGate()
        self.gate.register(self.aid, 1)
        self.c_r = call("r", 1)   # rides max_task_retries=1
        self.c_n = call("n", 0)   # no retry budget
        self.node1 = {"alive": True, "mailbox": []}
        self.actor_node = "n1"
        # Insertion-ordered (a set of id-hashed objects iterates in a
        # different order per process run — divergence under replay).
        self.inflight = []
        self.parked = []
        self.executions = {"r": 0, "n": 0}
        self.rejected = {}
        self._lock = threading.Lock()
        # c_r is already dispatched and in flight when the fault hits.
        self.inflight.append(self.c_r)
        self.node1["mailbox"].append(self.c_r)

    # -- model effects (the head's wiring, condensed) --------------------

    def _reject(self, spec, msg, dead):
        self.rejected[spec.name] = (msg, dead)

    def _exec_on_n2(self, spec):
        # The replacement node is warm and healthy: dispatch-to-exec is
        # synchronous in the model (the races under proof are around
        # the death, not the healthy node's queueing).
        with self._lock:
            self.executions[spec.name] += 1
        if spec in self.inflight:
            self.inflight.remove(spec)

    def _drain_parked(self):
        while self.parked:
            self._submit(self.parked.pop(0))

    def _park(self, spec):
        self.parked.append(spec)
        # Model of the park-waiter thread: an actor already ALIVE again
        # releases immediately.
        if self.gate.state(self.aid) == self._alive and \
                self.actor_node is not None:
            self._drain_parked()

    def _submit(self, spec):
        node = self.actor_node
        if node == "n1" and self.node1["alive"]:
            self.inflight.append(spec)
            self.node1["mailbox"].append(spec)
            return
        if node == "n2":
            self.inflight.append(spec)
            self._exec_on_n2(spec)
            return
        state = self.gate.state(self.aid)
        if state == self._dead:
            self._reject(spec, self.gate.death_cause(self.aid), True)
            return
        self.gate.route_call(spec, dispatch=None, park=self._park,
                             fail=self._reject)

    # -- actions ---------------------------------------------------------

    def actions(self):
        def caller():
            # Submitted at an arbitrary point relative to the death:
            # may execute on n1, reject mid-restart (naming the
            # budget), or run on the replacement.
            self._submit(self.c_n)

        def node1():
            # Two service beats: c_r is pre-queued, c_n may land during
            # the loop — both can execute pre-death; a third beat only
            # re-observes an empty mailbox (space, no coverage). One
            # crossing a beat: a death mid-call leaves the call popped
            # and in flight, which the sweep treats as it treats a call
            # still in the dead node's mailbox (it reads the in-flight
            # table alone), so a second crossing between the pop and
            # the execution reaches no state the first does not.
            for _ in range(2):
                sanitize_hooks.sched_point("mc.sync.exec1")
                if not self.node1["alive"]:
                    return
                if self.node1["mailbox"]:
                    spec = self.node1["mailbox"].pop(0)
                    with self._lock:
                        self.executions[spec.name] += 1
                    if spec in self.inflight:
                        self.inflight.remove(spec)

        def env_kill():
            # mark_node_dead condensed: kill, restart decision,
            # replay-or-reject every in-flight call via the REAL gate,
            # then the creation resubmit completing (set_actor_node →
            # ready → parked calls drain). The sweep-vs-ready thread
            # race is pinned separately by a deterministic unit test
            # (test_fault_semantics) — a fourth event-blocked thread
            # here costs exhaustiveness.
            self.node1["alive"] = False
            self.actor_node = None
            restarted = self.gate.begin_restart(self.aid,
                                                "its node n1 died")
            for spec in list(self.inflight):
                self.inflight.remove(spec)
                self.gate.recover_call(spec, resubmit=self._submit,
                                       fail=self._reject)
            if not restarted:
                # tombstoned: parked calls fail fast
                for spec in list(self.parked):
                    self.parked.remove(spec)
                    self._reject(spec,
                                 self.gate.death_cause(self.aid), True)
                return
            self.actor_node = "n2"
            self.gate.ready(self.aid)
            self._drain_parked()

        return [("caller", caller), ("node1", node1),
                ("env_kill", env_kill)]

    # -- properties ------------------------------------------------------

    def invariants(self):
        def at_most_once(s):
            over = {k: v for k, v in s.executions.items() if v > 1}
            return (not over) or f"calls executed more than once: {over}"

        def no_double_outcome(s):
            both = [k for k in s.executions
                    if s.executions[k] >= 1 and k in s.rejected]
            return (not both) or \
                f"calls both executed AND rejected: {both}"

        def rejects_name_budget(s):
            bad = [
                (k, msg) for k, (msg, _dead) in s.rejected.items()
                if "max_task_retries" not in msg
                and "max_restarts" not in msg
            ]
            return (not bad) or \
                f"rejection errors do not name the budget: {bad}"

        return [
            Invariant("actor-at-most-once", at_most_once,
                      description="<=1 execution per call, always"),
            Invariant("actor-single-outcome", no_double_outcome,
                      description="a call resolves exactly one way"),
            Invariant("actor-reject-names-budget", rejects_name_budget,
                      description="rejects name restart/retry budgets"),
        ]

    def liveness(self):
        def budget_call_exactly_once(s):
            return s.executions["r"] == 1

        def no_budget_call_resolves(s):
            return (s.executions["n"] + (1 if "n" in s.rejected
                                         else 0)) == 1

        return [
            Liveness("actor-retry-exactly-once",
                     budget_call_exactly_once, timeout_s=3.0,
                     description="a call with retry budget executes "
                                 "exactly once despite the death"),
            Liveness("actor-zero-budget-resolves",
                     no_budget_call_resolves, timeout_s=3.0,
                     description="a call without budget either ran "
                                 "pre-death or was rejected — exactly "
                                 "one of the two"),
        ]

    def conformance(self):
        # rayspec refinement: the REAL gate's FSM state and remaining
        # budget must match a linearization of the recorded
        # register/restart/ready/route/replay history at every
        # quiescent state of every death placement.
        return [("actor_gate", lambda: self.gate)]

    def teardown(self) -> None:
        pass


# -- tenancy: quota admission + WFQ delivery under concurrency ---------------


class QuotaAdmissionScenario(Scenario):
    name = "quota_admission"
    description = ("concurrent submits + a release racing a grant "
                   "against a cpus:1/queued:1 quota, WFQ puts racing "
                   "pops: grants never exceed the quota, admissions "
                   "never exceed the ceiling, the fair queue neither "
                   "loses nor duplicates items, and no backlogged "
                   "class is bypassed past the WFQ bound")
    # The WFQ edges gate scenario-side (mc.sync.wfq.*): a product
    # crossing inside FairTaskQueue.get would fire on every idle
    # dispatch-loop poll of the runtime's own queue and the explorer
    # would adopt the raylet dispatcher into this exploration.
    points = ("tenancy.acquire", "tenancy.release", "mc.sync.wfq.put",
              "mc.sync.wfq.pop")
    max_steps = 40
    # Measured exhaustive sweep: 7122 schedules (~9s standalone); the
    # floor leaves headroom so the tier-1 `exhausted` claim stays
    # honest.
    max_schedules = 12000
    block_grace_s = 0.04

    # The REAL decision cores (QuotaLedger, FairTaskQueue) under a
    # condensed model of the product wiring: submitters are the
    # cluster mixin's admission+charge path, the releaser is a
    # finishing task's release (the moment parked work may dispatch),
    # and the consumer is the dispatch loop serving the runnable WFQ.

    def setup(self) -> None:
        from types import SimpleNamespace

        from ray_tpu._private.config import ray_config
        from ray_tpu._private.tenancy import FairTaskQueue, QuotaLedger

        self._old_enf = ray_config.tenancy_enforcement
        self._old_quotas = ray_config.job_quotas
        ray_config.tenancy_enforcement = True
        ray_config.job_quotas = "a=cpus:1,queued:1"
        self.ledger = QuotaLedger()

        def spec(name):
            return SimpleNamespace(job_id="a", resources={"CPU": 1.0},
                                   attempt=0, name=name)

        # One slot already held when the race begins (the setup grant
        # the releaser will free mid-flight).
        self.s0 = spec("s0")
        assert self.ledger.try_acquire_cpu(self.s0)
        self.s1, self.s2 = spec("s1"), spec("s2")
        self.admits: List = []   # note_queued outcomes (None = admitted)
        self.grants: List = []   # try_acquire_cpu outcomes
        self.released = False
        # Weighted fair queue: class "a" (the quota'd job) vs class "b"
        # — explicit weights force fair mode independent of config.
        self.wfq = FairTaskQueue(weights={"a": 1.0, "b": 1.0})
        self.put_items: List = []
        self.inflight_puts: set = set()
        self.popped: List = []
        self._wlock = threading.Lock()
        # Class "a" is already backlogged when the race begins (seeded
        # here, not concurrently — a third concurrent put multiplies
        # the space past the tier-1 budget): the explored pop always
        # has two classes competing, so the bypass bookkeeping — the
        # non-starvation witness — is live in every interleaving where
        # b1's put lands first.
        self._put("a", "a0")

    def _put(self, job, tag) -> None:
        from types import SimpleNamespace

        item = SimpleNamespace(job_id=job, tag=tag)
        # The put's crossing sits BEFORE the enqueue, so a quiescent
        # state can observe the put started-but-not-landed: track the
        # window explicitly and let the conservation invariant allow
        # an in-flight item on either side.
        with self._wlock:
            self.inflight_puts.add(tag)
        sanitize_hooks.sched_point("mc.sync.wfq.put")
        self.wfq.put(item)
        with self._wlock:
            self.inflight_puts.discard(tag)
            self.put_items.append(tag)

    def actions(self):
        import queue as _queue

        def pop_one():
            # One dispatch beat: whatever is enqueued serves in WFQ
            # order; an empty beat is a recorded miss, never a hang.
            sanitize_hooks.sched_point("mc.sync.wfq.pop")
            try:
                item = self.wfq.get_nowait()
            except _queue.Empty:
                return
            with self._wlock:
                self.popped.append(item.tag)

        def sub1():
            self.admits.append(self.ledger.note_queued(self.s1))
            self.grants.append(self.ledger.try_acquire_cpu(self.s1))

        def sub2():
            # Second racing submitter doubles as the dispatch-loop
            # beat serving the runnable WFQ (a fourth action thread
            # multiplies the space past the tier-1 budget).
            self.admits.append(self.ledger.note_queued(self.s2))
            self.grants.append(self.ledger.try_acquire_cpu(self.s2))
            pop_one()

        def releaser():
            # The setup grant completes: its CPU charge frees (racing
            # both submitters' acquires), then class b's item arrives.
            self.ledger.release_cpu(self.s0)
            self.released = True
            self._put("b", "b1")

        return [("sub1", sub1), ("sub2", sub2), ("rel", releaser)]

    # -- properties ------------------------------------------------------

    def invariants(self):
        def quota_never_exceeded(s):
            peak = s.ledger.usage("a")["peak_cpu_milli"]
            return peak <= 1000 or \
                f"peak running milli-CPU {peak} over the cpus:1 quota"

        def conservation(s):
            held = (0 if s.released else 1) \
                + sum(1 for g in s.grants if g)
            used = s.ledger.usage("a")["cpu_milli"]
            return used == held * 1000 or \
                f"ledger says {used} milli held, model says {held} slots"

        def ceiling_respected(s):
            admitted = sum(1 for a in s.admits if a is None)
            return admitted <= 1 or \
                f"{admitted} submits admitted past queued:1"

        def wfq_no_loss_no_dup(s):
            with s._wlock:
                popped = list(s.popped)
                put = set(s.put_items)
                inflight = set(s.inflight_puts)
            if len(popped) != len(set(popped)):
                return f"duplicate delivery: {popped}"
            remaining = [item.tag for q in s.wfq._classes.values()
                         for item in q]
            seen = set(popped) | set(remaining)
            if len(popped) + len(remaining) != len(seen):
                return (f"item both popped and queued: "
                        f"popped={popped} remaining={remaining}")
            lost = put - seen  # a COMPLETED put must be somewhere
            forged = seen - put - inflight
            if lost or forged:
                return (f"lost={sorted(lost)} forged={sorted(forged)} "
                        f"(put={sorted(put)} popped={popped} "
                        f"remaining={remaining} "
                        f"inflight={sorted(inflight)})")
            return True

        def wfq_non_starvation(s):
            # Equal weights: a backlogged class is served at least
            # every other pop — a bypass streak past 2 means the
            # virtual-time law broke and a class can starve.
            return s.wfq.max_bypass <= 2 or \
                f"a backlogged class was bypassed " \
                f"{s.wfq.max_bypass} consecutive times"

        return [
            Invariant("quota-never-exceeded", quota_never_exceeded,
                      description="grants never exceed the CPU quota, "
                                  "across every submit/release race"),
            Invariant("quota-conservation", conservation,
                      description="ledger usage equals model holds"),
            Invariant("queued-ceiling", ceiling_respected,
                      description="admissions never exceed queued:1"),
            Invariant("wfq-exactly-once", wfq_no_loss_no_dup,
                      description="the fair queue neither loses nor "
                                  "duplicates items"),
            Invariant("wfq-non-starvation", wfq_non_starvation,
                      description="no backlogged nonzero-weight class "
                                  "is bypassed past the WFQ bound"),
        ]

    def liveness(self):
        def all_resolved(s):
            # Every submitter observed a definite admission AND grant
            # outcome; with the release in flight at least one of the
            # racers (or the freed slot itself) must land a grant.
            return len(s.admits) == 2 and len(s.grants) == 2

        return [Liveness("submits-resolve", all_resolved,
                         timeout_s=2.0,
                         description="every racing submit resolves to "
                                     "a definite grant/deny outcome")]

    def conformance(self):
        # rayspec refinement: at every quiescent state, the REAL
        # ledger and fair queue must sit in a state some linearization
        # of the recorded charge/release/admit (resp. put/pop) history
        # reaches — the scenario's invariants prove the properties,
        # the conformance pass proves the state.
        return [("quota_ledger", lambda: self.ledger),
                ("fair_task_queue", lambda: self.wfq)]

    def conflict_key(self, point: str):
        # The ledger (quota counters + model grant/release lists) and
        # the fair queue (items + put/pop model lists) are DISJOINT
        # state: their crossings commute, and declaring so is what
        # keeps the exhaustive sweep inside the tier-1 budget. Model
        # bookkeeping respects the split — ledger ops touch only
        # admits/grants/released, wfq ops only put_items/popped.
        if point.startswith("mc.sync.wfq"):
            return "tenancy-wfq"
        if point.startswith("tenancy."):
            return "tenancy-ledger"
        return super().conflict_key(point)

    def teardown(self) -> None:
        from ray_tpu._private.config import ray_config

        ray_config.tenancy_enforcement = self._old_enf
        ray_config.job_quotas = self._old_quotas


# -- scheduler dep-park table: death sweep vs dep-ready claims ---------------


class ReplicaDirectScenario(Scenario):
    name = "replica_direct"
    description = ("serve replica-direct dispatch racing a long-poll "
                   "membership removal: no slot claim ever lands on a "
                   "replica whose removal committed before the claim "
                   "started, per-replica slots never exceed the cap "
                   "or go negative, and every claim releases")
    points = ("serve.direct.acquire", "serve.direct.update")
    max_steps = 24
    # Three single-crossing actions (dep_sweep's shape): the
    # exhaustive sweep is small; the floor leaves headroom so
    # `exhausted` stays honest. Release is deliberately NOT a gated
    # point here — the acquire crossing sits INSIDE the product's
    # snapshot→claim race window (the interleaving that matters), and
    # release-after-removal is reached via the pre-held rB token that
    # disp-a releases after the updater may have committed.
    max_schedules = 6000
    block_grace_s = 0.02

    # The REAL ReplicaDirectTable (the proxy fleet's steady-state fast
    # path) under a condensed model of the wiring: two dispatchers are
    # concurrent proxy requests claiming slots, the updater is the
    # shared membership watch committing a snapshot that REMOVES
    # replica rB (a scale-down / death broadcast). The property is the
    # data plane's cache-invalidation contract: once the removal
    # commits, no acquire returns rB — a request is never dispatched
    # to a replica after its removal committed to long-poll state.

    def setup(self) -> None:
        from ray_tpu.serve._private.membership import ReplicaDirectTable

        self.table = ReplicaDirectTable(cap=1)
        self.table.update(1, ["rA", "rB"])
        # Pre-hold rB's only slot (round-robin: first acquire claims
        # rA — returned immediately — second claims rB): disp-a
        # releases it mid-run, so schedules where the updater's
        # removal commits FIRST exercise release-after-removal.
        first = self.table.acquire()
        self.held_rb = self.table.acquire()
        self.table.release(first)
        assert self.held_rb is not None and self.held_rb.replica == "rB"
        # version -> committed membership (the updater bumps
        # `committed` AFTER its update returns — commit is a return
        # edge).
        self.members = {1: {"rA", "rB"}, 2: {"rA"}}
        self.committed = 1
        self._wlock = threading.Lock()
        self.claims: List[Tuple[str, int, int]] = []

    def actions(self):
        def dispatcher(release_held):
            def body():
                pre = self.committed  # committed BEFORE this acquire
                token = self.table.acquire()
                if token is not None:
                    with self._wlock:
                        self.claims.append(
                            (token.replica, token.version, pre))
                    self.table.release(token)
                if release_held:
                    # Possibly AFTER rB's removal committed: the slot
                    # row is gone and the release must drop into the
                    # void, never corrupt the replacement accounting.
                    self.table.release(self.held_rb)
            return body

        return [("disp-a", dispatcher(True)),
                ("disp-b", dispatcher(False)),
                ("updater", self._update)]

    def _update(self):
        self.table.update(2, ["rA"])
        self.committed = 2

    def invariants(self):
        def no_stale_claim(s):
            with s._wlock:
                claims = list(s.claims)
            for replica, version, pre in claims:
                legal = s.members.get(version)
                if legal is None or replica not in legal:
                    return (f"claim on {replica!r} under version "
                            f"{version}, whose membership is {legal}")
                if pre >= 2 and replica == "rB":
                    return ("acquire started after rB's removal "
                            "committed yet returned rB")
            return True

        def slots_exact(s):
            with s.table._lock:
                slots = dict(s.table._slots)
            for replica, held in slots.items():
                if held < 0:
                    return f"slot count for {replica!r} is {held} (<0)"
                if held > s.table.cap:
                    return (f"slot count for {replica!r} is {held}, "
                            f"over cap {s.table.cap}")
            return True

        return [
            Invariant("no-stale-claim", no_stale_claim,
                      description="a request is never dispatched to a "
                                  "replica after its removal committed "
                                  "to long-poll state"),
            Invariant("slots-exact", slots_exact,
                      description="per-replica in-flight slots stay "
                                  "within [0, cap] at every quiescent "
                                  "state"),
        ]

    def liveness(self):
        return [Liveness(
            "slots-drain",
            lambda s: sum(s.table._slots.values()) == 0,
            timeout_s=2.0,
            description="every claimed slot is released (tokens for "
                        "since-removed replicas included)")]


class DepSweepScenario(Scenario):
    name = "dep_sweep"
    description = ("the scheduler's dep-park table under a racing "
                   "death sweep (ROADMAP FT gap d): two dep-ready "
                   "claims race one sweep over items parked on one and "
                   "two dependencies — every item is handed to exactly "
                   "one owner (ready path XOR sweep), nothing leaks a "
                   "per-dep entry, and every item resolves")
    points = ("sched.dep_ready", "sched.dep_sweep")
    max_steps = 24
    # Measured exhaustive sweep is tiny (3 single-crossing actions);
    # the floor leaves headroom so `exhausted` stays honest.
    max_schedules = 2000
    block_grace_s = 0.02

    # The REAL DepTable (the core LocalBackend parks dep-blocked work
    # in) under a condensed model of the product wiring: ready1/ready2
    # are _on_dep_ready for two objects landing concurrently, the
    # sweeper is _on_actor_death's claim over a dying actor's parked
    # specs. Item A parks on {d1}, item B on {d1, d2} — the multi-dep
    # item is what makes stale-entry purging and double-claim windows
    # reachable.

    def setup(self) -> None:
        from ray_tpu._private.sched_state import DepTable

        self.table = DepTable()
        self.item_a = SimpleNamespace(name="A")
        self.item_b = SimpleNamespace(name="B")
        self.table.park(b"A", self.item_a, ["d1"])
        self.table.park(b"B", self.item_b, ["d1", "d2"])
        self._wlock = threading.Lock()
        self.dispatched: List[str] = []
        self.failed: List[str] = []

    def actions(self):
        def claim(out, items):
            with self._wlock:
                out.extend(item.name for item in items)

        def ready1():
            claim(self.dispatched, self.table.dep_ready("d1"))

        def ready2():
            claim(self.dispatched, self.table.dep_ready("d2"))

        def sweeper():
            claim(self.failed,
                  self.table.sweep(lambda item: True))

        return [("ready1", ready1), ("ready2", ready2),
                ("sweeper", sweeper)]

    def invariants(self):
        def exactly_once(s):
            with s._wlock:
                dispatched = list(s.dispatched)
                failed = list(s.failed)
            both = set(dispatched) & set(failed)
            if both:
                return (f"items claimed by BOTH ready and sweep: "
                        f"{sorted(both)}")
            if len(dispatched) != len(set(dispatched)) or \
                    len(failed) != len(set(failed)):
                return (f"duplicate claim: dispatched={dispatched} "
                        f"failed={failed}")
            return True

        def conservation(s):
            with s._wlock:
                claimed = set(s.dispatched) | set(s.failed)
            waiting = s.table.waiting_count()
            if len(claimed) + waiting != 2:
                return (f"items lost or forged: claimed="
                        f"{sorted(claimed)} waiting={waiting}")
            return True

        def no_entry_leak(s):
            # A claimed item must not pin per-dep list entries: a dep
            # that never fires would hold them (and their args)
            # forever. Entries may only remain for UNCLAIMED items.
            waiting = s.table.waiting_count()
            entries = s.table.parked_entries()
            if waiting == 0 and entries != 0:
                return (f"{entries} stale per-dep entries with no "
                        f"unclaimed items")
            return True

        return [
            Invariant("dep-exactly-once-handoff", exactly_once,
                      description="each parked item is claimed by the "
                                  "ready path XOR the sweep, once"),
            Invariant("dep-conservation", conservation,
                      description="claimed + still-waiting == parked"),
            Invariant("dep-no-entry-leak", no_entry_leak,
                      description="claimed items leave no per-dep "
                                  "entries behind"),
        ]

    def liveness(self):
        def all_resolved(s):
            # The sweep matches everything, so by quiescence every
            # item has exactly one owner (sweep-first executions fail
            # both; ready-first dispatch some and sweep the rest).
            with s._wlock:
                return len(set(s.dispatched) | set(s.failed)) == 2

        return [Liveness("dep-items-resolve", all_resolved,
                         timeout_s=2.0,
                         description="every parked item ends owned by "
                                     "the ready path or the sweep")]

    def conformance(self):
        # rayspec refinement: the live DepTable's remaining-count rows
        # must match a linearization of the park/ready/sweep history
        # at every quiescent state (FT gap (d)'s exactly-once handoff,
        # now also proven as a refinement of the sequential model).
        return [("dep_table", lambda: self.table)]

    def teardown(self) -> None:
        pass


class KvCacheReuseScenario(Scenario):
    name = "kv_cache_reuse"
    description = ("LLM prefix/KV cache: a lookup hit racing block "
                   "admission and pressure eviction — a hit never "
                   "yields stale/freed KV bytes (pinned blocks are "
                   "never evicted), per-tenant charge is conserved, "
                   "and resident bytes stay under capacity")
    # Release is deliberately NOT gated (replica_direct's shape): the
    # race that matters is admit/evict landing between a lookup's pin
    # and the payload read — pinned by mc.sync.kv.read below.
    points = ("llm.kv.lookup", "llm.kv.admit", "llm.kv.evict",
              "mc.sync.kv.read")
    max_steps = 24
    # Three actions, 1-2 gated crossings each: the exhaustive sweep is
    # small; the floor leaves headroom so `exhausted` stays honest.
    max_schedules = 6000
    block_grace_s = 0.02

    # The REAL PrefixCache (the LLM engine's prefix-reuse decision
    # core) under a condensed model of the wiring: the reader is a
    # prefill hitting the shared prompt head and copying matched KV
    # payloads into its slot, the writer is another request admitting
    # a different prompt's blocks (capacity forces LRU eviction), the
    # evictor is arena-pressure reclaim. ``payloads`` stands in for
    # the host-side KV byte store: an evicted block's payload is
    # freed, so a hit observing a missing payload IS the
    # read-after-free the pinning protocol must make impossible.

    def setup(self) -> None:
        from ray_tpu._private.kv_cache import PrefixCache, chain_keys

        self.cache = PrefixCache(capacity_bytes=250, block_tokens=4)
        self.chain_p = chain_keys(list(range(8)), 4, "m")
        self.chain_q = chain_keys(list(range(100, 108)), 4, "m")
        self._wlock = threading.Lock()
        self.payloads: dict = {}
        self.stale: List[str] = []
        created, _ev = self.cache.admit(self.chain_p, "a", 100)
        assert len(created) == 2
        for h in created:
            self.payloads[h.block_id] = b"P"
        self.cache.release(created)

    def actions(self):
        def reader():
            hit = self.cache.lookup(self.chain_p, "a")
            # The pin-to-read window: admit/evict may be granted here.
            sanitize_hooks.sched_point("mc.sync.kv.read")
            with self._wlock:
                for h in hit:
                    if self.payloads.get(h.block_id) is None:
                        self.stale.append(h.key)
            self.cache.release(hit)

        def writer():
            created, evicted = self.cache.admit(self.chain_q, "b", 100)
            with self._wlock:
                for e in evicted:
                    self.payloads.pop(e.block_id, None)  # the free
                for h in created:
                    self.payloads[h.block_id] = b"Q"
            self.cache.release(created)

        def evictor():
            for e in self.cache.evict(100):
                with self._wlock:
                    self.payloads.pop(e.block_id, None)

        return [("reader", reader), ("writer", writer),
                ("evictor", evictor)]

    def invariants(self):
        def no_stale_hit(s):
            with s._wlock:
                stale = list(s.stale)
            if stale:
                return (f"lookup hit observed freed KV bytes for "
                        f"blocks {stale} — evicted while pinned")
            return True

        def charge_conserved(s):
            with s.cache._lock:
                derived: dict = {}
                total = 0
                for b in s.cache._blocks.values():
                    derived[b.job] = derived.get(b.job, 0) + b.nbytes
                    total += b.nbytes
                charge = dict(s.cache._charge)
                resident = s.cache._bytes
            if charge != derived:
                return (f"per-tenant charge {charge} != resident "
                        f"blocks' bytes {derived}")
            if resident != total:
                return f"byte counter {resident} != blocks {total}"
            if resident > s.cache.capacity_bytes:
                return (f"resident {resident} bytes over capacity "
                        f"{s.cache.capacity_bytes}")
            return True

        def refs_sane(s):
            with s.cache._lock:
                bad = {b.key: b.refs for b in s.cache._blocks.values()
                       if b.refs < 0}
            if bad:
                return f"negative refcounts: {bad}"
            return True

        return [
            Invariant("kv-no-stale-hit", no_stale_hit,
                      description="a prefix hit never reads bytes an "
                                  "eviction already freed"),
            Invariant("kv-charge-conserved", charge_conserved,
                      description="tenant charge == resident bytes per "
                                  "job; total within capacity"),
            Invariant("kv-refs-nonnegative", refs_sane,
                      description="block refcounts never go negative"),
        ]

    def liveness(self):
        def pins_drain(s):
            with s.cache._lock:
                return all(b.refs == 0
                           for b in s.cache._blocks.values())

        return [Liveness("kv-pins-drain", pins_drain, timeout_s=2.0,
                         description="every lookup/admit pin is "
                                     "released by quiescence")]

    def conformance(self):
        # rayspec refinement: the live block table + charge map must
        # match a linearization of the lookup/admit/release/evict
        # history at every quiescent state.
        return [("kv_cache", lambda: self.cache)]

    def teardown(self) -> None:
        pass


# -- head hard-crash: durability + node re-registration convergence ----------


class HeadCrashRecoveryScenario(Scenario):
    name = "head_crash_recovery"
    description = ("head killed at the commit boundary with a parked "
                   "submitter and a live node: acked-durable rows "
                   "survive, un-acked writes never resurrect, the node "
                   "re-registers through the report-returns-False path")
    # head.node_report / head.register are crossed by the node beats
    # but left UNGATED: registration orderings touch none of the
    # checked properties (the store and the node table are disjoint),
    # and gating them multiplies the space ~30x past the tier-1
    # budget. The convergence property is still driven through the
    # real handlers at EVERY crash placement (see on_crash).
    points = ("gcs.put",)
    crash_points = ("gcs.commit.before", "gcs.commit.after")
    crash_budget = 1
    max_steps = 26
    max_schedules = 4000
    block_grace_s = 0.02

    def setup(self) -> None:
        from types import SimpleNamespace

        from ray_tpu._private.config import ray_config
        from ray_tpu._private.gcs_storage import SqliteStoreClient
        from ray_tpu._private.memory_store import MemoryStore
        from ray_tpu.cluster_utils import ClusterHead

        self._saved_period = ray_config.health_check_period_s
        ray_config.health_check_period_s = 0
        fd, self.path = tempfile.mkstemp(prefix="raymc-headcrash-",
                                         suffix=".db")
        os.close(fd)
        os.unlink(self.path)
        # Group-commit mode, committer-driven (see gcs_durability).
        self.store = SqliteStoreClient(self.path, commit_interval_s=0)
        self.store._interval = 3600.0

        def make_head():
            worker = SimpleNamespace(memory_store=MemoryStore(),
                                     shm_plane=None, gcs=None,
                                     backend=None)
            return ClusterHead(worker, start_server=False)

        self._make_head = make_head
        self.head = make_head()
        self.node_addr = ("127.0.0.1", 7093)
        self.head._register_node("n1", self.node_addr, {"CPU": 1})
        self.accepted: List[bytes] = []
        self.acked: set = set()
        self.durable: set = set()
        self.present: set = set()
        self.crashed: str = ""
        self._post_crash = False
        self.converged_after_crash = False

    def actions(self):
        def writer():
            try:
                self.store.put("t", b"k1", b"v")
            except Exception:
                return  # store died under us: the write never took
            self.accepted.append(b"k1")

        def committer():
            for window in range(2):
                snap = list(self.accepted)
                self.store.flush()
                self.acked.update(snap)
                if window == 0:
                    sanitize_hooks.sched_point("mc.sync.commit_gap")

        # No node ACTION thread: even a gate-only third thread
        # multiplies the interleaving space ~70x past the tier-1
        # budget, and the node's pre-crash report beats touch nothing
        # the properties read. Its post-crash convergence handshake is
        # driven through the REAL head handlers inside on_crash, at
        # every explored crash placement.
        return [("writer", writer), ("committer", committer)]

    def _node_converge_step(self) -> bool:
        """One report-loop beat against the CURRENT head; True once
        convergence post-crash is established. Keys off the internal
        _post_crash flag — the public ``crashed`` field is set LAST in
        on_crash so mid-crash invariant evaluations stay vacuous."""
        head = self.head
        ok = head._report_resources("n1", {"CPU": 1})
        if ok:
            if self._post_crash:
                self.converged_after_crash = True
                return True
            return False
        head._register_node("n1", self.node_addr, {"CPU": 1})
        return False

    def on_point(self, point: str, role: str) -> None:
        if point == "gcs.commit.after":
            self.durable.update(self.accepted)

    def on_crash(self, point: str) -> None:
        from ray_tpu._private.gcs_storage import SqliteStoreClient

        # The head process dies: connection drops (open window rolls
        # back) and every in-memory table is gone. crash() takes the
        # store lock, sequencing the close after any in-flight
        # statement (a lock-blocked writer then sees a clean
        # ProgrammingError).
        self.store.crash()
        survivor = SqliteStoreClient(self.path, commit_interval_s=0)
        try:
            self.present = {k for k, _ in survivor.get_all("t")}
        finally:
            survivor.close()
        old = self.head
        self.head = self._make_head()  # fresh head, EMPTY node table
        old.stop()
        self._post_crash = True
        # The node's report loop keeps beating after the failover (in
        # product it is an infinite timer loop; the bounded action
        # thread may already have drained its iterations). Driving the
        # remaining beats here keeps every crash placement's
        # convergence CHECKED without an unbounded action: report →
        # False → re-register → report → True, all real head handlers.
        for _ in range(3):
            if self._node_converge_step():
                break
        self.crashed = point  # LAST: invariants key off it

    def invariants(self):
        def durability(s):
            if not s.crashed:
                return True
            lost = s.acked - s.present
            return (not lost
                    or f"acked-durable rows lost across head crash at "
                       f"{s.crashed}: {sorted(lost)}")

        def no_resurrection(s):
            if not s.crashed:
                return True
            ghosts = s.present - s.durable
            return (not ghosts
                    or f"un-acked writes resurrected after head crash "
                       f"at {s.crashed}: {sorted(ghosts)}")

        def reregistered(s):
            # Evaluated at end-state: by then on_crash has driven the
            # node's remaining report beats, so a crash execution that
            # did NOT converge is a real protocol failure, not a
            # bounded-thread artifact. (An invariant, not a Liveness:
            # the state is final when the actions drain — polling
            # would only burn the budget.)
            if not s.crashed:
                return True
            record = s.head.nodes.get("n1")
            if record is None or not record.alive:
                return ("node n1 never re-registered with the "
                        "post-crash head")
            return s.converged_after_crash or \
                "node n1 re-registered but never reconverged (no " \
                "True report after the crash)"

        return [
            Invariant("head-crash-durability", durability,
                      description="acked table rows survive the crash"),
            Invariant("head-crash-no-resurrection", no_resurrection,
                      description="window-riding writes stay dead"),
            Invariant("head-crash-node-converges", reregistered,
                      description="the live node re-registers through "
                                  "report-returns-False, no driver "
                                  "intervention"),
        ]

    def teardown(self) -> None:
        from ray_tpu._private.config import ray_config

        ray_config.health_check_period_s = self._saved_period
        try:
            self.head.stop()
        except Exception:
            pass
        try:
            if not self.crashed:
                self.store.close()
        except Exception:
            pass
        for suffix in ("", "-wal", "-shm"):
            try:
                os.unlink(self.path + suffix)
            except OSError:
                pass


SCENARIOS = {
    cls.name: cls
    for cls in (RouterCapScenario, PipelinedCloseScenario,
                GroupCommitDurabilityScenario, CrossShardScenario,
                ExactlyOnceResubmitScenario, LongPollRecoveryScenario,
                SpillRaceScenario, LineageReconstructionScenario,
                ActorRestartScenario, HeadCrashRecoveryScenario,
                QuotaAdmissionScenario, DepSweepScenario,
                ReplicaDirectScenario, KvCacheReuseScenario)
}

# The bounded tier-1 leg: real code, small configs, exhaustive where
# the scenario supports it (see test_raymc_ci_leg.py).
# dep_sweep and quota_admission run FIRST: they are the scenarios that
# never need the ray_tpu runtime, and explorer executions are an order
# of magnitude cheaper before a needs_ray scenario brings the runtime
# (and its background threads, which every quiescence settle must
# scan) up for the rest of the leg (run order matters — cheap
# scenarios first).
DEFAULT_SCENARIOS = ("dep_sweep", "kv_cache_reuse", "quota_admission",
                     "cross_shard", "replica_direct", "router_cap",
                     "gcs_durability", "pipelined_close", "spill_race",
                     "lineage_reconstruction", "actor_restart",
                     "head_crash_recovery")
