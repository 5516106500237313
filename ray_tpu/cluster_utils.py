"""Cluster mode: multiprocess nodes on one machine (or many).

Reference: `python/ray/cluster_utils.py:99` — `Cluster` runs N
raylet-equivalents as separate OS processes, which is how the reference
tests multi-node scheduling and failure handling without real machines
(SURVEY.md §4). Here:

- the driver process is the head: it hosts the GCS-style services
  (node table, object directory) and its own LocalBackend;
- `add_node()` spawns `ray_tpu._private.cluster_node` subprocesses that
  register and execute shipped tasks;
- scheduling: local-first pack, spill to the least-loaded remote node
  with capacity (the reference's hybrid policy shape);
- objects stay with their executing node (owner-based directory); gets
  pull node→node.
"""

from __future__ import annotations

import logging
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private import critical_path
from ray_tpu._private import perf_stats as _perf_stats
from ray_tpu._private import sanitize_hooks
from ray_tpu._private import sched_state
from ray_tpu._private import state as state_mod
from ray_tpu._private import tenancy
from ray_tpu._private import worker as worker_mod
from ray_tpu._private.config import ray_config
from ray_tpu._private.ids import ObjectID
from ray_tpu._private.resources import spec_milli
from ray_tpu._private.rpc import RpcClient, RpcServer
from ray_tpu._private.task_spec import TaskKind
from ray_tpu.exceptions import ActorDiedError, OwnerDiedError

# Object-plane observability (ray_tpu_object_* in /api/metrics via the
# runtime-metrics fold; node-tagged through the snapshot-shipping
# plane): shm probe outcome, native pull volume/latency, and time spent
# waiting for a bounded pull slot.
_SHM_HITS = _perf_stats.counter("object_shm_hit")
_SHM_MISSES = _perf_stats.counter("object_shm_miss")
_PULL_BYTES = _perf_stats.counter("object_pull_bytes")
_PULL_SECONDS = _perf_stats.latency("object_pull_seconds")
_PULL_SLOT_WAIT = _perf_stats.latency("object_pull_slot_wait_seconds")

# Fault-path observability (ray_tpu_node_deaths_total,
# ray_tpu_node_death_lost_bytes_total, ray_tpu_reconstructions_total
# {outcome}, ray_tpu_actor_restarts_total{outcome} after the runtime-
# metrics fold): every recovery decision leaves a countable trace, so a
# chaos run's "the job completed" comes with "and here is what it cost".
_NODE_DEATHS = _perf_stats.counter("node_deaths")
_NODE_DEATH_LOST_BYTES = _perf_stats.counter("node_death_lost_bytes")

# Lease-cache observability (ray_tpu_sched_* after the runtime-metrics
# fold): a hit is a submission riding an already-granted (job, shape)
# lease with no head scheduling decision; a miss is a fresh grant; a
# spillback is a grant redirected off an overloaded lease target by the
# node's reported backlog signal.
_LEASE_CACHE_HITS = _perf_stats.counter("sched_lease_cache_hit")
_LEASE_CACHE_MISSES = _perf_stats.counter("sched_lease_cache_miss")
_SPILLBACKS = _perf_stats.counter("sched_spillbacks")


def _recon_counter(outcome: str):
    """reconstructions{outcome}: reexecute | from_spill | exhausted."""
    return _perf_stats.counter("reconstructions", {"outcome": outcome})


def _restart_counter(outcome: str):
    """actor_restarts{outcome}: restarted | exhausted | call_replayed |
    call_rejected | call_deduped."""
    return _perf_stats.counter("actor_restarts", {"outcome": outcome})


def fetch_backoff(attempt: int) -> None:
    """Escalating poll interval for object-arrival waits: sub-ms first
    probes (most objects land within a few ms of submission — a flat
    10 ms sleep put a hard floor under every cross-process get), backing
    off for slow producers. Curve knobs:
    ``object_fetch_backoff_base_s`` / ``object_fetch_backoff_cap_s``."""
    time.sleep(min(
        ray_config.object_fetch_backoff_base_s * (1.6 ** min(attempt, 10)),
        ray_config.object_fetch_backoff_cap_s))


def try_shm_fetch(worker, oid) -> bool:
    """Zero-copy read from the node's shared segment, if the object is
    there. Faster and cheaper than any RPC — always tried first."""
    plane = getattr(worker, "shm_plane", None)
    if plane is None:
        return False
    try:
        found, value = plane.get(oid)
    except Exception:
        return False
    if not found:
        _SHM_MISSES.inc()
        return False
    _SHM_HITS.inc()
    worker.memory_store.put(oid, value, shm=True)
    return True


# Bandwidth-aware pull bounding (reference: pull_manager.h:52 — cap
# in-flight pull bytes): at most `object_pull_max_concurrent` wire
# pulls at once; excess callers wait their turn instead of thrashing
# the link with parallel streams that each crawl. Rebuilt when the
# config knob changes (tests, tuning).
_pull_slots_lock = threading.Lock()
_pull_slots: Optional[threading.BoundedSemaphore] = None
_pull_slots_cap = 0


def _wire_pull_slots() -> threading.BoundedSemaphore:
    global _pull_slots, _pull_slots_cap
    cap = max(1, int(ray_config.object_pull_max_concurrent))
    with _pull_slots_lock:
        if _pull_slots is None or _pull_slots_cap != cap:
            _pull_slots = threading.BoundedSemaphore(cap)
            _pull_slots_cap = cap
        return _pull_slots


def pull_via_transfer(worker, plane, oid, host: str, port: int) -> bool:
    """One bounded, range-striped native pull into the local segment,
    then the zero-copy shm read (reference: ObjectManager Pull with
    chunked parallel transfers)."""
    sanitize_hooks.sched_point("objplane.pull")
    try:
        # Bounded wait for a pull slot: a hung peer must degrade the
        # bound, never deadlock the whole object plane (the C layer's
        # per-syscall socket timeout reclaims the slot eventually).
        slots = _wire_pull_slots()
        t0 = time.monotonic()
        acquired = slots.acquire(timeout=30.0)
        _PULL_SLOT_WAIT.record(time.monotonic() - t0)
        t1 = critical_path.clock()
        try:
            rc = plane.store.pull_from_striped(
                oid.binary(), host, port,
                streams=max(1, int(ray_config.object_pull_streams)),
                allow_local=getattr(plane, "allow_local_pull", True))
        finally:
            if acquired:
                slots.release()
        if rc not in (0, -5):
            return False
        if rc == 0:
            pull_s = critical_path.clock() - t1
            _PULL_SECONDS.record(pull_s)
            _PULL_BYTES.inc(plane.store.object_size(oid.binary()) or 0)
            # Critical-path stage: a pull inside a traced task charges
            # the request; outside one it still reaches the flight ring.
            if critical_path.enabled():
                critical_path.record_stage(
                    critical_path.ambient_trace_id(), "object.pull", pull_s)
        return try_shm_fetch(worker, oid)
    except Exception:
        return False


def try_transfer_fetch(worker, oid, loc_info) -> bool:
    """Chunked native pull from the owner's transfer server into the
    local segment, then zero-copy read — the cross-host object plane
    (reference: ObjectManager Pull, `pull_manager.h:52`). Skipped when
    the owner shares our segment (plain shm read suffices) or the
    object isn't shm-backed."""
    plane = getattr(worker, "shm_plane", None)
    if plane is None or not loc_info:
        return False
    transfer = loc_info.get("transfer")
    if transfer is None or loc_info.get("shm") == plane.name:
        return False
    return pull_via_transfer(worker, plane, oid, transfer[0], transfer[1])


def resolve_descriptor(worker, oid, desc) -> bool:
    """Materialize an object the owner answered with a descriptor for:
    same segment → plain zero-copy read; served cross-segment → striped
    native pull; no plane here → cannot (caller retries the value
    path)."""
    plane = getattr(worker, "shm_plane", None)
    if plane is None:
        return False
    if desc.shm == plane.name:
        return try_shm_fetch(worker, oid)
    if desc.host:
        return pull_via_transfer(worker, plane, oid, desc.host, desc.port)
    return False


def batch_fetch_objects(worker, oids, locate, self_address):
    """Shared batched-pull core (driver fetch dispatcher + node dep
    fetch): local/shm probes per object, ONE ``locate(need)`` call for
    the rest, transfer-plane pull where possible, then one
    ``get_objects_batch`` RPC per owner — whose replies carry
    ``wire.ObjectDescriptor``s for plane-reachable payloads (resolved
    by shm read / native pull) and framed-pickle values only for small
    or plane-less objects. Returns ``(resolved set, failed {oid: exc},
    unresolved list)`` — unresolved objects simply aren't anywhere yet
    (slow producer) and are the caller's to retry.
    """
    from ray_tpu._private import wire

    store = worker.memory_store
    plane = getattr(worker, "shm_plane", None)
    resolved: set = set()
    failed: Dict[Any, Exception] = {}
    unresolved: list = []
    need = []
    for oid in oids:
        if store.contains(oid) or try_shm_fetch(worker, oid):
            resolved.add(oid)
        else:
            need.append(oid)
    if not need:
        return resolved, failed, unresolved
    infos = locate(need)
    by_addr: Dict[tuple, list] = {}
    for oid, info in zip(need, infos):
        if info is not None and tuple(info["address"]) != tuple(self_address):
            if plane is not None and info.get("shm") == plane.name:
                # Owner shares our segment: the pre-locate probe may
                # simply have raced the seal — re-probe before falling
                # back to a payload-copying RPC.
                if try_shm_fetch(worker, oid):
                    resolved.add(oid)
                    continue
            elif try_transfer_fetch(worker, oid, info):
                resolved.add(oid)
                continue
            by_addr.setdefault(tuple(info["address"]), []).append(oid)
        elif store.contains(oid):
            resolved.add(oid)
        else:
            unresolved.append(oid)
    for addr, group in by_addr.items():
        try:
            replies = RpcClient.to(addr).call(
                "get_objects_batch",
                oids=[o.binary() for o in group], timeout=10.0,
                shm=plane.name if plane is not None else None,
                can_pull=plane is not None)
        except Exception as e:
            for oid in group:
                failed[oid] = e
            continue
        for oid, reply in zip(group, replies):
            ok, value, err = reply
            if not ok:
                unresolved.append(oid)
            elif isinstance(value, wire.ObjectDescriptor):
                if resolve_descriptor(worker, oid, value):
                    resolved.add(oid)
                else:
                    unresolved.append(oid)
            else:
                store.put(oid, value, error=err)
                resolved.add(oid)
    return resolved, failed, unresolved


def descriptor_object_read(worker, transfer_addr, get_object, oids,
                           timeout: float = 30.0, shm=None,
                           can_pull: bool = False):
    """Owner-side ``get_objects_batch`` core: resolve every requested
    object under a shared deadline, then answer with an
    ``ObjectDescriptor`` wherever the requester can reach the sealed
    bytes — same segment (zero-copy read) or our transfer server
    (native pull) — and with the framed-pickle value otherwise. An
    object that left the arena (spilled, evicted) but is large enough
    is republished on demand so the descriptor path stays the default.
    """
    from ray_tpu._private import wire
    from ray_tpu._private.rpc import batched_object_read
    from ray_tpu._private.shm_plane import share_value

    out = batched_object_read(get_object, oids, timeout)
    plane = getattr(worker, "shm_plane", None)
    if plane is None:
        return out
    same_seg = shm is not None and shm == plane.name
    served = can_pull and transfer_addr is not None
    if not (same_seg or served):
        return out
    for i, (oid, reply) in enumerate(zip(oids, out)):
        ok, value, err = reply
        if not ok or err is not None:
            continue
        if not plane.store.contains(oid):
            # Left the arena (spilled/evicted) or never crossed the
            # threshold: republish large restored values on demand.
            if value is None or not share_value(worker, ObjectID(oid),
                                                value):
                continue
        size = plane.store.object_size(oid)
        if size is None:
            continue
        host, port = ("", 0) if same_seg else tuple(transfer_addr)
        out[i] = [True, wire.ObjectDescriptor(
            oid=oid, shm=plane.name, host=host, port=int(port),
            size=int(size)), None]
    return out


# Template-cached milli-demand of a spec (shared core with the local
# backend's _spec_milli — resources.spec_milli).
_spec_milli_of = spec_milli


class _NodeRecord:
    def __init__(self, node_id: str, address: Tuple[str, int],
                 resources: Dict[str, float],
                 transfer: Optional[Tuple[str, int]] = None,
                 shm_name: Optional[str] = None,
                 labels: Optional[Dict[str, str]] = None):
        self.node_id = node_id
        self.address = tuple(address)
        self.resources = resources
        self.alive = True
        # Object-plane endpoints: the native transfer server serving this
        # node's shm segment, and the segment name (nodes sharing a
        # segment read each other's objects without any transfer).
        self.transfer = tuple(transfer) if transfer else None
        self.shm_name = shm_name
        # Scheduling labels, e.g. {"ici_slice": "slice-0"}.
        self.labels = dict(labels or {})
        # Pushed resource view (reference: ray_syncer RESOURCE_VIEW
        # deltas): refreshed by report_resources; the scheduler reads
        # this instead of pinging the node per submission.
        self.available: Dict[str, float] = dict(resources)
        self.last_report: float = time.monotonic()
        # Queued-not-running task count from the node's last report
        # (reference: raylet backlog reporting) — lease grants and
        # spill decisions prefer shallow queues.
        self.backlog: int = 0
        # Latest physical-stats sample from the node's in-process agent
        # (node_stats.py), carried on resource reports.
        self.stats: Dict[str, Any] = {}
        # Function-ids whose definitions this node has already received
        # (function-distribution cache; see _strip_exported_func).
        self.known_fns: set = set()
        # Interned spec-template ids this node has received: later
        # submissions of the same shape ship as small TaskCall headers.
        # LRU-bounded at HALF the node cache's capacity, so an id still
        # claimed here cannot have been evicted node-side; an id evicted
        # HERE is simply re-shipped on next use.
        from ray_tpu._private.rpc import LruTable

        self.known_templates = LruTable(4096)
        # In-flight ACTOR-CREATION reservations (milli-resources),
        # charged at record_inflight and released when the creation
        # completes or unwinds. The pushed availability view is stale
        # within a report period, which tasks tolerate (an over-placed
        # task queues and runs when the node frees up) but creations do
        # NOT: an actor pins its CPUs for life, so a burst of creations
        # placed against one stale view overcommits a node with work
        # that can never start while other nodes idle. _choose_node
        # subtracts this. Mutations under the head lock (creations are
        # rare next to tasks); racy reads see a momentarily-stale int.
        self.reserved_milli: Dict[str, int] = {}
        # Head-shard epoch this node last converged with: when a shard
        # process is restarted (its open commit window lost), the head
        # bumps its epoch and the node's next report_resources returns
        # False ONCE — the node re-registers and re-reports its actors
        # and owned objects, repopulating the lost window's keys.
        self.shard_epoch = 0

    def reserve(self, milli: Dict[str, int]) -> None:
        sched_state.milli_add(self.reserved_milli, milli)

    def unreserve(self, milli: Dict[str, int]) -> None:
        sched_state.milli_sub(self.reserved_milli, milli)


class _NullServer:
    """Transport stub for a head constructed with ``start_server=False``
    (model-checking / unit harnesses): carries the address identity and
    a no-op shutdown, nothing listens."""

    def __init__(self, address: Tuple[str, int] = ("127.0.0.1", 0)):
        self.address = tuple(address)

    def shutdown(self) -> None:
        pass


class ClusterHead:
    """GCS-equivalent services hosted in the driver process.

    Beyond the node table and object directory this owns the failure
    story: task *lineage* (creating TaskSpec per return object —
    reference: `reference_count.h:61` lineage pinning), the in-flight
    dispatch table, and a proactive health checker (reference:
    `gcs_health_check_manager.h:39`) that marks dead nodes and triggers
    re-execution of lost work.
    """

    def __init__(self, worker, port: int = 0, start_server: bool = True):
        self.worker = worker
        # The head lock guards the cold/cross-keyed tables (node
        # records, pins, borrowers, actor directory). The HOT tables —
        # object directory, in-flight dispatches, lineage — are
        # lock-partitioned ShardedTables keyed by object/task id, so
        # concurrent submit batches and node object reports stop
        # serializing on one lock. Ordering rule: shard locks are LEAF
        # locks — code holding self._lock may call into a sharded
        # table, never the reverse.
        self._lock = threading.Lock()
        shards = ray_config.sched_head_shards
        self.nodes: Dict[str, _NodeRecord] = {}
        self.object_locations = sched_state.ShardedTable(shards)
        # Reported payload sizes alongside locations (same lifecycle):
        # what locality-aware lease placement scores by — the directory
        # knows where the bytes are AND how many they are.
        self.object_sizes = sched_state.ShardedTable(shards)
        self.actor_nodes: Dict[bytes, str] = {}
        # Failure/recovery state. lineage maps each task-return object to
        # its creating spec; inflight maps task_id -> (node_id, spec)
        # until outputs are reported; actor_specs keeps creation specs for
        # restart-on-node-death; the gate owns restart budgets, the
        # ALIVE/RESTARTING/DEAD FSM, and per-call replay-or-reject.
        self.lineage = sched_state.ShardedTable(shards)
        self.inflight = sched_state.ShardedTable(shards)
        self.actor_specs: Dict[bytes, Any] = {}
        from ray_tpu._private.actor_gate import ActorRestartGate

        self.actor_gate = ActorRestartGate()
        # Gate-registered actors whose (restarted) home is the HEAD's
        # local backend: distinguishes "ALIVE with no directory entry
        # because it lives here" from the transient no-location window
        # mid-death-sweep (where calls must park, not fall through to a
        # backend that has never heard of the actor).
        self.actor_local: set = set()
        self._recon_attempts: Dict[bytes, int] = {}
        # Durable spilled copies by object (node-reported): when a node
        # dies, its spilled RTS1 files outlive the process (they sit on
        # the node-local disk this single-host simulation shares — a
        # real deployment needs shared/remote spill storage for this to
        # hold across hosts), so reconstruction restores from spill
        # instead of re-executing the creating task.
        self.object_spill_urls: Dict[bytes, str] = {}
        # Distributed refcount (reference: reference_count.h borrower
        # protocol, adapted to head-owned objects). A driver release is
        # deferred while any node holds a handle (borrowers) or any
        # dispatched-but-unfinished task's args reference the object
        # (task_pins); the actual free runs when the last holder drops.
        self.borrowers: Dict[bytes, set] = {}          # oid -> {node_id}
        self.task_pins: Dict[bytes, set] = {}          # oid -> {task_id}
        self._task_pinned: Dict[bytes, list] = {}      # task_id -> [oid]
        self.driver_released: set = set()
        # Cluster-wide unfulfilled resource demands (task_id -> request):
        # what the autoscaler reads (reference: GCS resource load). With
        # autoscaling_enabled, no-node-fits tasks wait for capacity
        # instead of failing fast.
        self.pending_demands: Dict[bytes, Dict[str, float]] = {}
        self.autoscaling_enabled = False
        # Function definitions exported to the KV (namespace __fn__).
        self.exported_fns: set = set()
        # Placement-group bundle locations: (pg_id_binary, index) ->
        # node_id, or None for the head itself.
        self.pg_bundle_nodes: Dict[Tuple[bytes, int], Optional[str]] = {}
        handlers = {
            "register_node": self._register_node,
            "report_objects": self._report_objects,
            "report_spilled": self._report_spilled,
            "report_resources": self._report_resources,
            "add_borrowers": self._add_borrowers,
            "remove_borrowers": self._remove_borrowers,
            "locate": self._locate,
            "locate2": self._locate2,
            "locate_batch": self._locate_batch,
            "get_object": self._get_object,
            "get_objects_batch": self._get_objects_batch,
            "get_nodes": self._get_nodes,
            "subscribe": self._subscribe,
            # Typed GCS accessor surface (reference gcs_client.h:61):
            # node processes reach the head's tables through
            # _private/gcs_client.GcsClient instead of raw RPC strings.
            "gcs_kv_put": lambda **kw: self.worker.gcs.kv_put(**kw),
            "gcs_kv_get": lambda **kw: self.worker.gcs.kv_get(**kw),
            "gcs_kv_del": lambda **kw: self.worker.gcs.kv_del(**kw),
            "gcs_kv_keys": lambda **kw: self.worker.gcs.kv_keys(**kw),
            "gcs_named_actors":
                lambda **kw: self.worker.gcs.list_named_actors(**kw),
            "gcs_pg_table": self._gcs_pg_table,
            "gcs_events": self._gcs_events,
            "gcs_record_event": self._gcs_record_event,
            # Cross-node actor plumbing: nodes route actor tasks for
            # non-local actors through the head's cluster backend
            # (reference: the owner's direct actor transport reaches any
            # node; here the head is the directory), and resolve named
            # actors from the head's registry.
            "route_task": self._route_task,
            "report_actor": self._report_actor,
            "report_actors": self._report_actors,
            "gcs_named_actor_register": self._named_actor_register,
            "gcs_named_actor_get": self._named_actor_get,
            "gcs_named_actor_remove": self._named_actor_remove,
            # Observability plane: node task-event deltas + metric
            # snapshots land in the head-side aggregator
            # (_private/obs_plane.py — the GcsTaskManager role).
            "obs_report": self._obs_report,
        }
        if start_server:
            self.server = RpcServer(
                handlers, port=port,
                dedupe_methods=frozenset({"gcs_kv_put", "route_task",
                                          "gcs_named_actor_register"}))
        else:
            # Transport-less head (the model checker drives handlers
            # directly): every directory/recovery code path stays real,
            # only the socket server is stubbed.
            self.server = _NullServer()
        # Long-poll pubsub channels (reference: pubsub/publisher.h:302);
        # node lifecycle events publish here.
        from ray_tpu._private.pubsub import Publisher

        self.publisher = Publisher()
        # Cluster-wide observability aggregator: node-shipped task
        # events + per-node metric snapshots (timeline/tracing/state
        # and the dashboard's merged /api/metrics read this).
        from ray_tpu._private.obs_plane import ObsAggregator

        self.obs = ObsAggregator()
        self.transfer_addr: Optional[Tuple[str, int]] = None
        # node_id -> local log path (populated by Cluster.add_node).
        self.node_logs: Dict[str, str] = {}
        self._health_stop = threading.Event()
        self._health_thread: Optional[threading.Thread] = None
        # Multi-process head control plane (head_shards > 1): the hot
        # row tables above stay as this coordinator's in-memory working
        # copy (read paths never pay an RPC), while every mutation ALSO
        # streams — coalesced per shard — to the owning head shard
        # process, which group-commits it into its own sqlite store.
        # Lease grants additionally consult the owning shard as the
        # registration authority (_grant_lease). Default (1) spawns
        # nothing: today's single-process head byte-for-byte.
        self.shard_router = None
        self._shard_epoch = 0
        self._shard_db_dir = ""
        if start_server and ray_config.head_shards > 1:
            import tempfile

            from ray_tpu._private import head_shards as _head_shards

            self._shard_db_dir = ray_config.head_shard_db_dir or \
                tempfile.mkdtemp(prefix="ray_tpu_head_shards_")
            interval = ray_config.head_shard_commit_interval_s
            self.shard_router = _head_shards.ShardRouter(
                ray_config.head_shards, self._shard_db_dir,
                commit_interval_s=interval if interval > 0 else None)
            from ray_tpu._private import health as _health

            _health.register_section_provider(
                "head_shards", self.shard_health)
            _health.register_degraded_provider(
                "head_shards", self._shard_degraded_reasons)

    # -- registration / directory ---------------------------------------

    def _register_node(self, node_id, address, resources,
                       transfer=None, shm_name=None, labels=None):
        sanitize_hooks.sched_point("head.register")
        with self._lock:
            record = _NodeRecord(node_id, address, resources,
                                 transfer, shm_name, labels)
            # A (re-)registration converges with the CURRENT shard
            # epoch: the re-reports that follow it repopulate any
            # restarted shard's lost window, so this node owes no
            # further re-registration for it.
            record.shard_epoch = self._shard_epoch
            self.nodes[node_id] = record
        self.publisher.publish("node_events", {
            "event": "NODE_ADDED", "node_id": node_id,
            "address": tuple(address)})
        from ray_tpu._private.events import record_event

        record_event("node", f"node {node_id} joined",
                     node_id=node_id, resources=dict(resources or {}))
        self._ensure_health_checker()
        return True

    def _report_resources(self, node_id: str, available, total=None,
                          labels=None, stats=None, backlog=None):
        """Pushed resource-view delta (reference: ray_syncer.h:86). Also
        treated as a liveness heartbeat by the health checker, and the
        carrier for per-node agent stats (node_stats.py). Returning
        False tells an unknown (restarted-head) node to re-register."""
        sanitize_hooks.sched_point("head.node_report")
        with self._lock:
            record = self.nodes.get(node_id)
            if record is None:
                return False  # unknown: node should re-register
            if record.shard_epoch != self._shard_epoch:
                # A head shard process was restarted since this node
                # last converged: its open commit window died with it.
                # Ride the existing re-register path — the node will
                # re-register and re-report its actors and owned
                # objects, restoring the lost window's keys on the
                # restarted shard.
                record.shard_epoch = self._shard_epoch
                return False
            record.available = dict(available)
            if backlog is not None:
                record.backlog = int(backlog)
            if total:
                record.resources = dict(total)
            if labels:
                record.labels = dict(labels)
            if stats:
                record.stats = dict(stats)
            record.last_report = time.monotonic()
        return True

    def _subscribe(self, channel: str, subscriber_id: str, cursor: int,
                   timeout: float = 10.0):
        """Long-poll subscription endpoint (reference: long-poll pubsub,
        `pubsub/publisher.h:188-216`)."""
        return self.publisher.poll(channel, subscriber_id, cursor, timeout)

    def _report_objects(self, oids: List[bytes], address, sizes=None):
        frees = []
        finished = []
        addr = tuple(address)
        # FT gap (a) guard: a dying node's last-gasp report must not
        # apply after the death sweep ran — it would re-point the
        # directory at an unreachable address and pop a REPLAYED call's
        # fresh in-flight record (the head would then believe the
        # replay finished while it is still running). The copies it
        # announces died with the node; recovery owns them now.
        if self._addr_dead(addr) and not self._addr_alive(addr):
            return True
        router = self.shard_router
        for i, oid in enumerate(oids):
            self.object_locations[oid] = addr
            if router is not None:
                # Mirror the directory row to its owning shard process
                # (streamed, coalesced per shard; the shard group-
                # commits it — per-shard durability window).
                router.put("objects", oid, addr)
            if sizes is not None and i < len(sizes) and sizes[i]:
                self.object_sizes[oid] = int(sizes[i])
                if router is not None:
                    router.put("sizes", oid, int(sizes[i]))
            # Outputs landed: the producing task is no longer in
            # flight anywhere; its arg pins drop with it.
            tid = ObjectID(oid).task_id().binary()
            entry = self.inflight.pop(tid, None)
            if entry is not None:
                if router is not None:
                    router.delete("inflight", tid)
                finished.append(entry[1])
                if entry[1].kind == TaskKind.ACTOR_TASK:
                    # Exactly-once protocol tap (rayspec): the call's
                    # output REPORT is applied — its effect is now
                    # observable. A second apply for the same task id
                    # is the FT-gap-(a) double execution the
                    # exactly-once register spec flags.
                    sanitize_hooks.spec_op("spec.call.apply", "call",
                                           self, tid)
                    sanitize_hooks.spec_op("spec.call.apply", "ret",
                                           self, (tid, "applied"))
                if entry[1].kind == TaskKind.ACTOR_CREATION:
                    # Constructed: the node's own reports carry the
                    # held CPUs from dispatch on — drop the reservation.
                    self._unreserve_creation(entry[0], entry[1])
            elif sanitize_hooks.spec_taps_active \
                    and addr != tuple(self.server.address):
                # Recorder installed only: a NODE's report for an
                # actor-task output whose in-flight entry is ALREADY
                # gone (popped by a death sweep that replayed the
                # call, or by the other execution's report) is a
                # further application of the same call — exactly the
                # history the exactly-once spec exists to flag.
                # Head-address self-reports are excluded: those are
                # re-advertisements (local-arg publication, spill
                # restore), not executions; failover re-registration
                # lands on a FRESH head whose history starts empty.
                # The lineage row identifies the oid as an actor-call
                # output; the whole probe is gated so the uninstalled
                # hot path pays nothing for it.
                lspec = self.lineage.get(oid)
                if lspec is not None and \
                        getattr(lspec, "kind", None) == \
                        TaskKind.ACTOR_TASK:
                    sanitize_hooks.spec_op("spec.call.apply", "call",
                                           self, tid)
                    sanitize_hooks.spec_op("spec.call.apply", "ret",
                                           self, (tid, "applied"))
            # Lock-free membership prechecks keep the common case (no
            # pins, no reconstruction attempt) off the head lock
            # entirely. Safe: dict membership is GIL-atomic, and both
            # entries are written strictly BEFORE the dispatch whose
            # report this is (pins at record_inflight, the attempt at
            # reconstruct request), so by report time they are visible.
            if oid in self._recon_attempts or tid in self._task_pinned:
                with self._lock:
                    self._recon_attempts.pop(oid, None)
                    frees.extend(self._unpin_task_locked(tid))
        self._quota_release(finished)
        self._fan_out_frees(frees)
        # Wake the driver's fetch dispatcher for anything it awaits.
        notify = getattr(self.worker, "_fetch_notify", None)
        if notify is not None:
            notify(oids)
        return True

    def _report_spilled(self, oids, urls, node_id=None):
        """A node spilled objects to durable storage: record the URLs so
        reconstruction can restore from disk instead of re-executing
        when the node later dies. A None/empty url drops the record."""
        with self._lock:
            for oid, url in zip(oids, urls):
                if url:
                    self.object_spill_urls[oid] = url
                else:
                    self.object_spill_urls.pop(oid, None)
        return True

    def note_spilled(self, oid: bytes, url: Optional[str]) -> None:
        """In-process form of report_spilled (the head process's own
        store spills through the same directory)."""
        self._report_spilled([oid], [url])

    # -- dispatch bookkeeping (called by ClusterBackendMixin) -----------

    def record_lineage(self, spec) -> None:
        from ray_tpu._private.task_spec import TaskKind

        # Actor-task outputs are reconstructable iff the call has
        # retry budget (reference semantics: objects created by
        # actor tasks can be re-created when max_task_retries > 0;
        # re-execution routes through the restart gate like any
        # replay). Without budget the output is lost with its node
        # and the caller gets a typed ObjectLostError, never a
        # hang (see mark_node_dead's poison pass). Lineage writes are
        # shard-locked only: the lease submit path stops serializing
        # on the head lock here.
        router = self.shard_router
        if spec.kind in (TaskKind.NORMAL_TASK,
                         TaskKind.ACTOR_CREATION) or \
                (spec.kind == TaskKind.ACTOR_TASK
                 and spec.max_retries != 0):
            for oid in spec.return_ids:
                self.lineage[oid.binary()] = spec
                if router is not None:
                    # Durable lineage EDGE (oid -> creating task id):
                    # specs are code-bearing and stay coordinator-
                    # resident; the edge is what a failed-over head
                    # needs to tell "reconstructable" from "lost"
                    # before node re-reports refill the spec tables.
                    router.put("lineage", oid.binary(),
                               spec.task_id.binary())
        if spec.kind == TaskKind.ACTOR_CREATION:
            with self._lock:
                key = spec.actor_id.binary()
                self.actor_specs[key] = spec
            # Gate registration is idempotent: a restart's resubmitted
            # creation spec never resets a partially-consumed budget.
            # `restarts_used` rides the spec (incremented per restart,
            # shipped with it), so a FRESH gate — a failed-over head
            # whose nodes re-report their actors — seeds the budget
            # with the consumed count instead of resetting it
            # (ROADMAP FT gap c).
            self.actor_gate.register(spec.actor_id.binary(),
                                     getattr(spec, "max_restarts", 0),
                                     used=getattr(spec, "restarts_used",
                                                  0))
            if router is not None:
                # Durable restart budget: a failed-over head seeds a
                # fresh gate with the CONSUMED count (ROADMAP FT gap
                # c) even when the re-reporting node itself is gone.
                router.put("actors", spec.actor_id.binary(),
                           (getattr(spec, "max_restarts", 0),
                            getattr(spec, "restarts_used", 0)))

    def _unreserve_creation(self, node_id: str, spec) -> None:
        record = self.nodes.get(node_id)
        if record is not None:
            with self._lock:
                record.unreserve(_spec_milli_of(spec))

    def record_inflight(self, spec, node_id: str) -> None:
        # All kinds, actor calls included: a node death must *fail* an
        # in-flight actor call (typed ActorDiedError) rather than leave
        # its caller hanging on a never-located return object.
        tid = spec.task_id.binary()
        if sanitize_hooks.spec_taps_active and \
                spec.kind == TaskKind.ACTOR_TASK:
            # Exactly-once protocol tap (rayspec): one dispatch attempt
            # of this call is now in flight. `attempt` distinguishes a
            # replay's re-invocation from the original. Guarded like
            # every per-dispatch tap: uninstalled cost is one flag
            # read, no payload construction.
            sanitize_hooks.spec_op(
                "spec.call.invoke", "call", self,
                (tid, getattr(spec, "attempt", 0)))
            sanitize_hooks.spec_op("spec.call.invoke", "ret", self, tid)
        self.inflight[tid] = (node_id, spec)
        if self.shard_router is not None:
            # Durable in-flight row (tid -> node): what a failed-over
            # head re-derives the QuotaLedger's outstanding charges
            # from, keyed to survive on the owning shard alone.
            self.shard_router.put("inflight", tid, node_id)
        if spec.kind == TaskKind.ACTOR_CREATION:
            # Creation reservation: charge the placement against the
            # head's availability view NOW — the node's next report is
            # up to a report period away, and a creation burst placed
            # against one stale view pins a node with actors that can
            # never start (see _NodeRecord.reserved_milli).
            record = self.nodes.get(node_id)
            if record is not None:
                with self._lock:
                    record.reserve(_spec_milli_of(spec))
        # Pin arg objects for the task's lifetime: a driver release
        # racing the dispatch must not free an argument out from
        # under the executing task. Dep-free submissions (the fan-out
        # common case) skip the head lock entirely.
        deps = spec.nested_dependencies()
        if deps:
            with self._lock:
                pinned = []
                for dep in deps:
                    ob = dep.binary()
                    self.task_pins.setdefault(ob, set()).add(tid)
                    pinned.append(ob)
                self._task_pinned[tid] = pinned

    def clear_inflight(self, spec) -> None:
        tid = spec.task_id.binary()
        entry = self.inflight.pop(tid, None)
        if entry is not None and self.shard_router is not None:
            self.shard_router.delete("inflight", tid)
        if entry is not None and spec.kind == TaskKind.ACTOR_CREATION:
            self._unreserve_creation(entry[0], spec)
        frees = []
        if tid in self._task_pinned:  # GIL-atomic precheck (see report)
            with self._lock:
                frees = self._unpin_task_locked(tid)
        self._quota_release([spec])
        self._fan_out_frees(frees)

    def _quota_release(self, specs) -> None:
        """Release tenancy CPU charges for specs leaving the in-flight
        table (token-guarded: no-ops for unquota'd jobs and for specs
        whose charge a local execution already released). Actor
        CREATIONS are lifetime charges — they release at actor death
        (`release_actor_quota`), never at inflight-clear."""
        if not specs:
            return
        backend = getattr(self.worker, "backend", None)
        ledger = getattr(backend, "quota_ledger", None)
        if ledger is None:
            return
        for spec in specs:
            if spec.kind != TaskKind.ACTOR_CREATION:
                ledger.release_cpu(spec)

    def release_actor_quota(self, actor_id: bytes) -> None:
        """An actor died for real (tombstoned/killed): free its
        creation's lifetime CPU charge."""
        backend = getattr(self.worker, "backend", None)
        ledger = getattr(backend, "quota_ledger", None)
        if ledger is None:
            return
        with self._lock:
            spec = self.actor_specs.get(actor_id)
        if spec is not None:
            ledger.release_cpu(spec)

    def _unpin_task_locked(self, tid: bytes) -> list:
        frees = []
        for ob in self._task_pinned.pop(tid, ()):
            pins = self.task_pins.get(ob)
            if pins is not None:
                pins.discard(tid)
                if not pins:
                    del self.task_pins[ob]
                    frees.extend(self._maybe_free_locked(ob))
        return frees

    def _maybe_free_locked(self, oid: bytes) -> list:
        """If the driver released oid and nothing pins/borrows it any
        longer, free it for real. Returns [(addr, oid)] RPC work to do
        outside the lock."""
        if oid not in self.driver_released:
            return []
        if self.borrowers.get(oid) or self.task_pins.get(oid):
            return []
        self.driver_released.discard(oid)
        self.lineage.pop(oid, None)
        self._recon_attempts.pop(oid, None)
        self.object_spill_urls.pop(oid, None)
        self.object_sizes.pop(oid, None)
        loc = self.object_locations.pop(oid, None)
        if loc is not None and loc != self.server.address:
            return [(loc, oid)]
        return []

    def _fan_out_frees(self, frees: list) -> None:
        by_addr: Dict[Tuple[str, int], List[bytes]] = {}
        for addr, oid in frees:
            by_addr.setdefault(addr, []).append(oid)
        for addr, batch in by_addr.items():
            try:
                RpcClient.to(addr).call("free_objects", oids=batch)
            except Exception:
                pass

    def _add_borrowers(self, oids: List[bytes], node_id: str) -> bool:
        with self._lock:
            for oid in oids:
                self.borrowers.setdefault(oid, set()).add(node_id)
        return True

    def _remove_borrowers(self, oids: List[bytes], node_id: str) -> bool:
        frees = []
        with self._lock:
            for oid in oids:
                holders = self.borrowers.get(oid)
                if holders is not None:
                    holders.discard(node_id)
                    if not holders:
                        del self.borrowers[oid]
                        frees.extend(self._maybe_free_locked(oid))
        self._fan_out_frees(frees)
        return True

    # -- health checking -------------------------------------------------

    def _ensure_health_checker(self):
        from ray_tpu._private.config import ray_config

        with self._lock:
            if self._health_thread is not None or \
                    ray_config.health_check_period_s <= 0:
                return
            self._health_thread = threading.Thread(
                target=self._health_loop, daemon=True,
                name="ray_tpu-health-check")
            self._health_thread.start()

    def _health_loop(self):
        from ray_tpu._private.config import ray_config

        failures: Dict[str, int] = {}
        while not self._health_stop.wait(ray_config.health_check_period_s):
            self.poll_shards()
            with self._lock:
                records = [n for n in self.nodes.values() if n.alive]
            fresh_window = ray_config.resource_report_period_s * \
                ray_config.resource_report_fresh_periods
            for record in records:
                # A recent pushed resource report doubles as a heartbeat:
                # no need to burn an RPC on it.
                if time.monotonic() - record.last_report < fresh_window:
                    failures[record.node_id] = 0
                    continue
                try:
                    RpcClient.to(record.address).call("ping")
                    failures[record.node_id] = 0
                except Exception:
                    count = failures.get(record.node_id, 0) + 1
                    failures[record.node_id] = count
                    if count >= ray_config.health_check_failure_threshold:
                        self.mark_node_dead(record.node_id,
                                            reason="health check failed")

    def poll_shards(self) -> list:
        """Supervise the head shard processes: restart any crashed one
        from its own durable db (acked rows reload) and bump the shard
        epoch so every node's next report returns False once — the
        re-registration path repopulates the crashed shard's lost
        commit window. Returns the restarted shard indices."""
        router = self.shard_router
        if router is None:
            return []
        restarted = router.poll()
        try:
            self._shard_stats_cache = {row["index"]: row
                                       for row in router.stats()}
            self._fold_shard_commit_stats(self._shard_stats_cache)
        except Exception:
            pass
        if restarted:
            from ray_tpu._private.events import record_event

            with self._lock:
                self._shard_epoch += 1
            record_event(
                "head", f"head shard(s) {restarted} restarted; nodes "
                f"will re-register (epoch {self._shard_epoch})",
                severity="WARNING", shards=list(restarted))
        return restarted

    def _fold_shard_commit_stats(self, cache: dict) -> None:
        """Fold shard-side group-commit progress into the coordinator's
        fast-path stats so runtime_metrics exports
        ``ray_tpu_head_shard_commit_seconds_p50/_p95{shard}``: the
        shard processes keep their own counters, so the supervisor's
        poll records the mean window duration of the commits completed
        since the previous poll."""
        from ray_tpu._private import perf_stats

        last = getattr(self, "_shard_commit_seen", None)
        if last is None:
            last = self._shard_commit_seen = {}
        for index, row in cache.items():
            commits = row.get("commits")
            if commits is None:
                continue
            seen_n, seen_s = last.get(index, (0, 0.0))
            total_s = row.get("commit_seconds_total", 0.0)
            if commits > seen_n:
                perf_stats.latency(
                    "head_shard_commit_seconds",
                    {"shard": str(index)}).record(
                        (total_s - seen_s) / (commits - seen_n))
            last[index] = (commits, total_s)

    def shard_health(self) -> list:
        """Per-shard verdicts for /api/healthz: liveness + streamed
        backlog read locally (the provider contract forbids RPC here),
        merged with the shard-side stats the supervisor's last poll
        cached (rows held, group-commit count/latency)."""
        router = self.shard_router
        if router is None:
            return []
        cache = getattr(self, "_shard_stats_cache", {})
        out = []
        for row in router.local_stats():
            verdict = "ok" if row.get("alive") else "dead"
            if row.get("alive") and row.get("backlog", 0) > 4096:
                verdict = "backlogged"
            merged = {"shard": row.get("index"), "verdict": verdict,
                      "backlog": row.get("backlog", 0)}
            cached = cache.get(row.get("index"))
            if cached:
                merged.update({k: cached[k] for k in
                               ("applied", "rows", "commits",
                                "last_commit_s") if k in cached})
            out.append(merged)
        return out

    def _shard_degraded_reasons(self) -> list:
        return [f"head shard {row['shard']} {row['verdict']}"
                for row in self.shard_health()
                if row["verdict"] != "ok"]

    def stop(self):
        self._health_stop.set()
        if self.shard_router is not None:
            from ray_tpu._private import health as _health

            _health.unregister_section_provider("head_shards")
            _health.unregister_degraded_provider("head_shards")
            self.shard_router.close()
            self.shard_router = None

    # -- node death + recovery -------------------------------------------

    def mark_node_dead(self, node_id: str, reason: str = "") -> None:
        """Purge the dead node from the directory and re-execute what it
        held: in-flight tasks are resubmitted, its actors restarted on
        surviving nodes (within max_restarts), and objects it owned are
        left to on-demand lineage reconstruction (`_maybe_reconstruct`).
        Reference: `gcs_node_manager` death flow + `task_manager.h`
        resubmit + `object_recovery_manager.h:106`.
        """
        with self._lock:
            record = self.nodes.get(node_id)
            if record is None or not record.alive:
                return
            record.alive = False
            addr = record.address
            # Objects whose only copy was there are gone. (Their spill
            # URLs — durable disk copies — survive in
            # object_spill_urls: reconstruction restores from those
            # first.)
            # Sharded-table scans under the head lock are fine (shard
            # locks are leaf locks); per-shard snapshots are consistent
            # enough — a report racing the sweep could always land
            # wholly before or after it.
            lost = [oid for oid, loc in self.object_locations.items()
                    if loc == addr]
            lost_bytes = sum(self.object_sizes.get(oid, 0)
                             for oid in lost)
            router = self.shard_router
            for oid in lost:
                self.object_locations.pop(oid, None)
                self.object_sizes.pop(oid, None)
                if router is not None:
                    router.delete("objects", oid)
                    router.delete("sizes", oid)
            resubmit = [spec for (nid, spec) in self.inflight.values()
                        if nid == node_id]
            for spec in resubmit:
                self.inflight.pop(spec.task_id.binary(), None)
                if router is not None:
                    router.delete("inflight", spec.task_id.binary())
            from ray_tpu._private.events import record_event

            # The death event carries the damage assessment: what the
            # recovery machinery now has to make good on.
            record_event("node", f"node {node_id} marked dead: {reason}",
                         severity="ERROR", node_id=node_id,
                         lost_objects=len(lost),
                         lost_bytes=int(lost_bytes),
                         inflight_tasks=len(resubmit))
            _NODE_DEATHS.inc()
            _NODE_DEATH_LOST_BYTES.inc(int(lost_bytes))
            # A dead node can no longer borrow anything; dropping it may
            # unblock deferred frees (fanned out after the lock).
            dead_frees = []
            for oid in [o for o, holders in self.borrowers.items()
                        if node_id in holders]:
                holders = self.borrowers[oid]
                holders.discard(node_id)
                if not holders:
                    del self.borrowers[oid]
                    dead_frees.extend(self._maybe_free_locked(oid))
            dead_actors = [aid for aid, nid in self.actor_nodes.items()
                           if nid == node_id]
            # Bundles reserved there are gone; tasks targeting them fail
            # with PlacementGroupSchedulingError until re-reserved.
            for key, nid in list(self.pg_bundle_nodes.items()):
                if nid == node_id:
                    del self.pg_bundle_nodes[key]
        logging.getLogger(__name__).warning(
            "node %s marked dead (%s): %d objects lost, %d tasks in "
            "flight, %d actors", node_id, reason, len(lost),
            len(resubmit), len(dead_actors))
        # Unrecoverable losses fail FAST: a lost object with no lineage
        # (e.g. a zero-retry actor call's output) and no durable spill
        # copy can never be produced again — a waiting get must raise a
        # typed ObjectLostError, not hang out its deadline. put() is a
        # no-op on entries the driver already resolved.
        from ray_tpu.exceptions import ObjectLostError

        with self._lock:
            unrecoverable = [
                oid for oid in lost
                if oid not in self.lineage
                and oid not in self.object_spill_urls]
        for oid in unrecoverable:
            if not self.worker.memory_store.contains(ObjectID(oid)):
                self.worker.memory_store.put(
                    ObjectID(oid), None, error=ObjectLostError(
                        oid.hex()[:12],
                        f"object {oid.hex()[:12]} was lost when node "
                        f"{node_id} died and has no lineage or spilled "
                        f"copy to recover from"))
        self.publisher.publish("node_events", {
            "event": "NODE_DEAD", "node_id": node_id, "reason": reason})
        # A dead node stops scraping-by-proxy: drop its metric snapshot
        # so the merged exposition doesn't freeze its last values
        # forever (its task events stay — history outlives the node).
        self.obs.forget_node(node_id)
        self._fan_out_frees(dead_frees)
        # An actor whose CREATION was still in flight on the dead node
        # is not restarting — it never finished constructing. The
        # resubmit loop re-drives the creation under the spec's own
        # max_retries; routing it through _restart_actor too would
        # double-submit the creation AND burn restart budget on a
        # first attempt.
        inflight_creations = {
            spec.actor_id.binary() for spec in resubmit
            if spec.kind == TaskKind.ACTOR_CREATION}
        # Restart actors first so resubmitted / queued actor tasks find a
        # live location.
        for aid in dead_actors:
            if aid in inflight_creations:
                with self._lock:
                    self.actor_nodes.pop(aid, None)
                continue
            self._restart_actor(aid, node_id)
        # Dead-node tasks left the in-flight table: release their
        # tenancy CPU charges BEFORE the resubmit re-enters admission
        # (a replay must re-acquire like any dispatch, not double-hold).
        # _quota_release itself keeps creations' lifetime charges held
        # through the restart, and actor-task releases are token-
        # guarded no-ops.
        self._quota_release(resubmit)
        for spec in resubmit:
            if spec.kind == TaskKind.ACTOR_TASK:
                # Replay-or-reject (reference: max_task_retries covers
                # system failures): a call with retry budget replays
                # against the restarted actor; one without rejects with
                # an error naming the restart state and budgets.
                self.recover_actor_call(spec)
                continue
            self._resubmit_lost_task(spec, node_id)

    def _restart_actor(self, actor_id: bytes, dead_node: str) -> None:
        with self._lock:
            spec = self.actor_specs.get(actor_id)
            self.actor_nodes.pop(actor_id, None)
        reason = f"its node {dead_node} died"
        if spec is None:
            self.actor_gate.mark_dead(
                actor_id, reason + " and no creation spec is recorded")
            return
        if not self.actor_gate.begin_restart(actor_id, reason):
            # Budget exhausted: tombstoned by the gate — later calls
            # fail FAST with the cause, instead of falling through to a
            # backend that has never heard of the actor. The dead
            # actor's lifetime CPU charge frees with it.
            _restart_counter("exhausted").inc()
            self.release_actor_quota(actor_id)
            return
        _restart_counter("restarted").inc()
        # The consumed-restart count travels ON the spec: the node
        # hosting the replacement re-reports it on head failover, so a
        # fresh gate never resets a partially-spent budget.
        spec.restarts_used = getattr(spec, "restarts_used", 0) + 1
        # Re-run the creation spec through the normal scheduler; it
        # re-registers the actor's node on dispatch (set_actor_node →
        # gate.ready releases parked callers).
        self._resubmit(spec)

    def set_actor_node(self, actor_id: bytes, node_id: str) -> None:
        """The ONE place an actor gains a live location: directory entry
        plus the gate's RESTARTING→ALIVE edge (parked calls dispatch)."""
        with self._lock:
            self.actor_nodes[actor_id] = node_id
            self.actor_local.discard(actor_id)
        self.actor_gate.ready(actor_id)

    def recover_actor_call(self, spec) -> None:
        """An actor call that was in flight on (or failed to reach) a
        dead node: gate-decided replay-or-reject.

        Caller-side dedupe on return-object identity first (ROADMAP FT
        gap a): the death sweep's in-flight snapshot races the call's
        output REPORT — a call whose output was already applied by the
        time we decide here EXECUTED; replaying it would run its
        effects twice and burn its retry budget on a success. "Applied"
        is judged by the call's own return objects: already resolved in
        the caller's store, located on a surviving node, or durably
        spilled. An output genuinely lost with the node (none of the
        above) still replays — that residual window is the documented
        at-least-once slice reference semantics share."""
        if self._call_output_applied(spec):
            _restart_counter("call_deduped").inc()
            with self._lock:
                frees = self._unpin_task_locked(spec.task_id.binary())
            self._fan_out_frees(frees)
            return

        def resubmit(s):
            _restart_counter("call_replayed").inc()
            self._resubmit(s)

        def fail(s, msg, dead):
            _restart_counter("call_rejected").inc()
            self._fail_actor_call(s, msg, dead)

        self.actor_gate.recover_call(spec, resubmit, fail)

    def _call_output_applied(self, spec) -> bool:
        """Every return object of the call is already obtainable — the
        dedupe predicate for replay decisions (see
        recover_actor_call)."""
        if not spec.return_ids:
            return False
        for oid in spec.return_ids:
            ob = oid.binary()
            if self.worker.memory_store.contains(oid):
                continue
            if ob in self.object_spill_urls:
                continue
            loc = self.object_locations.get(ob)
            if loc is not None and self._addr_alive(loc):
                continue
            return False
        return True

    def _addr_alive(self, addr) -> bool:
        addr = tuple(addr)
        with self._lock:
            return any(record.alive and record.address == addr
                       for record in self.nodes.values())

    def _addr_dead(self, addr) -> bool:
        """The address belongs to a node marked dead (an UNKNOWN
        address answers False: in-process self-reports have no node
        record and must keep flowing)."""
        addr = tuple(addr)
        with self._lock:
            return any(not record.alive and record.address == addr
                       for record in self.nodes.values())

    def _fail_actor_call(self, spec, msg: str, dead: bool) -> None:
        from ray_tpu.exceptions import ActorDiedError, \
            ActorUnavailableError

        err = ActorDiedError(spec.actor_id.hex()[:8], msg) if dead \
            else ActorUnavailableError(msg)
        for oid in spec.return_ids:
            self.worker.memory_store.put(oid, None, error=err)
        with self._lock:
            frees = self._unpin_task_locked(spec.task_id.binary())
        self._fan_out_frees(frees)

    def _resubmit_lost_task(self, spec, node_id: str) -> None:
        """Node-death resubmit with per-spec retry accounting
        (reference: max_retries covers worker/node failures): each
        death consumes one unit of the spec's own budget — and rides
        the wire on the resubmitted TaskCall — instead of resubmitting
        unconditionally forever."""
        from ray_tpu import exceptions as exc

        if spec.max_retries == 0:
            attempts = getattr(spec, "attempt", 0)
            for oid in spec.return_ids:
                self.worker.memory_store.put(
                    oid, None, error=exc.TaskError(
                        exc.WorkerCrashedError(
                            f"node {node_id} died with the task in "
                            f"flight and its retry budget is exhausted "
                            f"(attempt {attempts + 1}, 0 retries left)"),
                        spec.describe()))
            with self._lock:
                frees = self._unpin_task_locked(spec.task_id.binary())
            self._fan_out_frees(frees)
            return
        if spec.max_retries > 0:
            spec.max_retries -= 1
        spec.attempt = getattr(spec, "attempt", 0) + 1
        self._resubmit(spec)

    def _resubmit(self, spec) -> None:
        try:
            self.worker.backend.submit(spec)
        except Exception as e:  # pragma: no cover - best effort
            from ray_tpu import exceptions as exc

            for oid in spec.return_ids:
                self.worker.memory_store.put(
                    oid, None, error=exc.TaskError(e, spec.describe()))
            # The task will never complete: drop its arg pins or any
            # driver-released arg stays pinned (and unfreed) forever.
            with self._lock:
                frees = self._unpin_task_locked(spec.task_id.binary())
            self._fan_out_frees(frees)

    def release_objects(self, oids: List[bytes]) -> None:
        """Driver refcount hit zero. Objects still borrowed by a node or
        pinned by an in-flight task's args defer their free until the
        last holder drops (reference: ReferenceCounter borrower
        protocol); the rest free immediately."""
        frees = []
        with self._lock:
            for oid in oids:
                self.driver_released.add(oid)
                frees.extend(self._maybe_free_locked(oid))
        self._fan_out_frees(frees)

    def unrelease_objects(self, oids: List[bytes]) -> None:
        """The driver re-acquired a handle (e.g. an actor returned a
        borrowed ref back): a pending deferred release must not fire
        when the last borrower later drops."""
        with self._lock:
            for oid in oids:
                self.driver_released.discard(oid)

    def _maybe_reconstruct(self, oid: bytes, _chain=None) -> None:
        """On-demand lineage reconstruction: a requested object with no
        live copy restores from its durable spilled copy when one is
        known, else re-executes its creating task — and does so
        TRANSITIVELY: a re-executed task whose own arguments were also
        lost reconstructs them first (depth/cycle-guarded; each object
        charged its own max_reconstruction_attempts)."""
        from ray_tpu._private.config import ray_config

        if not ray_config.enable_object_reconstruction:
            return
        with self._lock:
            spec = self.lineage.get(oid)
            spill_url = self.object_spill_urls.get(oid)
            # A durable spilled copy is recoverable WITHOUT lineage
            # (e.g. a zero-retry actor call's spilled output), so the
            # spill check must not sit behind the lineage requirement.
            if spec is None and spill_url is None:
                return
            if spec is not None and \
                    spec.task_id.binary() in self.inflight:
                return  # already being re-executed
            attempts = self._recon_attempts.get(oid, 0)
            if attempts >= ray_config.max_reconstruction_attempts:
                _recon_counter("exhausted").inc()
                return
            self._recon_attempts[oid] = attempts + 1
        sanitize_hooks.sched_point("recon.request")
        if spill_url is not None and \
                self._restore_from_spill(oid, spill_url):
            _recon_counter("from_spill").inc()
            return
        if spec is None:
            # The spill copy was the ONLY recovery path and it is gone
            # (stale URL): poison waiting gets now — never a hang.
            from ray_tpu.exceptions import ObjectLostError

            object_id = ObjectID(oid)
            if not self.worker.memory_store.contains(object_id):
                self.worker.memory_store.put(
                    object_id, None, error=ObjectLostError(
                        oid.hex()[:12],
                        f"object {oid.hex()[:12]} has no lineage and "
                        f"its spilled copy could not be restored"))
            return
        # Cycle/depth guard for the recursive walk: a lineage loop (or a
        # pathological chain) terminates; the per-object attempt charge
        # above remains the authoritative bound.
        chain = _chain if _chain is not None else set()
        tid = spec.task_id.binary()
        if tid in chain or \
                len(chain) >= ray_config.max_reconstruction_depth:
            return
        chain = chain | {tid}
        # Transitive: re-executing this spec needs its args resident
        # somewhere — eagerly reconstruct the ones that are lost too,
        # so the re-execution's dep fetch finds (or soon finds) them
        # instead of burning its whole deadline polling.
        for dep in spec.nested_dependencies():
            db = dep.binary()
            with self._lock:
                have = db in self.object_locations
            if not have and not self.worker.memory_store.contains(dep):
                self._maybe_reconstruct(db, chain)
        logging.getLogger(__name__).info(
            "reconstructing object %s via lineage (attempt %d)",
            oid.hex()[:12], attempts + 1)
        _recon_counter("reexecute").inc()
        sanitize_hooks.sched_point("recon.resubmit")
        self._resubmit(spec)

    def _restore_from_spill(self, oid: bytes, url: str) -> bool:
        """Restore a lost object from its durable spilled payload: the
        surviving copy IS the object — no re-execution. The restored
        value republishes through the object plane (share_value) so
        outstanding descriptors and cross-node reads stay valid."""
        sanitize_hooks.sched_point("recon.restore")
        from ray_tpu._private.spilling import restore_spilled_payload

        try:
            value = restore_spilled_payload(url)
        except Exception:
            # Stale URL (file reclaimed, dead node's dir destroyed):
            # drop the record and fall back to re-execution.
            with self._lock:
                self.object_spill_urls.pop(oid, None)
            return False
        object_id = ObjectID(oid)
        self.worker.memory_store.put(object_id, value)
        from ray_tpu._private.shm_plane import share_value

        share_value(self.worker, object_id, value)
        logging.getLogger(__name__).info(
            "restored lost object %s from spilled copy %s",
            oid.hex()[:12], url)
        # The head itself now owns a live copy: advertise it (also
        # wakes the driver's fetch dispatcher for waiting gets).
        self._report_objects([oid], self.server.address)
        return True

    def _locate(self, oid: bytes):
        """Owner's RPC address, or None. (Legacy callers; see _locate2.)"""
        info = self._locate2(oid)
        return info["address"] if info else None

    def _locate2(self, oid: bytes):
        """Rich location: {"address", "transfer", "shm"} of the owner.
        A miss for an object with known lineage kicks off reconstruction
        (the caller keeps polling and picks up the re-executed result)."""
        with self._lock:
            loc = self.object_locations.get(oid)
            if loc is not None:
                for n in self.nodes.values():
                    if n.address == loc:
                        return {"address": loc, "transfer": n.transfer,
                                "shm": n.shm_name}
                if loc == self.server.address:
                    return self._self_location()
                return {"address": loc, "transfer": None, "shm": None}
        if self.worker.memory_store.contains(ObjectID(oid)):
            return self._self_location()
        self._maybe_reconstruct(oid)
        return None

    def _self_location(self):
        plane = getattr(self.worker, "shm_plane", None)
        return {"address": self.server.address,
                "transfer": getattr(self, "transfer_addr", None),
                "shm": plane.name if plane else None}

    def _get_object(self, oid: bytes, timeout: float = 30.0):
        object_id = ObjectID(oid)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            ready, value, error = self.worker.memory_store.peek(object_id)
            if ready:
                return True, value, error
            time.sleep(0.005)
        return False, None, None

    def _locate_batch(self, oids):
        """One RPC locates a whole dependency set (batched arg-fetch:
        the per-arg locate round trips were the forced-remote dispatch
        tax)."""
        return [self._locate2(oid) for oid in oids]

    def _get_objects_batch(self, oids, timeout: float = 30.0,
                           shm=None, can_pull: bool = False):
        return descriptor_object_read(
            self.worker, getattr(self, "transfer_addr", None),
            lambda oid, t: self._get_object(oid, timeout=t), oids,
            timeout, shm=shm, can_pull=can_pull)

    def _route_task(self, spec) -> bool:
        """Submit a node-originated spec through the head's cluster
        backend (which knows where every actor lives); results travel
        back through the object plane like any other output."""
        self.worker.backend.submit(spec)
        return True

    def _report_actor(self, spec, node_id: str,
                      restarts_used: Optional[int] = None) -> bool:
        """An actor created LOCALLY inside a node process registers with
        the head's directory, so handles to it route from anywhere and
        it gets the same restart bookkeeping as head-dispatched actors.
        ``restarts_used`` rides a node's RE-report after head failover:
        the fresh gate must seed the budget with what the actor already
        consumed (head-driven restarts on the spec + node-local worker
        restarts), not reset it (ROADMAP FT gap c)."""
        if restarts_used is not None:
            spec.restarts_used = max(
                getattr(spec, "restarts_used", 0), int(restarts_used))
        self.record_lineage(spec)
        self.set_actor_node(spec.actor_id.binary(), node_id)
        return True

    def _report_actors(self, specs, node_id: str,
                       restarts_used=None) -> bool:
        """Group-committed actor registration: one RPC registers a
        whole node's actors (same record_lineage/restart-gate calls as
        the singular form — semantics unchanged, transport O(batches))."""
        for i, spec in enumerate(specs):
            used = restarts_used[i] if restarts_used is not None \
                and i < len(restarts_used) else None
            self._report_actor(spec, node_id, restarts_used=used)
        return True

    def _named_actor_register(self, name, namespace, handle) -> bool:
        self.worker.gcs.register_named_actor(name, namespace, handle)
        return True

    def _named_actor_get(self, name, namespace):
        return self.worker.gcs.get_named_actor(name, namespace)

    def _named_actor_remove(self, actor_id: bytes) -> bool:
        from ray_tpu._private.ids import ActorID

        self.worker.gcs.remove_named_actor_by_id(ActorID(actor_id))
        return True

    def _obs_report(self, node_id: str, events=None, metrics=None,
                    stages=None):
        return self.obs.report(node_id, events=events, metrics=metrics,
                               stages=stages)

    @staticmethod
    def _gcs_events(limit: int = 200, source=None):
        from ray_tpu._private.events import list_events

        return list_events(limit=limit, source=source)

    @staticmethod
    def _gcs_record_event(source: str, message: str,
                          severity: str = "INFO", metadata=None):
        """Node-forwarded event lands in the head's (observable) buffer."""
        from ray_tpu._private.events import record_event

        record_event(source, message, severity=severity,
                     **(metadata or {}))
        return True

    def _gcs_pg_table(self):
        """Placement-group table as PLAIN data: the in-process table
        holds PlacementGroup handles whose unpickling side-effects a
        full local runtime into an external tool's process."""
        table = self.worker.gcs.placement_group_table()

        def plain(v):
            if isinstance(v, dict):
                return {str(k): plain(x) for k, x in v.items()}
            if isinstance(v, (list, tuple)):
                return [plain(x) for x in v]
            if isinstance(v, (str, int, float, bool, type(None), bytes)):
                return v
            return str(v)

        return plain(table)

    def _get_nodes(self):
        with self._lock:
            return [
                {"NodeID": n.node_id, "Address": n.address,
                 "Resources": n.resources, "Alive": n.alive,
                 "Available": n.available, "Labels": n.labels,
                 "Stats": n.stats}
                for n in self.nodes.values()
            ]


class ClusterBackendMixin:
    """Installed over the driver's LocalBackend: route specs to nodes."""

    def __init__(self, worker, head: ClusterHead):
        self.worker = worker
        self.head = head
        self.local_backend = worker.backend
        self._rr = 0
        # Lease-based decentralized dispatch (reference:
        # `direct_task_transport.h:75,211` + `lease_policy.h:56`): the
        # head's scheduler is consulted ONCE per task shape to pick a
        # node (locality-aware); subsequent same-shape tasks stream to
        # the leased node over a pipelined channel with no per-task
        # scheduling or round-trip. Leases are returned after
        # `_LEASE_IDLE_S` idle; backlog flows back on resource reports
        # (and, past `sched_spillback_backlog`, spills the lease to a
        # better target). Lease state is LOCK-PARTITIONED by (job,
        # shape) key so concurrent submitters of different shapes never
        # serialize; `_lease_lock` remains the channel/global lock
        # (pipes, batchers, drainer spawn). Ordering rule: shard locks
        # before `_lease_lock`, never the reverse; whole-table
        # operations take every shard lock in index order first.
        self._leases: Dict[tuple, list] = {}
        n_shards = sched_state.round_up_pow2(ray_config.sched_head_shards)
        self._lease_locks = [threading.Lock() for _ in range(n_shards)]
        self._lease_lock = threading.Lock()
        self._pipes: Dict[str, Any] = {}  # node_id -> PipelinedClient
        # node_id -> CoalescingBatcher feeding that node's pipe with
        # submit_batch frames (batched control RPC: many submissions,
        # one framed request + one server dispatch), plus the per-node
        # lock making template-claim + enqueue atomic.
        self._batchers: Dict[str, Any] = {}
        self._submit_locks: Dict[str, Any] = {}
        # (node_id, oid) pairs already pushed (push_manager dedupe).
        self._pushed: set = set()
        # Tenancy: over-CPU-quota specs park in the shared ledger; ONE
        # drainer thread resubmits them as their jobs free capacity
        # (lazily spawned, retires when the park list drains). Actor
        # calls parked for a restart window share the same design: one
        # dispatcher draining the parked list on gate.wait_change —
        # NOT a waiter thread per call.
        self._quota_stop = threading.Event()
        self._quota_drainer: Optional[threading.Thread] = None
        self._parked_calls: list = []
        self._park_lock = threading.Lock()
        self._park_thread: Optional[threading.Thread] = None
        self._fallback_ledger = None

    @property
    def quota_ledger(self):
        # Shared with the local backend (one ledger per head process);
        # harness-built mixins over a stub backend get their own.
        ledger = getattr(self.local_backend, "quota_ledger", None)
        if ledger is None:
            if self._fallback_ledger is None:
                self._fallback_ledger = tenancy.QuotaLedger()
            ledger = self._fallback_ledger
        return ledger

    def submit(self, spec) -> None:
        head = self.head
        if spec.kind == TaskKind.ACTOR_TASK:
            aid = spec.actor_id.binary()
            node_id = head.actor_nodes.get(aid)
            if node_id is not None:
                record = head.nodes.get(node_id)
                if record is None or not record.alive:
                    # The directory still points at a dead node (the
                    # death sweep hasn't run or finished): run it, then
                    # let the gate decide replay-or-reject for THIS
                    # call like any other call caught by the death.
                    # The stale mapping is dropped FIRST — a replay
                    # resubmit must route through the gate, not recurse
                    # back into this branch (mark_node_dead is a no-op
                    # for an already-removed record and would pop
                    # nothing).
                    head.mark_node_dead(node_id,
                                        reason="found dead at dispatch")
                    with head._lock:
                        if head.actor_nodes.get(aid) == node_id:
                            head.actor_nodes.pop(aid, None)
                    head.recover_actor_call(spec)
                    return
                try:
                    self._send(record, spec)
                except (ConnectionError, OSError) as e:
                    # Transport failure: the node itself is
                    # unreachable. mark_node_dead restarts the actor
                    # elsewhere (budget permitting); this call then
                    # replays against the replacement when its own
                    # max_task_retries covers it, else rejects with an
                    # error naming the restart state and budget.
                    head.mark_node_dead(node_id,
                                        reason=f"unreachable: {e}")
                    head.recover_actor_call(spec)
                except Exception as e:
                    # Handler-level error: the node is healthy, this
                    # submission failed — fail the task, keep the node.
                    self._fail_spec(spec, e)
                return
            from ray_tpu._private.actor_gate import ActorRestartState

            state = head.actor_gate.state(aid)
            if state == ActorRestartState.DEAD:
                # Tombstoned (restart budget exhausted): fail FAST with
                # the recorded cause — never fall through to the local
                # backend, which has no such actor and would bury the
                # call behind a generic "unknown actor".
                self._fail_spec(spec, ActorDiedError(
                    spec.actor_id.hex()[:8],
                    head.actor_gate.death_cause(aid)
                    or "restart budget exhausted"))
                return
            if state == ActorRestartState.RESTARTING:
                head.actor_gate.route_call(
                    spec, dispatch=None,
                    park=self._park_actor_call,
                    fail=head._fail_actor_call)
                return
            if state is not None and aid not in head.actor_local:
                # Gate-registered (cluster-dispatched) actor, no
                # location, and not known to live on the head: we
                # raced the death sweep's window between
                # record.alive=False and the gate's RESTARTING flip.
                # Park — falling through to the local backend would
                # fail a retryable call with a generic "unknown
                # actor".
                self._park_actor_call(spec)
                return
            self._submit_local(spec)
            return
        # Tenancy quotas, BEFORE any placement work (reference: lease
        # admission policies): a job at its queued-task ceiling is
        # rejected with a typed error; a job at its CPU quota parks the
        # spec in the ledger — behind its OWN limit, consuming no
        # cluster capacity — until one of its running tasks releases.
        # Both checks are idempotent per spec, so quota-drained
        # resubmits and the local backend's own admission never
        # double-charge.
        if spec.kind in (TaskKind.NORMAL_TASK, TaskKind.ACTOR_CREATION):
            ledger = self.quota_ledger
            reason = ledger.note_queued(spec)
            if reason is not None:
                from ray_tpu.exceptions import JobQuotaExceededError

                self._fail_spec(spec, JobQuotaExceededError(
                    spec.job_id or "", reason))
                return
            if not ledger.try_acquire_cpu(spec):
                if spec.kind == TaskKind.ACTOR_CREATION:
                    # Register the gate BEFORE parking the creation:
                    # method calls submitted meanwhile then park at
                    # the restart gate (ALIVE, no location yet) and
                    # dispatch when the creation finally lands,
                    # instead of failing against an unknown actor.
                    head.record_lineage(spec)
                ledger.park(spec)
                self._ensure_quota_drainer()
                return
        # Strategy-directed routing (reference: the scheduling-policy set
        # of `scheduling/policy/` — PG-affinity, node-affinity, spread).
        routed = self._route_by_strategy(spec)
        if routed is not False:
            return
        # Plain tasks: ONE local-fit check decides — fits → straight to
        # the local backend (the hot path; _choose_node would conclude
        # the same after redundant work); doesn't fit → ride a held
        # lease without per-task head scheduling.
        from ray_tpu._private.task_spec import DefaultSchedulingStrategy

        if spec.kind == TaskKind.NORMAL_TASK and \
                isinstance(spec.scheduling_strategy,
                           (DefaultSchedulingStrategy, type(None))):
            request = _spec_milli_of(spec)
            if self._local_fits_now(request):
                # Locality override: a task whose large args live on a
                # remote node should follow the bytes, not pull them
                # here to follow a small spec.
                if self._locality_prefers_remote(spec) and \
                        self._lease_submit(spec, request):
                    return
                self._submit_local(spec)
                return
            if self._lease_submit(spec, request):
                return
        # Normal tasks / actor creations: try nodes until one accepts.
        attempted: set = set()
        while True:
            target = self._choose_node(spec, exclude=attempted)
            if target is None:
                from ray_tpu._private.resources import to_milli

                request = _spec_milli_of(spec)
                local_total = to_milli(dict(
                    self.local_backend.resources.total))
                if all(local_total.get(k, 0) >= v
                       for k, v in request.items()):
                    if spec.kind != TaskKind.ACTOR_CREATION:
                        # A head-local task may still depend on remote
                        # objects.
                        self._submit_local(spec)
                        return
                    # Lifetime placement: a creation queued on the head
                    # behind lifetime-pinned actor CPUs NEVER constructs
                    # (actors don't release), while a remote node whose
                    # stale report reads full may free on its next
                    # report cycle. Land it locally only when it can
                    # construct NOW; otherwise queue cluster-wide and
                    # let fresh reports (or a local release) decide.
                    if self._submit_local_if_fits(spec, request):
                        return
                # Too big for the head and no remote capacity *right now*:
                # queue cluster-wide (the reference raylet queues leases),
                # failing fast only if no live node could ever fit it.
                if spec.kind == TaskKind.ACTOR_CREATION:
                    # Register the gate BEFORE queueing (mirrors the
                    # quota-park arm): method calls submitted while the
                    # creation waits for capacity park at the gate
                    # (ALIVE, no location yet) and dispatch when it
                    # lands, instead of failing "unknown actor".
                    head.record_lineage(spec)
                self._queue_for_cluster(spec, request)
                return
            if spec.kind == TaskKind.ACTOR_CREATION:
                head.set_actor_node(spec.actor_id.binary(), target.node_id)
                if ray_config.sched_group_actor_creation and \
                        self._send_creation_batched(target, spec):
                    return
            try:
                self._send(target, spec)
                return
            except (ConnectionError, OSError) as e:
                # Not yet in the in-flight table (that happens only after
                # a successful send), so mark_node_dead won't resubmit
                # this spec — the loop retries it on another node.
                attempted.add(target.node_id)
                if spec.kind == TaskKind.ACTOR_CREATION:
                    # Unwind the never-landed placement BEFORE the
                    # death sweep: the sweep must not see this aid in
                    # its dead-actor set — begin_restart would burn
                    # restart budget (tombstoning a max_restarts=0
                    # actor forever) for a creation the loop is about
                    # to retry cleanly elsewhere. The gate's ALIVE flip
                    # rolls back too, so concurrent calls park instead
                    # of dispatching into a backend that has never
                    # heard of the actor.
                    head.actor_nodes.pop(spec.actor_id.binary(), None)
                    head.actor_gate.rollback_ready(
                        spec.actor_id.binary())
                head.mark_node_dead(target.node_id,
                                    reason=f"unreachable: {e}")

    def _fail_spec(self, spec, error: Exception) -> None:
        # Terminal: release any tenancy charges the spec still holds
        # (token-guarded no-ops otherwise).
        ledger = self.quota_ledger
        ledger.note_dequeued(spec)
        ledger.release_cpu(spec)
        store = self.worker.memory_store
        for oid in spec.return_ids:
            store.put(oid, None, error=error)

    def _ensure_quota_drainer(self) -> None:
        with self._lease_lock:
            t = self._quota_drainer
            if t is not None and t.is_alive():
                return
            self._quota_drainer = threading.Thread(
                target=self._quota_drain_loop, daemon=True,
                name="ray_tpu-quota-drain")
            self._quota_drainer.start()

    def _quota_drain_loop(self) -> None:
        """ONE thread drains the quota park list (never a thread per
        parked spec): as a job's running tasks release their CPU
        charges, its parked specs are popped — charged atomically under
        the ledger lock — and re-enter the normal scheduling path."""
        ledger = self.quota_ledger
        while not self._quota_stop.is_set():
            for spec in ledger.take_dispatchable():
                try:
                    self.submit(spec)  # charge held: skips the gate
                except Exception as e:
                    self._fail_spec(spec, e)
            with self._lease_lock:
                if ledger.parked_count() == 0 or \
                        self._quota_stop.is_set():
                    # Retire under the spawn lock: a park landing after
                    # this check sees the dead thread and respawns.
                    self._quota_drainer = None
                    return
            ledger.wait_change(0.5)

    def kill_actor(self, actor_id, no_restart: bool = True) -> None:
        """Deliberate kill in cluster mode: reach the HOSTING node (the
        local backend only knows head-local actors — delegating there
        was a silent no-op for remote ones) and, for no_restart kills,
        tombstone the gate so later calls fail fast with the real
        cause instead of parking or probing a dead mailbox."""
        head = self.head
        aid = actor_id.binary()
        node_id = head.actor_nodes.get(aid)
        if no_restart and head.actor_gate.state(aid) is not None:
            with head._lock:
                head.actor_nodes.pop(aid, None)
            head.actor_gate.mark_dead(
                aid, "killed via ray_tpu.kill(no_restart=True)")
            head.release_actor_quota(aid)
        if node_id is None:
            self.local_backend.kill_actor(actor_id, no_restart)
            return
        record = head.nodes.get(node_id)
        if record is None or not record.alive:
            return  # the death sweep owns cleanup
        try:
            RpcClient.to(record.address).call(
                "kill_actor", actor_id=actor_id, no_restart=no_restart)
        except Exception:
            pass  # node unreachable: the health checker owns it

    def _submit_local(self, spec) -> None:
        """The ONE local-dispatch path in cluster mode: dep fetch +
        local backend, plus the restart gate's ready edge for actor
        creations — a RESTARTED actor that lands on the head (remote
        nodes saturated) has no directory entry (None = head-local),
        but its parked callers must still observe it alive again."""
        self._ensure_local_deps(spec)
        self.local_backend.submit(spec)
        self._local_ready_edge(spec)

    def _local_ready_edge(self, spec) -> None:
        if spec.kind == TaskKind.ACTOR_CREATION:
            aid = spec.actor_id.binary()
            if self.head.actor_gate.state(aid) is not None:
                with self.head._lock:
                    self.head.actor_local.add(aid)
            self.head.actor_gate.ready(aid)

    # Serializes head-local CREATION placement decisions: the fits
    # check and the backend submit (whose pending-demand add IS the
    # claim) must be one atomic step, or concurrent creations all pass
    # the same free CPU and over-pack the head with lifetime-pinned
    # actors that never construct (there is no head-local analogue of
    # _NodeRecord.reserved_milli otherwise).
    _local_place_lock = threading.Lock()

    def _submit_local_if_fits(self, spec, request) -> bool:
        """Atomic check-and-claim for head-local placement of work that
        must be able to START NOW (creations; also safe for tasks).
        Returns False when the head cannot run it immediately."""
        reserve = spec.kind == TaskKind.ACTOR_CREATION
        self._ensure_local_deps(spec)  # may fetch: outside the lock
        with ClusterBackendMixin._local_place_lock:
            if not self._local_fits_now(request,
                                        reserve_dep_parked=reserve):
                return False
            self.local_backend.submit(spec)
        self._local_ready_edge(spec)
        return True

    def _park_actor_call(self, spec) -> None:
        """A call with retry budget submitted during an actor's restart
        window: park in the shared list (the submitter keeps its
        ObjectRef and waits through get()), dispatch when the
        replacement registers, reject when the window expires or the
        actor dies. ONE dispatcher thread drains the whole list on the
        gate's wait_change signal — N parked calls used to cost N
        sleeping waiter threads (the PR 11 accepted trade-off, retired:
        WFQ can park a whole job class's calls at once)."""
        deadline = time.monotonic() + ray_config.actor_restart_timeout_s
        with self._park_lock:
            self._parked_calls.append((spec, deadline))
            t = self._park_thread
            if t is not None and t.is_alive():
                return
            self._park_thread = threading.Thread(
                target=self._park_dispatch_loop, daemon=True,
                name="ray_tpu-actor-park")
            self._park_thread.start()

    def _park_eval(self, spec, deadline: float):
        """Disposition of one parked call: ``None`` = keep parked,
        else a zero-arg effect to run OUTSIDE the park lock."""
        from ray_tpu._private.actor_gate import ActorRestartState

        head = self.head
        aid = spec.actor_id.binary()
        state = head.actor_gate.state(aid)
        if state == ActorRestartState.DEAD:
            cause = head.actor_gate.death_cause(aid) \
                or "actor died during the restart window"
            return lambda: head._fail_actor_call(spec, cause, True)
        # Dispatch only once the actor has a real home again: a node
        # entry, the head itself, or no gate record at all.
        # ALIVE-without-location is the mid-sweep transient —
        # re-submitting there would just re-park.
        if head.actor_nodes.get(aid) is not None or state is None \
                or aid in head.actor_local:
            def dispatch():
                try:
                    self.submit(spec)
                except Exception as e:
                    self._fail_spec(spec, e)
            return dispatch
        if time.monotonic() >= deadline:
            timeout = ray_config.actor_restart_timeout_s
            left = head.actor_gate.restarts_left(aid)
            return lambda: head._fail_actor_call(
                spec,
                f"actor did not become available within "
                f"actor_restart_timeout_s={timeout:g}s (call parked "
                f"with retry budget while the actor was restarting "
                f"or its creation was quota-parked; actor restarts: "
                f"{left} left)",
                False)
        return None

    def _park_dispatch_loop(self) -> None:
        """The one parked-call dispatcher: wakes on every gate
        transition (condition-signalled, no busy polling), sweeps the
        parked list, runs the matured effects outside the lock, and
        retires when the list drains."""
        while not self._quota_stop.is_set():
            effects = []
            with self._park_lock:
                still = []
                for spec, deadline in self._parked_calls:
                    effect = self._park_eval(spec, deadline)
                    if effect is None:
                        still.append((spec, deadline))
                    else:
                        effects.append(effect)
                self._parked_calls = still
            for effect in effects:
                effect()
            with self._park_lock:
                if not self._parked_calls or self._quota_stop.is_set():
                    # Retire under the spawn lock: a park landing after
                    # this check sees the dead thread and respawns.
                    self._park_thread = None
                    return
                nearest = min(d for _s, d in self._parked_calls)
            # Read self.head per iteration: restart_head swaps it.
            self.head.actor_gate.wait_change(
                min(0.5, max(0.01, nearest - time.monotonic())))

    # -- lease-based dispatch (direct_task_transport role) ---------------

    @property
    def _LEASE_IDLE_S(self) -> float:
        return ray_config.sched_lease_idle_s

    # How far a lease may over-subscribe its granted slots before the
    # manager asks the head for another lease on a different node (the
    # reference's backlog-driven extra lease requests).
    _LEASE_BACKLOG_FACTOR = 4

    def _lease_lock_for(self, key: tuple):
        return self._lease_locks[hash(key)
                                 & (len(self._lease_locks) - 1)]

    def _all_lease_locks(self):
        """Acquire every lease shard lock in index order (whole-table
        ops: pipe drops, drains) — deadlock-free against per-key
        holders by the fixed ordering."""
        import contextlib

        stack = contextlib.ExitStack()
        for lock in self._lease_locks:
            stack.enter_context(lock)
        return stack

    def _shape_key(self, spec) -> tuple:
        # Keyed by (job, resource shape): leases are per-TENANT, so
        # the `leases:` quota genuinely bounds a job's pipelined
        # channels — a shape-only key let other jobs ride (and keep
        # alive) a lease charged to whoever asked first, making the
        # cap bound nothing. Untagged traffic shares the "" tenant.
        return (getattr(spec, "job_id", "") or "",) + tuple(
            sorted((k, float(v))
                   for k, v in (spec.resources or {}).items()))

    def _lease_submit(self, spec, request) -> bool:
        """Dispatch through a held (or newly granted) lease; False when
        the task should take the per-task scheduling path instead (no
        node has capacity). Caller has already ruled out local-first."""
        key = self._shape_key(spec)
        now = time.monotonic()
        # A "hit" is a submission with NO head scheduling decision: any
        # _grant_lease attempt (fresh, locality extra, saturated extra,
        # spill) flips it to a miss so hit+miss == submissions and the
        # cache-hit ratio reads true.
        decided = False
        with self._lease_lock_for(key):
            leases = self._leases.get(key)
            if leases:
                # Prune leases on dead nodes and idle-expired ones
                # (lease return: the node's capacity is only "ours"
                # while we keep it busy).
                live, dropped = [], []
                for lease in leases:
                    record = self.head.nodes.get(lease["node_id"])
                    if record is None or not record.alive:
                        dropped.append(lease)
                        continue
                    if lease["pipe"].in_flight == 0 and \
                            now - lease["last_used"] > self._LEASE_IDLE_S:
                        dropped.append(lease)
                        continue
                    live.append(lease)
                if live:
                    self._leases[key] = live
                else:
                    del self._leases[key]
                self._retire_leases(dropped)
                leases = live or None
            if not leases:
                decided = True
                lease = self._grant_lease(key, spec)
                if lease is None:
                    _LEASE_CACHE_MISSES.inc()
                    return False
            else:
                # Leases are keyed by resource SHAPE; a held lease may
                # sit on the wrong node for THIS task's bytes. Prefer a
                # lease already on the locality target, granting one
                # there if none exists yet.
                loc = self._locality_target(spec)
                preferred = [l for l in leases
                             if loc is not None
                             and l["node_id"] == loc.node_id]
                if loc is not None and not preferred:
                    decided = True
                    extra = self._grant_lease(key, spec, target=loc)
                    if extra is not None:
                        preferred = [extra]
                lease = min(preferred or leases,
                            key=lambda l: l["pipe"].in_flight)
                # Saturated: ask for one more lease on another node.
                if lease["pipe"].in_flight >= max(
                        1, lease["slots"]) * self._LEASE_BACKLOG_FACTOR:
                    decided = True
                    extra = self._grant_lease(
                        key, spec,
                        exclude={l["node_id"] for l in leases})
                    if extra is not None:
                        lease = extra
            # Backlog spillback (reference: raylet spillback on deep
            # local queues): the node's own pushed backlog signal says
            # its queue is past the spill threshold — redirect to a
            # lease on a better target (locality-scored grant) instead
            # of piling deeper. The overloaded lease stays held; it
            # re-wins once its backlog drains below the threshold.
            record = self.head.nodes.get(lease["node_id"])
            if record is not None and \
                    record.backlog > ray_config.sched_spillback_backlog:
                spill = None
                if lease.get("spill_denied_at") != record.last_report:
                    decided = True
                    spill = self._grant_lease(
                        key, spec,
                        exclude={l["node_id"]
                                 for l in self._leases.get(key, ())})
                    if spill is None:
                        # Nowhere to GRANT a spill (every candidate
                        # leased or full): stamp the node's report so
                        # saturated submissions stop re-paying the
                        # O(nodes) grant scan until a fresh resource
                        # report changes the picture.
                        lease["spill_denied_at"] = record.last_report
                if spill is None:
                    # Fall back to an already-held lease on a node
                    # whose backlog is below the threshold:
                    # min(in_flight) can keep picking the overloaded
                    # lease (a deep node queue acks frames fast, so
                    # its in_flight stays low), and without this the
                    # flood keeps piling onto it while a healthy
                    # lease idles. O(held leases), so it runs even in
                    # the grant-scan backoff window.
                    thresh = ray_config.sched_spillback_backlog
                    for alt in self._leases.get(key, ()):
                        if alt is lease:
                            continue
                        alt_rec = self.head.nodes.get(alt["node_id"])
                        if alt_rec is None or not alt_rec.alive or \
                                alt_rec.backlog > thresh:
                            continue
                        if spill is None or alt["pipe"].in_flight < \
                                spill["pipe"].in_flight:
                            spill = alt
                if spill is not None:
                    _SPILLBACKS.inc()
                    lease = spill
            lease["last_used"] = now
            (_LEASE_CACHE_MISSES if decided else _LEASE_CACHE_HITS).inc()
        return self._lease_send(lease, spec)

    def _grant_lease(self, key, spec, exclude=(),
                     target=None) -> Optional[dict]:
        """One head scheduling decision for a task SHAPE (not a task):
        locality-aware node choice + slot count from the pushed view.
        Caller holds the key's lease shard lock; a caller that already
        computed the locality target passes it to skip the re-scan."""
        from ray_tpu._private.resources import to_milli

        if target is None:
            target = self._locality_target(spec, exclude)
        if target is None:
            target = self._choose_node(spec, exclude=exclude)
        if target is None:
            return None
        # Concurrent-lease quota: a job at its cap keeps riding the
        # leases it already holds (queueing behind its own limit)
        # instead of opening another pipelined channel.
        job = getattr(spec, "job_id", "") or ""
        if not self.quota_ledger.try_acquire_lease(job):
            return None
        router = getattr(getattr(self, "head", None),
                         "shard_router", None)
        if router is not None:
            # The (job, shape) key's OWNING shard is the registration
            # authority: the grant is recorded there (durably, group-
            # committed) before it exists anywhere else, so one key's
            # grants can never be tracked on two shards and a crashed
            # shard's key range stops granting — callers queue behind
            # their held leases or retry — until the supervisor
            # restarts it, while every other shard keeps granting.
            if not router.lease_register(repr(key).encode(),
                                         target.node_id):
                self.quota_ledger.release_lease(job)
                return None
        request = to_milli(spec.resources)
        slots = 1
        if request:
            slots = max(1, min(
                int(target.available.get(k, 0) * 1000 // v)
                for k, v in request.items() if v > 0))
        pipe = self._node_pipe(target)
        lease = {"node_id": target.node_id, "pipe": pipe,
                 "slots": slots, "last_used": time.monotonic(),
                 "address": target.address, "job": job, "key": key}
        self._leases.setdefault(key, []).append(lease)
        return lease

    def _node_pipe(self, node: "_NodeRecord"):
        """The node's pipelined channel, created on first use. Channel
        registry mutations are under the global channel lock (shard
        lock -> _lease_lock is the one legal nesting order)."""
        with self._lease_lock:
            pipe = self._pipes.get(node.node_id)
            if pipe is None:
                from ray_tpu._private.rpc import PipelinedClient

                pipe = PipelinedClient(node.address,
                                       on_error=self._pipe_error)
                self._pipes[node.node_id] = pipe
            return pipe

    def _retire_leases(self, leases) -> None:
        """Release the lease-quota charge of every retired lease (any
        removal path: idle prune, dead node, broken pipe, drain)."""
        ledger = self.quota_ledger
        # getattr on SELF with a default: `self.head` delegates through
        # __getattr__ to local_backend, which harness-built mixins stub.
        router = getattr(getattr(self, "head", None),
                         "shard_router", None)
        for lease in leases:
            job = lease.get("job")
            if job is not None:
                ledger.release_lease(job)
            if router is not None and lease.get("key") is not None:
                router.lease_retire(repr(lease["key"]).encode(),
                                    lease["node_id"])

    def _arg_bytes_by_addr(self, spec) -> Dict[tuple, int]:
        """Resident argument bytes per owner address, from the head's
        object directory (locations + reported sizes). Cheap when the
        spec has no ObjectRef args — the common fan-out case."""
        from ray_tpu.object_ref import ObjectRef

        head = self.head
        out: Dict[tuple, int] = {}
        for arg in list(spec.args) + list(spec.kwargs.values()):
            if not isinstance(arg, ObjectRef):
                continue
            ob = arg.id.binary()
            loc = head.object_locations.get(ob)
            if loc is None:
                continue
            addr = tuple(loc)
            out[addr] = out.get(addr, 0) + head.object_sizes.get(ob, 0)
        return out

    def _locality_target(self, spec, exclude=()):
        """Lease policy (reference `lease_policy.h:56`): score candidate
        nodes by RESIDENT ARGUMENT BYTES — a task with a 64MB argument
        runs where the bytes already live instead of pulling them to
        follow a 200-byte spec. Ties (equal bytes) fall back to the
        least-loaded ordering the default policy uses; nodes below
        ``locality_min_arg_bytes`` never win on locality alone."""
        if not ray_config.locality_aware_scheduling:
            return None
        bytes_by_addr = self._arg_bytes_by_addr(spec)
        if not bytes_by_addr:
            return None
        from ray_tpu._private.resources import to_milli

        request = to_milli(spec.resources)
        best, best_bytes, best_load = None, 0, -1.0
        for node in self.head.nodes.values():
            if node.node_id in exclude or not node.alive:
                continue
            nbytes = bytes_by_addr.get(tuple(node.address), 0)
            if nbytes < ray_config.locality_min_arg_bytes:
                continue
            if not all(node.available.get(k, 0) * 1000 >= v
                       for k, v in request.items()):
                continue
            load_score = sum(node.available.values()) \
                - 0.1 * node.backlog
            if nbytes > best_bytes or (nbytes == best_bytes
                                       and load_score > best_load):
                best, best_bytes, best_load = node, nbytes, load_score
        return best

    def _locality_prefers_remote(self, spec) -> bool:
        """True when the spec's resident argument bytes make a REMOTE
        node the cheaper home even though the task fits locally (the
        local-first fast path would otherwise pull the bytes here)."""
        if not ray_config.locality_aware_scheduling:
            return False
        bytes_by_addr = self._arg_bytes_by_addr(spec)
        if not bytes_by_addr:
            return False
        local = bytes_by_addr.get(tuple(self.head.server.address), 0)
        remote = max((b for addr, b in bytes_by_addr.items()
                      if addr != tuple(self.head.server.address)),
                     default=0)
        return remote >= ray_config.locality_min_arg_bytes \
            and remote > local

    def _promote_large_args(self, spec):
        """Large plain-value args are published to the object plane and
        replaced by ObjectRefs at the wire boundary, so the TaskCall /
        shipped spec carries a descriptor-resolvable reference instead
        of megabytes of pickle (the reference puts big args in plasma
        at submission). Only obviously-sized values promote (arrays,
        buffers, strings — `nbytes`/`len` is authoritative); containers
        ship as before."""
        plane = getattr(self.worker, "shm_plane", None)
        if plane is None:
            return spec
        from ray_tpu.object_ref import ObjectRef

        threshold = max(int(ray_config.shm_share_threshold_bytes), 1)

        def big(v) -> bool:
            if v is None or isinstance(v, (ObjectRef, bool, int, float)):
                return False
            nbytes = getattr(v, "nbytes", None)
            if isinstance(nbytes, int):
                return nbytes >= threshold
            if isinstance(v, (bytes, bytearray, str)):
                return len(v) >= threshold
            return False

        if not any(big(a) for a in spec.args) and \
                not any(big(v) for v in spec.kwargs.values()):
            return spec
        put = self.worker.put_object
        spec.args = tuple(put(a) if big(a) else a for a in spec.args)
        spec.kwargs = {k: (put(v) if big(v) else v)
                       for k, v in spec.kwargs.items()}
        return spec

    def _lease_send(self, lease, spec) -> bool:
        record = self.head.nodes.get(lease["node_id"])
        if record is None or not record.alive:
            return False
        spec = self._promote_large_args(spec)
        self._publish_local_args(record, spec)
        # Same bookkeeping as _send: lineage + inflight BEFORE the wire.
        self.head.record_lineage(spec)
        self.head.record_inflight(spec, lease["node_id"])
        # Dispatching: the spec leaves the head's queued-ceiling count
        # (its CPU charge stays held until the in-flight entry clears).
        self.quota_ledger.note_dequeued(spec)
        # Coalesced, non-blocking enqueue: the node's batcher drains
        # whatever accumulates while the previous frame is on the wire
        # into ONE submit_batch request. Transport failures surface
        # asynchronously (frame-send fallback / _pipe_error) and
        # re-route through submit() — by then this task is recorded
        # in-flight, so no completion can be lost. The template claim
        # and the enqueue happen under ONE per-node lock: a racing
        # submitter that observes the claim must enqueue BEHIND the
        # claiming item, or its call-only header could reach the node
        # first and hit UnknownTemplate.
        node_id = lease["node_id"]
        for _attempt in range(2):
            with self._submit_lock_for(node_id):
                call, templates = self._wire_item_for(spec, record)
                try:
                    self._batcher_for(node_id, lease["pipe"]).add(
                        (call, templates, spec, lease))
                    return True
                except ConnectionError:
                    # Batcher closed by a concurrent pipe drop: unwind
                    # the claim and retry once with a fresh batcher.
                    for t in templates:
                        record.known_templates.discard(t.template_id)
                    continue
        self.head.clear_inflight(spec)
        return False

    def _send_creation_batched(self, node: "_NodeRecord", spec) -> bool:
        """Group-committed actor creation: the creation rides the
        node's coalescing submit_batch channel — one frame commits a
        GROUP of creations (plus any leased tasks already queued for
        that node, order preserved) instead of one synchronous RPC per
        actor. Bookkeeping is byte-identical to _send — lineage +
        in-flight recorded BEFORE the wire — so a node death re-drives
        the creation through the resubmit loop's inflight_creations
        path (never _restart_actor: no restart budget burned for a
        never-constructed actor) and ActorRestartGate semantics are
        unchanged. Returns False to fall back to the synchronous path
        (channel unavailable/closed)."""
        try:
            pipe = self._node_pipe(node)
        except Exception:
            return False
        spec = self._promote_large_args(spec)
        self._publish_local_args(node, spec)
        self.head.record_lineage(spec)
        self.head.record_inflight(spec, node.node_id)
        self.quota_ledger.note_dequeued(spec)
        # Pseudo-lease tag: the batch error paths only read node_id
        # (and retire via identity against _leases, where this never
        # appears — creations hold no lease-quota charge).
        tag = {"node_id": node.node_id, "pipe": pipe, "job": None}
        with self._submit_lock_for(node.node_id):
            wire_spec = self._strip_exported_func(spec, node)
            try:
                self._batcher_for(node.node_id, pipe).add(
                    (wire_spec, [], spec, tag))
                return True
            except ConnectionError:
                self.head.clear_inflight(spec)
                return False

    def _submit_lock_for(self, node_id: str):
        lock = self._submit_locks.get(node_id)
        if lock is None:
            with self._lease_lock:
                lock = self._submit_locks.setdefault(node_id,
                                                     threading.Lock())
        return lock

    def _batcher_for(self, node_id: str, pipe):
        batcher = self._batchers.get(node_id)
        if batcher is None:
            with self._lease_lock:
                batcher = self._batchers.get(node_id)
                if batcher is None:
                    from ray_tpu._private.rpc import CoalescingBatcher

                    batcher = CoalescingBatcher(
                        lambda batch, nid=node_id, p=pipe:
                        self._send_submit_frame(nid, p, batch),
                        name=f"submit-{node_id}")
                    self._batchers[node_id] = batcher
        return batcher

    def _wire_item_for(self, spec, record: "_NodeRecord"):
        """The wire form of one submission: a TaskCall header against an
        interned template (plus the template itself on its first trip to
        this node), or the full spec for shapes that can't intern
        (actor tasks, unexportable functions)."""
        from ray_tpu._private import wire
        from ray_tpu._private.task_spec import get_template

        if spec.kind == TaskKind.NORMAL_TASK and spec.template_id \
                and spec.func_id:
            # A compact header carries its template strongly — immune
            # to intern-cache eviction; full specs re-resolve by id.
            tpl = getattr(spec, "tpl", None) or \
                get_template(spec.template_id)
            if tpl is not None:
                templates = []
                if spec.template_id not in record.known_templates:
                    # Claimed optimistically; racing submitters may ship
                    # the template twice, which registers idempotently.
                    record.known_templates.add(spec.template_id)
                    templates.append(wire.TaskTemplate(
                        template_id=spec.template_id,
                        payload=wire.Opaque(tpl)))
                call = wire.TaskCall(
                    template_id=spec.template_id,
                    task_id=spec.task_id.binary(),
                    args=wire.Opaque(spec.args) if spec.args else None,
                    kwargs=wire.Opaque(spec.kwargs) if spec.kwargs else None,
                    num_returns=spec.num_returns,
                    depth=spec.depth,
                    trace_parent=spec.trace_parent,
                    max_retries=spec.max_retries,
                    job_id=spec.job_id or "",
                    attempt=getattr(spec, "attempt", 0))
                return call, templates
        return self._strip_exported_func(spec, record), []

    def _send_submit_frame(self, node_id: str, pipe, batch) -> None:
        """Flush one coalesced batch as a single submit_batch request.
        Encode failures retry items individually (so one unpicklable
        payload fails alone); transport failures re-route every item
        through submit()."""
        templates, calls, tags = [], [], []
        for call, tpls, spec, lease in batch:
            templates.extend(tpls)
            calls.append(call)
            tags.append((spec, lease))
        kwargs = {"templates": templates, "calls": calls}
        try:
            pipe.send("submit_batch", tag=("__batch__", tags, kwargs),
                      **kwargs)
            return
        except (ConnectionError, OSError):
            # The claiming frame never arrived: un-claim its templates
            # or every later TaskCall of these shapes to this (still
            # alive) node would hit UnknownTemplate forever.
            record = self.head.nodes.get(node_id)
            if record is not None:
                for t in templates:
                    record.known_templates.discard(t.template_id)
            self._drop_lease_pipe(node_id, None)
            for spec, lease in tags:
                self.head.clear_inflight(spec)
                try:
                    self.submit(spec)
                except Exception as e:
                    self._fail_spec(spec, e)
            return
        except BaseException as e:  # encode failure (unpicklable payload)
            if len(batch) == 1:
                # The frame (and any template it carried) never reached
                # the node: un-claim, or later call-only headers of this
                # shape would hit UnknownTemplate forever.
                record = self.head.nodes.get(node_id)
                if record is not None:
                    for t in templates:
                        record.known_templates.discard(t.template_id)
                spec = batch[0][2]
                self.head.clear_inflight(spec)
                self._fail_spec(spec, e)
                return
            for item in batch:
                self._send_submit_frame(node_id, pipe, [item])

    def drain_channels(self, timeout: float = 2.0) -> None:
        """Shutdown-boundary drain: flush-and-close every submit
        batcher and pipelined channel so accepted submissions reach the
        wire (and are acked) before the cluster tears down. Also stops
        the tenancy drainer + parked-call dispatcher threads (their
        parked work is abandoned with the cluster)."""
        self._quota_stop.set()
        # Bounded joins: both loops wake within their 0.5s wait slice,
        # observe the stop flag, and retire.
        for t in (self._quota_drainer, self._park_thread):
            if t is not None and t.is_alive():
                t.join(timeout=1.0)
        with self._all_lease_locks():
            self._retire_leases(
                [l for ls in self._leases.values() for l in ls])
            self._leases.clear()
        with self._lease_lock:
            batchers = list(self._batchers.values())
            pipes = list(self._pipes.values())
            self._batchers.clear()
            self._pipes.clear()
        for batcher in batchers:
            batcher.close(drain_timeout=timeout)
        for pipe in pipes:
            pipe.close(flush_timeout=timeout)

    def _drop_lease_pipe(self, node_id: str, lease) -> None:
        # Pop the channel FIRST: a concurrent _grant_lease racing this
        # drop then mints a fresh pipe (and batcher) via _node_pipe
        # instead of binding a new lease to the broken one about to be
        # closed — those sends would fail and burn the spec's bounded
        # lease reroutes on a node that may be healthy. A lease granted
        # in the window is swept by the retirement pass below and
        # simply re-grants on its next use.
        with self._lease_lock:
            pipe = self._pipes.pop(node_id, None)
            batcher = self._batchers.pop(node_id, None)
        with self._all_lease_locks():
            retired = []
            for ls in self._leases.values():
                if lease is None:
                    retired += [l for l in ls
                                if l["node_id"] == node_id]
                    ls[:] = [l for l in ls if l["node_id"] != node_id]
                elif lease in ls:
                    retired.append(lease)
                    ls[:] = [l for l in ls if l is not lease]
            self._retire_leases(retired)
        if batcher is not None:
            batcher.close()  # flusher drains then retires (no thread leak)
        if pipe is not None:
            pipe.close()  # immediate: the channel is already broken

    def _pipe_error(self, tag, message: str, rid: str, lost: bool):
        """Async failure from a pipelined channel (reader thread)."""
        if isinstance(tag, tuple) and len(tag) == 3 and \
                tag[0] == "__batch__":
            return self._batch_pipe_error(tag, message, rid, lost)
        spec, lease = tag
        if not lost:
            # The node processed the request but its HANDLER failed —
            # a control-plane problem (function-resolution hiccup,
            # queue rejection), not a user-code error (those land in
            # the result object). Re-route through the per-task
            # scheduling path like the non-leased loop would, bounded
            # so a deterministic failure still surfaces.
            self.head.clear_inflight(spec)
            retries = getattr(spec, "_lease_reroutes", 0)
            if retries < 3:
                spec._lease_reroutes = retries + 1
                with self._all_lease_locks():
                    retired = []
                    for ls in self._leases.values():
                        if lease in ls:
                            retired.append(lease)
                            ls[:] = [l for l in ls if l is not lease]
                    self._retire_leases(retired)
                try:
                    self.submit(spec)
                    return
                except Exception:
                    pass
            self._fail_spec(spec, RuntimeError(
                f"leased submit failed on {lease['node_id']} after "
                f"{retries} reroutes: {message}"))
            return
        # Connection lost with the request un-acked: resubmit under the
        # SAME request id — the node's dedupe cache makes this exactly-
        # once whether or not the original arrived. If the node is
        # truly dead, the inflight table resubmits via mark_node_dead.
        record = self.head.nodes.get(lease["node_id"])
        # Pop the broken pipe BEFORE retiring the lease (same order as
        # _drop_lease_pipe): a _grant_lease racing this handler must
        # mint a fresh pipe, not bind a new lease to the dead one and
        # burn the spec's bounded reroutes on a healthy node.
        with self._lease_lock:
            self._pipes.pop(lease["node_id"], None)
        with self._all_lease_locks():
            retired = []
            for ls in self._leases.values():
                if lease in ls:
                    retired.append(lease)
                    ls[:] = [l for l in ls if l is not lease]
            self._retire_leases(retired)
        if record is None or not record.alive:
            return  # node-death sweep owns recovery
        try:
            wire_spec = self._strip_exported_func(spec, record)
            RpcClient.to(record.address).call_with_rid(
                rid, "submit_task", spec=wire_spec)
        except Exception as e:
            self.head.clear_inflight(spec)
            self.head.mark_node_dead(lease["node_id"],
                                     reason=f"unreachable: {e}")

    def _batch_pipe_error(self, tag, message: str, rid: str, lost: bool):
        """Failure of one coalesced submit_batch frame. Non-lost means
        the node received and dispatched the frame but the HANDLER
        failed wholesale (per-call failures never reach here — the node
        stores those into the calls' return objects): re-route every
        item. Lost means the connection died un-acked: resubmit the
        whole frame under the SAME request id — the node's dedupe cache
        makes that exactly-once."""
        _, tags, kwargs = tag
        node_id = tags[0][1]["node_id"] if tags else None
        record = self.head.nodes.get(node_id) if node_id else None
        if not lost:
            # The node rejected the frame WHOLESALE (decode/handler
            # failure before dispatch): its templates never registered,
            # so un-claim them or every later call-only header of these
            # shapes fails with UnknownTemplate forever.
            if record is not None:
                for t in kwargs.get("templates") or []:
                    record.known_templates.discard(t.template_id)
            for spec, lease in tags:
                self.head.clear_inflight(spec)
            if node_id is not None:
                self._drop_lease_pipe(node_id, None)
            for spec, _lease in tags:
                retries = getattr(spec, "_lease_reroutes", 0)
                if retries < 3:
                    spec._lease_reroutes = retries + 1
                    try:
                        self.submit(spec)
                        continue
                    except Exception:
                        pass
                self._fail_spec(spec, RuntimeError(
                    f"batched submit failed on {node_id}: {message}"))
            return
        if node_id is not None:
            self._drop_lease_pipe(node_id, None)
        if record is None or not record.alive:
            return  # node-death sweep owns recovery
        try:
            RpcClient.to(record.address).call_with_rid(
                rid, "submit_batch", **kwargs)
        except Exception as e:
            for spec, _lease in tags:
                self.head.clear_inflight(spec)
            self.head.mark_node_dead(node_id,
                                     reason=f"unreachable: {e}")

    def _route_by_strategy(self, spec):
        """Route a spec per its scheduling strategy. Returns False when
        the default (hybrid local-first) policy should decide instead."""
        from ray_tpu._private.task_spec import (
            NodeAffinitySchedulingStrategy,
            PlacementGroupSchedulingStrategy,
            SpreadSchedulingStrategy,
        )
        from ray_tpu import exceptions as exc

        strat = spec.scheduling_strategy
        head = self.head

        if isinstance(strat, PlacementGroupSchedulingStrategy) and \
                strat.placement_group is not None:
            pg = strat.placement_group
            # Resolve the canonical handle (serialized handles may be
            # detached reconstructions with a stale ready bit).
            canonical = self.worker.gcs.placement_group_table().get(pg.id)
            if canonical is not None:
                pg = canonical
            pgid = pg.id.binary()
            idx = strat.placement_group_bundle_index
            if not pg._ready.is_set():
                # Reservation still in flight: queue until it commits
                # (the reference queues PG-targeted leases likewise).
                def wait_then_submit(spec=spec, pg=pg):
                    pg._ready.wait(timeout=300)
                    self.submit(spec)

                threading.Thread(target=wait_then_submit, daemon=True,
                                 name="ray_tpu-pg-wait").start()
                return True
            if pg._failed:
                self._fail_spec(spec, exc.PlacementGroupSchedulingError(
                    f"placement group reservation failed: {pg._failed}"))
                return True
            entries = {k: v for k, v in head.pg_bundle_nodes.items()
                       if k[0] == pgid}
            if not entries:
                return False  # single-node PG (head-local pools)
            if idx >= 0:
                node_id = entries.get((pgid, idx), "__missing__")
                if node_id == "__missing__":
                    self._fail_spec(spec, exc.PlacementGroupSchedulingError(
                        f"bundle {idx} of placement group is not reserved"))
                    return True
            else:
                # Any bundle: prefer one on this (head) node, else first.
                node_id = None if None in entries.values() else \
                    next(iter(entries.values()))
            if node_id is None:
                self._submit_local(spec)
                return True
            record = head.nodes.get(node_id)
            if record is None or not record.alive:
                self._fail_spec(spec, exc.PlacementGroupSchedulingError(
                    f"placement group bundle's node {node_id} is dead"))
                return True
            if spec.kind == TaskKind.ACTOR_CREATION:
                head.set_actor_node(spec.actor_id.binary(), record.node_id)
            try:
                self._send(record, spec)
            except (ConnectionError, OSError) as e:
                if spec.kind == TaskKind.ACTOR_CREATION:
                    # Unwind the never-landed placement BEFORE the
                    # sweep (see submit's creation handler).
                    head.actor_nodes.pop(spec.actor_id.binary(), None)
                    head.actor_gate.rollback_ready(
                        spec.actor_id.binary())
                head.mark_node_dead(record.node_id,
                                    reason=f"unreachable: {e}")
                self._fail_spec(spec, exc.PlacementGroupSchedulingError(
                    f"placement group bundle's node {node_id} became "
                    f"unreachable: {e}"))
            return True

        if isinstance(strat, NodeAffinitySchedulingStrategy) and \
                strat.node_id is not None:
            wanted = strat.node_id
            if isinstance(wanted, bytes):
                wanted = wanted.decode()
            record = head.nodes.get(str(wanted))
            if record is None or not record.alive:
                if strat.soft:
                    return False
                self._fail_spec(spec, RuntimeError(
                    f"node affinity target {wanted!r} is not available"))
                return True
            if spec.kind == TaskKind.ACTOR_CREATION:
                head.set_actor_node(spec.actor_id.binary(), record.node_id)
            try:
                self._send(record, spec)
            except (ConnectionError, OSError) as e:
                if spec.kind == TaskKind.ACTOR_CREATION:
                    head.actor_nodes.pop(spec.actor_id.binary(), None)
                    head.actor_gate.rollback_ready(
                        spec.actor_id.binary())
                head.mark_node_dead(record.node_id,
                                    reason=f"unreachable: {e}")
                if strat.soft:
                    return False
                self._fail_spec(spec, RuntimeError(
                    f"node affinity target {wanted!r} became unreachable"))
            return True

        if isinstance(strat, SpreadSchedulingStrategy):
            # Round-robin over head + alive nodes with capacity
            # (reference: spread_scheduling_policy.h:27).
            from ray_tpu._private.resources import to_milli

            request = to_milli(spec.resources)
            slots: List[Optional[_NodeRecord]] = [None]
            slots += [n for n in head.nodes.values() if n.alive]
            for attempt in range(len(slots)):
                target = slots[(self._rr + attempt) % len(slots)]
                if target is None:
                    local = self.local_backend.resources
                    with local._cond:
                        fits = all(local._available.get(k, 0) >= v
                                   for k, v in request.items())
                    if not fits:
                        continue
                    self._rr += attempt + 1
                    self._submit_local(spec)
                    return True
                if all(target.available.get(k, 0) * 1000 >= v
                       for k, v in request.items()):
                    self._rr += attempt + 1
                    if spec.kind == TaskKind.ACTOR_CREATION:
                        head.set_actor_node(spec.actor_id.binary(),
                                            target.node_id)
                    try:
                        self._send(target, spec)
                        return True
                    except (ConnectionError, OSError) as e:
                        if spec.kind == TaskKind.ACTOR_CREATION:
                            head.actor_nodes.pop(
                                spec.actor_id.binary(), None)
                            head.actor_gate.rollback_ready(
                                spec.actor_id.binary())
                        head.mark_node_dead(target.node_id,
                                            reason=f"unreachable: {e}")
                        continue
            return False  # nothing fits now: fall back to default queueing

        return False

    def _ensure_local_deps(self, spec):
        from ray_tpu.object_ref import ObjectRef

        store = self.worker.memory_store
        head = self.head
        missing = [a.id for a in
                   list(spec.args) + list(spec.kwargs.values())
                   if isinstance(a, ObjectRef) and not store.contains(a.id)]
        for oid in missing:
            def fetch(oid=oid):
                if try_shm_fetch(self.worker, oid):
                    return
                # Transport failures are retried until the deadline (a
                # brief owner stall must not poison the object); if the
                # owner stayed unreachable the whole window, `get` raises
                # OwnerDiedError instead of hanging. A never-located
                # object is left pending — its producer may just be slow.
                from ray_tpu._private.config import ray_config

                deadline = time.monotonic() + ray_config.fetch_deadline_s
                transport_err = None
                attempt = 0
                while time.monotonic() < deadline:
                    if store.contains(oid):
                        return
                    info = head._locate2(oid.binary())
                    if info is not None and \
                            tuple(info["address"]) != head.server.address:
                        if try_transfer_fetch(self.worker, oid, info):
                            return
                        try:
                            ok, value, err = RpcClient.to(
                                tuple(info["address"])).call(
                                "get_object", oid=oid.binary())
                        except Exception as e:
                            transport_err = e
                            time.sleep(0.2)
                            continue
                        if ok:
                            store.put(oid, value, error=err)
                            return
                    fetch_backoff(attempt)
                    attempt += 1
                if transport_err is not None and not store.contains(oid):
                    store.put(oid, None, error=OwnerDiedError(
                        oid.hex()[:12],
                        f"owner of {oid.hex()[:12]} unreachable past the fetch deadline: "
                        f"{transport_err}"))

            threading.Thread(target=fetch, daemon=True).start()

    def _queue_for_cluster(self, spec, request) -> None:
        """Background retry until some node frees capacity (or none could
        ever fit). Keeps the head's LocalBackend out of it: its hard
        infeasibility check is per-node, not cluster-wide."""
        from ray_tpu._private.resources import to_milli
        from ray_tpu import exceptions as exc

        tid = spec.task_id.binary()
        self.head.pending_demands[tid] = dict(spec.resources) \
            or {"CPU": 1.0}

        def loop():
            try:
                local_total = to_milli(dict(
                    self.local_backend.resources.total))
                local_possible = all(local_total.get(k, 0) >= v
                                     for k, v in request.items())
                while True:
                    feasible = local_possible
                    for record in self.head.nodes.values():
                        if feasible:
                            break
                        if not record.alive:
                            continue
                        total = to_milli(dict(record.resources))
                        if all(total.get(k, 0) >= v
                               for k, v in request.items()):
                            feasible = True
                            break
                    if not feasible and \
                            not self.head.autoscaling_enabled:
                        # No autoscaler: nothing will ever fit — fail
                        # fast. With one, stay pending: the demand is
                        # what makes the autoscaler launch capacity.
                        self._fail_spec(spec, exc.RayTpuError(
                            f"task {spec.describe()} requests "
                            f"{spec.resources} which no live cluster "
                            "node can satisfy"))
                        return
                    target = (self._choose_node(spec, exclude=())
                              if feasible else None)
                    if target is not None:
                        if spec.kind == TaskKind.ACTOR_CREATION:
                            self.head.set_actor_node(
                                spec.actor_id.binary(), target.node_id)
                        try:
                            self._send(target, spec)
                            return
                        except (ConnectionError, OSError) as e:
                            if spec.kind == TaskKind.ACTOR_CREATION:
                                # Unwind BEFORE the sweep (see submit).
                                self.head.actor_nodes.pop(
                                    spec.actor_id.binary(), None)
                                self.head.actor_gate.rollback_ready(
                                    spec.actor_id.binary())
                            self.head.mark_node_dead(
                                target.node_id,
                                reason=f"unreachable: {e}")
                    elif local_possible and \
                            self._submit_local_if_fits(spec, request):
                        # _choose_node returns None both for "the head
                        # fits it now" and "nothing remote fits" —
                        # dispatch locally only in the first case (a
                        # queued CREATION must construct immediately,
                        # never park behind lifetime-pinned CPUs; the
                        # atomic check-and-claim stops concurrent queue
                        # threads from over-packing one freed CPU).
                        return
                    time.sleep(0.1)
            finally:
                self.head.pending_demands.pop(tid, None)

        threading.Thread(target=loop, daemon=True,
                         name="ray_tpu-cluster-queue").start()

    def _local_fits_now(self, request,
                        reserve_dep_parked: bool = False) -> bool:
        """Run/construct-NOW feasibility on the head's local backend:
        available minus already-queued demand covers the milli request.
        ``reserve_dep_parked`` additionally reserves for dep-parked
        work — lifetime-pinned CREATIONS must see it (a dep-blocked
        burst's demand is invisible to the backlog counter until the
        deps resolve, by which time over-landed creations park behind
        pinned CPUs forever); plain tasks queue and release, so they
        keep the cheaper check."""
        local = self.local_backend.resources
        pending = self.local_backend.pending_demand_milli()
        dep_parked = (self.local_backend.dep_parked_demand_milli()
                      if reserve_dep_parked else {})
        with local._cond:
            return all(
                local._available.get(k, 0) - pending.get(k, 0)
                - dep_parked.get(k, 0) >= v
                for k, v in request.items())

    def _choose_node(self, spec, exclude=()) -> Optional[_NodeRecord]:
        """Local-first pack; spill to remote capacity when local can't run
        it now (reference hybrid policy shape)."""
        request = _spec_milli_of(spec)
        if self._local_fits_now(
                request,
                reserve_dep_parked=spec.kind == TaskKind.ACTOR_CREATION):
            return None
        # Pushed resource view (ray_syncer role): no per-submit pings.
        # Staleness is fine — the receiving node queues anything that no
        # longer fits, and the next report corrects the view.
        candidates = [n for n in self.head.nodes.values()
                      if n.alive and n.node_id not in exclude]
        best, best_avail = None, -1.0
        for node in candidates:
            avail = node.available
            reserved = node.reserved_milli
            if all(avail.get(k, 0) * 1000 - reserved.get(k, 0) >= v
                   for k, v in request.items()):
                # Reported backlog discounts a node that looks free but
                # has a deep queue (lease pipelining fills queues ahead
                # of the availability view).
                score = sum(avail.values()) - 0.1 * node.backlog
                if score > best_avail:
                    best, best_avail = node, score
        return best

    # Args at or above this size are PUSHED to the target node ahead of
    # the task (reference push_manager.h: proactive transfers beat the
    # node's on-demand dep pull by one full round trip + queue wait).
    _PUSH_ARG_BYTES = 4 << 20

    def _publish_local_args(self, node: _NodeRecord, spec) -> None:
        """The ONE publish path both dispatch flavors share: report
        driver-local arg locations to the head, then proactively push
        big ones to the target node (off-thread, deduped — the node's
        on-demand dep fetch remains the fallback for every miss)."""
        from ray_tpu.object_ref import ObjectRef

        store = self.worker.memory_store
        local_refs = [arg for arg in list(spec.args)
                      + list(spec.kwargs.values())
                      if isinstance(arg, ObjectRef)
                      and store.contains(arg.id)]
        if not local_refs:
            return
        local_oids = [arg.id.binary() for arg in local_refs]
        self.head._report_objects(
            local_oids, self.head.server.address,
            sizes=[store.entry_size(arg.id) for arg in local_refs])
        self._maybe_push_args(node, local_oids)

    def _maybe_push_args(self, node: _NodeRecord, local_oids) -> None:
        plane = getattr(self.worker, "shm_plane", None)
        if plane is None or node.transfer is None or \
                node.shm_name == plane.name:
            return  # shared segment: dep is already zero-copy visible
        to_push = []
        for ob in local_oids:
            key = (node.node_id, ob)
            if key in self._pushed:
                continue
            try:
                size = plane.store.object_size(ob)
            except Exception:
                size = None
            if size is None or size < self._PUSH_ARG_BYTES:
                continue
            self._pushed.add(key)  # claim before the async push races
            to_push.append(ob)
        if not to_push:
            return

        def run(addr=node.transfer, oids=to_push, nid=node.node_id):
            for ob in oids:
                try:
                    rc = plane.store.push_to(ob, addr[0], addr[1])
                    if rc not in (0, -5):
                        self._pushed.discard((nid, ob))
                except Exception:
                    self._pushed.discard((nid, ob))

        # Off the dispatch path: a GB-scale push must never stall
        # submission; the dep fetch covers the in-flight window.
        threading.Thread(target=run, daemon=True,
                         name="arg-push").start()

    def _send(self, node: _NodeRecord, spec):
        spec = self._promote_large_args(spec)
        # Ordering fence: this synchronous submission must not overtake
        # coalesced frames already enqueued for the same node on the
        # pipelined channel (e.g. tasks submitted just before an actor
        # creation that will pin the node's resources). Flush the
        # batcher (frames handed to the socket) and the pipe (frames
        # ACKED, i.e. dispatched node-side) first; both are no-ops on
        # idle channels and best-effort on sick ones — the node-death
        # paths own real failures.
        batcher = self._batchers.get(node.node_id)
        if batcher is not None:
            batcher.flush(timeout=30.0)
            pipe = self._pipes.get(node.node_id)
            if pipe is not None:
                pipe.flush(timeout=30.0)
        self._publish_local_args(node, spec)
        # Lineage + in-flight BEFORE the wire: a fast task can execute
        # and report its outputs before this function returns, and that
        # report must find (and clear) the in-flight entry — recording
        # after the ack leaves a stale entry that a later node-death
        # sweep would re-drive as a duplicate. On send failure the entry
        # is cleared before the caller's mark_node_dead sweep runs, so
        # only the caller retries.
        self.head.record_lineage(spec)
        self.head.record_inflight(spec, node.node_id)
        self.quota_ledger.note_dequeued(spec)
        wire_spec = self._strip_exported_func(spec, node)
        try:
            RpcClient.to(node.address).call("submit_task",
                                            spec=wire_spec)
        except BaseException:
            self.head.clear_inflight(spec)
            raise

    def _strip_exported_func(self, spec, node: "_NodeRecord"):
        """Function-distribution cache (reference: function_manager
        export via GCS KV + worker import thread). The first shipment of
        a function to the cluster exports its cloudpickle to the head KV
        under its content hash; once a node has seen the id, later task
        specs travel WITHOUT the function body (often the bulk of a
        small task's wire bytes) and the node re-resolves from its local
        cache, falling back to the head KV."""
        from ray_tpu._private.task_spec import QueuedTaskHeader

        if type(spec) is QueuedTaskHeader:
            # Full-spec shipping boundary: materialize the header for
            # the wire WITHOUT moving its quota tokens — the head keeps
            # the header in its lineage/in-flight tables, and releases
            # must find the charge there, not on the wire copy.
            spec = spec.materialize(transfer_tokens=False)
        fid = getattr(spec, "func_id", None)
        if fid is None or spec.kind == TaskKind.ACTOR_TASK:
            return spec
        head = self.head
        if fid not in head.exported_fns:
            from ray_tpu.remote_function import get_export_blob

            blob = get_export_blob(fid)
            if blob is None:
                # No registry entry in THIS process (e.g. spec arrived
                # through the ray-client server): re-pickle, and key the
                # export by the hash of what we actually store — the
                # KV blob and its id must never diverge.
                import hashlib

                import cloudpickle

                try:
                    blob = cloudpickle.dumps(spec.func)
                except Exception:
                    return spec  # unexportable: ship inline as before
                actual = hashlib.sha1(blob).digest()
                if actual != fid:
                    fid = actual
                    import copy

                    spec = copy.copy(spec)
                    spec.func_id = fid
            if fid not in head.exported_fns:
                try:
                    head.worker.gcs.kv_put(fid, blob,
                                           namespace=b"__fn__")
                except Exception:
                    return spec
                head.exported_fns.add(fid)
        if fid in node.known_fns:
            import copy

            wire_spec = copy.copy(spec)
            wire_spec.func = None
            return wire_spec
        node.known_fns.add(fid)  # first shipment carries the body
        return spec

    def shutdown(self):
        """Stop the mixin's own threads (quota drainer, parked-call
        dispatcher), then the local backend's engine."""
        self._quota_stop.set()
        for t in (self._quota_drainer, self._park_thread):
            if t is not None and t.is_alive():
                t.join(timeout=1.0)
        self.local_backend.shutdown()

    # Delegate everything else to the local backend.

    def __getattr__(self, name):
        if name == "local_backend":
            # A half-constructed mixin (harness __new__) must raise,
            # not recurse through this delegation forever.
            raise AttributeError(name)
        return getattr(self.local_backend, name)


class ClusterDriverMixin:
    """get()/wait() that pull remote objects on demand."""

    @staticmethod
    def install(worker, head: ClusterHead):
        worker.cluster_head = head
        original_get = worker.get_objects
        original_wait = worker.wait
        # Both driver-plumbing threads (fetch dispatcher + release
        # batcher) stop through this event at worker shutdown: daemon
        # threads die with the PROCESS, but a long-lived process
        # (test suite, multi-job driver) reconnects and must get its
        # threads back — the leak sanitizer enforces it.
        plumbing_stop = threading.Event()

        # ONE event-driven fetch dispatcher instead of a polling thread
        # per awaited ref (reference: pull_manager.h:52 — a single pull
        # manager with location-notification wakeups). A thread per ref
        # melts down at fan-out scale: 2k awaited refs = 2k threads
        # spinning locate2 polls, starving the executors they wait on.
        # The head's report_objects handler NOTIFIES the dispatcher, so
        # the common case is exactly one fetch attempt per object, right
        # when it becomes available; a slow sweep covers stragglers.
        pending: Dict[bytes, dict] = {}
        cond = threading.Condition()
        hot: set = set()

        def _resolved_locally(object_id):
            # The object landed in the local store (local execution, or
            # a completed fetch): retire its pending entry so the sweep
            # never has to scan resolved refs.
            with cond:
                pending.pop(object_id.binary(), None)

        def ensure_fetch(ref):
            if worker.memory_store.contains(ref.id):
                return
            from ray_tpu._private.config import ray_config

            key = ref.id.binary()
            # First attempt only when the object is ALREADY located
            # somewhere (get-after-completion); otherwise stay purely
            # event-driven — probing shm/directory per awaited ref costs
            # more than the fan-out being awaited.
            with cond:
                if key in pending:
                    return
                pending[key] = {
                    "ref": ref,
                    "deadline": time.monotonic()
                    + ray_config.fetch_deadline_s,
                    "err": None,
                }
            # Location check AFTER the pending insert: a report landing
            # between a pre-insert check and the insert would notify
            # nobody and strand the ref until the slow sweep.
            if key in worker.cluster_head.object_locations:
                with cond:
                    hot.add(key)
                    cond.notify()
            worker.memory_store.on_ready(ref.id, _resolved_locally)

        def on_objects_reported(oids):
            with cond:
                wanted = [o for o in oids if o in pending]
                if wanted:
                    hot.update(wanted)
                    cond.notify()

        worker._fetch_notify = on_objects_reported

        def try_fetch_batch(items) -> set:
            """Batched fetch round over the shared pull core (a
            completed fan-out used to drain with one synchronous round
            trip per object). Returns resolved keys; failures leave
            their error on the entry for deadline handling."""
            # Read through worker.cluster_head (not the install-time
            # capture): restart_head swaps it.
            live_head = worker.cluster_head

            def locate(need):
                return [live_head._locate2(o.binary()) for o in need]

            resolved, failed, _unresolved = batch_fetch_objects(
                worker, [entry["ref"].id for _key, entry in items],
                locate, live_head.server.address)
            done: set = set()
            for key, entry in items:
                oid = entry["ref"].id
                if oid in resolved:
                    done.add(key)
                elif oid in failed:
                    entry["err"] = failed[oid]
            return done

        def dispatcher():
            # Notifications (head reports + local-store callbacks) carry
            # the fast path; the periodic full sweep is only the safety
            # net for missed reports, so it can be SLOW — sweeping every
            # pending ref at high frequency burns the very core the
            # executors need.
            sweep_at = 0.0
            while not plumbing_stop.is_set():
                with cond:
                    cond.wait(timeout=0.05)
                    batch = list(hot)
                    hot.clear()
                    # The sweep runs ON SCHEDULE, not only on idle
                    # cycles — steady hot traffic must never starve the
                    # stragglers the sweep exists to rescue.
                    if pending and time.monotonic() >= sweep_at:
                        batch = list(pending)
                        sweep_at = time.monotonic() + 1.0
                now = time.monotonic()
                items = []
                with cond:
                    for key in batch:
                        entry = pending.get(key)
                        if entry is not None:
                            items.append((key, entry))
                try:
                    done_keys = try_fetch_batch(items)
                except Exception as e:
                    done_keys = set()
                    for _key, entry in items:
                        entry["err"] = e
                for key, entry in items:
                    done = key in done_keys
                    if not done and now > entry["deadline"]:
                        done = True
                        if entry["err"] is not None and \
                                not worker.memory_store.contains(
                                    entry["ref"].id):
                            worker.memory_store.put(
                                entry["ref"].id, None,
                                error=OwnerDiedError(
                                    entry["ref"].id.hex()[:12],
                                    "owner unreachable past the fetch "
                                    f"deadline: {entry['err']}"))
                    if done:
                        with cond:
                            pending.pop(key, None)
                # Drop loop locals: a lingering `entry` binding would
                # pin its ObjectRef (blocking the driver's zero-ref
                # release) across the next wait.
                entry = batch = items = done_keys = None

        dispatcher_thread = threading.Thread(
            target=dispatcher, daemon=True,
            name="cluster-fetch-dispatcher")
        dispatcher_thread.start()

        def get_objects(refs, timeout=None):
            for ref in refs:
                ensure_fetch(ref)
            return original_get(refs, timeout)

        def wait(refs, num_returns, timeout, fetch_local=True):
            for ref in refs:
                ensure_fetch(ref)
            return original_wait(refs, num_returns, timeout, fetch_local)

        worker.get_objects = get_objects
        worker.wait = wait

        # -- distributed release: when the driver's refcount for an
        # object hits zero, batch-release it cluster-wide (owner node
        # drops its copy; lineage unpins). Reference: ReferenceCounter
        # release → FreeObjects fan-out.
        import queue as _queue

        release_q: _queue.Queue = _queue.Queue()
        original_unregister = worker.unregister_object_ref
        original_register = worker.register_object_ref

        def register(ref):
            count = original_register(ref)
            if count == 1:
                # Re-acquiring a handle the driver had fully dropped
                # (e.g. an actor handed a borrowed ref back): cancel any
                # pending deferred release synchronously — before this
                # call returns the driver may rely on the object.
                head.unrelease_objects([ref.id.binary()])
            return count

        def unregister(oid):
            # Only a drop to zero releases cluster-wide: a second driver
            # handle to the same object (e.g. a deserialized copy) must
            # keep it alive.
            if original_unregister(oid):
                release_q.put(oid.binary())

        def release_loop():
            from ray_tpu._private.ids import ObjectID as _OID

            while not plumbing_stop.is_set():
                first = release_q.get()
                if first is None:
                    return  # shutdown sentinel
                batch = [first]
                time.sleep(0.05)
                while True:
                    try:
                        batch.append(release_q.get_nowait())
                    except _queue.Empty:
                        break
                # Level check at apply time: a handle re-acquired while
                # the release sat in this queue must win (the register
                # hook's synchronous unrelease covers the post-apply
                # window; this covers the pre-apply one).
                batch = [ob for ob in batch
                         if ob is not None
                         and worker.memory_store.local_ref_count(
                             _OID(ob)) == 0]
                try:
                    if batch:
                        head.release_objects(batch)
                except Exception:
                    pass

        worker.register_object_ref = register
        worker.unregister_object_ref = unregister
        t = threading.Thread(target=release_loop, daemon=True,
                             name="ray_tpu-release")
        t.start()

        def stop_cluster_plumbing():
            plumbing_stop.set()
            release_q.put(None)  # wake the blocking get
            with cond:
                cond.notify_all()
            dispatcher_thread.join(timeout=1.0)
            t.join(timeout=1.0)

        worker.stop_cluster_plumbing = stop_cluster_plumbing


class Cluster:
    """Reference: `ray.cluster_utils.Cluster` (`cluster_utils.py:99`)."""

    def __init__(self, initialize_head: bool = True,
                 head_node_args: Optional[dict] = None,
                 shm_capacity: Optional[int] = None,
                 log_to_driver: bool = True):
        import os

        head_node_args = head_node_args or {}
        worker_mod.shutdown()
        self.driver_worker = worker_mod.init(
            num_cpus=head_node_args.get("num_cpus", 2),
            # The head coordinates; chips belong to the nodes given
            # num_tpus, so the head only counts its own when asked to.
            num_tpus=head_node_args.get("num_tpus", 0),
            resources=head_node_args.get("resources"))
        self.head = ClusterHead(self.driver_worker)
        backend = ClusterBackendMixin(self.driver_worker, self.head)
        self.driver_worker.backend = backend
        ClusterDriverMixin.install(self.driver_worker, self.head)
        self._wire_driver_spill_reports()
        # Node-wide shared object segment (plasma role): the head creates
        # it; node subprocesses attach by name. Large objects then cross
        # process boundaries zero-copy instead of via pickle RPC.
        self.shm_plane = None
        from ray_tpu._private import shm_plane as shm_mod
        from ray_tpu._private import shm_store

        # A clean checkout has no built library yet; a build that fails
        # is an error here, not a cluster that quietly pickles instead.
        shm_store.ensure_built()
        try:
            kwargs = {"capacity": shm_capacity} if shm_capacity else {}
            self.shm_plane = shm_mod.SharedPlane(
                f"/ray_tpu_{os.getpid()}", create=True, **kwargs)
            self.shm_plane.install(self.driver_worker)
            port = self.shm_plane.store.start_transfer_server()
            # Advertise on the host nodes already use to reach the head's
            # RPC server — loopback in single-host simulation, the real
            # head host otherwise.
            self.head.transfer_addr = (self.head.server.address[0], port)
        except OSError:  # no usable /dev/shm: pickle RPC still works
            self.shm_plane = None
        self._procs: Dict[str, subprocess.Popen] = {}
        self._logs: Dict[str, str] = {}
        self._counter = 0
        # Driver log mirroring (reference log_monitor.py role): node
        # subprocess output re-prints here with a node prefix.
        self._log_monitor = None
        if log_to_driver:
            from ray_tpu._private.log_monitor import LogMonitor

            self._log_monitor = LogMonitor().start()

    @property
    def address(self) -> str:
        host, port = self.head.server.address
        return f"{host}:{port}"

    def add_node(self, num_cpus: float = 1, num_tpus: float = 0,
                 wait: bool = True, simulate_remote_host: bool = False,
                 labels: Optional[Dict[str, str]] = None,
                 **_kw) -> str:
        """Spawn a node subprocess. With ``simulate_remote_host`` the node
        gets its own shm segment instead of attaching the head's, so the
        native transfer plane (cross-host path) is exercised on one
        machine — the reference's fake-multinode testing idea. The
        simulated node's own pulls force the TCP stream (its plane sets
        ``allow_local_pull=False``); pulls BY other processes FROM its
        segment may still take the same-host fast path, since the gate
        lives on the puller."""
        import os
        import tempfile

        self._counter += 1
        node_id = f"node-{self._counter}"
        cmd = [sys.executable, "-m", "ray_tpu._private.cluster_node",
               "--head", self.address, "--num-cpus", str(num_cpus),
               "--node-id", node_id]
        if num_tpus:
            cmd += ["--num-tpus", str(num_tpus)]
        for key, value in (labels or {}).items():
            cmd += ["--label", f"{key}={value}"]
        if self.shm_plane is not None and not simulate_remote_host:
            cmd += ["--shm-name", self.shm_plane.name]
        env = dict(os.environ)
        if not num_tpus:
            # A CPU node must not open the host's chip if one of its
            # tasks imports jax; a node given TPUs owns them.
            env.setdefault("JAX_PLATFORMS", "cpu")
        # Node subprocesses must resolve ray_tpu the same way the driver
        # does (a driver using sys.path.insert — e.g. a checkout not on
        # PYTHONPATH — would otherwise spawn nodes that can't import us).
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        existing = env.get("PYTHONPATH", "")
        if pkg_root not in existing.split(os.pathsep):
            env["PYTHONPATH"] = pkg_root + (
                os.pathsep + existing if existing else "")
        # Child output goes to a log file: a node that dies during
        # bring-up must leave evidence, not vanish silently.
        log_path = os.path.join(tempfile.gettempdir(),
                                f"ray_tpu_{os.getpid()}_{node_id}.log")
        log_f = open(log_path, "wb")
        proc = subprocess.Popen(cmd, env=env, stdout=log_f, stderr=log_f)
        log_f.close()
        self._procs[node_id] = proc
        self._logs[node_id] = log_path
        # Dashboard log module reads these (reference: dashboard log
        # module serving per-node files).
        self.head.node_logs[node_id] = log_path
        if self._log_monitor is not None:
            self._log_monitor.add_file(node_id, log_path)
        if wait:
            # Generous deadline: imports alone can take tens of seconds
            # on a busy single-core box.
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                if node_id in self.head.nodes:
                    return node_id
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"node process exited with {proc.returncode};"
                        f" log tail:\n{self._log_tail(node_id)}")
                time.sleep(0.05)
            raise TimeoutError(
                f"node failed to register within 120s; log tail:\n"
                f"{self._log_tail(node_id)}")
        return node_id

    def _log_tail(self, node_id: str, nbytes: int = 4096) -> str:
        path = self._logs.get(node_id)
        if not path:
            return "<no log>"
        try:
            with open(path, "rb") as f:
                f.seek(0, 2)
                f.seek(max(0, f.tell() - nbytes))
                return f.read().decode(errors="replace")
        except OSError as e:
            return f"<log unreadable: {e}>"

    def remove_node(self, node_id: str, graceful: bool = True):
        record = self.head.nodes.get(node_id)
        proc = self._procs.pop(node_id, None)
        if record is not None:
            if graceful:
                record.alive = False
                try:
                    RpcClient.to(record.address).call("shutdown")
                except Exception:
                    pass
            else:
                # Ungraceful removal is the fault-injection path (the
                # reference's NodeKiller): kill first, then run the full
                # death flow so in-flight work and actors recover.
                if proc is not None:
                    proc.kill()
                self.head.mark_node_dead(node_id, reason="killed")
            self.head.nodes.pop(node_id, None)
        if proc is not None:
            if not graceful:
                proc.kill()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()

    def kill_node(self, node_id: str):
        """`kill -9` the node process *without* telling the head — death
        must be discovered by the health checker (chaos-test hook)."""
        proc = self._procs.get(node_id)
        if proc is not None:
            proc.kill()
            proc.wait(timeout=10)

    def restart_head(self, mode: str = "graceful"):
        """Head (GCS) failover: tear the head's services down and bring
        a FRESH head up on the same address, recovering durable tables
        from gcs_storage (reference: GCS restart +
        `node_manager.proto:356` RayletNotifyGCSRestart).

        Two modes:

        - ``"graceful"`` (default): planned handoff — the old store's
          deferred group-commit batch is flushed before the swap, so
          the successor recovers EVERYTHING the old head accepted.
        - ``"crash"``: hard process death — NO flush; the sqlite
          connection drops with the open group-commit window
          uncommitted (WAL rolls it back). The documented loss bound is
          exactly that window (``gcs_commit_interval_s``): writes whose
          flush() returned (acked durable) survive, writes still
          riding the window may be lost, and nothing un-acked ever
          resurrects — the same contract raymc's ``gcs_durability`` /
          ``head_crash_recovery`` scenarios prove at small scope. Live
          nodes re-register through the report-returns-False path with
          no driver intervention; in-flight callers ride the fetch
          retry window to completion.

        What this simulates/recovers, and what it loses:
        - KV, named-actor, and placement-group tables reload from the
          configured ``gcs_storage_path`` (empty path = in-memory store
          → tables start empty, like the non-FT reference deployment).
        - The node table starts EMPTY; live node processes re-register
          through their resource-report loop (the report returns False
          for an unknown node → the node re-registers and re-reports
          its hosted actors and owned objects — the NotifyGCSRestart
          re-publish). Nodes that stay unreachable past the node-side
          suicide window exit themselves.
        - In-flight dispatch state (``inflight``) is lost: tasks already
          running on nodes complete and re-report their outputs after
          re-registration; callers keep waiting through the fetch
          retry window rather than getting spurious errors.
        - The driver process itself survives (the head is in-process
          here); in a real deployment driver death is a separate event.
        """
        if mode not in ("graceful", "crash"):
            raise ValueError(f"restart_head mode must be 'graceful' or "
                             f"'crash', got {mode!r}")
        old = self.head
        addr = old.server.address
        old.stop()
        old.server.shutdown()
        old_gcs = self.driver_worker.gcs
        if mode == "graceful":
            # Graceful handoff boundary: drain the old store's deferred
            # group-commit batch so the fresh GlobalState's new
            # connection recovers everything the old head accepted,
            # then close it (stops the flusher thread).
            flush = getattr(old_gcs, "flush_storage", None)
            if flush is not None:
                flush()
            close = getattr(old_gcs, "close_storage", None)
            if close is not None:
                close()
        else:
            # Hard crash: the connection dies with the group-commit
            # window open — sqlite rolls the pending transaction back,
            # exactly what a SIGKILL'd head process leaves behind.
            crash = getattr(old_gcs, "crash_storage", None)
            if crash is not None:
                crash()
        # Fresh GlobalState: prove recovery comes from durable storage,
        # not this process's memory.
        self.driver_worker.gcs = state_mod.GlobalState(self.driver_worker)
        new = ClusterHead(self.driver_worker, port=addr[1])
        new.transfer_addr = old.transfer_addr
        new.node_logs = dict(old.node_logs)
        # Recover placed-bundle locations from the durable PG table.
        for pg in self.driver_worker.gcs.placement_group_table().values():
            for i, nid in enumerate(getattr(pg, "bundle_nodes", None)
                                    or []):
                if nid is not None:
                    new.pg_bundle_nodes[(pg.id.binary(), i)] = nid
        self.head = new
        self.driver_worker.backend.head = new
        self.driver_worker.cluster_head = new
        self._wire_driver_spill_reports()
        new._ensure_health_checker()
        return new

    def _wire_driver_spill_reports(self):
        """Driver-local spills feed the (current) head's spill-URL
        directory the same way node spills do over RPC."""
        store = self.driver_worker.memory_store
        cluster = self

        def on_spilled(oid, url):
            try:
                cluster.head.note_spilled(oid.binary(), url)
            except Exception:
                pass

        store.on_spilled = on_spilled

    def nodes(self) -> List[dict]:
        return self.head._get_nodes()

    def shutdown(self):
        # Drain the group-committed submit channels BEFORE tearing nodes
        # down: a batch parked in a CoalescingBatcher or an un-acked
        # pipelined request is an accepted submission, and the shutdown
        # boundary is exactly where a non-draining close would lose it.
        backend = getattr(self.driver_worker, "backend", None)
        if isinstance(backend, ClusterBackendMixin):
            backend.drain_channels(timeout=2.0)
        self.head.stop()
        for node_id in list(self._procs):
            self.remove_node(node_id)
        if self._log_monitor is not None:
            self._log_monitor.stop()  # final drain catches exit output
            self._log_monitor = None
        self.head.server.shutdown()
        if self.shm_plane is not None:
            # Detach from the worker first (new fetches skip shm), then
            # unlink WITHOUT unmapping: a fetch thread mid-read keeps a
            # valid mapping instead of segfaulting on teardown.
            if getattr(self.driver_worker, "shm_plane", None) \
                    is self.shm_plane:
                self.driver_worker.shm_plane = None
            self.shm_plane.destroy(unmap=False)
            self.shm_plane = None
        worker_mod.shutdown()
