"""Worker-node process for multiprocess cluster mode.

Role-equivalent to the reference's raylet + worker pool on one node
(SURVEY.md §1 process topology): registers with the head, executes tasks
submitted over the control plane on a LocalBackend, serves its objects to
peers (owner-based pull — the reference's
`ownership_based_object_directory.h` pattern: the head only stores
*locations*, payloads move node→node directly), and pulls remote
dependencies before dispatch.

Entry: ``python -m ray_tpu._private.cluster_node --head HOST:PORT ...``.
"""

from __future__ import annotations

import argparse
import os
import threading
import time
from typing import Any, Dict, Optional

from ray_tpu._private import sanitize_hooks
from ray_tpu._private import worker as worker_mod
from ray_tpu._private.ids import NodeID, ObjectID
from ray_tpu._private.rpc import RpcClient, RpcServer, routable_host


class NodeRuntime:
    def __init__(self, head_address, resources: Dict[str, float],
                 node_id: Optional[str] = None,
                 shm_name: Optional[str] = None,
                 labels: Optional[Dict[str, str]] = None):
        self.head = RpcClient.to(tuple(head_address))
        self.node_id = node_id or NodeID.from_random().hex()
        # Scheduling labels (e.g. {"ici_slice": "slice-0"} marking which
        # contiguous TPU slice this host belongs to).
        self.labels = dict(labels or {})
        # Objects whose location this node has advertised — replayed to
        # a RESTARTED head (whose location map starts empty).
        self._reported_oids: set = set()

        # Bring up a standard in-process runtime for this node.
        worker_mod.shutdown()
        self.worker = worker_mod.init(**_res_kwargs(resources))
        self.worker.is_cluster_node = True
        # Tenancy quotas are CLUSTER-wide, enforced once at the head's
        # grant/admission path; a node re-enforcing them against its
        # local slice of capacity would double-charge every job.
        self.worker.backend.quota_ledger.disable()
        # Endpoints are advertised at the interface the head routes us
        # on (loopback in single-host simulation, the NIC IP on a real
        # multi-host deployment) — the reference's node manager likewise
        # registers the node's resolved IP, not loopback.
        self._adv_host = routable_host(tuple(head_address))
        self.transfer_addr: Optional[tuple] = None
        self.plane = None
        plane = None
        try:
            from ray_tpu._private.shm_plane import SharedPlane

            if shm_name:
                # Same host as the head: attach its segment — objects
                # move zero-copy between processes with no transfer.
                plane = SharedPlane(shm_name, create=False)
            else:
                # Own segment (remote host, or simulating one): peers
                # reach our objects through the native transfer server.
                # Pulls from this node must take the wire even if the
                # peer's segment happens to be mappable here — that is
                # exactly the remote-host-on-one-machine simulation the
                # same-host fast path would otherwise silently bypass.
                plane = SharedPlane(f"/ray_tpu_node_{os.getpid()}",
                                    create=True)
                plane.allow_local_pull = False
            # Server first, install last: if anything here raises the
            # worker has not been touched yet.
            port = plane.store.start_transfer_server()
            plane.install(self.worker)
            self.transfer_addr = (self._adv_host, port)
            self.plane = plane
        except Exception:
            # Heap/RPC path is still correct — but don't leak a
            # half-installed plane or an orphaned /dev/shm segment.
            if plane is not None:
                if getattr(self.worker, "shm_plane", None) is plane:
                    self.worker.shm_plane = None
                try:
                    if shm_name:
                        plane.close()      # attached: owner cleans up
                    else:
                        plane.destroy()    # ours: unlink the segment
                except Exception:
                    pass
            self.transfer_addr = None
        self._fn_cache: Dict[bytes, Any] = {}  # function-import cache
        # Interned spec templates received over the wire (the
        # serialize-once TaskSpec cache): template_id -> SpecTemplate.
        # LRU at 2x the head's per-node claim bound: every template
        # carries a pickled user callable + captured environment, so an
        # unbounded cache would grow node RSS forever under dynamic
        # function minting; the capacity margin keeps every id the head
        # still claims resident (both sides touch in the same order).
        from ray_tpu._private.rpc import LruTable

        self._spec_templates = LruTable(8192)
        self._shutdown_event = threading.Event()
        self._install_report_hook()
        self._install_spill_report()
        self._install_borrow_hooks()
        self._install_cluster_actor_routing()
        self._install_cluster_kv()
        self._install_fetch_on_get()
        self._install_cluster_named_actors()

        self.server = RpcServer({
            "submit_task": self._submit_task,
            "submit_batch": self._submit_batch,
            "get_object": self._get_object,
            "get_objects_batch": self._get_objects_batch,
            "contains_object": self._contains_object,
            "free_objects": self._free_objects,
            "kill_actor": self._kill_actor,
            "prepare_bundle": self._prepare_bundle,
            "commit_bundle": self._commit_bundle,
            "return_bundle": self._return_bundle,
            "ping": self._ping,
            "flight_snapshot": self._flight_snapshot,
            "shutdown": self._shutdown,
        }, host="0.0.0.0",
           dedupe_methods=frozenset({"submit_task", "submit_batch",
                                     "kill_actor"}))
        # 2PC bundle reservation state: (pg_id, idx) -> milli request held
        # in "prepared" until commit or return (reference:
        # `raylet/placement_group_resource_manager.h`).
        self._prepared_bundles: Dict[tuple, Dict[str, int]] = {}
        # Advertised control address (bind is all-interfaces).
        self.address = (self._adv_host, self.server.address[1])
        # Registration is idempotent; retry through transient head
        # unavailability during cluster bring-up.
        from ray_tpu._private.config import ray_config

        last_err: Optional[BaseException] = None
        plane = getattr(self.worker, "shm_plane", None)
        for _ in range(ray_config.rpc_connect_retries):
            try:
                self.head.call("register_node", node_id=self.node_id,
                               address=self.address,
                               resources=resources,
                               transfer=self.transfer_addr,
                               shm_name=plane.name if plane else None,
                               labels=self.labels)
                # Events recorded in THIS process (e.g. a serve
                # controller actor placed here) must reach the head's
                # observable buffer, not die in a local deque.
                from ray_tpu._private import events as _events

                head = self.head
                _events.set_forwarder(
                    lambda **kw: head.call("gcs_record_event", **kw))
                # Observability shipping: task-event deltas + metric
                # snapshots flow to the head's aggregator so timeline/
                # tracing/state/dashboard views are cluster-wide. Shares
                # the node's shutdown event — the loop's exit path ships
                # the final terminal states.
                from ray_tpu._private.obs_plane import NodeObsShipper

                self.obs_shipper = NodeObsShipper(
                    self.worker, tuple(head_address), self.node_id,
                    stop_event=self._shutdown_event).start()
                break
            except Exception as e:
                last_err = e
                time.sleep(ray_config.rpc_retry_backoff_s)
        else:
            raise RuntimeError(
                f"node {self.node_id} could not register with head at "
                f"{head_address}: {last_err}")

    # -- object plane ----------------------------------------------------

    def _install_report_hook(self):
        """Report object locations to the head as task outputs land."""
        worker = self.worker
        orig = worker.store_task_outputs
        node = self
        # Output reports BATCH across tasks (reference: raylet object
        # report batching): at fan-out rates a synchronous head RPC per
        # task serializes every executor thread behind the report
        # connection. A dedicated reporter flushes accumulated oids
        # every couple of ms — results become cluster-visible one batch
        # later, execution never blocks on the head.
        import queue as _q

        report_q: "_q.SimpleQueue" = _q.SimpleQueue()

        def report_loop():
            while True:
                items = [report_q.get()]
                t0 = time.monotonic()
                while time.monotonic() - t0 < 0.002:
                    try:
                        items.append(report_q.get_nowait())
                    except _q.Empty:
                        time.sleep(0.0005)
                # Borrow registrations first: the output report unpins
                # these tasks' args at the head, so any borrow they
                # created must be on record before that (same head
                # connection → ordered).
                getattr(node, "_flush_borrows", lambda: None)()
                try:
                    # Sizes ride the report: the head's directory feeds
                    # locality-aware placement (bytes, not just where).
                    node.head.call("report_objects",
                                   oids=[ob for ob, _ in items],
                                   address=node.address,
                                   sizes=[sz for _, sz in items])
                except Exception:
                    pass

        threading.Thread(target=report_loop, daemon=True,
                         name="output-reporter").start()

        def store_and_report(spec, values, error=None):
            orig(spec, values, error=error)
            # Primary-copy pin (reference: plasma primary copies stay
            # pinned until the owner frees them): local handle churn (an
            # actor holding then releasing a ref to an object that lives
            # here) must never evict the only copy; the head's
            # free_objects is what drops it.
            dynamic = list(getattr(spec, "dynamic_return_ids", ()))
            for roid in list(spec.return_ids) + dynamic:
                worker.memory_store.pin_object(roid)
            returns = list(spec.return_ids) + dynamic
            if returns:
                node._reported_oids.update(r.binary() for r in returns)
                for roid in returns:
                    report_q.put((roid.binary(),
                                  worker.memory_store.entry_size(roid)))

        worker.store_task_outputs = store_and_report

    def _install_spill_report(self):
        """Spilled objects report their durable URL to the head: if
        this node later dies, the head restores the lost object from
        the surviving disk copy instead of re-executing its creating
        task (reconstruction-composes-with-spill). Reports COALESCE on
        a drainer thread (same shape as the output reporter): one
        pressure sweep spilling dozens of objects makes one RPC, not
        one per object, and the spill path never blocks on the head."""
        import queue as _q

        node = self
        report_q: "_q.SimpleQueue" = _q.SimpleQueue()

        def report_loop():
            while True:
                items = [report_q.get()]
                t0 = time.monotonic()
                while time.monotonic() - t0 < 0.05:
                    try:
                        items.append(report_q.get_nowait())
                    except _q.Empty:
                        time.sleep(0.005)
                try:
                    node.head.call("report_spilled",
                                   oids=[ob for ob, _ in items],
                                   urls=[u for _, u in items],
                                   node_id=node.node_id)
                except Exception:
                    pass  # best effort: re-execution remains the net

        threading.Thread(target=report_loop, daemon=True,
                         name="spill-reporter").start()
        self.worker.memory_store.on_spilled = \
            lambda object_id, url: report_q.put((object_id.binary(),
                                                 url))

    def _install_borrow_hooks(self):
        """Register this node as a borrower of every object it holds a
        handle to (reference: ReferenceCounter borrower protocol). A ref
        deserialized here (task arg, value inside actor state) adds this
        node to the head's borrower set for its object; the last local
        handle dropping removes it.

        Reporting is LEVEL-based, not edge-based: hooks only mark an oid
        "touched"; the flush consults the store's current handle count
        and diffs against what the head was last told. This is immune to
        drop-then-reacquire races inside one flush window (an edge queue
        could deliver add+remove in the wrong order), and a failed flush
        simply re-touches the batch for the next round. Adds are flushed
        BEFORE task-output reports on the same head connection, so the
        head never unpins a task's args before learning about a borrow
        the task created."""
        worker = self.worker
        node = self
        orig_register = worker.register_object_ref
        orig_unregister = worker.unregister_object_ref
        touched: set = set()
        reported: set = set()  # oids the head believes we borrow
        lock = threading.Lock()
        flush_lock = threading.Lock()  # one flush at a time (loop +
        #                                pre-report flushes can race)
        from ray_tpu._private.ids import ObjectID as _OID

        def flush():
            with flush_lock:
                _flush_inner()  # raylint: disable=R2 -- flush_lock exists ONLY to serialize this flush RPC (loop + pre-report flushes race); nothing else ever contends on it, so holding it across the head call is its entire job

        def _flush_inner():
            with lock:
                batch = list(touched)
                touched.clear()
            if not batch:
                return
            adds, removes = [], []
            for ob in batch:
                holding = worker.memory_store.local_ref_count(
                    _OID(ob)) > 0
                if holding and ob not in reported:
                    adds.append(ob)
                elif not holding and ob in reported:
                    removes.append(ob)
            try:
                if adds:
                    node.head.call("add_borrowers", oids=adds,
                                   node_id=node.node_id)
                    reported.update(adds)
                if removes:
                    node.head.call("remove_borrowers", oids=removes,
                                   node_id=node.node_id)
                    reported.difference_update(removes)
            except Exception:
                # Head unreachable: nothing was dropped — re-touch so the
                # next flush retries (a lost add would let the head free
                # a borrowed object; a lost remove would leak it).
                with lock:
                    touched.update(batch)

        def register(ref):
            count = orig_register(ref)
            if count == 1:
                with lock:
                    touched.add(ref.id.binary())
            return count

        def unregister(oid):
            zero = orig_unregister(oid)
            if zero:
                with lock:
                    touched.add(oid.binary())
            return zero

        worker.register_object_ref = register
        worker.unregister_object_ref = unregister
        self._flush_borrows = flush

        def flush_loop():
            while not self._shutdown_event.wait(0.2):
                flush()

        threading.Thread(target=flush_loop, daemon=True,
                         name="borrow-flush").start()

    def _fetch_dependency(self, oid: ObjectID,
                          timeout: Optional[float] = None):
        from ray_tpu._private.config import ray_config

        if self.worker.memory_store.contains(oid):
            return
        if timeout is None:
            timeout = ray_config.fetch_deadline_s
        deadline = time.monotonic() + timeout
        attempt = 0
        while time.monotonic() < deadline:
            if self.worker.memory_store.contains(oid):
                return  # produced locally while we were polling
            from ray_tpu.cluster_utils import (fetch_backoff,
                                               try_shm_fetch,
                                               try_transfer_fetch)

            if try_shm_fetch(self.worker, oid):
                return
            # Local probes (memory store, shm) are cheap and run every
            # attempt; the head locate RPC is rate-limited to every 4th
            # fine-grained probe so sub-ms polling doesn't turn into an
            # RPC storm.
            if attempt % 4 == 0:
                info = self.head.call("locate2", oid=oid.binary())
                if info is not None and \
                        tuple(info["address"]) != self.address:
                    if try_transfer_fetch(self.worker, oid, info):
                        return
                    ok, value, err = RpcClient.to(
                        tuple(info["address"])).call(
                        "get_object", oid=oid.binary())
                    if ok:
                        self.worker.memory_store.put(oid, value,
                                                     error=err)
                        return
            fetch_backoff(attempt)
            attempt += 1
        raise TimeoutError(f"could not fetch {oid.hex()} from cluster")

    # -- RPC handlers ----------------------------------------------------

    def _submit_task(self, spec):
        from ray_tpu.object_ref import ObjectRef

        if spec.func is None and getattr(spec, "func_id", None):
            spec.func = self._resolve_function(spec.func_id)
        elif spec.func is not None and getattr(spec, "func_id", None):
            # Prime the cache from the full-body first shipment so the
            # first STRIPPED spec doesn't pay a head-KV round trip on
            # the dispatch hot path.
            self._fn_cache[spec.func_id] = spec.func
        deps = [arg.id for arg in
                list(spec.args) + list(spec.kwargs.values())
                if isinstance(arg, ObjectRef)]
        missing = [d for d in deps
                   if not self.worker.memory_store.contains(d)]
        submit = getattr(self, "_orig_backend_submit",
                         self.worker.backend.submit)
        if not missing:
            submit(spec)
            return True

        # Pull remote deps off the RPC thread: ack immediately so the
        # driver isn't blocked on our fetches (the reference's
        # DependencyManager is likewise async). The batched fetch
        # resolves ALL missing args with one locate RPC + one pull per
        # owner, not one round trip per argument.
        def fetch_then_submit():
            try:
                self._fetch_dependencies(missing)
                submit(spec)
            except BaseException as e:  # noqa: BLE001
                from ray_tpu import exceptions as exc

                self.worker.store_task_outputs(
                    spec, None,
                    error=exc.TaskError(e, spec.describe()))

        threading.Thread(target=fetch_then_submit, daemon=True).start()
        return True

    # -- batched submission (interned templates + coalesced frames) ------

    def _submit_batch(self, templates=None, calls=None):
        """One coalesced frame of task submissions. Templates register
        first (a frame always carries a template before the first call
        referencing it); calls then dispatch in order. Per-call failures
        land in that call's return objects — the frame itself only fails
        on transport/decode problems, where nothing was dispatched."""
        # Yield point at the frame boundary: everything before this
        # crossing is "the frame arrived but nothing dispatched" —
        # where a node death leaves the driver's exactly-once resubmit
        # (same frame rid, server-side dedupe) to do the recovery.
        sanitize_hooks.sched_point("cluster.submit_batch")
        for t in templates or []:
            payload = t.payload
            if payload is not None:
                self._spec_templates.add(t.template_id, payload)
        for c in calls or []:
            try:
                from ray_tpu._private import wire

                spec = self._spec_from_call(c) \
                    if isinstance(c, wire.TaskCall) else c
                self._submit_task(spec)
            except BaseException as e:  # noqa: BLE001 — isolate per call
                self._fail_call(c, e)
        return True

    def _spec_from_call(self, call):
        tpl = self._spec_templates.get(call.template_id)
        if tpl is None:
            raise RuntimeError(
                f"UnknownTemplateError: {call.template_id.hex()[:12]} "
                "not registered on this node")
        from ray_tpu._private.config import ray_config
        from ray_tpu._private.ids import TaskID

        if ray_config.sched_compact_queue:
            # Node-side compact queueing: the wire call stays a header
            # until this node's scheduler dispatches it, so a deep
            # remote backlog is header-sized here too.
            from ray_tpu._private.task_spec import QueuedTaskHeader

            spec = QueuedTaskHeader(
                tpl, TaskID(call.task_id),
                tuple(call.args or ()),
                dict(call.kwargs or {}),
                depth=call.depth,
                trace_parent=tuple(call.trace_parent)
                if call.trace_parent else None,
                job_id=getattr(call, "job_id", "") or "",
            )
            if call.num_returns is not None:
                spec.num_returns = call.num_returns
        else:
            spec = tpl.make_spec(
                TaskID(call.task_id),
                tuple(call.args or ()),
                dict(call.kwargs or {}),
                depth=call.depth,
                trace_parent=tuple(call.trace_parent)
                if call.trace_parent else None,
                num_returns=call.num_returns,
                job_id=getattr(call, "job_id", "") or "",
            )
        spec.max_retries = call.max_retries
        spec.attempt = getattr(call, "attempt", 0) or 0
        spec.assign_return_ids()
        return spec

    def _fail_call(self, c, e: BaseException):
        """Fail one batch item into its return objects (num_returns
        rides on the call precisely so this works without the
        template)."""
        from types import SimpleNamespace

        from ray_tpu import exceptions as exc
        from ray_tpu._private import wire
        from ray_tpu._private.ids import TaskID

        try:
            if isinstance(c, wire.TaskCall):
                n = 1 if c.num_returns == "dynamic" else int(c.num_returns)
                n = max(n, 1)
                tid = TaskID(c.task_id)
                return_ids = [ObjectID.for_task_return(tid, i)
                              for i in range(n)]
                desc = f"task {tid.hex()[:8]} (batched)"
            else:
                return_ids = list(c.return_ids) or c.assign_return_ids()
                desc = c.describe()
            self.worker.store_task_outputs(
                SimpleNamespace(return_ids=return_ids,
                                dynamic_return_ids=()),
                None, error=exc.TaskError(e, desc))
        except Exception:
            pass  # best effort: the head's fetch deadline is the backstop

    def _fetch_dependencies(self, oids, timeout=None):
        """Batched arg-fetch: resolve every missing dependency with ONE
        head locate RPC for the whole set, then one batched pull per
        owner node (reference: PullManager batches object requests) —
        the shared core in cluster_utils. Anything still unresolved (or
        whose owner errored) falls back to the per-object polling fetch
        (slow producers, racing relocation)."""
        from ray_tpu.cluster_utils import batch_fetch_objects

        def locate(need):
            try:
                return self.head.call(
                    "locate_batch", oids=[o.binary() for o in need])
            except Exception:
                return [None] * len(need)

        _resolved, failed, unresolved = batch_fetch_objects(
            self.worker, oids, locate, self.address)
        for oid in list(failed) + unresolved:
            self._fetch_dependency(oid, timeout)

    def _install_cluster_actor_routing(self):
        """Actor handles work from ANY process (reference: the direct
        actor transport reaches actors wherever they live). A task here
        holding a handle to an actor that does NOT live in this node
        routes the call through the head, whose cluster backend knows
        every actor's home; results come back over the object plane."""
        backend = self.worker.backend
        node = self
        orig_submit = backend.submit
        # Submissions ARRIVING over RPC (the head directed them here)
        # must bypass the wrapper: routing them back to the head when a
        # creation's mailbox isn't registered yet would ping-pong
        # head<->node in nested blocking RPCs.
        self._orig_backend_submit = orig_submit

        def submit(spec):
            from ray_tpu._private.task_spec import TaskKind

            if spec.kind == TaskKind.ACTOR_TASK and \
                    spec.actor_id not in backend._actors:
                node.head.call("route_task", spec=spec)
                return
            if spec.kind == TaskKind.ACTOR_CREATION:
                # A locally-created actor must exist in the head's
                # directory or handles to it can't route from other
                # processes.
                orig_submit(spec)
                for attempt in range(3):
                    try:
                        node.head.call("report_actor", spec=spec,
                                       node_id=node.node_id)
                        break
                    except Exception:
                        # Unregistered = unroutable from every other
                        # process; worth a few retries and a loud log.
                        if attempt == 2:
                            import logging

                            logging.getLogger(__name__).warning(
                                "could not register actor %s with the "
                                "head; remote handles to it will fail",
                                spec.actor_id.hex()[:8])
                        time.sleep(0.2)
                return
            orig_submit(spec)

        backend.submit = submit

    def _install_fetch_on_get(self):
        """On-demand remote-object fetch for get()/wait() issued INSIDE
        node code (e.g. a routed actor call's result): the dep-fetch
        machinery covers task ARGUMENTS; this covers refs acquired
        mid-task. Mirrors the driver's ClusterDriverMixin."""
        worker = self.worker
        node = self
        fetching: set = set()
        lock = threading.Lock()

        def ensure_fetch(ref):
            if worker.memory_store.contains(ref.id):
                return
            key = ref.id.binary()
            with lock:
                if key in fetching:
                    return
                fetching.add(key)

            def fetch(oid=ref.id):
                from ray_tpu import exceptions as exc

                try:
                    node._fetch_dependency(oid)
                except TimeoutError:
                    # Deadline expiry is NOT evidence of a dead owner —
                    # the producer may simply still be running. Give up
                    # quietly (the caller's own get timeout governs);
                    # dropping the fetching entry lets a later get
                    # retry. Poisoning here would fail healthy slow
                    # calls AND stick for every later reader.
                    pass
                except BaseException as e:  # noqa: BLE001
                    if not worker.memory_store.contains(oid):
                        worker.memory_store.put(
                            oid, None, error=exc.OwnerDiedError(
                                oid.hex()[:12],
                                f"fetch failed on node "
                                f"{node.node_id}: {e}"))
                finally:
                    with lock:
                        fetching.discard(key)

            threading.Thread(target=fetch, daemon=True).start()

        original_get = worker.get_objects
        original_wait = worker.wait

        def get_objects(refs, timeout=None):
            for ref in refs:
                ensure_fetch(ref)
            return original_get(refs, timeout)

        def wait(refs, num_returns, timeout, *args, **kw):
            for ref in refs:
                ensure_fetch(ref)
            return original_wait(refs, num_returns, timeout, *args,
                                 **kw)

        worker.get_objects = get_objects
        worker.wait = wait

    def _install_cluster_kv(self):
        """Internal KV is a CLUSTER-wide table living on the head
        (reference: gcs_kv_manager.h behind the GCS client); node-local
        kv_put/get/del/keys delegate there so components running on any
        node (e.g. the serve controller's checkpoints) read and write
        the same — durable, when configured — store."""
        gcs = self.worker.gcs
        head = self.head
        gcs.kv_put = lambda key, value, overwrite=True, namespace=None: \
            head.call("gcs_kv_put", key=key, value=value,
                      overwrite=overwrite, namespace=namespace)
        gcs.kv_get = lambda key, namespace=None: \
            head.call("gcs_kv_get", key=key, namespace=namespace)
        gcs.kv_del = lambda key, namespace=None: \
            head.call("gcs_kv_del", key=key, namespace=namespace)
        gcs.kv_keys = lambda prefix, namespace=None: \
            head.call("gcs_kv_keys", prefix=prefix, namespace=namespace)

    def _install_cluster_named_actors(self):
        """Named actors are a CLUSTER-wide registry (reference:
        GcsActorManager named actors); node-local registrations/lookups
        delegate to the head."""
        gcs = self.worker.gcs
        head = self.head

        def register(name, namespace, handle):
            head.call("gcs_named_actor_register", name=name,
                      namespace=namespace, handle=handle)

        def get(name, namespace):
            try:
                return head.call("gcs_named_actor_get", name=name,
                                 namespace=namespace)
            except Exception as e:
                raise ValueError(
                    f"Failed to look up actor {name!r}") from e

        def list_named(all_namespaces=False):
            return head.call("gcs_named_actors",
                             all_namespaces=all_namespaces)

        def remove_by_id(actor_id):
            head.call("gcs_named_actor_remove",
                      actor_id=actor_id.binary())

        gcs.register_named_actor = register
        gcs.get_named_actor = get
        gcs.list_named_actors = list_named
        gcs.remove_named_actor_by_id = remove_by_id

    def _resolve_function(self, fid: bytes):
        """Function-distribution import side (reference: the worker
        import thread pulling exported definitions from GCS KV). Specs
        shipped without a body resolve here: process cache first, head
        KV on miss."""
        fn = self._fn_cache.get(fid)
        if fn is None:
            import cloudpickle

            blob = self.head.call("gcs_kv_get", key=fid,
                                  namespace=b"__fn__")
            if blob is None:
                raise RuntimeError(
                    f"function {fid.hex()[:12]} not found in the "
                    "cluster function store")
            fn = cloudpickle.loads(blob)
            self._fn_cache[fid] = fn
        return fn

    def _get_object(self, oid: bytes, timeout: float = 30.0):
        object_id = ObjectID(oid)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            ready, value, error = self.worker.memory_store.peek(object_id)
            if ready:
                return True, value, error
            time.sleep(0.005)
        return False, None, None

    def _get_objects_batch(self, oids, timeout: float = 30.0,
                           shm=None, can_pull: bool = False):
        """Batched peer read: one RPC returns, per object, either an
        ObjectDescriptor (requester can reach the sealed bytes — same
        segment or our transfer server) or (ok, value, error) with the
        framed-pickle value for small/plane-less objects."""
        from ray_tpu.cluster_utils import descriptor_object_read

        return descriptor_object_read(
            self.worker, self.transfer_addr,
            lambda oid, t: self._get_object(oid, timeout=t), oids,
            timeout, shm=shm, can_pull=can_pull)

    def _contains_object(self, oid: bytes):
        return self.worker.memory_store.contains(ObjectID(oid))

    def _free_objects(self, oids):
        """Drop objects whose driver-side refcount hit zero (the head
        fans the release out to owners — reference: FreeObjects RPC,
        `object_manager.proto:61`)."""
        object_ids = [ObjectID(o) for o in oids]
        self._reported_oids.difference_update(oids)
        self.worker.memory_store.evict(object_ids)
        plane = getattr(self.worker, "shm_plane", None)
        if plane is not None:
            for object_id in object_ids:
                try:
                    # Owner-side free: drop the pin AND reclaim the
                    # arena block (a released-but-undeleted object only
                    # leaves under later LRU pressure).
                    plane.evict_object(object_id)
                except Exception:
                    pass
        return True

    def _kill_actor(self, actor_id, no_restart: bool = True):
        self.worker.backend.kill_actor(actor_id, no_restart)
        return True

    # -- placement-group 2PC (prepare / commit / return) -----------------

    def _prepare_bundle(self, pg_id: bytes, index: int, request):
        """Phase 1: tentatively acquire the bundle's resources."""
        key = (pg_id, index)
        if key in self._prepared_bundles:
            return True  # idempotent retry
        milli = {k: int(v) for k, v in request.items()}
        if self.worker.backend.resources.try_acquire(milli):
            self._prepared_bundles[key] = milli
            return True
        return False

    def _commit_bundle(self, pg_id: bytes, index: int, bundle):
        """Phase 2: convert the held resources into a bundle pool tasks
        can target via PlacementGroupSchedulingStrategy."""
        from ray_tpu._private.ids import PlacementGroupID
        from ray_tpu._private.resources import ResourceSet

        key = (pg_id, index)
        if key not in self._prepared_bundles:
            return False
        self._prepared_bundles.pop(key)
        self.worker.backend.bundle_resources[
            (PlacementGroupID(pg_id), index)] = ResourceSet(bundle)
        return True

    def _return_bundle(self, pg_id: bytes, index: int):
        """Abort a prepared bundle, or release a committed one."""
        from ray_tpu._private.ids import PlacementGroupID
        from ray_tpu._private.resources import to_milli

        key = (pg_id, index)
        held = self._prepared_bundles.pop(key, None)
        if held is not None:
            self.worker.backend.resources.release(held)
            return True
        pool = self.worker.backend.bundle_resources.pop(
            (PlacementGroupID(pg_id), index), None)
        if pool is not None:
            self.worker.backend.resources.release(to_milli(pool.total))
            return True
        return False

    def _ping(self):
        return {
            "node_id": self.node_id,
            "available": self.worker.backend.resources.available,
            "total": self.worker.backend.resources.total,
            "labels": self.labels,
        }

    def _flight_snapshot(self):
        """Freeze this node's flight-recorder rings (recent stage
        spans + health samples + slow in-flight waterfalls) for the
        head's correlated FLIGHT_<ts>.json post-mortem dump."""
        from ray_tpu._private import flight_recorder

        return flight_recorder.local_snapshot()

    def _shutdown(self):
        self._shutdown_event.set()
        return True

    # -- lifecycle -------------------------------------------------------

    def _resource_report_loop(self):
        """Push the availability view to the head (reference:
        ray_syncer.h RESOURCE_VIEW deltas). Doubles as a heartbeat; only
        deltas are sent (an unchanged view is skipped, with a periodic
        keepalive so the head's freshness window stays warm)."""
        from ray_tpu._private.config import ray_config

        last_sent = None
        last_time = 0.0
        while not self._shutdown_event.wait(
                max(ray_config.resource_report_period_s, 0.01)):
            view = dict(self.worker.backend.resources.available)
            keepalive = time.monotonic() - last_time > \
                ray_config.resource_report_period_s * \
                (ray_config.resource_report_fresh_periods / 2)
            if view == last_sent and not keepalive:
                continue
            try:
                from ray_tpu._private.node_stats import sample_node_stats

                # Backlog rides the report (reference: raylet backlog
                # reports in lease requests): queued-not-running task
                # count, so lease grants see queue depth, not just the
                # resource view.
                backlog = self.worker.backend.backlog_count()
                ok = self.head.call("report_resources",
                                    node_id=self.node_id,
                                    available=view, labels=self.labels,
                                    stats=sample_node_stats(),
                                    backlog=backlog)
                last_sent = view
                last_time = time.monotonic()
                if ok is False:
                    # Head lost us (restart?): re-register and
                    # re-publish our state.
                    self._reregister()
            except Exception:
                pass

    def _reregister(self):
        """Re-join a restarted head (reference:
        `node_manager.proto:356` RayletNotifyGCSRestart → raylets
        re-publish). Registration alone rebuilds only the node table;
        the head's actor directory and object-location map started
        empty, so re-report every hosted actor (restoring routing AND
        restart bookkeeping via record_lineage) and every object this
        node still owns."""
        plane = getattr(self.worker, "shm_plane", None)
        self.head.call(
            "register_node", node_id=self.node_id,
            address=self.address,
            resources=dict(self.worker.backend.resources.total),
            transfer=self.transfer_addr,
            shm_name=plane.name if plane else None,
            labels=self.labels)
        # Consumed-restart count = head-driven restarts recorded on
        # the spec + this node's own in-place worker restarts: the
        # fresh head's gate seeds the REMAINING budget, not a reset
        # one. Re-reports BATCH into one report_actors RPC (group-
        # committed registration: a node hosting 10k actors reconverges
        # in O(1) round trips, not O(actors)); old heads without the
        # batch handler get the per-actor fallback.
        live = [(actor.spec,
                 getattr(actor.spec, "restarts_used", 0)
                 + actor.num_restarts)
                for actor in list(getattr(self.worker.backend,
                                          "_actors", {}).values())
                if actor.state != "DEAD"]
        try:
            if live:
                self.head.call(
                    "report_actors",
                    specs=[spec for spec, _ in live],
                    node_id=self.node_id,
                    restarts_used=[used for _, used in live])
        except Exception:
            for spec, used in live:
                try:
                    self.head.call("report_actor", spec=spec,
                                   node_id=self.node_id,
                                   restarts_used=used)
                except Exception:
                    pass
        oids = [oid for oid in self._reported_oids
                if self.worker.memory_store.contains(ObjectID(oid))]
        if oids:
            try:
                # Sizes ride the re-report too: a head (or head SHARD)
                # that lost its directory needs bytes back, not just
                # locations — locality-aware placement and the sharded
                # head's re-registration repair path both read them.
                sizes = [self.worker.memory_store.entry_size(
                    ObjectID(oid)) for oid in oids]
                self.head.call("report_objects", oids=oids,
                               address=self.address, sizes=sizes)
            except Exception:
                pass

    def serve_forever(self):
        """Serve until shutdown — or until the head stays unreachable
        past the health window (a dead head orphans the node; exiting
        mirrors the reference raylet's GCS-disconnect suicide)."""
        from ray_tpu._private.config import ray_config

        reporter = threading.Thread(target=self._resource_report_loop,
                                    daemon=True, name="resource-report")
        reporter.start()
        misses = 0
        try:
            while not self._shutdown_event.wait(
                    max(ray_config.health_check_period_s, 0.1)):
                try:
                    self.head.call("get_nodes")
                    misses = 0
                except Exception:
                    misses += 1
                    if misses >= 4 * \
                            ray_config.health_check_failure_threshold:
                        break
        finally:
            self.server.shutdown()
            plane = getattr(self, "plane", None)
            if plane is not None:
                if plane._owner:
                    plane.destroy()
                else:
                    plane.close()
            worker_mod.shutdown()


def _res_kwargs(resources: Dict[str, float]) -> dict:
    kw: Dict[str, Any] = {}
    res = dict(resources)
    if "CPU" in res:
        kw["num_cpus"] = res.pop("CPU")
    # Always told, so a CPU node's init() never imports JAX.
    kw["num_tpus"] = res.pop("TPU", 0)
    if res:
        kw["resources"] = res
    return kw


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--head", required=True)
    parser.add_argument("--num-cpus", type=float, default=1)
    parser.add_argument("--num-tpus", type=float, default=0)
    parser.add_argument("--node-id", default=None)
    parser.add_argument("--shm-name", default=None)
    parser.add_argument("--label", action="append", default=[],
                        help="node label key=value (repeatable)")
    args = parser.parse_args()
    host, port = args.head.rsplit(":", 1)
    resources = {"CPU": args.num_cpus}
    if args.num_tpus:
        resources["TPU"] = args.num_tpus
        # This process runs the node's TPU tasks, so it is the one that
        # opens the chips: a node that advertises more than it can open
        # must not register.
        import jax

        found = sum(1 for d in jax.devices() if d.platform == "tpu")
        if found < args.num_tpus:
            raise SystemExit(
                f"node advertises --num-tpus {args.num_tpus:g} but JAX "
                f"found {found} TPU device(s) (platform "
                f"{jax.devices()[0].platform!r})")
    labels = dict(kv.split("=", 1) for kv in args.label)
    runtime = NodeRuntime((host, int(port)), resources,
                          node_id=args.node_id, shm_name=args.shm_name,
                          labels=labels)
    runtime.serve_forever()


if __name__ == "__main__":
    main()
