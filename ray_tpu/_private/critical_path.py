"""Critical-path attribution: where did a request's wall time go?

The trace plane (PR 3) can show *that* a request crossed the proxy, a
router, a replica, and an LLM engine; the SLO plane (PR 6/15) can show
*that* a route is slow. Neither can answer the operator's actual
question — *which stage* made THIS request slow — and that attribution
is the measured input every adaptive control-loop decision (ROADMAP
item 4) needs.

This module is the pure core, and the program's one recorder. What
it records is a *span*: a name, a start and an end, the trace id of
the request it worked for (if any), the enclosing span of the same
thread as parent, and a few integer attributes. Two forms write the
same record:

- :func:`span` (a context manager; :func:`begin` / :func:`end` where a
  ``with`` block does not fit) times the work where it happens. Start
  is read from ``time.time`` — CLOCK_REALTIME, the clock the JAX
  profiler's host plane stamps its events with (an xplane holds them
  relative to the ``profile_start_time`` of its ``Task Environment``
  plane) — and the duration from :func:`clock` (``time.perf_counter``).
  While a ``jax.profiler`` trace is being taken the span also enters a
  ``jax.profiler.TraceAnnotation`` of the same name and attributes, so
  the program's spans lie in the trace's host plane beside the device's
  ops, on one clock, with nothing to align. The engine loop
  (``serve/llm.py`` ``engine.*``), the ingest iterator (``data.*``) and
  the train step's wrapper (``train.*``) are timed this way.
- :func:`record_stage` is the thin form for a duration a call site
  measured itself (with :func:`clock`): start = now - duration. The
  per-request stages use it (proxy dispatch and first byte, router
  assign, replica-direct acquire, replica execute, LLM
  admit/kv-lookup/prefill/first-token/decode, scheduler queue,
  object-plane pull/spill/restore).

Either way the hot path pays clock reads and ONE tuple append to a
bounded deque, nothing else. Everything downstream of that append
(trace accumulation, histogram folds, exemplar upkeep, the flight
ring, the ship queue) happens in :func:`flush`, driven by a
process-lifetime folder thread at ~100 ms cadence and synchronously by
every reader. The deferral is the whole performance story: on a serial
request path every instruction between "replica produced the result"
and "client read the response" is paid at GIL-scheduling granularity,
so 20 µs of inline folding measured as ~70 µs of added latency — while
an append costs ~0.15 µs and the fold runs when the loop would
otherwise be idle. The folder thread records its own beat's lateness
(``process.wake_late``, :func:`_folder_loop`): whether the process
let a thread run that wanted to.

Every record reaches the flight recorder's ring. Only a *request*
opens an accumulator: the proxy's envelope calls :func:`open_request`
where it mints (or honours) the trace id and :func:`finish_request`
once when the response is out; records in between that carry the
request's trace id collect there, and a record whose trace id no
request opened goes to the ring (and, on a worker node, the ship
queue) and nowhere else — so the tasks of the runtime, each a trace
root of its own, cannot crowd a long request's early stages out.
:func:`finish_request` (at fold time):

- attributes the request's wall time to its recorded stages (the
  remainder is folded as the ``unattributed`` stage, so the vector
  always sums to the measured total; the envelope span
  ``proxy.first_byte`` lies over its children and is left out of the
  sum),
- derives ``front.ttft_self``: ``proxy.first_byte`` less the stretch
  the engine's stages cover (``llm.admit`` to the first token's
  hand-over) — the proxy's, router's, scheduler's, replica's and
  stream hop's own share of the time to first token,
- folds each stage duration into the
  ``request_stage_seconds{route,stage}`` fast-path distribution —
  exported as ``ray_tpu_request_stage_seconds_p50/_p99`` per
  (route, stage) by ``runtime_metrics``, the per-route *attribution
  vector*,
- pins an exemplar trace-id to the slowest observation per histogram
  bucket (the Prometheus-exemplar idea, JSON-shaped), and
- retains a bounded waterfall for ``/api/slow_requests`` and the CLI
  ``ray_tpu slow``.

Stage records born on worker nodes ride the existing obs shipper
(``drain_records`` → ``obs_report(stages=...)`` → :func:`ingest`), so
the head, which opened the request, folds cluster-wide attribution —
replica/engine stages land seconds after the proxy already finished
the request, which is why late arrivals for a finished trace fold
immediately against the route the finish recorded.

Layering: imports only peer ``_private`` modules (perf_stats,
flight_recorder); never serve, and never ``jax``: the annotation is
bound the first time a span runs in a process that has imported JAX
itself (a process that never does cannot be taking a trace).
"""

from __future__ import annotations

import bisect
import itertools
import sys
import threading
import time
from collections import OrderedDict, deque
from typing import Dict, Iterable, List, Optional, Tuple

from ray_tpu._private import flight_recorder, perf_stats
from ray_tpu._private.config import ray_config

ENABLED = True


def _on() -> bool:
    return ENABLED and ray_config.stage_spans_enabled

# Bounded process-global state. Aliasing contract matches perf_stats:
# hot paths reference the module, tests snapshot/restore IN PLACE.
MAX_TRACES = 2048          # in-flight trace accumulators
MAX_STAGES_PER_TRACE = 64  # a runaway decode loop can't grow one trace
MAX_FINISHED = 256         # retained waterfalls for slow_requests
MAX_PENDING = 8192         # node-side records awaiting shipping

STAGE_METRIC = "request_stage_seconds"

# Attribution floor: spans shorter than this are noise at SLO scale
# (they cannot be dominant, and the tiling contract charges their time
# to ``unattributed`` regardless) — dropping them at the record site
# is the single biggest term in the recorder's fast-route overhead.
MIN_SPAN_S = 5e-5

# A span record is the tuple (t, trace_id, stage, dur_s, route, t0, id,
# parent, attrs): ``t`` is its end (= t1) and ``t0`` its start, both
# seconds on time.time's clock; ``parent`` is the id of the span that
# enclosed it on its thread (0: none); ``attrs`` a dict of integers or
# None. The dict shape only exists at the edges (the obs-ship wire
# format, snapshots). A finish marker is the 6-tuple (t, trace_id,
# status, total_s, route, None), an open marker the 3-tuple (t,
# trace_id, route) — length is the dispatch tag.
_T, _TRACE, _STAGE, _DUR, _ROUTE, _T0, _ID, _PARENT, _ATTRS = range(9)

# The one clock of every duration this module is handed or takes.
clock = time.perf_counter

# The envelope lies over the stages it encloses: it is folded and shown
# but not summed into the tiling of the request's wall time.
ENVELOPE_STAGE = "proxy.first_byte"
FRONT_SELF_STAGE = "front.ttft_self"
# What the engine covers of the time to first token: from the request's
# arrival in its queue to the first token's hand-over.
_ENGINE_TTFT_STAGES = frozenset(
    ("llm.admit", "llm.kv_lookup", "llm.prefill", "llm.first_token"))

_ids = itertools.count(1)  # next() is GIL-atomic
_tls = threading.local()   # .stack: ids of the thread's open spans

# Raw hot-path appends awaiting a fold. Sized for several fold periods
# at full serve throughput; sustained overflow drops oldest (bounded
# memory beats bounded truth for a diagnostics plane).
MAX_RAW = 65536
_raw: "deque[tuple]" = deque(maxlen=MAX_RAW)

_FOLD_PERIOD_S = 0.1
_folder_started = False
_folder_lock = threading.Lock()

_lock = threading.Lock()
# trace_id -> [stages[(stage, dur_s)], route, t0]
_traces: "OrderedDict[str, list]" = OrderedDict()
# finished waterfalls, oldest-first ("stages" holds (stage, dur) pairs)
_finished: "deque[dict]" = deque(maxlen=MAX_FINISHED)
# trace_id -> route for finished traces: late-arriving node records
# (shipped after the proxy closed the request) still fold.
_finished_routes: "OrderedDict[str, str]" = OrderedDict()
# record tuples awaiting the obs shipper. Only processes that actually
# ship (a NodeObsShipper exists) pay the append: the head folds its own
# records in place and would otherwise queue 8192 tuples for nobody.
SHIPPING = False
_pending: "deque[tuple]" = deque(maxlen=MAX_PENDING)
# (route, stage) -> {bucket_index: (dur_s, trace_id)} — slowest
# observation per histogram bucket.
_exemplars: Dict[Tuple[str, str], Dict[int, Tuple[float, str]]] = {}
# (route, stage) -> interned Dist. perf_stats mutates interned stats in
# place (never replaces them), so caching skips the sorted-tuple key
# build + registry probe on every finish_request fold.
_dist_cache: Dict[Tuple[str, str], perf_stats.Dist] = {}


def set_enabled(on: bool) -> None:
    """A/B kill switch (``perf_bench.py --ab-observability`` flips it
    to prove the stage-span tax on the serve keep-alive path)."""
    global ENABLED
    ENABLED = bool(on)


def enabled() -> bool:
    """Public gate for call sites whose *argument computation* has a
    cost (ambient trace lookup, task-spec trace extraction) — skip it
    entirely when the recorder is off."""
    return _on()


def set_shipping(on: bool) -> None:
    """Mark this process as one whose records are drained by an obs
    shipper (worker nodes). Off — the default, and the head's state —
    ``record_stage`` skips the pending queue entirely."""
    global SHIPPING
    SHIPPING = bool(on)


# Lazily-bound (circular-import-safe) collaborators of
# ambient_trace_id: resolved once, not per request — the sys.modules
# probes of a per-call import are measurable on the serve fast path.
_ambient_fns: Optional[tuple] = None


def ambient_trace_id() -> Optional[str]:
    """Trace id of the currently executing task (None outside one) —
    what in-task stage sites (replica execute, LLM engine, object
    plane) attribute their work to. Cheap: two dict lookups when a
    task context exists."""
    global _ambient_fns
    try:
        if _ambient_fns is None:
            from ray_tpu._private.task_spec import trace_id_of
            from ray_tpu._private.worker import global_worker_or_none
            _ambient_fns = (trace_id_of, global_worker_or_none)
        trace_id_of, global_worker_or_none = _ambient_fns

        w = global_worker_or_none()
        if w is None:
            return None
        ctx = w.task_context.current()
        if ctx is None:
            return None
        return trace_id_of(ctx["task_spec"])
    except Exception:
        return None


def _stage_dist(route: str, stage: str) -> perf_stats.Dist:
    key = (route, stage)
    d = _dist_cache.get(key)
    if d is None:
        d = perf_stats.dist(STAGE_METRIC,
                            {"route": route, "stage": stage},
                            bounds=perf_stats.SERVE_LATENCY_BOUNDS)
        _dist_cache[key] = d
    return d


# Exemplar floor: an exemplar exists so the operator can drill from a
# SLOW histogram bucket into one concrete trace. Observations below
# this land in buckets nobody ever drills into, and their upkeep
# (bisect + dict probe per stage per finish) would dominate the fold
# cost on fast routes.
_EXEMPLAR_MIN_S = 0.005


def _fold(route: str, stage: str, dur_s: float, trace_id: str) -> None:
    """One stage observation into the attribution vector + exemplars.
    Callers hold ``_lock`` (exemplar upkeep mutates a shared dict)."""
    _stage_dist(route, stage).record(dur_s)
    if dur_s < _EXEMPLAR_MIN_S:
        return
    idx = bisect.bisect_left(perf_stats.SERVE_LATENCY_BOUNDS, dur_s)
    bucket = _exemplars.setdefault((route, stage), {})
    cur = bucket.get(idx)
    if cur is None or dur_s > cur[0]:
        bucket[idx] = (dur_s, trace_id)


def _open_parent() -> int:
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else 0


def record_stage(trace_id: Optional[str], stage: str, dur_s: float,
                 route: str = "") -> None:
    """Attribute ``dur_s`` seconds of ``stage`` work, measured by the
    caller with :func:`clock` and ending now, to ``trace_id``: the thin
    form of a span record (start = now - duration; parent = the span
    open on this thread, if one is).

    Hot-path cost: one scalar-tuple append (GIL-atomic, no lock) —
    folding is deferred to :func:`flush`. Records without a trace id
    (object-plane work running outside any request) still reach the
    flight ring at fold time — they are real cluster activity the
    post-mortem wants — but never the attribution vectors.

    A request's stages under :data:`MIN_SPAN_S` are dropped at the
    door: a stage that took tens of microseconds can never be the
    answer to "which stage made this request slow", it folds into
    ``unattributed`` by the tiling contract anyway, and recording it
    costs exactly as much as recording a meaningful one — on a fast
    route the floor drops most of the per-request records. The floor
    is attribution's: a record without a trace id goes in whatever it
    took, as a span does (a hand-over lag of 20 µs is the reading, and
    a floor would make every quantile of it read high)."""
    if not _on() or (trace_id and dur_s < MIN_SPAN_S):
        return
    t1 = time.time()
    dur_s = float(dur_s)
    _raw.append((t1, trace_id or "", stage, dur_s, route, t1 - dur_s,
                 next(_ids), _open_parent(), None))
    if not _folder_started:
        _ensure_folder()


# jax.profiler.TraceAnnotation, bound the first time a span runs in a
# process that has imported JAX itself (this module never imports it).
_annotation = None


def _bind_annotation():
    global _annotation
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)  # None while jax imports
    _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


class Span:
    """One timed stretch of work on one thread; see :func:`span`."""

    __slots__ = ("name", "trace_id", "route", "attrs", "id", "parent",
                 "t0", "dur_s", "_p0", "_ann")

    def __init__(self, name: str, trace_id: str, route: str,
                 attrs: Optional[dict]):
        self.name = name
        self.trace_id = trace_id
        self.route = route
        self.attrs = attrs
        self._ann = None

    def set(self, **attrs) -> None:
        """Attributes known only once the work is done."""
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)

    def __enter__(self) -> "Span":
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self.parent = stack[-1] if stack else 0
        self.id = next(_ids)
        stack.append(self.id)
        ann = _annotation or _bind_annotation()
        if ann is not None and ann.is_enabled():  # a trace is being taken
            self._ann = ann(self.name, **(self.attrs or {}))
            self._ann.__enter__()
        self.t0 = time.time()
        self._p0 = clock()
        return self

    def __exit__(self, *exc) -> None:
        # Kept on the span: whoever opened it reads what it took
        # without a pair of clock reads of its own.
        self.dur_s = dur_s = clock() - self._p0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _tls.stack.pop()
        _raw.append((self.t0 + dur_s, self.trace_id, self.name, dur_s,
                     self.route, self.t0, self.id, self.parent,
                     self.attrs))
        if not _folder_started:
            _ensure_folder()


class _NullSpan:
    """What :func:`span` hands out while the recorder is off."""

    __slots__ = ()
    dur_s = 0.0

    def set(self, **attrs) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NULL_SPAN = _NullSpan()


def span(name: str, trace_id: Optional[str] = None, route: str = "",
         **attrs: int):
    """Context manager that records the stretch it encloses as a span:
    start, end, the span open on this thread as parent, ``attrs``
    (integers; more through ``.set(**attrs)`` inside the block), and
    the same stretch as a ``jax.profiler.TraceAnnotation`` while a
    profiler trace is being taken. Spans of one thread nest. With no
    trace being taken it costs three clock reads and one deque
    append; no floor applies (the engine loop is tiled by spans, and a
    tile that is left out reads as a gap)."""
    if not _on():
        return _NULL_SPAN
    return Span(name, trace_id or "", route, attrs or None)


def begin(name: str, trace_id: Optional[str] = None, route: str = "",
          **attrs: int):
    """:func:`span` for a stretch that no ``with`` block fits: returns
    the entered span for :func:`end`, on the same thread."""
    return span(name, trace_id, route, **attrs).__enter__()


def end(sp, **attrs: int) -> None:
    if attrs:
        sp.set(**attrs)
    sp.__exit__(None, None, None)


def open_request(trace_id: Optional[str], route: str = "") -> None:
    """Open a request's accumulator (the proxy's envelope, where the
    trace id is minted or honoured): from here to
    :func:`finish_request` records that carry ``trace_id`` collect
    there. Same one-append hot path as :func:`record_stage`."""
    if not _on() or not trace_id:
        return
    _raw.append((time.time(), trace_id, route))
    if not _folder_started:
        _ensure_folder()


def finish_request(trace_id: Optional[str], route: str, status: str,
                   total_s: float) -> None:
    """Close a request: at fold time its stage vector (plus the
    unattributed remainder) lands in
    ``request_stage_seconds{route,stage}`` and the waterfall is
    retained. Called from the proxy's request envelope once per
    request — same one-append hot path as :func:`record_stage`."""
    if not _on() or not trace_id:
        return
    _raw.append((time.time(), trace_id, status, float(total_s), route,
                 None))
    if not _folder_started:
        _ensure_folder()


def _ensure_folder() -> None:
    """Start the process-lifetime folder thread (idempotent). It owns
    the fold cadence so no request ever pays for folding; readers
    still :func:`flush` synchronously for deterministic answers."""
    global _folder_started
    with _folder_lock:
        if _folder_started:
            return
        t = threading.Thread(target=_folder_loop, daemon=True,
                             name="critical-path-folder")
        t.start()
        _folder_started = True


def _folder_loop() -> None:
    """Fold every `_FOLD_PERIOD_S`, and record each beat's lateness as
    the thin record ``process.wake_late``: the beat's wall time less
    the sleeps it asked for, clamped at 0. That is how long a Python
    thread of this process that wanted to run, or to be done, was kept
    from it (its own folding included), ten readings a second with no
    floor. Beside a long stretch of another thread it tells the two
    cases apart: a lateness near 0 says that thread blocked with the
    interpreter released (a sleep, a lock, a full arena), one of the
    stretch's own length that the process stood still as a whole (a
    thread that held the interpreter, the machine)."""
    top = clock()
    while True:
        asked = _FOLD_PERIOD_S
        time.sleep(_FOLD_PERIOD_S)
        try:
            # Fold in small slices with a real sleep between them: one
            # monolithic fold of a period's backlog holds the GIL for
            # milliseconds at a stretch, and on a serial request path
            # that burst reads as added latency — the exact
            # amplification the deferral exists to remove. Sliced, the
            # folder's cost converges to its true CPU share.
            while flush(_FOLD_SLICE) == _FOLD_SLICE:
                asked += 0.002
                time.sleep(0.002)
        except Exception:
            pass  # diagnostics must never take the process down
        now = clock()
        record_stage(None, "process.wake_late",
                     max(0.0, now - top - asked))
        top = now


# Records folded per GIL slice in the folder thread. ~200 folds cost
# well under a millisecond; the 2ms yield between slices lets every
# in-flight request proceed before the next slice.
_FOLD_SLICE = 200


def flush(max_n: Optional[int] = None) -> int:
    """Drain raw hot-path appends into the folded state (traces, the
    flight ring, histograms, exemplars, retained waterfalls, the ship
    queue); returns the number of records folded. Idempotent and
    multi-thread safe: popleft is GIL-atomic so the folder thread and
    a concurrent reader each fold a record at most once. Readers call
    it unbounded for deterministic answers; the folder thread passes
    ``max_n`` to bound each GIL slice."""
    n = 0
    while max_n is None or n < max_n:
        try:
            rec = _raw.popleft()
        except IndexError:
            break
        if len(rec) == 9:
            _fold_span(rec)
        elif len(rec) == 6:
            _fold_finish(rec)
        else:
            _fold_open(rec)
        n += 1
    return n


def _fold_open(rec: tuple) -> None:
    t, trace_id, route = rec
    if trace_id in _traces:
        return
    _traces[trace_id] = [[], route, t]
    if len(_traces) > MAX_TRACES:
        with _lock:
            while len(_traces) > MAX_TRACES:
                _traces.popitem(last=False)


def _accumulate(rec: tuple) -> None:
    """A span record into the request that opened its trace id, or,
    after the request closed, straight into the finished route's
    vector. No request opened it: nothing (the ring has it)."""
    trace_id = rec[_TRACE]
    tr = _traces.get(trace_id)
    if tr is None:
        route_done = _finished_routes.get(trace_id)
        if route_done is not None:
            # Late arrival (node record shipped — or locally folded —
            # after the request closed).
            with _lock:
                _fold(route_done, rec[_STAGE], rec[_DUR], trace_id)
        return
    if rec[_ROUTE] and not tr[1]:
        tr[1] = rec[_ROUTE]
    if len(tr[0]) < MAX_STAGES_PER_TRACE:
        tr[0].append(rec)


def _fold_span(rec: tuple) -> None:
    flight_recorder.note_span(rec)
    if not rec[_TRACE]:
        return
    if SHIPPING:
        _pending.append(rec)
    _accumulate(rec)


def _front_ttft_self(stages: List[tuple]) -> Optional[tuple]:
    """The derived record ``front.ttft_self`` of a request whose stages
    hold the envelope and the engine's part of it: the envelope less
    the engine's extent (two stamps of one process's clock, so a node's
    clock offset cancels), a child of the envelope."""
    envelope = next((r for r in stages if r[_STAGE] == ENVELOPE_STAGE),
                    None)
    engine = [r for r in stages if r[_STAGE] in _ENGINE_TTFT_STAGES]
    if envelope is None or not engine:
        return None
    extent = max(r[_T] for r in engine) - min(r[_T0] for r in engine)
    self_s = max(0.0, envelope[_DUR] - extent)
    return (envelope[_T], envelope[_TRACE], FRONT_SELF_STAGE, self_s,
            envelope[_ROUTE], envelope[_T] - self_s, next(_ids),
            envelope[_ID], None)


def _fold_finish(rec: tuple) -> None:
    t, trace_id, status, total_s, route = rec[:5]
    with _lock:
        tr = _traces.pop(trace_id, None)
        stages = tr[0] if tr else []
        front = _front_ttft_self(stages)
        if front is not None:
            flight_recorder.note_span(front)
            _fold(route, FRONT_SELF_STAGE, front[_DUR], trace_id)
        for r in stages:
            if r[_STAGE] == ENVELOPE_STAGE:
                _fold(route, ENVELOPE_STAGE, r[_DUR], trace_id)
        agg = _agg(stages)
        for stage, dur in agg.items():
            _fold(route, stage, dur, trace_id)
        unattributed = max(0.0, total_s - sum(agg.values()))
        _fold(route, "unattributed", unattributed, trace_id)
        agg["unattributed"] = unattributed
        dominant = max(agg.items(), key=lambda kv: kv[1])[0]
        entry = {
            "trace_id": trace_id, "route": route, "status": status,
            "total_s": total_s, "dominant_stage": dominant,
            "unattributed_s": unattributed, "ts": t,
            "stages": stages,
        }
        if front is not None:
            entry["front_ttft_self_s"] = front[_DUR]
        _finished.append(entry)
        _finished_routes[trace_id] = route
        while len(_finished_routes) > MAX_TRACES:
            _finished_routes.popitem(last=False)


def _unwire(rec: dict) -> tuple:
    """The obs-ship wire shape back into a record tuple (raises on a
    malformed entry). Records of an older sender carry no start."""
    t = float(rec.get("t") or time.time())
    dur_s = float(rec["dur_s"])
    return (t, rec["trace_id"], rec["stage"], dur_s,
            rec.get("route") or "", float(rec.get("t0") or t - dur_s),
            int(rec.get("id") or 0), int(rec.get("parent") or 0),
            rec.get("attrs") or None)


def ingest(records: Optional[List[dict]]) -> None:
    """Head-side fold of node-shipped stage records (the
    ``obs_report(stages=...)`` path). Same accumulation as a local
    record, minus re-shipping and re-ringing — the origin node already
    ringed them. The head's own backlog is folded first, so that the
    request's open marker is in before its node-born stages."""
    if not _on() or not records:
        return
    flush()
    for rec in records:
        try:
            rec = _unwire(rec)
        except (KeyError, TypeError, ValueError):
            continue  # malformed entry must not poison the frame
        if rec[_TRACE]:
            _accumulate(rec)


def span_dict(rec: tuple) -> dict:
    """Record tuple -> the dict shape of the edges: the obs-ship wire
    format :func:`ingest` reads, and the flight ring's snapshot."""
    out = {"trace_id": rec[_TRACE], "stage": rec[_STAGE],
           "dur_s": rec[_DUR], "route": rec[_ROUTE], "t": rec[_T],
           "t0": rec[_T0], "t1": rec[_T], "id": rec[_ID],
           "parent": rec[_PARENT]}
    if rec[_ATTRS]:
        out["attrs"] = rec[_ATTRS]
    return out


def drain_records(max_n: int = 1000) -> List[dict]:
    """Pop up to ``max_n`` pending records for the obs shipper (worker
    nodes), in wire (dict) shape. Popleft is GIL-atomic; an empty race
    just ends the drain."""
    flush()
    out: List[dict] = []
    while len(out) < max_n:
        try:
            out.append(span_dict(_pending.popleft()))
        except IndexError:
            break
    return out


def requeue_records(records: List[dict]) -> None:
    """Put drained records back after a failed ship (bounded: the deque
    drops oldest if the head stays unreachable)."""
    _pending.extend(_unwire(r) for r in records)


def self_seconds(spans: Iterable[dict]) -> Dict[int, float]:
    """{span id: its duration less what its children cover} over span
    dicts (``id``, ``parent``, ``t0``, ``t1``): children are the spans
    naming it as parent, clipped to it, overlaps counted once."""
    spans = [s for s in spans if s.get("id")]
    children: Dict[int, List[Tuple[float, float]]] = {}
    for s in spans:
        if s.get("parent"):
            children.setdefault(s["parent"], []).append(
                (s["t0"], s["t1"]))
    out = {}
    for s in spans:
        covered, edge = 0.0, s["t0"]
        for c0, c1 in sorted(children.get(s["id"], ())):
            c0, c1 = max(c0, edge), min(c1, s["t1"])
            if c1 > c0:
                covered += c1 - c0
                edge = c1
        out[s["id"]] = max(0.0, (s["t1"] - s["t0"]) - covered)
    return out


def _stage_dicts(stages: Iterable[tuple]) -> List[dict]:
    return [{"stage": r[_STAGE], "dur_s": r[_DUR], "t0": r[_T0],
             "t1": r[_T]} for r in stages]


def _agg(stages: Iterable[tuple]) -> Dict[str, float]:
    """{stage: summed seconds} of what tiles a request's wall time (the
    envelope lies over the others and is left out)."""
    agg: Dict[str, float] = {}
    for r in stages:
        if r[_STAGE] != ENVELOPE_STAGE:
            agg[r[_STAGE]] = agg.get(r[_STAGE], 0.0) + r[_DUR]
    return agg


def _waterfall(entry: dict) -> dict:
    """Presentation shape shared by the API, the CLI, and the flight
    recorder: stages (with start and end) plus each stage's share of
    the total. Retained entries hold record tuples; the dict shape is
    built here, at read time, not per request."""
    total = entry.get("total_s") or 0.0
    out = dict(entry)
    out["stages"] = _stage_dicts(entry.get("stages") or [])
    for s in out["stages"]:
        s["frac"] = round(s["dur_s"] / total, 4) if total > 0 else 0.0
    return out


def slow_requests(n: int = 10,
                  include_inflight: bool = False) -> List[dict]:
    """Top-``n`` slowest retained requests (waterfalls, dominant stage
    named). ``include_inflight`` adds still-open traces (their total is
    age-so-far) — what the flight recorder wants mid-incident."""
    flush()
    with _lock:
        items = [dict(e) for e in _finished]
        if include_inflight:
            now = time.time()
            for trace_id, tr in _traces.items():
                agg = _agg(tr[0])
                age = max(0.0, now - tr[2])
                items.append({
                    "trace_id": trace_id, "route": tr[1],
                    "status": "in_flight", "total_s": age,
                    "dominant_stage": max(agg.items(),
                                          key=lambda kv: kv[1])[0]
                    if agg else "unattributed",
                    "unattributed_s": max(
                        0.0, age - sum(agg.values())),
                    "ts": tr[2], "in_flight": True,
                    "stages": list(tr[0]),
                })
    items.sort(key=lambda e: e.get("total_s") or 0.0, reverse=True)
    return [_waterfall(e) for e in items[:max(0, n)]]


def exemplars() -> List[dict]:
    """Exemplar trace-ids for the slowest observation in each
    (route, stage) histogram bucket — the jump-off from a p99 panel to
    the trace that caused it."""
    flush()
    bounds = perf_stats.SERVE_LATENCY_BOUNDS
    out: List[dict] = []
    with _lock:
        for (route, stage), buckets in _exemplars.items():
            for idx, (dur_s, trace_id) in buckets.items():
                le = bounds[idx] if idx < len(bounds) else float("inf")
                out.append({"route": route, "stage": stage,
                            "bucket_le": le, "dur_s": dur_s,
                            "trace_id": trace_id})
    out.sort(key=lambda e: (e["route"], e["stage"], e["dur_s"]))
    return out


def attribution_vectors() -> Dict[str, Dict[str, Dict[str, float]]]:
    """{route: {stage: {p50, p99, count, sum}}} read straight from the
    fast-path dists — the JSON twin of the Prometheus exposition, used
    by ``/api/slow_requests`` and the CLI summary header."""
    flush()
    out: Dict[str, Dict[str, Dict[str, float]]] = {}
    for name, tags, stat in perf_stats.stats_items():
        if name != STAGE_METRIC or not isinstance(stat, perf_stats.Dist):
            continue
        if stat.total == 0:
            continue  # interned-but-reset series: nothing to report
        tagd = dict(tags)
        route = tagd.get("route", "")
        stage = tagd.get("stage", "")
        out.setdefault(route, {})[stage] = {
            "p50": stat.quantile(0.5), "p99": stat.quantile(0.99),
            "count": stat.total, "sum": stat.sum}
    return out


def finished_waterfalls() -> List[dict]:
    flush()
    with _lock:
        out = []
        for e in _finished:
            e = dict(e)
            e["stages"] = _stage_dicts(e["stages"])
            out.append(e)
        return out


# -- test isolation -----------------------------------------------------------


def snapshot_state() -> dict:
    """Plain-data snapshot of this module's process-global state; with
    :func:`restore_state` (both IN PLACE — hot paths alias the module
    globals) this is the conftest-baseline API that keeps one test's
    stage recordings out of the next."""
    flush()
    with _lock:
        return {
            "enabled": ENABLED,
            "shipping": SHIPPING,
            "traces": {k: [list(v[0]), v[1], v[2]]
                       for k, v in _traces.items()},
            "finished": [dict(e) for e in _finished],
            "finished_routes": dict(_finished_routes),
            "pending": list(_pending),
            "exemplars": {k: dict(v) for k, v in _exemplars.items()},
        }


def restore_state(snapshot: dict) -> None:
    global ENABLED, SHIPPING
    with _lock:
        ENABLED = snapshot.get("enabled", True)
        SHIPPING = snapshot.get("shipping", False)
        _traces.clear()
        for k, v in snapshot.get("traces", {}).items():
            _traces[k] = [list(v[0]), v[1], v[2]]
        _finished.clear()
        _finished.extend(dict(e) for e in snapshot.get("finished", []))
        _finished_routes.clear()
        _finished_routes.update(snapshot.get("finished_routes", {}))
        _pending.clear()
        _pending.extend(snapshot.get("pending", []))
        _exemplars.clear()
        for k, v in snapshot.get("exemplars", {}).items():
            _exemplars[k] = dict(v)
        _raw.clear()
        _dist_cache.clear()


def reset() -> None:
    restore_state({"enabled": True})
