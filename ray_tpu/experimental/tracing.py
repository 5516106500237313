"""Distributed tracing: task spans with cross-task parent linkage.

Role-equivalent to the reference's OpenTelemetry integration
(`ray.init(_tracing_startup_hook=...)` + `tracing_helper.py`, which
monkey-wraps remote calls to propagate span context through task
metadata): here the span context rides the TaskSpec itself
(`trace_parent`), every execution records a span in the task-event
buffer, and this module exports them in an OTLP-shaped JSON form any
OpenTelemetry backend can ingest after a trivial transform. No network
exporter is wired (the image has no collector); `export_spans()` returns
the list, `save_spans(path)` writes it.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional

from ray_tpu._private import worker as worker_mod


def export_spans(worker=None) -> List[Dict[str, Any]]:
    """All recorded task spans, OTLP-shaped: traceId / spanId /
    parentSpanId / name / kind / start-end (ns) / status / attributes.

    On a cluster head this is the CLUSTER-wide view: worker-node events
    arrive through the shipping plane (`_private/obs_plane.py`), so one
    request's trace stitches across every node it touched, each span
    tagged with the node that executed it."""
    import time

    from ray_tpu._private.obs_plane import cluster_task_events

    w = worker or worker_mod.global_worker()
    spans = []
    # The full buffer (public snapshot API), not list_events' default
    # 10k tail — a truncated export would drop trace roots out from
    # under their children.
    for ev in cluster_task_events(w):
        running = ev.end_s is None
        end = time.time() if running else ev.end_s
        spans.append({
            "traceId": ev.trace_id or ev.task_id,
            "spanId": ev.task_id,
            "parentSpanId": ev.parent_span_id or None,
            "name": ev.name,
            "kind": "SPAN_KIND_INTERNAL",
            "startTimeUnixNano": int(ev.start_s * 1e9),
            "endTimeUnixNano": int(end * 1e9),
            # A still-running task must not export as a completed OK
            # span; UNSET + live end time mirrors chrome_trace.
            "status": {"code": "STATUS_CODE_ERROR" if ev.error
                       else ("STATUS_CODE_UNSET" if running
                             else "STATUS_CODE_OK"),
                       "message": ev.error},
            "attributes": {
                "ray_tpu.task_kind": ev.kind,
                "ray_tpu.node_id": ev.node_id,
                "ray_tpu.worker": ev.worker,
                "ray_tpu.actor_id": ev.actor_id or "",
                "ray_tpu.state": ev.state,
            },
        })
    spans.extend(_stage_spans({s["traceId"] for s in spans}))
    return spans


def _stage_spans(trace_ids) -> List[Dict[str, Any]]:
    """Synthetic stage spans from the critical-path engine, one per
    finished-request waterfall entry, sharing the request's traceId so
    an OTLP viewer shows the stage anatomy (proxy dispatch → replica
    execute → llm.prefill → ...) inside the same trace as the task
    spans, each where the recorder's span says it started and ended."""
    from ray_tpu._private import critical_path

    out: List[Dict[str, Any]] = []
    for entry in critical_path.finished_waterfalls():
        trace_id = entry["trace_id"]
        parent = trace_id if trace_id in trace_ids else None
        for i, st in enumerate(entry.get("stages") or []):
            out.append({
                "traceId": trace_id,
                "spanId": f"stage:{st['stage']}:{i}:{trace_id[:8]}",
                "parentSpanId": parent,
                "name": f"stage.{st['stage']}",
                "kind": "SPAN_KIND_INTERNAL",
                "startTimeUnixNano": int(st["t0"] * 1e9),
                "endTimeUnixNano": int(st["t1"] * 1e9),
                "status": {"code": "STATUS_CODE_OK", "message": None},
                "attributes": {
                    "ray_tpu.stage": st["stage"],
                    "ray_tpu.route": entry.get("route") or "",
                    "ray_tpu.dominant_stage":
                        entry.get("dominant_stage") or "",
                },
            })
    return out


def get_trace(trace_id: str, worker=None) -> List[Dict[str, Any]]:
    """Spans belonging to one trace, in start-time order."""
    spans = [s for s in export_spans(worker) if s["traceId"] == trace_id]
    spans.sort(key=lambda s: s["startTimeUnixNano"])
    return spans


def save_spans(path: str, worker=None) -> int:
    spans = export_spans(worker)
    with open(path, "w") as f:
        json.dump(spans, f)
    return len(spans)


def current_trace_id(worker=None) -> Optional[str]:
    """The trace id of the currently executing task (None in the driver
    outside any task)."""
    w = worker or worker_mod.global_worker()
    from ray_tpu._private.task_spec import trace_id_of

    ctx = w.task_context.current()
    if ctx is None:
        return None
    return trace_id_of(ctx["task_spec"])
