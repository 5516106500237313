"""Critical-path attribution engine + flight recorder (PR 18).

Pure-core coverage for `_private/critical_path.py` (stage folding,
late-arrival ingest, waterfalls, exemplars) and
`_private/flight_recorder.py` (rings, edge-triggered dump, debounce),
plus the dashboard surfaces (`/api/slow_requests`, `/api/debug/dump`)
and the chaos leg: an SLO flood on a 2-node cluster produces exactly
one correlated FLIGHT dump with rings from every live node.
"""

import json

import pytest

import ray_tpu
from ray_tpu._private import critical_path, flight_recorder, perf_stats
from ray_tpu._private.config import ray_config


@pytest.fixture(autouse=True)
def _clean_engines():
    critical_path.reset()
    flight_recorder.reset()
    perf_stats.restore_records(critical_path.STAGE_METRIC, {})
    yield


def test_finish_folds_stages_and_unattributed():
    critical_path.open_request("t1")
    critical_path.record_stage("t1", "proxy.dispatch", 0.01,
                               route="/r")
    critical_path.record_stage("t1", "replica.execute", 0.05,
                               route="/r")
    critical_path.finish_request("t1", "/r", "200", 0.10)

    vecs = critical_path.attribution_vectors()
    assert set(vecs["/r"]) == {"proxy.dispatch", "replica.execute",
                               "unattributed"}
    # The vector tiles the measured total: 0.01 + 0.05 + 0.04.
    assert vecs["/r"]["unattributed"]["sum"] == pytest.approx(0.04)
    assert vecs["/r"]["replica.execute"]["count"] == 1

    (entry,) = critical_path.finished_waterfalls()
    assert entry["dominant_stage"] == "replica.execute"
    assert entry["unattributed_s"] == pytest.approx(0.04)

    # Exemplars pin the trace id to its (route, stage) bucket.
    exes = critical_path.exemplars()
    assert any(e["trace_id"] == "t1" and e["stage"] == "replica.execute"
               for e in exes)


def test_late_arrival_folds_into_finished_route():
    """Node-born stage records ship seconds after the proxy closed the
    request; they must still land in the route's attribution vector."""
    critical_path.open_request("t2")
    critical_path.record_stage("t2", "proxy.dispatch", 0.01, route="/r")
    critical_path.finish_request("t2", "/r", "200", 0.02)
    # Arrives via the obs shipper after the finish:
    critical_path.ingest([{"trace_id": "t2", "stage": "llm.prefill",
                           "dur_s": 0.5, "route": ""}])
    vecs = critical_path.attribution_vectors()
    assert vecs["/r"]["llm.prefill"]["sum"] == pytest.approx(0.5)


def test_drain_requeue_roundtrip():
    # Only shipping processes (a NodeObsShipper started) queue records.
    critical_path.set_shipping(True)
    try:
        critical_path.record_stage("t3", "sched.queue", 0.001)
        recs = critical_path.drain_records()
        assert [r["stage"] for r in recs] == ["sched.queue"]
        assert critical_path.drain_records() == []
        critical_path.requeue_records(recs)
        assert critical_path.drain_records() == recs
    finally:
        critical_path.set_shipping(False)


def test_head_process_does_not_queue_for_shipping():
    """The head folds its own records in place; with no shipper
    started, nothing accumulates in the pending queue."""
    critical_path.open_request("t3b")
    critical_path.record_stage("t3b", "sched.queue", 0.001)
    assert critical_path.drain_records() == []
    # ...but the request that opened the trace accumulated it locally.
    critical_path.finish_request("t3b", "/r", "200", 0.002)
    assert critical_path.attribution_vectors()["/r"]["sched.queue"][
        "count"] == 1


def test_unopened_trace_reaches_ring_only():
    """A record whose trace id no request opened (every task of the
    runtime is a trace root of its own) goes to the flight ring and
    nowhere else: no accumulator, no waterfall, no vector."""
    critical_path.record_stage("task-root", "sched.queue", 0.002)
    critical_path.finish_request("never-opened", "/r", "200", 0.01)
    snap = critical_path.snapshot_state()
    assert snap["traces"] == {}
    assert [s["stage"] for s in
            flight_recorder.local_snapshot()["spans"]] == ["sched.queue"]
    (entry,) = critical_path.finished_waterfalls()
    assert entry["stages"] == []
    assert set(critical_path.attribution_vectors()["/r"]) == \
        {"unattributed"}


def test_long_request_keeps_every_stage_under_task_flood():
    """The eviction (PERF.md section 6, PR 23, finding 2): a request
    whose first stage is followed by 5,000 records of unrelated trace
    ids, more than MAX_TRACES, keeps every stage when it finishes."""
    critical_path.open_request("long")
    critical_path.record_stage("long", "proxy.dispatch", 0.004,
                               route="/llm")
    critical_path.record_stage("long", "router.assign", 0.001)
    for i in range(5000):
        critical_path.record_stage(f"task-{i}", "sched.queue", 0.001)
    critical_path.record_stage("long", "llm.admit", 9.6)
    critical_path.record_stage("long", "llm.decode", 5.0)
    critical_path.finish_request("long", "/llm", "200", 14.7)
    assert 5000 > critical_path.MAX_TRACES
    (row,) = critical_path.slow_requests(n=1)
    assert [s["stage"] for s in row["stages"]] == [
        "proxy.dispatch", "router.assign", "llm.admit", "llm.decode"]
    assert row["dominant_stage"] == "llm.admit"
    assert critical_path.snapshot_state()["traces"] == {}


def test_span_records_start_end_parent_and_attributes():
    """The span form: t0 <= t1 on time.time's clock, the enclosing span
    of the same thread as parent, attributes from the call and from
    `.set`; the begin/end form writes the same record; another thread's
    span has no parent here."""
    import threading
    import time

    t_before = time.time()
    with critical_path.span("engine.admit_wave") as wave:
        with critical_path.span("engine.prefill_dispatch", real=5,
                                bucket=8):
            time.sleep(0.002)
        sp = critical_path.begin("engine.sample_dispatch")
        other = threading.Thread(
            target=lambda: critical_path.span("data.block_fetch")
            .__enter__().__exit__(None, None, None))
        other.start()
        other.join()
        critical_path.end(sp, admitted=1)
        wave.set(admitted=1, left_over=0)
    t_after = time.time()
    # (`process.wake_late` is the recorder's own beat of a process that
    # stood still: a loaded host's, not this test's.)
    spans = {s["stage"]: s
             for s in flight_recorder.local_snapshot()["spans"]
             if s["stage"] != "process.wake_late"}
    assert set(spans) == {"engine.admit_wave", "engine.prefill_dispatch",
                          "engine.sample_dispatch", "data.block_fetch"}
    for s in spans.values():
        assert t_before <= s["t0"] <= s["t1"] <= t_after
        assert s["t"] == s["t1"]
        assert s["dur_s"] == pytest.approx(s["t1"] - s["t0"], abs=1e-6)
    wave_id = spans["engine.admit_wave"]["id"]
    assert spans["engine.admit_wave"]["parent"] == 0
    assert spans["engine.prefill_dispatch"]["parent"] == wave_id
    assert spans["engine.sample_dispatch"]["parent"] == wave_id
    assert spans["data.block_fetch"]["parent"] == 0  # its own thread
    assert spans["engine.prefill_dispatch"]["attrs"] == \
        {"real": 5, "bucket": 8}
    assert spans["engine.admit_wave"]["attrs"] == \
        {"admitted": 1, "left_over": 0}
    assert spans["engine.sample_dispatch"]["attrs"] == {"admitted": 1}
    # A thin record made inside a span names it as parent too.
    with critical_path.span("engine.admit_wave") as wave:
        critical_path.record_stage("", "llm.admit", 0.01)
    ring = flight_recorder.local_snapshot()["spans"]
    assert ring[-2]["stage"] == "llm.admit"
    assert ring[-2]["parent"] == ring[-1]["id"]
    assert ring[-2]["t1"] - ring[-2]["t0"] == pytest.approx(0.01, abs=1e-6)


def test_self_time_is_duration_less_childrens_cover():
    """A hand-made nest: self time = duration less what the children
    cover, overlaps counted once, children clipped to the parent,
    grandchildren charged to their own parent only."""
    nest = [
        {"id": 1, "parent": 0, "t0": 0.0, "t1": 10.0},
        {"id": 2, "parent": 1, "t0": 1.0, "t1": 4.0},
        {"id": 3, "parent": 1, "t0": 3.0, "t1": 6.0},   # overlaps 2
        {"id": 4, "parent": 1, "t0": 9.0, "t1": 12.0},  # sticks out
        {"id": 5, "parent": 2, "t0": 1.5, "t1": 2.0},   # grandchild
        {"id": 0, "parent": 0, "t0": 0.0, "t1": 1.0},   # no id: skipped
    ]
    assert critical_path.self_seconds(nest) == {
        1: pytest.approx(10.0 - 5.0 - 1.0), 2: pytest.approx(2.5),
        3: pytest.approx(3.0), 4: pytest.approx(3.0),
        5: pytest.approx(0.5)}
    # The flight ring's snapshot carries it for every span it holds.
    import time

    with critical_path.span("engine.admit_wave"):
        time.sleep(0.002)
        with critical_path.span("engine.flush_pending"):
            time.sleep(0.002)
    child, parent = flight_recorder.local_snapshot()["spans"]
    assert parent["self_s"] == pytest.approx(
        parent["dur_s"] - child["dur_s"], abs=1e-5)
    assert child["self_s"] == pytest.approx(child["dur_s"], abs=1e-5)


def test_front_ttft_self_is_envelope_less_engine_extent():
    """`finish_request` derives front.ttft_self from the envelope
    (`proxy.first_byte`) and the engine's stages, leaves the envelope
    out of the tiling, and emits nothing when the engine's stages are
    not there."""
    import time

    critical_path.open_request("f1")
    critical_path.record_stage("f1", "proxy.dispatch", 0.002, route="/l")
    time.sleep(0.03)
    critical_path.record_stage("f1", "llm.admit", 0.01)
    time.sleep(0.02)
    # 0.01 s of admit + 0.02 s of sleep lie between the engine's first
    # start and its last end.
    critical_path.record_stage("f1", "llm.prefill", 0.015)
    critical_path.record_stage("f1", "proxy.first_byte", 0.070, route="/l")
    critical_path.record_stage("f1", "llm.decode", 0.1)
    critical_path.finish_request("f1", "/l", "200", 0.2)
    critical_path.open_request("f2")
    critical_path.record_stage("f2", "proxy.first_byte", 0.05, route="/l")
    critical_path.finish_request("f2", "/l", "200", 0.06)
    rows = {r["trace_id"]: r for r in critical_path.slow_requests()}
    front = rows["f1"]["front_ttft_self_s"]
    assert 0.0 < front < 0.070
    assert front == pytest.approx(0.070 - 0.030, abs=0.008)
    assert "front_ttft_self_s" not in rows["f2"]
    # The envelope is shown, but does not count twice in the tiling.
    assert "proxy.first_byte" in [s["stage"] for s in rows["f1"]["stages"]]
    assert rows["f1"]["unattributed_s"] == pytest.approx(
        0.2 - 0.002 - 0.01 - 0.015 - 0.1)
    vec = critical_path.attribution_vectors()["/l"]
    assert vec["front.ttft_self"]["count"] == 1
    assert vec["proxy.first_byte"]["count"] == 2
    derived = [s for s in flight_recorder.local_snapshot()["spans"]
               if s["stage"] == "front.ttft_self"]
    assert len(derived) == 1 and derived[0]["trace_id"] == "f1"
    assert derived[0]["dur_s"] == pytest.approx(front)


def test_critical_path_loads_without_jax():
    """Every process of the runtime imports the recorder; one that was
    told its chips (and so never imports JAX) must not get JAX from
    it, and its spans still record."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from ray_tpu._private import critical_path, flight_recorder\n"
        "with critical_path.span('engine.idle_wait'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        "assert flight_recorder.local_snapshot()['spans'][0]['stage'] "
        "== 'engine.idle_wait'\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_disabled_records_nothing():
    critical_path.set_enabled(False)
    try:
        critical_path.open_request("t4")
        critical_path.record_stage("t4", "proxy.dispatch", 0.01,
                                   route="/r")
        with critical_path.span("engine.idle_wait") as sp:
            sp.set(n=1)
        critical_path.finish_request("t4", "/r", "200", 0.1)
        assert critical_path.finished_waterfalls() == []
        assert critical_path.drain_records() == []
        assert critical_path.attribution_vectors() == {}
    finally:
        critical_path.set_enabled(True)


def test_slow_requests_ranked_with_fracs():
    for i, total in enumerate((0.1, 0.5, 0.3)):
        tid = f"t5-{i}"
        critical_path.open_request(tid)
        critical_path.record_stage(tid, "replica.execute", total / 2,
                                   route="/r")
        critical_path.finish_request(tid, "/r", "200", total)
    rows = critical_path.slow_requests(n=2)
    assert [r["trace_id"] for r in rows] == ["t5-1", "t5-2"]
    assert rows[0]["stages"][0]["frac"] == pytest.approx(0.5)


def test_stage_metric_p99_exported():
    """runtime_metrics exports the p99 gauge for the attribution
    metric (the per-route p50/p99 vector contract)."""
    from ray_tpu._private.runtime_metrics import _collect_fastpath_stats
    from ray_tpu.util.metrics import snapshot_registry

    critical_path.open_request("t6")
    critical_path.record_stage("t6", "replica.execute", 0.05,
                               route="/r")
    critical_path.finish_request("t6", "/r", "200", 0.06)
    _collect_fastpath_stats()
    snap = snapshot_registry()
    assert "ray_tpu_request_stage_seconds_p50" in snap
    assert "ray_tpu_request_stage_seconds_p99" in snap


def test_flight_rings_bounded_and_snapshotted(monkeypatch):
    monkeypatch.setattr(ray_config, "flight_ring_size", 8)
    for i in range(32):
        flight_recorder.note_span({"trace_id": f"x{i}",
                                   "stage": "s", "dur_s": 0.0})
        flight_recorder.note_sample("health", {"i": i})
    snap = flight_recorder.local_snapshot()
    assert len(snap["spans"]) == 8
    assert snap["spans"][-1]["trace_id"] == "x31"
    assert len(snap["samples"]) == 8
    assert "slow_requests" in snap


def test_observe_verdict_edge_and_debounce(tmp_path, monkeypatch):
    monkeypatch.setattr(ray_config, "flight_recorder_dir",
                        str(tmp_path))
    monkeypatch.setattr(ray_config, "flight_min_interval_s", 3600.0)
    ok = {"status": "ok", "reasons": []}
    bad = {"status": "degraded", "reasons": ["slo_burn: route /r"]}

    assert flight_recorder.observe_verdict(ok) is None
    payload = flight_recorder.observe_verdict(bad)
    assert payload is not None and "path" in payload
    # Still degraded: no new edge, no new dump.
    assert flight_recorder.observe_verdict(bad) is None
    # Recovered then re-degraded inside the debounce window: edge
    # detected but the dump is suppressed.
    assert flight_recorder.observe_verdict(ok) is None
    assert flight_recorder.observe_verdict(bad) is None
    files = list(tmp_path.glob("FLIGHT_*.json"))
    assert len(files) == 1
    on_disk = json.loads(files[0].read_text())
    assert on_disk["verdict"] == "degraded"
    assert on_disk["reasons"] == bad["reasons"]
    assert on_disk["trigger"] == "degraded"


def test_observe_verdict_no_dir_never_writes(tmp_path, monkeypatch):
    monkeypatch.setattr(ray_config, "flight_recorder_dir", "")
    bad = {"status": "degraded", "reasons": ["r"]}
    flight_recorder.observe_verdict({"status": "ok", "reasons": []})
    assert flight_recorder.observe_verdict(bad) is None
    assert list(tmp_path.glob("FLIGHT_*.json")) == []


def test_api_slow_requests_and_debug_dump(ray_start_2_cpus):
    import urllib.request

    from ray_tpu.dashboard import shutdown_dashboard, start_dashboard

    critical_path.open_request("t7")
    critical_path.record_stage("t7", "replica.execute", 0.2,
                               route="/demo")
    critical_path.finish_request("t7", "/demo", "200", 0.25)
    server = start_dashboard(port=0)
    base = f"http://{server.host}:{server.port}"
    try:
        with urllib.request.urlopen(base, timeout=10) as resp:
            endpoints = json.loads(resp.read())["endpoints"]
        assert "/api/slow_requests" in endpoints
        assert "/api/debug/dump" in endpoints
        with urllib.request.urlopen(f"{base}/api/slow_requests",
                                    timeout=10) as resp:
            body = json.loads(resp.read())
        rows = body["slow_requests"]
        assert rows and rows[0]["trace_id"] == "t7"
        assert rows[0]["dominant_stage"] == "replica.execute"
        assert body["attribution"]["/demo"]["replica.execute"]["count"] \
            == 1
        assert any(e["trace_id"] == "t7" for e in body["exemplars"])
        with urllib.request.urlopen(f"{base}/api/debug/dump",
                                    timeout=10) as resp:
            dump = json.loads(resp.read())
        assert dump["trigger"] == "api"
        assert dump["nodes"]  # at least this process's rings
        ring = next(iter(dump["nodes"].values()))
        assert "spans" in ring and "samples" in ring
        # No directory configured: inline payload only, nothing on disk.
        assert "path" not in dump
    finally:
        shutdown_dashboard()


def test_cli_slow_prints_waterfalls(ray_start_2_cpus, capsys):
    from ray_tpu.scripts.cli import main as cli_main

    critical_path.open_request("t8")
    critical_path.record_stage("t8", "llm.prefill", 0.3, route="/llm")
    critical_path.finish_request("t8", "/llm", "200", 0.4)
    cli_main(["slow", "-n", "5"])
    out = capsys.readouterr().out
    assert "t8" in out
    assert "dominant=llm.prefill" in out
    cli_main(["slow", "--json"])
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["slow_requests"][0]["trace_id"] == "t8"
    assert "/llm" in parsed["attribution"]


def test_slo_flood_dumps_once_with_rings_from_every_node(
        tmp_path, monkeypatch):
    """Chaos leg: flood a route past its SLO target on a 2-node
    cluster. The ok→degraded edge must produce EXACTLY one flight dump
    whose verdict names slo_burn and whose rings cover every live
    node; repeated degraded polls must not dump again."""
    from ray_tpu._private.health import evaluate_health
    from ray_tpu.cluster_utils import Cluster

    route = "/flood"
    monkeypatch.setattr(ray_config, "serve_slo_targets",
                        f"{route}=0.05:0.9")
    monkeypatch.setattr(ray_config, "flight_recorder_dir",
                        str(tmp_path))
    monkeypatch.setattr(ray_config, "flight_min_interval_s", 3600.0)
    # Only the SLO signal may trip on a loaded CI box: park the other
    # thresholds out of reach so the baseline verdict is "ok".
    monkeypatch.setattr(ray_config, "health_memory_pressure_threshold",
                        1.1)
    monkeypatch.setattr(ray_config, "health_loop_lag_threshold_s", 60.0)
    monkeypatch.setattr(ray_config, "health_backlog_threshold",
                        10 ** 6)

    ray_tpu.shutdown()
    cluster = Cluster(head_node_args={"num_cpus": 1})
    try:
        cluster.add_node(num_cpus=1)
        v0 = evaluate_health()
        assert v0["status"] == "ok", v0["reasons"]

        # The flood: 50 requests at 10x the 50ms target burn the whole
        # error budget (objective 0.9 -> any >10% bad is >1x burn).
        dist = perf_stats.dist(
            "serve_request_seconds",
            tags={"route": route, "status": "200"},
            bounds=perf_stats.SERVE_LATENCY_BOUNDS)
        for _ in range(50):
            dist.record(0.5)

        v1 = evaluate_health()
        assert v1["status"] == "degraded"
        assert any(r.startswith("slo_burn:") for r in v1["reasons"]), \
            v1["reasons"]
        # Still degraded on later polls: the edge fired once.
        evaluate_health()
        evaluate_health()

        files = list(tmp_path.glob("FLIGHT_*.json"))
        assert len(files) == 1, [f.name for f in files]
        payload = json.loads(files[0].read_text())
        assert payload["verdict"] == "degraded"
        assert any("slo_burn:" in r for r in payload["reasons"])
        # Rings from every live node: the head's own plus a
        # flight_snapshot RPC answer from the added worker node.
        rings = payload["nodes"]
        assert len(rings) >= 2, list(rings)
        for node_id, ring in rings.items():
            assert "error" not in ring, (node_id, ring)
            assert "spans" in ring and "samples" in ring, node_id
    finally:
        cluster.shutdown()
