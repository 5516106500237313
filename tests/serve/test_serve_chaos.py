"""Chaos under load: kill one proxy AND one replica mid-load.

The acceptance contract (ISSUE 15):

- requests drain with bounded p99 — nobody waits out a queue/result
  deadline while the fleet reconverges;
- ZERO double-dispatch: every request that got a 200 executed exactly
  once, and no request executed more than once (the proxy's
  fallback-on-ActorDiedError retry is only taken for provably
  never-executed calls);
- ``/api/healthz`` NAMES the dead components while degraded
  (``serve_replica_dead: ...``, ``serve_proxy_dead: ...``) and then
  recovers to ok once the controller replaces the replica and the
  fleet supervisor restarts the proxy on its original port.
"""

import json
import socket
import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu._private import health
from ray_tpu._private.config import ray_config

# In-process replicas share this module's globals: per-request-id
# execution counts are the double-dispatch witness.
EXEC_COUNTS = {}
EXEC_LOCK = threading.Lock()


@pytest.fixture
def fast_chaos(monkeypatch):
    monkeypatch.setattr(ray_config, "serve_replica_health_period_s", 0.2)
    monkeypatch.setattr(ray_config, "serve_proxy_supervise_period_s",
                        0.3)
    yield


@pytest.fixture
def serve_up(fast_chaos):
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    EXEC_COUNTS.clear()
    yield
    serve.shutdown()
    ray_tpu.shutdown()


@serve.deployment(num_replicas=3, max_concurrent_queries=8)
class Chaos:
    def __call__(self, payload):
        rid = payload["id"]
        with EXEC_LOCK:
            EXEC_COUNTS[rid] = EXEC_COUNTS.get(rid, 0) + 1
        time.sleep(0.002)
        return {"id": rid}


def _request_bytes(rid):
    body = json.dumps({"id": rid}).encode()
    return (b"POST /chaos HTTP/1.1\r\nHost: t\r\n"
            b"Content-Type: application/json\r\nContent-Length: "
            + str(len(body)).encode() + b"\r\n\r\n" + body)


def _read_response(sock, buf):
    while b"\r\n\r\n" not in buf:
        chunk = sock.recv(65536)
        if not chunk:
            raise ConnectionError("server closed")
        buf += chunk
    head, buf = buf.split(b"\r\n\r\n", 1)
    status = int(head.split(b" ", 2)[1])
    clen = 0
    for ln in head.split(b"\r\n")[1:]:
        if ln.lower().startswith(b"content-length:"):
            clen = int(ln.split(b":", 1)[1])
    while len(buf) < clen:
        chunk = sock.recv(65536)
        if not chunk:
            raise ConnectionError("closed mid-body")
        buf += chunk
    return status, buf[clen:]


class _Worker(threading.Thread):
    """One keep-alive load client pinned to one proxy port; on a
    transport error it reconnects (the proxy restarts on the SAME
    port) and moves on to a FRESH request id — a request whose
    response was lost is never resent, so its execution count stays
    <= 1 by construction (the double-dispatch witness must come from
    the SERVER side, not client retries)."""

    def __init__(self, name, port, stop):
        super().__init__(name=name, daemon=True)
        self.port = port
        self.stop_evt = stop
        self.latencies = []
        self.statuses = {}
        self.ok_ids = []
        self.lost = 0
        self.seq = 0

    def run(self):
        sock = None
        buf = b""
        while not self.stop_evt.is_set():
            rid = f"{self.name}-{self.seq}"
            self.seq += 1
            t0 = time.perf_counter()
            try:
                if sock is None:
                    sock = socket.create_connection(
                        ("127.0.0.1", self.port), timeout=10)
                    sock.setsockopt(socket.IPPROTO_TCP,
                                    socket.TCP_NODELAY, 1)
                    buf = b""
                sock.sendall(_request_bytes(rid))
                status, buf = _read_response(sock, buf)
            except (OSError, ConnectionError):
                self.lost += 1
                try:
                    if sock is not None:
                        sock.close()
                except OSError:
                    pass
                sock = None
                time.sleep(0.05)
                continue
            self.latencies.append(time.perf_counter() - t0)
            self.statuses[status] = self.statuses.get(status, 0) + 1
            if status == 200:
                self.ok_ids.append(rid)
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass


def _percentile(sorted_vals, q):
    import math

    return sorted_vals[min(len(sorted_vals) - 1,
                           max(0, math.ceil(len(sorted_vals) * q) - 1))]


def test_hung_replica_struck_out_and_replaced(serve_up, monkeypatch):
    """A WEDGED (alive but deadlocked) replica — not just a dead one —
    is detected by the ping-timeout strike path
    (serve_replica_health_timeout_s), named in healthz, killed, and
    replaced; traffic recovers. A busy replica serving its FIFO'd ping
    within one item's time never strikes out."""
    monkeypatch.setattr(ray_config, "serve_replica_health_timeout_s",
                        0.3)
    wedge = threading.Event()

    @serve.deployment(num_replicas=1, max_concurrent_queries=1,
                      name="Wedgeable")
    class Wedgeable:
        def __call__(self, payload):
            if payload == "wedge":
                wedge.wait(20)  # deadlock stand-in: pings queue behind
            return {"ok": payload}

    import ray_tpu as rt
    from ray_tpu import serve as serve_mod

    handle = serve_mod.run(Wedgeable.bind(), route_prefix="/wedge")
    assert rt.get(handle.remote("a"), timeout=30)["ok"] == "a"

    wedger = threading.Thread(
        target=lambda: rt.get(handle.remote("wedge"), timeout=60),
        daemon=True)
    wedger.start()
    try:
        # Strikes accumulate (0.2s period, 0.3s timeout, 2 failures):
        # detection + replacement within a few seconds.
        deadline = time.monotonic() + 15
        seen = False
        while time.monotonic() < deadline and not seen:
            seen = any("serve_replica_dead" in r and "Wedgeable" in r
                       and "unresponsive" in r
                       for r in health.provider_reasons())
            time.sleep(0.02)
        assert seen, "wedged replica never struck out"
        # The replacement serves (poll: it must construct first and
        # the handle may briefly retry the broadcast-removed victim).
        deadline = time.monotonic() + 20
        ok = False
        while time.monotonic() < deadline and not ok:
            try:
                ok = rt.get(handle.remote("b"),
                            timeout=10)["ok"] == "b"
            except Exception:
                time.sleep(0.1)
        assert ok, "replacement replica never served"
    finally:
        wedge.set()
        wedger.join(timeout=30)


def test_constructing_replica_is_not_struck_out(serve_up, monkeypatch):
    """A replica whose constructor outlasts the whole strike window (a
    model server compiling its programs) is starting, not hung: its
    first ping waits behind the constructor and no strike is counted."""
    monkeypatch.setattr(ray_config, "serve_replica_health_timeout_s",
                        0.3)

    @serve.deployment(num_replicas=1, name="SlowStart")
    class SlowStart:
        def __init__(self):
            # period 0.2 x 2 failures + timeout 0.3 strikes out in < 1s.
            time.sleep(2.0)

        def __call__(self, payload):
            return {"ok": payload}

    import ray_tpu as rt
    from ray_tpu import serve as serve_mod
    from ray_tpu._private.worker import global_worker

    handle = serve_mod.run(SlowStart.bind(), route_prefix="/slowstart")
    orig = {n for n in global_worker().gcs.list_named_actors()
            if str(n).startswith("SERVE_REPLICA::SlowStart::")}
    assert rt.get(handle.remote("a"), timeout=30)["ok"] == "a"
    now = {n for n in global_worker().gcs.list_named_actors()
           if str(n).startswith("SERVE_REPLICA::SlowStart::")}
    assert now == orig, f"constructing replica was replaced: {orig} -> {now}"
    assert not any("SlowStart" in r for r in health.provider_reasons())


def test_saturated_replica_is_not_struck_out(serve_up, monkeypatch):
    """The kill-loop guard: a SATURATED replica — health ping FIFO'd
    behind a backlog deeper than its execution slots, but completing
    requests continuously — must never strike out. Only a replica
    making NO progress since the ping was sent is 'unresponsive'."""
    monkeypatch.setattr(ray_config, "serve_replica_health_timeout_s",
                        0.3)

    @serve.deployment(num_replicas=1, max_concurrent_queries=8,
                      name="Busy")
    class Busy:
        def __call__(self, payload):
            time.sleep(0.15)
            return {"ok": payload}

    import ray_tpu as rt
    from ray_tpu import serve as serve_mod

    handle = serve_mod.run(Busy.bind(), route_prefix="/busy")
    from ray_tpu._private.worker import global_worker

    orig = {n for n in global_worker().gcs.list_named_actors()
            if str(n).startswith("SERVE_REPLICA::Busy::")}
    # Sustained depth: 6 concurrent callers x 0.15s against ONE
    # execution slot stream keeps the ping parked well past the 0.3s
    # timeout for ~2.5s (>> period 0.2 x failures 2).
    stop = threading.Event()
    errors = []

    def pound():
        while not stop.is_set():
            try:
                rt.get(handle.remote(1), timeout=30)
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return

    threads = [threading.Thread(target=pound) for _ in range(6)]
    for t in threads:
        t.start()
    time.sleep(2.5)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    assert not errors, errors[0]
    now = {n for n in global_worker().gcs.list_named_actors()
           if str(n).startswith("SERVE_REPLICA::Busy::")}
    assert now == orig, f"saturated replica was replaced: {orig} -> {now}"
    assert not any("Busy" in r for r in health.provider_reasons())


def test_chaos_kill_proxy_and_replica_mid_load(serve_up):
    serve.run(Chaos.bind(), route_prefix="/chaos")
    fleet = serve.ProxyFleet(num_proxies=2, queue_timeout_s=5.0)
    try:
        ports = [port for _host, port in fleet.addresses()]
        stop = threading.Event()
        workers = [_Worker(f"w{i}", ports[i % len(ports)], stop)
                   for i in range(6)]
        for w in workers:
            w.start()

        # Warm: all workers serving.
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and any(
                not w.latencies for w in workers):
            time.sleep(0.05)
        assert all(w.latencies for w in workers), "load never warmed"

        # -- chaos: kill one replica and one proxy mid-load ----------
        from ray_tpu._private.worker import global_worker

        names = [n for n in global_worker().gcs.list_named_actors()
                 if str(n).startswith("SERVE_REPLICA::Chaos::")]
        assert len(names) == 3
        victim_replica = ray_tpu.get_actor(names[0])
        victim_proxy = fleet.actors()[1]
        ray_tpu.kill(victim_replica)
        ray_tpu.kill(victim_proxy)

        # healthz must NAME the dead components while degraded. Poll
        # fast — supervision replaces them within a couple seconds.
        seen_replica = seen_proxy = False
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and not (
                seen_replica and seen_proxy):
            reasons = health.provider_reasons()
            seen_replica = seen_replica or any(
                "serve_replica_dead" in r and "Chaos" in r
                for r in reasons)
            seen_proxy = seen_proxy or any(
                "serve_proxy_dead" in r and str(ports[1]) in r
                for r in reasons)
            time.sleep(0.01)
        assert seen_replica, "healthz never named the dead replica"
        assert seen_proxy, "healthz never named the dead proxy"

        # The provider reasons flow into the real /api/healthz payload:
        # while any serve component is dead the cluster verdict is
        # degraded with the component named.
        verdict = health.evaluate_health()
        if health.provider_reasons():  # still inside the window
            assert verdict["status"] == "degraded"
            assert any("serve_" in r for r in verdict["reasons"])

        # ...and then RECOVER: reasons drain once the replica is
        # replaced and the proxy restarted on its original port.
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and health.provider_reasons():
            time.sleep(0.05)
        assert health.provider_reasons() == [], (
            f"healthz stuck degraded: {health.provider_reasons()}")
        # The serve components are out of the healthz verdict too (the
        # overall status may still reflect unrelated load signals on a
        # busy CI box, so assert only the serve_* reasons drained).
        assert not any("serve_" in r
                       for r in health.evaluate_health()["reasons"])

        # Load keeps draining through recovery for a beat.
        time.sleep(1.0)
        stop.set()
        for w in workers:
            w.join(timeout=30)

        # -- assertions ---------------------------------------------
        all_lat = sorted(sum((w.latencies for w in workers), []))
        ok = sum(w.statuses.get(200, 0) for w in workers)
        lost = sum(w.lost for w in workers)
        non200 = {s: sum(w.statuses.get(s, 0) for w in workers)
                  for s in {st for w in workers for st in w.statuses}
                  if s != 200}
        assert ok >= 200, (ok, non200, lost)
        # Bounded p99: nobody waited out the 5s queue timeout, let
        # alone the 60s result deadline.
        p99 = _percentile(all_lat, 0.99)
        assert p99 < 3.0, f"p99 {p99:.2f}s unbounded under chaos " \
                          f"(statuses {non200}, lost {lost})"
        # Zero double-dispatch: every 200 executed exactly once, and
        # NOTHING executed twice (lost/shed requests executed <= 1).
        with EXEC_LOCK:
            over = {k: v for k, v in EXEC_COUNTS.items() if v > 1}
            counts = dict(EXEC_COUNTS)
        assert not over, f"double-executed requests: {over}"
        for w in workers:
            for rid in w.ok_ids:
                assert counts.get(rid) == 1, (rid, counts.get(rid))
        # The killed proxy's port answers again (restarted in place).
        status, _hdrs, _body = None, None, None
        sock = socket.create_connection(("127.0.0.1", ports[1]),
                                        timeout=10)
        try:
            sock.sendall(_request_bytes("post-recovery"))
            status, _ = _read_response(sock, b"")
        finally:
            sock.close()
        assert status == 200
        stats = fleet.stats()
        assert stats["restarts"] >= 1
        assert stats.get("direct_served", 0) > 0  # fast path was live
    finally:
        fleet.shutdown()
