"""Serve tests: deploy, handles, scaling, updates, batching, HTTP."""

import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(autouse=True)
def ray():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def test_function_deployment():
    @serve.deployment
    def echo(x):
        return {"echo": x}

    handle = serve.run(echo.bind())
    out = ray_tpu.get(handle.remote("hi"))
    assert out == {"echo": "hi"}


def test_class_deployment_with_state():
    @serve.deployment(num_replicas=1)
    class Counter:
        def __init__(self, start):
            self.n = start

        def __call__(self):
            self.n += 1
            return self.n

        def value(self):
            return self.n

    handle = serve.run(Counter.bind(10))
    assert ray_tpu.get(handle.remote()) == 11
    assert ray_tpu.get(handle.remote()) == 12
    assert ray_tpu.get(handle.value.remote()) == 12


def test_a_stopped_replica_calls_the_deployments_del():
    """Ray Serve's contract: a user class's `__del__` runs when its
    replica is stopped, so what it started (an engine's loop thread)
    ends with it and not at interpreter exit."""
    import threading

    stopped = threading.Event()

    @serve.deployment
    class Worker:
        def __call__(self):
            return "up"

        def __del__(self):
            stopped.set()

    handle = serve.run(Worker.bind())
    assert ray_tpu.get(handle.remote()) == "up"
    assert not stopped.is_set()
    serve.shutdown()
    assert stopped.is_set()


def test_multiple_replicas_round_robin():
    @serve.deployment(num_replicas=3)
    class Who:
        def __init__(self):
            # Replica identity = the instance, not the serving thread:
            # pooled multi-slot actors construct and serve on shared
            # executor threads, so thread names no longer distinguish
            # replicas.
            import uuid

            self.me = uuid.uuid4().hex

        def __call__(self):
            return self.me

    handle = serve.run(Who.bind())
    names = {ray_tpu.get(handle.remote()) for _ in range(12)}
    assert len(names) == 3


def test_scale_up_down():
    @serve.deployment(num_replicas=1, name="scaler")
    def f():
        return 1

    serve.run(f.bind())
    info = serve.status()["scaler"]
    assert info["num_replicas"] == 1
    serve.run(f.options(num_replicas=3).bind())
    info = serve.status()["scaler"]
    assert info["num_replicas"] == 3


def test_rolling_update_version_change():
    @serve.deployment(name="versioned", version="v1")
    class V:
        def __call__(self):
            return "v1"

    h = serve.run(V.bind())
    assert ray_tpu.get(h.remote()) == "v1"

    @serve.deployment(name="versioned", version="v2")
    class V2:
        def __call__(self):
            return "v2"

    h2 = serve.run(V2.bind())
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if ray_tpu.get(h2.remote()) == "v2":
            break
        time.sleep(0.05)
    assert ray_tpu.get(h2.remote()) == "v2"


def test_user_config_reconfigure():
    @serve.deployment(user_config={"threshold": 5})
    class Cfg:
        def __init__(self):
            self.threshold = None

        def reconfigure(self, config):
            self.threshold = config["threshold"]

        def __call__(self):
            return self.threshold

    h = serve.run(Cfg.bind())
    assert ray_tpu.get(h.remote()) == 5


def test_batching():
    calls = []

    @serve.deployment
    class Batched:
        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.05)
        def handle(self, items):
            calls.append(len(items))
            return [i * 2 for i in items]

        def __call__(self, x):
            return self.handle(x)

    h = serve.run(Batched.bind())
    refs = [h.remote(i) for i in range(8)]
    out = ray_tpu.get(refs)
    assert sorted(out) == [i * 2 for i in range(8)]
    assert max(calls) > 1  # at least some batching happened


def test_batch_aio_from_event_loop():
    """@serve.batch .aio: N awaiters on ONE event loop coalesce into a
    batch — the wakeup is delivered to the loop instead of blocking it
    (async deployments couldn't use the sync wrapper: every concurrent
    caller would deadlock the loop on Future.result)."""
    import asyncio

    calls = []

    @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.05)
    def double(items):
        calls.append(len(items))
        return [i * 2 for i in items]

    async def main():
        return await asyncio.gather(
            *[double.aio(i) for i in range(8)])

    out = asyncio.run(main())
    assert out == [i * 2 for i in range(8)]
    assert max(calls) > 1


def test_batch_aio_on_method_keeps_instance_binding():
    """`await self.method.aio(item)` from an async handler: the batch
    wrapper is a descriptor, so the instance rides into the batcher
    (a plain function attribute would drop `self` and the batched call
    would blow up with a missing-argument TypeError)."""
    import asyncio

    calls = []

    class Model:
        def __init__(self, scale):
            self.scale = scale

        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.05)
        def infer(self, items):
            calls.append(len(items))
            return [i * self.scale for i in items]

    m = Model(3)
    assert m.infer(2) == 6  # sync path still bound

    async def main():
        return await asyncio.gather(*[m.infer.aio(i) for i in range(8)])

    out = asyncio.run(main())
    assert out == [i * 3 for i in range(8)]
    assert max(calls) > 1

    # Two instances never share a batch.
    m2 = Model(10)
    assert m2.infer(2) == 20
    assert m.infer(2) == 6


def test_http_proxy():
    @serve.deployment(route_prefix="/api")
    def api(payload=None):
        return {"got": payload}

    serve.run(api.bind(), route_prefix="/api")
    proxy = serve.start_http_proxy()
    url = f"http://{proxy.host}:{proxy.port}/api"
    req = urllib.request.Request(
        url, data=json.dumps({"a": 1}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=10) as resp:
        body = json.loads(resp.read())
    assert body == {"got": {"a": 1}}


def test_delete_deployment():
    @serve.deployment(name="gone")
    def f():
        return 1

    serve.run(f.bind())
    assert "gone" in serve.status()
    serve.delete("gone")
    assert "gone" not in serve.status()
