"""Aux subsystem tests: jobs, autoscaler, runtime env, CLI, dashboard,
multiprocessing shim, accelerators, check_serialize."""

import json
import os
import sys
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.autoscaler import (
    AutoscalerConfig,
    FakeNodeProvider,
    NodeType,
    StandardAutoscaler,
)
from ray_tpu.autoscaler.autoscaler import bin_pack_demands
from ray_tpu.job_submission import JobStatus, JobSubmissionClient


@pytest.fixture(autouse=True)
def ray():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


def test_job_submission_lifecycle():
    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint=f"{sys.executable} -c \"print('job output 42')\"")
    info = client.wait_until_finish(job_id, timeout=60)
    assert info.status == JobStatus.SUCCEEDED
    assert "job output 42" in client.get_job_logs(job_id)


def test_job_failure_and_env():
    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint=f"{sys.executable} -c \"import os,sys; "
                   f"sys.exit(0 if os.environ.get('MY_FLAG')=='1' else 3)\"",
        runtime_env={"env_vars": {"MY_FLAG": "1"}})
    assert client.wait_until_finish(job_id).status == JobStatus.SUCCEEDED
    job2 = client.submit_job(entrypoint=f"{sys.executable} -c 'exit(5)'")
    info = client.wait_until_finish(job2)
    assert info.status == JobStatus.FAILED
    assert info.return_code == 5


def test_bin_pack_demands():
    types = [NodeType("small", {"CPU": 4}, max_workers=10),
             NodeType("tpu", {"CPU": 8, "TPU": 8}, max_workers=4)]
    plan = bin_pack_demands(
        [{"CPU": 2}] * 4 + [{"TPU": 8}], types, existing={})
    # TPU demand forces the slice type; its spare CPU absorbs the rest.
    assert plan == {"tpu": 1}
    plan2 = bin_pack_demands([{"CPU": 2}] * 10, types, existing={})
    assert plan2.get("small", 0) >= 5  # pure-CPU load uses the small type
    plan3 = bin_pack_demands([{"TPU": 8}] * 9, types, existing={})
    assert plan3 == {"tpu": 4}  # capped at max_workers


def test_autoscaler_scales_up_for_pending_tasks():
    provider = FakeNodeProvider({"worker": {"CPU": 4}})
    cfg = AutoscalerConfig(node_types=[NodeType("worker", {"CPU": 4},
                                                max_workers=5)],
                           interval_s=0.05)
    scaler = StandardAutoscaler(provider, cfg)

    @ray_tpu.remote
    def hog():
        time.sleep(0.8)
        return 1

    # 8 tasks × 2 CPU on a 4-CPU node → demand backlog.
    refs = [hog.options(num_cpus=2).remote() for _ in range(8)]
    time.sleep(0.1)  # let the backlog form
    scaler.update()
    assert scaler.launches > 0
    assert len(provider.non_terminated_nodes({})) > 0
    ray_tpu.get(refs)


def test_runtime_env_applied_to_task():
    @ray_tpu.remote(runtime_env={"env_vars": {"TASK_ENV_X": "hello"}})
    def read_env():
        return os.environ.get("TASK_ENV_X")

    assert ray_tpu.get(read_env.remote()) == "hello"
    assert os.environ.get("TASK_ENV_X") is None


def test_runtime_env_validation():
    from ray_tpu._private.runtime_env import validate_runtime_env

    with pytest.raises(ValueError):
        validate_runtime_env({"bogus_field": 1})
    with pytest.raises(TypeError):
        validate_runtime_env({"env_vars": "notadict"})
    validate_runtime_env({"env_vars": {"A": "B"}, "pip": ["numpy"]})


def test_cli_status_and_summary(capsys):
    from ray_tpu.scripts.cli import main

    main(["status"])
    out = json.loads(capsys.readouterr().out)
    assert "cluster_resources" in out

    @ray_tpu.remote
    def noop():
        return 1

    ray_tpu.get(noop.remote())
    main(["summary", "tasks"])
    out = json.loads(capsys.readouterr().out)
    assert any("noop" in k for k in out)


def test_dashboard_endpoints():
    from ray_tpu.dashboard import shutdown_dashboard, start_dashboard

    @ray_tpu.remote
    def marker_task():
        return 1

    ray_tpu.get(marker_task.remote())
    server = start_dashboard(port=0)
    try:
        base = f"http://{server.host}:{server.port}"
        with urllib.request.urlopen(f"{base}/api/cluster_status",
                                    timeout=10) as r:
            status = json.loads(r.read())
        assert "cluster_resources" in status
        with urllib.request.urlopen(f"{base}/api/tasks", timeout=10) as r:
            tasks = json.loads(r.read())
        assert any("marker_task" in t["name"] for t in tasks)
        with urllib.request.urlopen(f"{base}/api/metrics", timeout=10) as r:
            assert r.status == 200
    finally:
        shutdown_dashboard()


def test_multiprocessing_pool():
    from ray_tpu.util.multiprocessing import Pool

    with Pool() as pool:
        assert pool.map(lambda x: x * x, range(6)) == [0, 1, 4, 9, 16, 25]
        assert pool.apply(lambda a, b: a + b, (2, 3)) == 5
        r = pool.apply_async(lambda: 7)
        assert r.get(timeout=10) == 7
        assert sorted(pool.imap_unordered(lambda x: x + 1, [1, 2, 3])) == \
            [2, 3, 4]


def test_accelerators():
    from ray_tpu.util import accelerators

    spec = accelerators.chip_spec(accelerators.TPU_V5E)
    assert spec.hbm_bytes == 16 * 2**30
    # The CPU test devices are no TPU generation: an error, not a default.
    with pytest.raises(ValueError, match="device_kind 'cpu'"):
        accelerators.detect_tpu_type()


def test_check_serialize():
    from ray_tpu.util.check_serialize import inspect_serializability

    ok, _ = inspect_serializability({"a": 1})
    assert ok
    import threading

    lock = threading.Lock()

    def closure():
        return lock

    ok, failures = inspect_serializability(closure)
    assert not ok
    assert any("lock" in f for f in failures)
