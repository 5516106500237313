"""Job submission: run entrypoint commands as supervised jobs.

Reference: `dashboard/modules/job/` (SURVEY.md §2.2) — `JobManager`
(`job_manager.py:490`) spawns a detached `JobSupervisor` actor (`:136`)
per job that runs the entrypoint as a subprocess, captures logs, and
records `JobInfo`; the SDK (`python/ray/job_submission/`) talks to it.
Here the same actor architecture runs in-process; the HTTP surface is
exposed by `ray_tpu.dashboard`.
"""

from __future__ import annotations

import os
import subprocess
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import ray_tpu


class JobStatus:
    PENDING = "PENDING"
    RUNNING = "RUNNING"
    SUCCEEDED = "SUCCEEDED"
    FAILED = "FAILED"
    STOPPED = "STOPPED"

    TERMINAL = (SUCCEEDED, FAILED, STOPPED)


@dataclass
class JobInfo:
    job_id: str
    entrypoint: str
    status: str = JobStatus.PENDING
    message: str = ""
    start_time: Optional[float] = None
    end_time: Optional[float] = None
    metadata: Dict[str, str] = field(default_factory=dict)
    runtime_env: Optional[dict] = None
    return_code: Optional[int] = None


@ray_tpu.remote
class JobSupervisor:
    """One per job: runs the entrypoint subprocess, buffers logs."""

    def __init__(self, job_id: str, entrypoint: str,
                 runtime_env: Optional[dict], metadata: Dict[str, str]):
        self.info = JobInfo(job_id=job_id, entrypoint=entrypoint,
                            metadata=metadata, runtime_env=runtime_env)
        self._logs: List[str] = []
        self._proc: Optional[subprocess.Popen] = None
        self._stop_requested = False
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        env = dict(os.environ)
        renv = self.info.runtime_env or {}
        env.update({str(k): str(v)
                    for k, v in (renv.get("env_vars") or {}).items()})
        # The attribution channel into the entrypoint: a driver process
        # started under this env tags every submission with the job id
        # (task_spec.default_job_id), so the job's tasks/metrics/objects
        # are attributable cluster-wide without code changes.
        env["RAY_TPU_JOB_ID"] = self.info.job_id
        cwd = renv.get("working_dir") or None
        self.info.status = JobStatus.RUNNING
        self.info.start_time = time.time()
        try:
            self._proc = subprocess.Popen(
                self.info.entrypoint, shell=True, cwd=cwd, env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True)
            for line in self._proc.stdout:
                self._logs.append(line.rstrip("\n"))
            rc = self._proc.wait()
            self.info.return_code = rc
            if self._stop_requested:
                self.info.status = JobStatus.STOPPED
            elif rc == 0:
                self.info.status = JobStatus.SUCCEEDED
            else:
                self.info.status = JobStatus.FAILED
                self.info.message = f"entrypoint exited with code {rc}"
        except Exception as e:  # noqa: BLE001
            self.info.status = JobStatus.FAILED
            self.info.message = str(e)
        finally:
            self.info.end_time = time.time()

    def get_info(self) -> JobInfo:
        return self.info

    def get_logs(self) -> str:
        return "\n".join(self._logs)

    def stop(self) -> bool:
        self._stop_requested = True
        if self._proc and self._proc.poll() is None:
            self._proc.terminate()
        return True


class JobSubmissionClient:
    """Reference: `python/ray/job_submission/JobSubmissionClient` (the SDK
    normally speaks HTTP to the dashboard; in-process it drives the
    supervisors directly — same surface)."""

    def __init__(self, address: Optional[str] = None):
        self._jobs: Dict[str, Any] = {}
        # The jobs' own driver subprocesses open the chip, so a runtime
        # started here for supervising them must not.
        ray_tpu.init(num_tpus=0, ignore_reinit_error=True)

    def submit_job(self, *, entrypoint: str,
                   runtime_env: Optional[dict] = None,
                   metadata: Optional[Dict[str, str]] = None,
                   submission_id: Optional[str] = None) -> str:
        job_id = submission_id or f"raysubmit_{uuid.uuid4().hex[:12]}"
        if job_id in self._jobs:
            raise ValueError(f"job {job_id} already exists")
        # The supervisor actor (and anything it spawns in-process) is
        # part of the job it supervises: tag its creation so the job's
        # footprint starts at the supervisor, not at the first
        # entrypoint task.
        from ray_tpu._private.task_spec import set_ambient_job_id

        prev = set_ambient_job_id(job_id)
        try:
            supervisor = JobSupervisor.options(
                name=f"_job_supervisor:{job_id}", lifetime="detached",
                max_concurrency=4,
            ).remote(job_id, entrypoint, runtime_env, metadata or {})
        finally:
            set_ambient_job_id(prev)
        self._jobs[job_id] = supervisor
        return job_id

    def _supervisor(self, job_id: str):
        sup = self._jobs.get(job_id)
        if sup is None:
            sup = ray_tpu.get_actor(f"_job_supervisor:{job_id}")
            self._jobs[job_id] = sup
        return sup

    def get_job_status(self, job_id: str) -> str:
        return ray_tpu.get(
            self._supervisor(job_id).get_info.remote()).status

    def get_job_info(self, job_id: str) -> JobInfo:
        return ray_tpu.get(self._supervisor(job_id).get_info.remote())

    def get_job_logs(self, job_id: str) -> str:
        return ray_tpu.get(self._supervisor(job_id).get_logs.remote())

    def stop_job(self, job_id: str) -> bool:
        return ray_tpu.get(self._supervisor(job_id).stop.remote())

    def list_jobs(self) -> List[JobInfo]:
        # Discover supervisors from the named-actor registry, not the
        # client-local dict: any client (e.g. each REST request makes a
        # fresh one) must see every job in the cluster.
        from ray_tpu.experimental import state

        for row in state.list_actors():
            name = row.get("name") or ""
            if name.startswith("_job_supervisor:"):
                job_id = name[len("_job_supervisor:"):]
                if job_id not in self._jobs and row["state"] != "DEAD":
                    try:
                        self._jobs[job_id] = ray_tpu.get_actor(name)
                    except ValueError:
                        pass
        return [ray_tpu.get(s.get_info.remote())
                for s in self._jobs.values()]

    def wait_until_finish(self, job_id: str, timeout: float = 300.0,
                          poll: float = 0.2) -> JobInfo:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            info = self.get_job_info(job_id)
            if info.status in JobStatus.TERMINAL:
                return info
            time.sleep(poll)
        raise TimeoutError(f"job {job_id} not finished in {timeout}s")
