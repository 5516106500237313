"""JaxTrainer: SPMD training over TPU meshes.

The reference's TorchTrainer forms an NCCL process group per worker
(`train/torch/config.py:113`). The TPU-native model is different
(SURVEY.md §7 "multi-controller JAX"): one worker per *host*, each running
the same jit-compiled SPMD program; in-host (and cross-host, on pods)
parallelism is the `jax.sharding.Mesh`, with collectives inserted by XLA.
The trainer's job is (a) reserving the gang via placement group, (b)
initializing `jax.distributed` on each worker for multi-host, (c) handing
the train loop a ready mesh via `prepare_mesh()`.

Host-level data parallelism across *separate* processes without shared
ICI (e.g. CPU fleets) instead uses the object-plane collective group
(`ray_tpu.util.collective`) for gradient averaging — the gloo-DDP
equivalent; see `prepare_ddp`/`allreduce_gradients`.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

from ray_tpu._private.compile_cache import enable_persistent_cache
from ray_tpu.air import session
from ray_tpu.air.config import ScalingConfig
from ray_tpu.parallel.mesh import MeshConfig, create_mesh
from ray_tpu.train.backend import Backend, BackendConfig
from ray_tpu.train.data_parallel_trainer import DataParallelTrainer


@dataclass
class JaxConfig(BackendConfig):
    """Multi-host wiring config. With `distributed=True` each worker runs
    in its own OS process (the WorkerGroup forces `isolate_process`) and
    calls `jax.distributed.initialize(coordinator, num_processes,
    process_id)` before the loop — one JAX process per host, the
    multi-controller model. Single-host runs skip it.

    ``platform`` / ``num_local_devices`` pin the per-process backend
    (e.g. platform="cpu", num_local_devices=2 gives a 2-process ×
    2-device CPU test mesh — how multi-host is exercised without a pod;
    CPU collectives ride the gloo plugin)."""

    distributed: bool = False
    coordinator_port: int = 7010
    platform: Optional[str] = None
    num_local_devices: Optional[int] = None

    def backend_cls(self):
        return JaxBackend


class JaxBackend(Backend):
    def on_training_start(self, worker_group, backend_config: JaxConfig):
        if not getattr(backend_config, "distributed", False):
            # Before the loop's first compile, in the process that runs
            # it.
            worker_group.execute(enable_persistent_cache)
            return
        import ray_tpu

        # Rank-0's node is the coordinator.
        def get_ip():
            import socket

            return socket.gethostbyname(socket.gethostname())

        ip = worker_group.execute_single(0, get_ip)
        coord = f"{ip}:{backend_config.coordinator_port}"
        n = len(worker_group)
        platform = backend_config.platform
        local = backend_config.num_local_devices

        ray_tpu.get([
            w.execute.remote(_jax_dist_init, coord, n, i, platform, local)
            for i, w in enumerate(worker_group.workers)
        ])


def _jax_dist_init(coord, n, rank, platform=None, num_local_devices=None):
    """Per-rank jax.distributed bring-up. Runs inside a spawned worker
    process, which opens this host's chips itself: the driver that
    launches the ranks must not have (`ray_tpu.init(num_tpus=...)`)."""
    import os
    import re

    import jax

    if platform is not None:
        jax.config.update("jax_platforms", platform)
    if num_local_devices is not None and (platform or "") == "cpu":
        # Inherited test env may force a host device count; the explicit
        # per-rank setting wins.
        flags = os.environ.get("XLA_FLAGS", "")
        stripped = re.sub(
            r"--xla_force_host_platform_device_count=\d+", "", flags).strip()
        if stripped != flags:
            os.environ["XLA_FLAGS"] = stripped
        jax.config.update("jax_num_cpu_devices", num_local_devices)
    if (platform or "") == "cpu":
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coord, num_processes=n,
                               process_id=rank)
    enable_persistent_cache()
    return True


class JaxTrainer(DataParallelTrainer):
    _backend_config_cls = JaxConfig

    def __init__(self, train_loop_per_worker: Callable, *,
                 jax_config: Optional[JaxConfig] = None,
                 **kwargs):
        super().__init__(train_loop_per_worker,
                         backend_config=jax_config, **kwargs)


# -- in-loop helpers (reference parity: train.torch.prepare_model etc.) ----


def prepare_mesh(scaling_config: Optional[ScalingConfig] = None,
                 mesh_config: Optional[MeshConfig] = None):
    """Build the mesh for this worker's visible devices. Inside a Train
    worker the ScalingConfig's mesh axes apply; standalone it defaults to
    all devices on the data axis."""
    cfg = mesh_config or (scaling_config.mesh_config() if scaling_config
                          else MeshConfig())
    return create_mesh(cfg)


def allreduce_gradients(grads, group_name: str = "default"):
    """Host-plane gradient mean across the worker group (gloo-DDP
    equivalent for CPU fleets; on one mesh this is unnecessary — XLA
    averages via the batch sharding)."""
    from ray_tpu.util import collective

    if session.get_session() is None or session.get_world_size() == 1:
        return grads
    return collective.allreduce_pytree(grads, group_name=group_name,
                                       op=collective.ReduceOp.MEAN)
