"""Device-mesh construction with named parallelism axes.

The reference forms its parallel groups imperatively
(`torch.distributed.init_process_group(nccl)` at
`python/ray/train/torch/config.py:113`; NCCL groups in
`python/ray/util/collective/collective.py`). On TPU the idiomatic unit is a
`jax.sharding.Mesh` over the ICI torus: collectives are inserted by XLA from
sharding annotations, so the framework's job reduces to (a) choosing a mesh
shape whose fast-varying axes map onto ICI neighbours and (b) handing that
mesh to compiled programs. This module owns (a).

Axis convention (outer → inner, i.e. slowest → fastest varying):

    data   — pure data parallelism (replicated params); may span DCN
    fsdp   — data parallelism with parameter/optimizer sharding (ZeRO-3)
    expert — expert parallelism for MoE layers
    pipe   — pipeline-parallel stages
    seq    — sequence/context parallelism (ring attention / Ulysses)
    tensor — tensor (operator) parallelism; innermost so TP collectives
             ride single-hop ICI links

``tensor`` last matters: `mesh_utils.create_device_mesh` assigns physically
adjacent chips to the fastest-varying mesh dimension, and tensor-parallel
collectives (all-reduce per layer) are the most latency-sensitive.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, fields
from typing import Optional, Sequence

AXIS_NAMES = ("data", "fsdp", "expert", "pipe", "seq", "tensor")


@dataclass(frozen=True)
class MeshConfig:
    """Declarative mesh shape. Zero/negative → auto-fill from device count.

    The Train-layer `ScalingConfig` lowers its per-axis worker counts to one
    of these; users of the parallel layer can also build one directly.
    """

    data: int = -1  # -1: absorb remaining devices
    fsdp: int = 1
    expert: int = 1
    pipe: int = 1
    seq: int = 1
    tensor: int = 1

    def axis_sizes(self, n_devices: int) -> dict:
        sizes = {f.name: getattr(self, f.name) for f in fields(self)}
        fixed = math.prod(v for v in sizes.values() if v > 0)
        free = [k for k, v in sizes.items() if v <= 0]
        if not free:
            if fixed != n_devices:
                raise ValueError(
                    f"mesh {sizes} needs {fixed} devices, have {n_devices}"
                )
            return sizes
        if len(free) > 1:
            raise ValueError(f"at most one mesh axis may be auto (-1): {free}")
        if n_devices % fixed != 0:
            raise ValueError(
                f"cannot factor {n_devices} devices into mesh {sizes}"
            )
        sizes[free[0]] = n_devices // fixed
        return sizes

    def shape(self, n_devices: int) -> tuple:
        s = self.axis_sizes(n_devices)
        return tuple(s[a] for a in AXIS_NAMES)


def mesh_shape_for(n_devices: int, *, model_params: Optional[int] = None,
                   seq_len: Optional[int] = None) -> MeshConfig:
    """Heuristic mesh for a given device count and model/sequence size.

    Small models → pure data parallel. Models too big for one chip's HBM →
    fsdp. Very long sequences → carve a ``seq`` axis. This mirrors what the
    scaling-book recipe does by hand: pick the cheapest sharding that fits.
    """
    fsdp = 1
    seq = 1
    if model_params is not None:
        # ~18 bytes/param for bf16 params + f32 grads + adam moments.
        bytes_needed = model_params * 18
        per_chip_hbm = 14 * 2**30  # conservative v5e figure (16G - headroom)
        fsdp = max(1, 2 ** math.ceil(math.log2(max(1, bytes_needed // per_chip_hbm + 1))))
        fsdp = min(fsdp, n_devices)
        while n_devices % fsdp:
            fsdp *= 2
        fsdp = min(fsdp, n_devices)
    if seq_len is not None and seq_len >= 32768:
        seq = min(max(1, seq_len // 32768), max(1, n_devices // fsdp))
        while (n_devices // fsdp) % seq:
            seq -= 1
    return MeshConfig(data=-1, fsdp=fsdp, seq=seq)


def create_mesh(config: Optional[MeshConfig] = None,
                devices: Optional[Sequence] = None,
                axis_names: Sequence[str] = AXIS_NAMES):
    """Build a `jax.sharding.Mesh` with the canonical axis names.

    The device order comes from
    `jax.experimental.mesh_utils.create_device_mesh`, which matches mesh
    dims to the physical ICI torus on TPU hardware and reshapes in order
    on CPU/virtual devices. A shape it cannot lay out is an error.
    """
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    config = config or MeshConfig()
    shape = config.shape(len(devices))
    dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    return Mesh(dev_array, axis_names=tuple(axis_names))


def local_mesh(axis_names: Sequence[str] = AXIS_NAMES):
    """A 1×...×1 mesh over a single device — lets sharded code paths run
    unmodified on one chip (all collectives become no-ops)."""
    import jax

    return create_mesh(MeshConfig(data=1), devices=jax.devices()[:1],
                       axis_names=axis_names)
