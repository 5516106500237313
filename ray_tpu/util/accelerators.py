"""Accelerator type constants — TPU-first.

Reference: `python/ray/util/accelerators/accelerators.py` (NVIDIA-only in
the snapshot). Here TPU generations are first-class, with chip/HBM specs
the scheduler and mesh heuristics can consult; NVIDIA constants retained
for API compatibility.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

# TPU generations (per-chip figures; bf16 peak)
TPU_V4 = "TPU-V4"
TPU_V5E = "TPU-V5E"
TPU_V5P = "TPU-V5P"
TPU_V6E = "TPU-V6E"

# Reference-compat GPU constants
NVIDIA_TESLA_V100 = "V100"
NVIDIA_TESLA_P100 = "P100"
NVIDIA_TESLA_T4 = "T4"
NVIDIA_TESLA_A100 = "A100"
NVIDIA_A100_40G = "A100-40G"
NVIDIA_A100_80G = "A100-80G"
NVIDIA_H100 = "H100"


@dataclass(frozen=True)
class TPUChipSpec:
    name: str
    hbm_bytes: int
    peak_bf16_flops: float
    ici_bandwidth_gbps: float  # per link, one direction


TPU_SPECS: Dict[str, TPUChipSpec] = {
    TPU_V4: TPUChipSpec(TPU_V4, 32 * 2**30, 275e12, 50),
    TPU_V5E: TPUChipSpec(TPU_V5E, 16 * 2**30, 197e12, 50),
    TPU_V5P: TPUChipSpec(TPU_V5P, 95 * 2**30, 459e12, 100),
    TPU_V6E: TPUChipSpec(TPU_V6E, 32 * 2**30, 918e12, 100),
}


# `jax.Device.device_kind` → generation, as jax itself spells the kinds
# (`jax/_src/pallas/mosaic/tpu_info.py`).
_DEVICE_KINDS: Dict[str, str] = {
    "TPU v4": TPU_V4,
    "TPU v5 lite": TPU_V5E,
    "TPU v5e": TPU_V5E,
    "TPU v5": TPU_V5P,
    "TPU v5p": TPU_V5P,
    "TPU v6 lite": TPU_V6E,
    "TPU v6e": TPU_V6E,
}


def detect_tpu_type() -> str:
    """Generation of this process's first JAX device, from its
    ``device_kind``. A kind that is not in the table is an error, never a
    default: a peak taken from the wrong row makes every utilization
    computed against it wrong."""
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in _DEVICE_KINDS:
        raise ValueError(
            f"no TPU chip spec for device_kind {kind!r} (known: "
            f"{sorted(_DEVICE_KINDS)})")
    return _DEVICE_KINDS[kind]


def chip_spec(name: str = None) -> TPUChipSpec:
    return TPU_SPECS[name or detect_tpu_type()]


def require_tpu():
    """This process's JAX devices, or SystemExit naming the platform
    found: a script that measures the chip fails without one instead of
    timing the CPU under a device metric's name."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"needs a TPU; JAX found platform {devices[0].platform!r} "
            f"({devices[0].device_kind} x {len(devices)})")
    return devices
