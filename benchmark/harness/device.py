"""The device as JAX reports it, its published peaks, its memory, and
this process's compilations. Copied from `chip_smoke.py` (`CompileLog`,
`_memory`) and `ray_tpu/util/accelerators.py` (the peaks), so that the
yardstick cannot move with the program."""

from __future__ import annotations

import os
import time

from benchmark.harness.manifest import BENCH_DIR, load_json


def process_age_s():
    """Seconds since this process was started, from /proc (the clock
    ticks of `starttime` against the machine's uptime)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


class Phases:
    """Where set-up time goes: seconds from one mark to the next, the
    first from the start of the process."""

    def __init__(self):
        self.marks = [("", time.perf_counter() - process_age_s())]

    def mark(self, name):
        self.marks.append((name, time.perf_counter()))

    def __str__(self):
        return ", ".join(
            f"{name} {t - prev:.2f}" for (_, prev), (name, t)
            in zip(self.marks, self.marks[1:]))


def require_chips(n):
    """This process's TPU devices, exactly `n` of them in use; exits
    non-zero without a result line when JAX finds no accelerator or
    fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"needs a TPU; JAX found platform {devices[0].platform!r} "
            f"({devices[0].device_kind} x {len(devices)})")
    if len(devices) < n:
        raise SystemExit(f"the cell asks for {n} chips; JAX found "
                         f"{len(devices)}")
    return devices[:n]


def peaks(device_kind):
    """Published peaks of one chip, by `device_kind`. An unknown kind is
    an error, never a default."""
    table = load_json(BENCH_DIR, "peaks.json")["device_kinds"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device_kind {device_kind!r} in "
                         f"benchmark/peaks.json (has: {sorted(table)})")
    return table[device_kind]


def memory(device):
    """HBM of one device: `bytes_in_use` is live arrays, `bytes_reserved`
    what running programs take for their temporaries; the peaks are the
    process's. The CPU backend reports none."""
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in (
        "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
        "peak_bytes_reserved", "bytes_limit")}


def memory_peak_bytes(memories):
    """The peak on the fullest chip, from `memory()` of each: live
    arrays at their peak plus the largest reservation a running program
    made (PERF.md section 7 says why both)."""
    return max((m["peak_bytes_in_use"] or 0)
               + (m["peak_bytes_reserved"] or 0) for m in memories)


class CompileLog:
    """Every XLA compilation of this process, from jax's own monitoring
    events: (program, seconds, when) per backend compile (a load from
    the persistent cache counts, with its load time) and the cache's
    hits and misses."""

    def __init__(self):
        import jax.monitoring

        self.compiles = []
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append(
                (kw.get("fun_name", "?"), seconds, time.perf_counter()))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def between(self, t0, t1):
        """Compilations that ended in [t0, t1) of `time.perf_counter`."""
        return [(name, s) for name, s, t in self.compiles if t0 <= t < t1]
