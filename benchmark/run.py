"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

A new process: it opens the cell's chips (no chip: it fails, there is no
CPU branch), turns on the persistent compile cache at its in-checkout
path, hands the cell to its runner (`runners/<kind>.py`), which sets
up, checks the program against the plain reference and measures for
`--seconds`, reduces what came back to the cell's metrics through their
readers (`readers/<reader>.py`, named by `metrics/<metric>.json`), and
prints one JSON object as its last line: the end-to-end metrics with
`--trace 0`, the per-layer metrics with `--trace 1`. Earlier lines say
what else was seen; `report.json` in the run's directory
(`.bench_runs/<workload>/`, emptied when the cell runs again) keeps it,
beside the profiler's trace of a traced run
(`python3 benchmark/harness/trace.py <dir>` describes one).
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import device as hw  # noqa: E402
from benchmark.harness import trace as tr  # noqa: E402
from benchmark.harness.manifest import Cell, plugin  # noqa: E402


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def read_metrics(cell, group, ctx):
    out = {}
    for m in cell.metrics[group]:
        value = plugin("readers", m["reader"]).read(ctx, **m["args"])
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None):
    args = parse(argv)
    cell = Cell(args.workload)
    devices = hw.require_chips(cell.chips)
    faulthandler.dump_traceback_later(1150, exit=True)  # never hang a chip

    import jax

    from ray_tpu._private.compile_cache import enable_persistent_cache

    cache = enable_persistent_cache()
    compiles = hw.CompileLog()
    run_dir = os.path.join(ROOT, ".bench_runs", cell.name)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    trace_dir = os.path.join(run_dir, "trace") if args.trace else None
    kind = devices[0].device_kind
    print(f"{cell.name}: {len(devices)} of {len(jax.devices())} x {kind}, "
          f"seed {args.seed}, {args.seconds} s, trace {args.trace}, compile "
          f"cache {cache}", flush=True)

    run = cell.runner().run(
        cell, seed=args.seed, seconds=args.seconds, trace_dir=trace_dir,
        devices=devices, run_dir=run_dir)
    print(run["log"], flush=True)
    t0, t1 = run["window"]
    ctx = {
        "cell": cell, "run": run, "compiles": compiles, "trace": None,
        # The window opened `now - t0` ago, and the process is this old.
        "setup_s": hw.process_age_s() - (time.perf_counter() - t0),
        "device": {"kind": kind, "count": len(devices),
                   "peaks": hw.peaks(kind)},
    }
    device = {
        "platform": devices[0].platform, "kind": kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": hw.memory_peak_bytes(run["memory"]),
    }
    result = {"correct": all(run["checks"].values()),
              "attempted": run["attempted"], "failed": run["failed"]}
    if args.trace:
        events = tr.load_xplane(tr.find_xplane(trace_dir))
        ctx["trace"] = events
        device["busy_s"] = tr.busy_seconds(events)
        device["window_s"] = run["trace_t1"] - run["trace_t0"]
        result["metrics"] = read_metrics(cell, "per_layer", ctx)
        result["breakdown"] = tr.breakdown(events)
    else:
        result["metrics"] = read_metrics(cell, "end_to_end", ctx)
    result["device"] = device

    inside = compiles.between(t0, run.get("trace_t1", t1))
    print(f"checks {json.dumps(run['checks'])}; set-up "
          f"{ctx['setup_s']:.2f} s; {len(compiles.compiles)} compilations, "
          f"{len(inside)} in the window {inside}; compile cache hits "
          f"{compiles.cache['hits']} misses {compiles.cache['misses']}; "
          f"memory {json.dumps(run['memory'])}", flush=True)
    with open(os.path.join(run_dir, "report.json"), "w") as f:
        json.dump({"args": vars(args), "result": result,
                   "run": {k: v for k, v in run.items() if k != "records"}},
                  f, default=str)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
