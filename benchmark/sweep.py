"""Find an open-loop cell's knee once, on the chip: the highest rate the
system sustains without a growing backlog.

    python3 benchmark/sweep.py --workload <name> --rates 2,4,6,8 \
        [--traffic <mix>] [--seconds 20] [--seed 1]

One deployment (the cell's), one phase per rate, each the cell's own
mix, or the one `--traffic` names (a mix no cell has yet), with only
its gaps rescaled to the rate. For each rate it prints what was offered
and completed, the tails, and how the number of requests in flight
changed from the first half of the phase to the second: below the knee
it stays level, above it it grows all through the phase. The builder
writes four fifths of the knee into the mix's file; the benchmark
itself never searches.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import device as hw  # noqa: E402
from benchmark.harness import traffic  # noqa: E402
from benchmark.harness.manifest import (BENCH_DIR, Cell,  # noqa: E402
                                        load_json)
from benchmark.runners import serve  # noqa: E402


def in_flight(records, t):
    return sum(1 for r in records if r["sent"] is not None
               and r["sent"] <= t and (r["t_end"] or t + 1) > t)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--traffic")
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    cell = Cell(args.workload)
    if args.traffic:
        cell.traffic = load_json(BENCH_DIR, "traffic", args.traffic + ".json")
    hw.require_chips(cell.chips)

    from ray_tpu._private.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    run_dir = os.path.join(ROOT, ".bench_runs", cell.name + ".sweep")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    dep = serve.Deployment(cell, args.seed)
    try:
        for rate in map(float, args.rates.split(",")):
            phase = copy.copy(cell)
            scale = traffic.rate_rps(cell.traffic) / rate
            phase.traffic = {**cell.traffic, "gaps_s": [
                g * scale for g in traffic.arrival_gaps(cell.traffic)]}
            out = serve.measure(dep, phase, seed=args.seed,
                                seconds=args.seconds, trace_dir=None,
                                run_dir=run_dir)
            t0, t1 = out["window"]
            rec = out["records"]
            mid = (t0 + t1) / 2
            waits = traffic.first_token_waits(rec, t0, t1)
            halves = [
                sum(in_flight(rec, t0 + (t1 - t0) * k / 20)
                    for k in ks) / 10
                for ks in (range(0, 10), range(10, 20))]
            print(json.dumps({
                "rate_rps": rate,
                "due": len(waits),
                "tokens_per_s": traffic.tokens_in_window(rec, t0, t1)
                / (t1 - t0),
                "ttft_p50_ms": 1e3 * traffic.quantile(waits, 0.5),
                "ttft_p95_ms": 1e3 * traffic.quantile(waits, 0.95),
                "ttft_p95_first_half_ms": 1e3 * traffic.quantile(
                    traffic.first_token_waits(rec, t0, mid), 0.95),
                "ttft_p95_second_half_ms": 1e3 * traffic.quantile(
                    traffic.first_token_waits(rec, mid, t1), 0.95),
                "tpot_p50_ms": 1e3 * traffic.quantile(
                    traffic.gaps_in_window(rec, t0, t1), 0.5),
                "in_flight_mean_halves": halves,
                "queued_max": max(q for _, q in out["engine_samples"]),
            }), flush=True)
            while True:  # let what was cut off leave the engine
                s = dep.stats()
                if s["active_slots"] == 0 and s["queued"] == 0:
                    break
                time.sleep(0.2)
    finally:
        dep.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
