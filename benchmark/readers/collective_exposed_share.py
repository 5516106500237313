"""Collective time during which nothing else ran on the device, over
the device time of the programs traced, in percent (mean over chips)."""

from benchmark.harness import trace


def read(ctx):
    if ctx["trace"] is None:
        return None
    step_s = sum(sum(v) for v in trace.program_runs(ctx["trace"]).values())
    if not step_s:
        return None
    return 100.0 * trace.exposed_collective_seconds(ctx["trace"]) / step_s
