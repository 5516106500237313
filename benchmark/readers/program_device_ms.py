"""Median device time of one run of a program (an `XLA Modules` event of
the trace), in milliseconds."""

import statistics

from benchmark.harness import trace


def read(ctx, program):
    if ctx["trace"] is None:
        return None
    runs = trace.program_runs(ctx["trace"]).get(program)
    return 1e3 * statistics.median(runs) if runs else None
