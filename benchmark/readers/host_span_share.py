"""Share of the traced stretch that the host spent inside one of the
program's spans, in percent: the annotations of that name in the
trace's host plane (merged, so a name on two threads counts once) over
the stretch's length."""

from benchmark.harness import spans as sp
from benchmark.harness import trace


def read(ctx, span):
    if ctx["trace"] is None:
        return None
    held = sp.span_intervals(ctx["trace"], [span])
    if not held:
        return None
    stretch = ctx["run"]["trace_t1"] - ctx["run"]["trace_t0"]
    return 100.0 * trace.total(held) / 1e9 / stretch
