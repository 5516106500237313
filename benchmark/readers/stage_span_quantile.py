"""A quantile, in milliseconds, of one of the program's stage spans
(`critical_path.record_stage`, host clock) over the spans of that stage
recorded in the window."""

from benchmark.harness import traffic


def read(ctx, stage, q):
    spans = ctx["run"].get("stages", {}).get(stage)
    if not spans:
        return None
    return 1e3 * traffic.quantile(spans, q)
