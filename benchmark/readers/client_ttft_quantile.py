"""A quantile of due time to first token over every request that was
due in the window, in milliseconds."""

from benchmark.harness import traffic


def read(ctx, q):
    t0, t1 = ctx["run"]["window"]
    v = traffic.quantile(
        traffic.first_token_waits(ctx["run"]["records"], t0, t1), q)
    return None if v is None else 1e3 * v
