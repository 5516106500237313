"""A quantile of every gap between streamed tokens that the client saw
wholly inside the window, in milliseconds."""

from benchmark.harness import traffic


def read(ctx, q):
    t0, t1 = ctx["run"]["window"]
    v = traffic.quantile(
        traffic.gaps_in_window(ctx["run"]["records"], t0, t1), q)
    return None if v is None else 1e3 * v
