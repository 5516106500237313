"""Tokens that reached the client in the window over its length."""

from benchmark.harness import traffic


def read(ctx):
    t0, t1 = ctx["run"]["window"]
    return traffic.tokens_in_window(ctx["run"]["records"], t0, t1) / (t1 - t0)
