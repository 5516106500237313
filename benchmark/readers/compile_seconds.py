"""Seconds of XLA compilation (or loading from the persistent cache)
before the window opened, summed over compile threads, from
`jax.monitoring`."""


def read(ctx):
    return sum(s for _, s in ctx["compiles"].between(
        float("-inf"), ctx["run"]["window"][0]))
