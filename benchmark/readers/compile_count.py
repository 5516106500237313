"""Compilations that ended inside the measured stretch (window and
traced part), from `jax.monitoring`. Expected: 0."""


def read(ctx):
    run = ctx["run"]
    return float(len(ctx["compiles"].between(
        run["window"][0], run.get("trace_t1", run["window"][1]))))
