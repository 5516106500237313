"""Process start to window open, in seconds: loading, weights, warm-up,
compilation or loading from the compile cache, the reference check and
(serving) the ramp to steady state."""


def read(ctx):
    return ctx["setup_s"]
