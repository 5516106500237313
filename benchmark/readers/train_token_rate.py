"""Tokens trained on in the window over the window's length and the
chips: every step that ended in the window, timed to its loss fetch."""


def read(ctx):
    t0, t1 = ctx["run"]["window"]
    return ctx["run"]["tokens"] / (t1 - t0) / ctx["device"]["count"]
