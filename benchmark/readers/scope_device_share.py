"""Share of a program's device time spent in the ops of some scopes,
in percent: the self time of the ops, inside the runs of `program` on
the first device, whose scope path (`jax.named_scope`, and autodiff's
`jvp(` / `transpose(`) holds one of the names in `any_of` and none in
`none_of`, over the device time of those runs. Prints the whole split
the first time a program is read."""

from benchmark.harness import spans as sp

_printed = set()


def split(per_path):
    """{class: seconds}: `optimizer`, then `backward` (`transpose(`),
    then `loss`, the rest `forward`; `unnamed` where the trace gave the
    op no path."""
    out = {}
    for path, seconds in per_path.items():
        tokens = sp.scope_tokens(path) if path else set()
        cls = "unnamed" if not path else \
            "optimizer" if "optimizer" in tokens else \
            "backward" if "transpose" in tokens else \
            "loss" if "loss" in tokens else "forward"
        out[cls] = out.get(cls, 0.0) + seconds
    return out


def share(per_path, runs_s, any_of, none_of=()):
    """Percent of `runs_s` in the paths that hold a name of `any_of` and
    none of `none_of`; None if no path does."""
    any_of, none_of = set(any_of), set(none_of)
    hit = [s for path, s in per_path.items() if path
           and sp.scope_tokens(path) & any_of
           and not sp.scope_tokens(path) & none_of]
    if not hit or not runs_s:
        return None
    return 100.0 * sum(hit) / runs_s


def read(ctx, program, any_of, none_of=()):
    if ctx["trace"] is None:
        return None
    per_path, runs_s = sp.scope_seconds(
        ctx["trace"], sp.op_names(sp.xplane_path(ctx)), program)
    if program not in _printed and runs_s:
        _printed.add(program)
        parts = ", ".join(f"{k} {100 * v / runs_s:.1f} %"
                          for k, v in sorted(split(per_path).items()))
        print(f"  {program}: {runs_s:.3f} s on the device; by scope: {parts}")
    return share(per_path, runs_s, any_of, none_of)
