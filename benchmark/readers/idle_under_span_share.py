"""Share of the device's idle time in the traced stretch that lies
under some of the program's spans, in percent: the idle gaps of the
first device (`trace.py`'s window less its busy intervals) cut with the
host plane's annotations named `spans`, leaving out what lies under the
spans named `outside` (so that a loop's spans can be counted apart from
the same names nested in another span). Only the stretch from the
first to the last annotation whose name begins with `family` counts: a
span that was open when the trace began or ended left no annotation,
and the idle time under it could be laid to nothing."""

from benchmark.harness import spans as sp
from benchmark.harness import trace


def share(events, spans, family, outside=()):
    """(percent, idle seconds that count); None where the trace has no
    span of the family (the program before it had any) or the device
    never idled."""
    extent = [(s, s + d) for evs in events["host"].values()
              for name, s, d in evs if name.startswith(family)]
    if not extent:
        return None
    lo, hi = min(s for s, _ in extent), max(e for _, e in extent)
    idle = [(max(s, lo), min(e, hi)) for s, e in sp.idle_intervals(events)
            if min(e, hi) > max(s, lo)]
    idle_ns = trace.total(idle)
    if not idle_ns:
        return None
    if outside:
        idle = trace.subtract(idle, sp.span_intervals(events, outside))
    under = sp.covered(idle, sp.span_intervals(events, spans))
    return 100.0 * under / idle_ns, idle_ns / 1e9


def read(ctx, spans, family, outside=()):
    found = ctx["trace"] and share(ctx["trace"], spans, family, outside)
    if not found:
        return None
    print(f"  device idle {found[1]:.3f} s between the first and the last "
          f"{family}* span; {found[0]:.1f} % under {' + '.join(spans)}")
    return found[0]
