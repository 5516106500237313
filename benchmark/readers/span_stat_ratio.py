"""A ratio of two attributes summed over the annotations of one of the
program's spans in the traced stretch, in percent: sum of `num` over
sum of `den` (or its complement to 100). The attributes are the stats
of the annotation's event in the trace's host plane."""

from benchmark.harness import spans as sp


def read(ctx, span, num, den, complement=False):
    if ctx["trace"] is None:
        return None
    seen = sp.annotations(sp.xplane_path(ctx), [span])
    top = sum(stats.get(num, 0) for *_, stats in seen)
    bottom = sum(stats.get(den, 0) for *_, stats in seen)
    if not bottom:
        return None
    share = 100.0 * top / bottom
    print(f"  {span}: {len(seen)} annotations, {num} {top} of {den} {bottom}")
    return 100.0 - share if complement else share
