"""A quotient of two attributes summed over the annotations of one of
the program's spans in the traced stretch: sum of `num` over sum of
`den`, as it is (`span_stat_ratio` gives a share in percent). The
attributes are the stats of the annotation's event in the trace's host
plane. A program whose span lacks them, as the parent's, gives None."""

from benchmark.harness import spans as sp


def read(ctx, span, num, den):
    if ctx["trace"] is None:
        return None
    seen = sp.annotations(sp.xplane_path(ctx), [span])
    top = sum(stats.get(num, 0) for *_, stats in seen)
    bottom = sum(stats.get(den, 0) for *_, stats in seen)
    if not bottom:
        return None
    print(f"  {span}: {len(seen)} annotations, {num} {top} over {den} {bottom}")
    return top / bottom
