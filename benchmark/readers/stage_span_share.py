"""Share of the (untraced) window that one of the program's spans held,
in percent: the summed durations of the spans of that name recorded in
the window (`critical_path`, read from the flight recorder's ring with
the profiler off) over the window's length. For a span of one thread
that does not overlap itself, such as the engine loop's."""


def read(ctx, stage):
    spans = ctx["run"].get("stages", {}).get(stage)
    if not spans:
        return None
    t0, t1 = ctx["run"]["window"]
    return 100.0 * sum(spans) / (t1 - t0)
