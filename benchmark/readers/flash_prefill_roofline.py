"""Share of its roofline that the flash kernel reached in the served
prefills of the traced stretch, in percent: the least time the chip
could take for the calls the trace shows inside the runs of `program`
on the first device (the larger of operations over peak FLOP/s and
bytes over peak bytes/s; `flash_prefill_ops_and_bytes` of
`benchmark/flops/<flops>.py` counts a call from the rows it has and
from whether its rows see a window of keys or every key before them),
over their device time. A serving run's calls differ in shape, a
prefill bucket each, and the cell cannot say which ran: a call's rows
are read from the trace, where the TPU names an op event by its whole
HLO line and the kernel's result is [batch, heads, rows, head size];
a call is windowed if its scope path holds `scope`. Prints the calls
by shape. A program without the kernel, as the parent's, gives None."""

import bisect
import re

from benchmark.harness import spans as sp
from benchmark.harness import trace
from benchmark.harness.manifest import plugin

_RESULT = re.compile(r" = \(?\w+\[(\d+),(\d+),(\d+),(\d+)\]")


def kernel_calls(path, kernel):
    """[(instruction name, start ns, ns, (batch, heads, rows, head
    size))]: the first device plane's ops of class `kernel` whose HLO
    line gives a result of four dimensions."""
    from jax.profiler import ProfileData

    planes = [p for p in ProfileData.from_file(path).planes
              if p.name.startswith("/device:TPU:")]
    out = []
    for plane in sorted(planes, key=lambda p: p.name)[:1]:
        for line in plane.lines:
            if trace._LINES.get(line.name) != "ops":
                continue
            for e in line.events:
                name = trace.instruction_name(e.name)
                shape = _RESULT.search(e.name)
                if trace.op_class(name) == kernel and shape:
                    out.append((name, int(e.start_ns), int(e.duration_ns),
                                tuple(int(n) for n in shape.groups())))
    return out


def calls_seen(events, names, calls, program, scope):
    """{(batch, rows, windowed): [calls, seconds]} of `calls` inside the
    runs of `program` on the first device."""
    seen = {}
    for dev in trace._first(events):
        mods = sorted((m for m in dev["modules"]
                       if trace.program_name(m[0]) == program),
                      key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for name, start, ns, (batch, _, rows, _) in calls:
            i = bisect.bisect_right(starts, start) - 1
            if i < 0 or start >= mods[i][1] + mods[i][2]:
                continue
            path = names.get((sp.program_id(mods[i][0]), name)) or ""
            entry = seen.setdefault(
                (batch, rows, scope in sp.scope_tokens(path)), [0, 0.0])
            entry[0] += 1
            entry[1] += ns / 1e9
    return seen


def read(ctx, program, kernel, scope, flops):
    if ctx["trace"] is None:
        return None
    path = sp.xplane_path(ctx)
    seen = calls_seen(ctx["trace"], sp.op_names(path),
                      kernel_calls(path, kernel), program, scope)
    counts = plugin("flops", flops)
    least = spent = 0.0
    for (batch, rows, windowed), (calls, seconds) in sorted(seen.items()):
        ops, nbytes = counts.flash_prefill_ops_and_bytes(
            ctx["cell"].config, batch, rows, windowed)
        t, bound = counts.least_seconds(ops, nbytes, ctx["device"]["peaks"])
        print(f"  {kernel}, {rows} rows, "
              f"{'a window' if windowed else 'every key before'}: {calls} "
              f"calls, {1e3 * seconds / calls:.3f} ms a call, least "
              f"{1e3 * t:.3f} ms ({bound}-bound)")
        least += t * calls
        spent += seconds
    return 100.0 * least / spent if spent else None
