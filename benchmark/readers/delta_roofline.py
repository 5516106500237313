"""Share of its roofline that the delta rule reached in the traced
stretch, in percent: the least time the chip could take (the larger of
operations over peak FLOP/s and bytes over peak bytes/s, counted by
`benchmark/flops/<flops>.py` from shapes and token counts alone) over
the device time of the ops under `scope` inside the runs of `program`
on the first device.

`work` says what is counted. "scan": the prefills' chunked scans. A
prefill's bucket is padded and the device cannot tell; the engine's
span `engine.prefill_dispatch` carries the prompt's real tokens, and
prefills run in the order they were dispatched, so each annotation is
matched with the first run of `program` that starts after it and was
not matched before. A run dispatched before the trace began, or a
dispatch whose run the trace did not see, is left out of both sides.
The work is `delta_scan_ops_and_bytes` of the real tokens, a row a
call. "update": the decode steps' recurrence, `delta_update_bytes` of
the cell's slots a step, the steps a run taken from the engine's span
`engine.consume_block` (`slot_steps` over the slots). Prints which
bound holds. A program without the scope, as the parent's, gives None.
"""

import bisect

from benchmark.harness import spans as sp
from benchmark.harness import trace
from benchmark.harness.manifest import plugin


def scope_seconds_by_run(events, names, program, scope):
    """[(start ns, seconds under `scope`)] of the runs of `program` on
    the first device, in the order they ran."""
    for dev in trace._first(events):
        mods = sorted((m for m in dev["modules"]
                       if trace.program_name(m[0]) == program),
                      key=lambda m: m[1])
        starts = [m[1] for m in mods]
        seconds = [0.0] * len(mods)
        for name, start, self_ns, _ in trace._self_times(dev["ops"]):
            i = bisect.bisect_right(starts, start) - 1
            if i < 0 or start >= mods[i][1] + mods[i][2]:
                continue
            path = names.get((sp.program_id(mods[i][0]), name)) or ""
            if scope in sp.scope_tokens(path):
                seconds[i] += self_ns / 1e9
        return list(zip(starts, seconds))
    return []


def match(dispatched, runs):
    """[(real tokens, seconds)]: each of `dispatched` [(start ns, real
    tokens)] with the first of `runs` [(start ns, seconds)] that starts
    after it and is not taken, both in order."""
    out, j = [], 0
    for start, real in sorted(dispatched):
        while j < len(runs) and runs[j][0] < start:
            j += 1
        if j == len(runs):
            break
        out.append((real, runs[j][1]))
        j += 1
    return out


def read(ctx, program, scope, flops, work):
    if ctx["trace"] is None:
        return None
    path = sp.xplane_path(ctx)
    runs = scope_seconds_by_run(ctx["trace"], sp.op_names(path), program,
                                scope)
    if not any(seconds for _, seconds in runs):
        return None
    config = ctx["cell"].config
    counts = plugin("flops", flops)
    if work == "scan":
        seen = match([(start, stats.get("real", 0)) for _, start, _, stats
                      in sp.annotations(path, ["engine.prefill_dispatch"])],
                     runs)
        spent = sum(seconds for _, seconds in seen)
        tokens = sum(real for real, _ in seen)
        ops, nbytes = counts.delta_scan_ops_and_bytes(config, tokens,
                                                      len(seen))
        said = f"{len(seen)} prefills of {tokens} real tokens"
    else:
        slots = config["serve"]["max_batch_size"]
        blocks = [stats["slot_steps"] for *_, stats in sp.annotations(
            path, ["engine.consume_block"]) if stats.get("slot_steps")]
        if not blocks:
            return None
        steps = len(runs) * max(blocks) // slots
        spent = sum(seconds for _, seconds in runs)
        ops, nbytes = 0, steps * counts.delta_update_bytes(config, slots)
        said = f"{steps} decode steps of {slots} slots"
    if not spent:
        return None
    t, bound = counts.least_seconds(ops, nbytes, ctx["device"]["peaks"])
    print(f"  {scope}: {said}, {1e3 * spent:.1f} ms on the device, least "
          f"{1e3 * t:.2f} ms ({bound}-bound)")
    return 100.0 * t / spent
