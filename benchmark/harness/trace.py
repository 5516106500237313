"""From the profiler's trace to numbers: the one place that decides
what "busy", "idle", "a program's time", "a kernel's time" and "exposed
collective time" mean.

`load_xplane` flattens the `.xplane.pb` the JAX profiler writes into a
plain dict (`events`): per device plane its op and module events, and
the host threads' events. Everything else works on that dict, so the
tests check the reduction on a small recorded one
(`tests/benchmark/data/`). Times are nanoseconds on the profiler's
clock until a function's name says seconds.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

_LINES = {"XLA Ops": "ops", "XLA Modules": "modules"}
_COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|async-collective|send|recv)")


def find_xplane(trace_dir):
    paths = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load_xplane(path):
    """The trace as a dict: {"devices": {plane: {"ops": [[name, start,
    dur], ...], "modules": [...]}}, "host": {thread: [[name, start,
    dur], ...]}}. `ops` is what the core executed, one after the other:
    an asynchronous operation shows there as a `-start` of almost no
    length and a `-done` as long as the core waited for it (the `Async
    XLA Ops` line, which only the first chip's plane has, is not read).
    The TPU names an op event by its whole HLO line: only the
    instruction's name is kept."""
    from jax.profiler import ProfileData

    events = {"devices": {}, "host": {}}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            dev = events["devices"].setdefault(
                plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = _LINES.get(line.name)
                if key:
                    dev[key] = [[instruction_name(e.name), int(e.start_ns),
                                 int(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for i, line in enumerate(plane.lines):
                evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                       for e in line.events if e.duration_ns > 0]
                if evs:  # python threads' lines come without a name
                    events["host"][f"{line.name}#{i}"] = evs
    return events


def describe_xplane(path, per_line=12):
    """What is in a trace, for reading one by hand: planes, lines, how
    many events, and the first few names with their stats."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name}: {len(evs)} events")
            for e in evs[:per_line]:
                stats = {k: (v if not isinstance(v, (bytes, str))
                             else str(v)[:80]) for k, v in e.stats}
                out.append(f"    {e.name[:100]} start={e.start_ns:.0f} "
                           f"dur={e.duration_ns:.0f} {stats}")
    return "\n".join(out)


# -- interval arithmetic ----------------------------------------------------


def merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(intervals, cover):
    """The parts of merged `intervals` that merged `cover` leaves bare."""
    out = []
    starts = [c[0] for c in cover]
    for s, e in intervals:
        i = max(0, bisect.bisect_right(starts, s) - 1)
        cur = s
        while i < len(cover) and cover[i][0] < e:
            cs, ce = cover[i]
            if ce > cur:
                if cs > cur:
                    out.append((cur, min(cs, e)))
                cur = max(cur, ce)
            i += 1
        if cur < e:
            out.append((cur, e))
    return out


def _spans(evs):
    return [(s, s + d) for _, s, d in evs]


# -- names ------------------------------------------------------------------


def program_name(module_event_name):
    """`jit__decode_impl(6425...)` -> `_decode_impl`."""
    name = module_event_name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def instruction_name(event_name):
    """`%fusion.150 = bf16[1024,14336]{...} fusion(...)` -> `fusion.150`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_class(op_name):
    """`fusion.123` / `multiply_reduce_fusion.4` -> the name without the
    compiler's numbering."""
    return re.sub(r"[.\d]+$", "", op_name) or op_name


def is_collective(op_name):
    return bool(_COLLECTIVE.match(op_name))


# -- the reduction ----------------------------------------------------------


def window(events):
    """(first start, last end) over all device events."""
    los, his = [], []
    for dev in events["devices"].values():
        for key in ("ops", "modules"):
            if dev.get(key):
                los.append(min(s for _, s, _ in dev[key]))
                his.append(max(s + d for _, s, d in dev[key]))
    if not los:
        return None
    return min(los), max(his)


def busy_intervals(dev):
    """Where an operation ran on the device. Module events stand in for
    a plane that recorded no op line."""
    return merge(_spans(dev["ops"] or dev["modules"]))


def busy_seconds(events):
    """Seconds in which an operation ran on the device, averaged over
    the device planes."""
    per_dev = [total(busy_intervals(dev)) / 1e9
               for dev in events["devices"].values()]
    return sum(per_dev) / len(per_dev) if per_dev else 0.0


def program_runs(events):
    """{program: [seconds of each run]} on the first device plane (every
    chip runs the same programs)."""
    runs = {}
    for dev in _first(events):
        for name, _, dur in dev["modules"]:
            runs.setdefault(program_name(name), []).append(dur / 1e9)
    return runs


def _first(events):
    planes = sorted(events["devices"])
    return [events["devices"][planes[0]]] if planes else []


def _program_lookup(dev):
    mods = sorted(dev["modules"], key=lambda m: m[1])
    starts = [m[1] for m in mods]

    def lookup(t):
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < mods[i][1] + mods[i][2]:
            return program_name(mods[i][0])
        return "?"
    return lookup


def op_seconds(events):
    """{"program/op": seconds} on the first device plane: each op's
    device time under the program it ran in, numbering stripped. Ops
    that enclose others (a `while`, a `call`) are left out by keeping,
    at every instant, only the innermost op."""
    out = {}
    for dev in _first(events):
        lookup = _program_lookup(dev)
        for name, start, self_ns, _ in _self_times(dev["ops"]):
            key = f"{lookup(start)}/{op_class(name)}"
            out[key] = out.get(key, 0.0) + self_ns / 1e9
    return out


def _self_times(ops):
    """(name, start, self ns, encloses) per op: its duration minus what
    ops that start inside it cover, and whether it wholly encloses
    another op (a `while`, a `call`)."""
    evs = sorted(ops, key=lambda e: (e[1], -e[2]))
    out, stack = [], []  # stack of [name, start, end, child_ns, encloses]

    def pop():
        n, st, en, child, encloses = stack.pop()
        out.append((n, st, max(0, en - st - child), encloses))

    for name, s, d in evs:
        while stack and stack[-1][2] <= s:
            pop()
        if stack:
            stack[-1][3] += min(d, stack[-1][2] - s)
            stack[-1][4] = stack[-1][4] or s + d <= stack[-1][2]
        stack.append([name, s, s + d, 0, False])
    while stack:
        pop()
    return out


def _leaf_spans(ops, collective):
    """Merged intervals of the ops that enclose no other op and are
    (or are not) collectives."""
    enclosing = {(n, s) for n, s, _, enc in _self_times(ops) if enc}
    return merge((s, s + d) for n, s, d in ops
                 if d > 0 and (n, s) not in enclosing
                 and is_collective(n) == collective)


def kernel_seconds(events, kernels):
    """{kernel: (seconds, calls)} on the first device plane for ops
    whose class is one of `kernels`."""
    out = {k: [0.0, 0] for k in kernels}
    for dev in _first(events):
        for name, _, dur in dev["ops"]:
            k = op_class(name)
            if k in out:
                out[k][0] += dur / 1e9
                out[k][1] += 1
    return {k: tuple(v) for k, v in out.items()}


def exposed_collective_seconds(events):
    """Seconds, averaged over the device planes, that the core spent in
    a collective (one it runs itself, or the wait in the `-done` of an
    asynchronous one) while it executed nothing else."""
    per_dev = []
    for dev in events["devices"].values():
        coll = _leaf_spans(dev["ops"], True)
        comp = _leaf_spans(dev["ops"], False)
        per_dev.append(total(subtract(coll, comp)) / 1e9)
    return sum(per_dev) / len(per_dev) if per_dev else 0.0


def idle_gaps(events, top=10, longest=400):
    """[[what the host was doing, seconds], ...]: the device's idle gaps
    inside the traced window, each charged to the host event that
    matches it best (largest overlap over union), summed by name."""
    devs = _first(events)
    win = window(events)
    if not devs or win is None:
        return []
    gaps = subtract([win], busy_intervals(devs[0]))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:longest]
    host = sorted((s, s + d, name) for evs in events["host"].values()
                  for name, s, d in evs)
    starts = [h[0] for h in host]
    longest_host = max((h[1] - h[0] for h in host), default=0)
    by_name = {}
    for gs, ge in gaps:
        best, best_score = "unattributed", 0.0
        lo = bisect.bisect_left(starts, gs - longest_host)
        hi = bisect.bisect_right(starts, ge)
        for hs, he, name in host[lo:hi]:
            inter = min(ge, he) - max(gs, hs)
            if inter <= 0:
                continue
            score = inter / (max(ge, he) - min(gs, hs))
            if score > best_score:
                best, best_score = name, score
        by_name[best] = by_name.get(best, 0.0) + (ge - gs) / 1e9
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return [[_clean(n), s] for n, s in ranked]


def _clean(name):
    return re.sub(r"[^A-Za-z0-9_.:<>-]+", "_", name.lstrip("$"))[:80]


def breakdown(events, top=10):
    ops = sorted(op_seconds(events).items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": idle_gaps(events, top)}


if __name__ == "__main__":
    import sys

    target = sys.argv[1]
    print(describe_xplane(target if target.endswith(".pb")
                          else find_xplane(target)))
