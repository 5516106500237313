"""What a traced run holds of the program's own names, beside the
reduction in `trace.py`: the program's spans (`critical_path.span`
enters a `jax.profiler.TraceAnnotation` of the same name and
attributes, so they lie in the trace's host plane on the device
events' time line) and the scope path of every device op
(`jax.named_scope`; autodiff adds `jvp(...)` / `transpose(jvp(...))`).

`trace.load_xplane` keeps an op's instruction name and a host event's
name, start and duration. The rest is read here from the same
`.xplane.pb`: an annotation's attributes are the stats of its event
(`annotations`), and an op's scope path is the `tf_op` stat of its
event's *metadata*, which `jax.profiler.ProfileData` does not show, so
the metadata tables are decoded from the protobuf's wire format
(`op_names`; the TPU's op event carries the HLO line without its
`metadata={op_name=...}`). A program that has no such span or scope
(the parent of the PR that added one) gives empty results: the readers
return None and the metric is left out.
"""

from __future__ import annotations

import bisect
import functools
import os
import re

from benchmark.harness import trace
from benchmark.harness.manifest import ROOT


def xplane_path(ctx):
    """The `.xplane.pb` of the traced run `run.py` is reducing."""
    return trace.find_xplane(
        os.path.join(ROOT, ".bench_runs", ctx["cell"].name, "trace"))


# -- the program's spans, from the host plane ---------------------------------


def span_intervals(events, names):
    """Merged (start, end) nanosecond intervals of the host events whose
    name is one of `names`, over every host thread."""
    names = set(names)
    return trace.merge(
        (s, s + d) for evs in events["host"].values()
        for name, s, d in evs if name in names)


def covered(intervals, cover):
    """Nanoseconds of merged `intervals` that merged `cover` overlaps."""
    return trace.total(intervals) - trace.total(
        trace.subtract(intervals, cover))


def idle_intervals(events):
    """The first device's idle gaps inside the traced window."""
    devs = trace._first(events)
    win = trace.window(events)
    if not devs or win is None:
        return []
    return trace.subtract([win], trace.busy_intervals(devs[0]))


def annotations(path, names):
    """[(name, start ns, duration ns, {attribute: value}), ...] of the
    host plane's events named one of `names`."""
    from jax.profiler import ProfileData

    names = set(names)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    out.append((e.name, int(e.start_ns), int(e.duration_ns),
                                dict(e.stats)))
    return out


# -- scope paths of device ops, from the event metadata -----------------------


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    bytes for length-delimited and fixed fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = buf[i:i + 8]
            i += 8
        elif wire == 5:
            value = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, value


def _map_value(entry):
    return next((v for f, v in _fields(entry) if f == 2), b"")


@functools.lru_cache(maxsize=2)  # two metrics of a cell read one trace
def op_names(path):
    """{(program id, instruction name): scope path} for the ops of the
    first TPU plane: XSpace.planes(1) -> XPlane{name(2), event_metadata
    (4), stat_metadata(5)}; XEventMetadata{name(2), display_name(4),
    stats(5)}; XStat{metadata_id(1), uint64(3), str(5), ref(7)}. The
    scope path is the stat `tf_op` (`jit(step_fn)/transpose(jvp(mlp))/
    dot_general:`), the program the stat `program_id`, which is also the
    number in the module event's name."""
    with open(path, "rb") as f:
        space = f.read()
    planes = []
    for field, plane in _fields(space):
        if field != 1:
            continue
        name = next((v for f, v in _fields(plane) if f == 2), b"").decode()
        if name.startswith("/device:TPU:"):
            planes.append((name, plane))
    if not planes:
        return {}
    plane = min(planes)[1]
    stat_names, metadata = {}, []
    for field, value in _fields(plane):
        if field == 5:
            msg = dict(_fields(_map_value(value)))
            stat_names[msg.get(1, 0)] = msg.get(2, b"").decode()
        elif field == 4:
            metadata.append(_map_value(value))
    out = {}
    for meta in metadata:
        name = display = ""
        program = op_name = None
        for field, value in _fields(meta):
            if field == 2:
                name = value.decode(errors="replace")
            elif field == 4:
                display = value.decode(errors="replace")
            elif field == 5:
                stat = dict(_fields(value))
                kind = stat_names.get(stat.get(1))
                if kind == "program_id":
                    program = stat.get(3, stat.get(4))
                elif kind == "tf_op":
                    ref = stat.get(7)
                    op_name = stat_names.get(ref, "") if ref is not None \
                        else stat.get(5, b"").decode(errors="replace")
        if program is not None and op_name:
            out[(program, display or trace.instruction_name(name))] = op_name
    return out


def scope_tokens(op_name):
    """`jit(step_fn)/transpose(jvp(attn))/dot_general:` -> {"jit",
    "step_fn", "transpose", "jvp", "attn", "dot_general"}."""
    return set(re.split(r"[/():\s]+", op_name)) - {""}


def program_id(module_event_name):
    """`jit_step_fn(6503316649871221259)` -> 6503316649871221259."""
    m = re.search(r"\((\d+)\)$", module_event_name)
    return int(m.group(1)) if m else None


def scope_seconds(events, names, program):
    """({scope path: seconds}, seconds of the program's runs) on the
    first device plane: every op that ran inside a run of `program`, at
    its self time (an enclosing `while` does not count its body twice),
    under its scope path from `names` (`op_names(...)`), or None where
    the trace has no path for it."""
    per_op, runs = {}, 0.0
    for dev in trace._first(events):
        mods = sorted((m for m in dev["modules"]
                       if trace.program_name(m[0]) == program),
                      key=lambda m: m[1])
        runs = sum(m[2] for m in mods) / 1e9
        starts = [m[1] for m in mods]
        for name, start, self_ns, _ in trace._self_times(dev["ops"]):
            i = bisect.bisect_right(starts, start) - 1
            if i < 0 or start >= mods[i][1] + mods[i][2]:
                continue
            key = names.get((program_id(mods[i][0]), name))
            per_op[key] = per_op.get(key, 0.0) + self_ns / 1e9
    return per_op, runs
