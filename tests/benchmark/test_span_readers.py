"""The readers of PR 24 on hand-made events: what of the device's idle
time lies under a span, a span's share of a window, a ratio of two
span attributes, a scope's share of a program's device time (with the
scope paths decoded from a hand-made xplane), and the share of a
stretch the host spent in a span. A program without the span or scope
(the parent of the PR that adds it) reads as None, not as 0.
"""

import pytest

from benchmark.harness import spans as sp
from benchmark.readers import (host_span_share, idle_under_span_share,
                               scope_device_share, span_stat_ratio,
                               stage_span_share)

MS = 1_000_000  # ns


def _events(ops, modules, host):
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": host}


def test_idle_gap_half_under_a_span_reads_50_percent():
    # Busy 0-10 ms and 30-40 ms: one idle gap of 20 ms, the first half
    # of it under an admission wave, 4 ms of the second half under the
    # decode loop's spans, of which 1 ms lies inside the wave as well.
    events = _events(
        ops=[["fusion.1", 0, 10 * MS], ["fusion.2", 30 * MS, 10 * MS]],
        modules=[["jit__decode_impl(7)", 0, 10 * MS],
                 ["jit__decode_impl(7)", 30 * MS, 10 * MS]],
        host={"python#0": [["engine.decode_dispatch", 0, 2 * MS],
                           ["engine.decode_dispatch", 29 * MS, 2 * MS],
                           ["engine.admit_wave", 5 * MS, 15 * MS],
                           ["engine.token_fetch", 19 * MS, 3 * MS],
                           ["engine.consume_block", 25 * MS, 2 * MS],
                           ["$threading.py:323 wait", 0, 40 * MS]]})
    share, idle_s = idle_under_span_share.share(
        events, ["engine.admit_wave"], "engine.")
    assert share == pytest.approx(50.0)
    assert idle_s == pytest.approx(0.020)
    share, _ = idle_under_span_share.share(
        events, ["engine.token_fetch", "engine.consume_block"], "engine.",
        outside=["engine.admit_wave"])
    assert share == pytest.approx(100.0 * 4 / 20)
    ctx = {"trace": events}
    assert idle_under_span_share.read(
        ctx, spans=["engine.admit_wave"], family="engine.") == \
        pytest.approx(50.0)
    # The family is there but no span of this name: 0, not nothing.
    assert idle_under_span_share.read(
        ctx, spans=["engine.idle_wait"], family="engine.") == 0.0
    # No span of the family in the trace (the parent), or no trace.
    assert idle_under_span_share.read(
        ctx, spans=["data.batch_wait"], family="data.") is None
    assert idle_under_span_share.read(
        {"trace": None}, spans=["engine.admit_wave"],
        family="engine.") is None
    # A wave that was open when the trace began left no annotation: the
    # idle time before the first span of the family does not count.
    events["devices"]["/device:TPU:0"]["ops"].insert(
        0, ["fusion.0", -30 * MS, 10 * MS])
    events["devices"]["/device:TPU:0"]["modules"].insert(
        0, ["jit__prefill_impl(5)", -30 * MS, 10 * MS])
    share, idle_s = idle_under_span_share.share(
        events, ["engine.admit_wave"], "engine.")
    assert (share, idle_s) == (pytest.approx(50.0), pytest.approx(0.020))


def test_span_share_of_the_untraced_window():
    ctx = {"run": {"window": (100.0, 110.0),
                   "stages": {"engine.admit_wave": [0.5, 1.0, 1.5],
                              "llm.admit": [9.6]}}}
    assert stage_span_share.read(ctx, stage="engine.admit_wave") == \
        pytest.approx(30.0)
    assert stage_span_share.read(ctx, stage="engine.prefix_readback") \
        is None
    assert stage_span_share.read({"run": {"window": (0.0, 1.0)}},
                                 stage="engine.admit_wave") is None


def test_ratio_of_span_attributes(monkeypatch):
    seen = [("engine.decode_dispatch", 0, MS, {"active": 32, "n_slots": 32}),
            ("engine.decode_dispatch", 2 * MS, MS,
             {"active": 30, "n_slots": 32}),
            ("engine.decode_dispatch", 4 * MS, MS, {})]  # before the PR
    monkeypatch.setattr(sp, "xplane_path", lambda ctx: "a.xplane.pb")
    monkeypatch.setattr(
        sp, "annotations",
        lambda path, names: [a for a in seen if a[0] in names])
    ctx = {"trace": {}}
    assert span_stat_ratio.read(
        ctx, span="engine.decode_dispatch", num="active",
        den="n_slots") == pytest.approx(100.0 * 62 / 64)
    assert span_stat_ratio.read(
        ctx, span="engine.decode_dispatch", num="active", den="n_slots",
        complement=True) == pytest.approx(100.0 * 2 / 64)
    assert span_stat_ratio.read(
        ctx, span="engine.prefill_dispatch", num="real",
        den="bucket") is None
    assert span_stat_ratio.read({"trace": None}, span="x", num="a",
                                den="b") is None


def test_host_span_share_of_the_traced_stretch():
    events = _events([], [], {
        "python#0": [["data.batch_wait", 0, 3 * MS],
                     ["data.batch_wait", 10 * MS, 2 * MS]],
        "python#1": [["data.batch_wait", 1 * MS, 2 * MS],  # overlaps
                     ["data.to_device", 0, 50 * MS]]})
    ctx = {"trace": events, "run": {"trace_t0": 5.0, "trace_t1": 5.1}}
    assert host_span_share.read(ctx, span="data.batch_wait") == \
        pytest.approx(5.0)
    assert host_span_share.read(ctx, span="train.place_batch") is None


# -- scope paths: a hand-made xplane ------------------------------------------


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _map_entry(number, key, message):
    return _field(number, _field(1, key) + _field(2, message))


_STATS = {1: "program_id", 2: "tf_op", 3: "flops"}


def _event_metadata(mid, hlo_line, display, program, op_name=None,
                    by_ref=None):
    stats = _field(5, _field(1, 3) + _field(4, 12345))  # flops: skipped
    stats += _field(5, _field(1, 1) + _field(3, program))
    if op_name is not None:
        stats += _field(5, _field(1, 2) + _field(5, op_name))
    if by_ref is not None:
        stats += _field(5, _field(1, 2) + _field(7, by_ref))
    return _map_entry(4, mid, _field(1, mid) + _field(2, hlo_line)
                      + _field(4, display) + stats)


def _xspace(tmp_path):
    stat_names = dict(_STATS)
    stat_names[9] = "jit(step_fn)/optimizer/mul:"  # a string by reference
    plane = _field(1, 7) + _field(2, "/device:TPU:0")
    plane += _field(3, _field(2, "XLA Ops"))  # a line: skipped
    for sid, name in stat_names.items():
        plane += _map_entry(5, sid, _field(1, sid) + _field(2, name))
    plane += _event_metadata(
        1, "%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8] %p), kind=kOutput",
        "fusion.1", 77, "jit(step_fn)/jvp(attn)/dot_general:")
    plane += _event_metadata(
        2, "%fusion.2 = bf16[8,8]{1,0} fusion(bf16[8,8] %p), kind=kLoop",
        "", 77, "jit(step_fn)/transpose(jvp(mlp))/dot_general:")
    plane += _event_metadata(3, "%fusion.3 = f32[] fusion()", "fusion.3",
                             77, by_ref=9)
    plane += _event_metadata(4, "%copy.4 = f32[] copy()", "copy.4", 77)
    plane += _event_metadata(
        5, "%fusion.1 = bf16[2,2]{1,0} fusion()", "fusion.1", 88,
        "jit(_decode_impl)/while/body/attn/mul:")
    other = _field(2, "/host:CPU") + _field(3, _field(2, "python"))
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_field(1, other) + _field(1, plane)
                     + _field(4, "hostname"))
    return str(path)


def test_scope_paths_are_decoded_from_the_event_metadata(tmp_path):
    assert sp.op_names(_xspace(tmp_path)) == {
        (77, "fusion.1"): "jit(step_fn)/jvp(attn)/dot_general:",
        (77, "fusion.2"): "jit(step_fn)/transpose(jvp(mlp))/dot_general:",
        (77, "fusion.3"): "jit(step_fn)/optimizer/mul:",
        (88, "fusion.1"): "jit(_decode_impl)/while/body/attn/mul:"}
    assert sp.scope_tokens(
        "jit(step_fn)/transpose(jvp(attn))/dot_general:") == {
        "jit", "step_fn", "transpose", "jvp", "attn", "dot_general"}
    assert sp.program_id("jit_step_fn(6503316649871221259)") == \
        6503316649871221259


def test_scope_share_of_a_programs_device_time(tmp_path, monkeypatch):
    path = _xspace(tmp_path)
    # One run of step_fn, 100 ms: a `while` (fusion.2's program has no
    # name for it) that encloses 30 ms of forward attention and 50 ms
    # of backward MLP, then 10 ms of optimizer and 5 ms of an unnamed
    # copy. A run of the decode program beside it has its own fusion.1.
    events = _events(
        ops=[["while.9", 0, 85 * MS], ["fusion.1", 0, 30 * MS],
             ["fusion.2", 30 * MS, 50 * MS], ["fusion.3", 85 * MS, 10 * MS],
             ["copy.4", 95 * MS, 5 * MS], ["fusion.1", 200 * MS, 8 * MS]],
        modules=[["jit_step_fn(77)", 0, 100 * MS],
                 ["jit__decode_impl(88)", 200 * MS, 10 * MS]],
        host={})
    per_path, runs_s = sp.scope_seconds(events, sp.op_names(path),
                                        "step_fn")
    assert runs_s == pytest.approx(0.100)
    assert per_path == {
        "jit(step_fn)/jvp(attn)/dot_general:": pytest.approx(0.030),
        "jit(step_fn)/transpose(jvp(mlp))/dot_general:":
            pytest.approx(0.050),
        "jit(step_fn)/optimizer/mul:": pytest.approx(0.010),
        None: pytest.approx(0.010)}  # the while's own 5 ms and the copy
    assert scope_device_share.split(per_path) == {
        "forward": pytest.approx(0.030), "backward": pytest.approx(0.050),
        "optimizer": pytest.approx(0.010), "unnamed": pytest.approx(0.010)}
    monkeypatch.setattr(sp, "xplane_path", lambda ctx: path)
    ctx = {"trace": events}
    assert scope_device_share.read(
        ctx, program="step_fn", any_of=["transpose"],
        none_of=["optimizer"]) == pytest.approx(50.0)
    assert scope_device_share.read(
        ctx, program="step_fn", any_of=["optimizer"]) == pytest.approx(10.0)
    assert scope_device_share.read(
        ctx, program="_decode_impl", any_of=["attn"]) == pytest.approx(80.0)
    # A program that scopes nothing `loss` (the parent), a program that
    # never ran, no trace: nothing.
    assert scope_device_share.read(
        ctx, program="step_fn", any_of=["loss"]) is None
    assert scope_device_share.read(
        ctx, program="_prefill_impl", any_of=["attn"]) is None
    assert scope_device_share.read(
        {"trace": None}, program="step_fn", any_of=["attn"]) is None
