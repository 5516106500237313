"""The reduction from a profiler trace to numbers: interval arithmetic
on hand-made events, then busy/idle, time by program and kernel, and
exposed collective time on a small recorded trace (one step of
`train-moe-fsdp4` on two of the v5e's four chips, cut out by the builder
of PR 23; `data/moe_step_trace.json`)."""

import json
import os

import pytest

from benchmark.harness import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))

# One device, nanoseconds. A `while` encloses two fusions and, between
# them, the wait for an all-gather; a second program follows a gap.
HAND = {
    "devices": {"/device:TPU:0": {
        "ops": [["while.1", 0, 100], ["fusion.3", 10, 20],
                ["all-gather-done.2", 30, 20], ["fusion.4", 50, 20],
                ["copy.1", 120, 10]],
        "modules": [["jit_step_fn(123)", 0, 105], ["jit__f(9)", 118, 15]],
    }},
    "host": {"main#0": [["$a.py:1 fetch", 100, 20], ["$b.py:2 loop", 0, 200]]},
}


def test_interval_arithmetic():
    assert T.merge([(5, 7), (0, 2), (1, 3), (7, 7)]) == [[0, 3], [5, 7]]
    assert T.total(T.merge([(0, 2), (1, 3), (5, 7)])) == 5
    assert T.subtract([(0, 10)], [[2, 3], [5, 7]]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert T.subtract([(0, 4), (6, 9)], [[3, 7]]) == [(0, 3), (7, 9)]
    assert T.subtract([(0, 4)], []) == [(0, 4)]


def test_names():
    line = ("%fusion.150 = bf16[1024,14336]{1,0:T(8,128)(2,1)S(1)} "
            "fusion(bf16[16,4096,14336]{2,1,0} %get-tuple-element.702)")
    assert T.instruction_name(line) == "fusion.150"
    assert T.op_class("multiply_reduce_fusion.4") == "multiply_reduce_fusion"
    assert T.op_class("flash_bwd_dkv") == "flash_bwd_dkv"
    assert T.program_name("jit__decode_impl(6425179)") == "_decode_impl"
    assert T.is_collective("all-gather-start.3")
    assert T.is_collective("all-to-all.1")
    assert not T.is_collective("fusion.3")


def test_hand_made_trace():
    assert T.window(HAND) == (0, 133)
    # Busy is the union: the while covers 0-100, the copy 120-130.
    assert T.busy_seconds(HAND) == pytest.approx(110e-9)
    assert T.program_runs(HAND) == {"step_fn": [105e-9], "_f": [15e-9]}
    # The while keeps only what its children leave: 100 - 20 - 20 - 20.
    assert T.op_seconds(HAND) == pytest.approx({
        "step_fn/while": 40e-9, "step_fn/fusion": 40e-9,
        "step_fn/all-gather-done": 20e-9, "_f/copy": 10e-9})
    # The core waited for the all-gather from 30 to 50 and did nothing
    # else; the enclosing while does not count as something else.
    assert T.exposed_collective_seconds(HAND) == pytest.approx(20e-9)
    assert T.kernel_seconds(HAND, ["fusion", "flash_fwd"]) == {
        "fusion": (pytest.approx(40e-9), 2), "flash_fwd": (0.0, 0)}
    # The gaps inside the window: 100-120, while the host fetched, and
    # the second program's last 3 ns, in which no op ran.
    assert T.idle_gaps(HAND) == [["a.py:1_fetch", pytest.approx(20e-9)],
                                 ["b.py:2_loop", pytest.approx(3e-9)]]
    b = T.breakdown(HAND)
    assert b["device_ops"][0][0] in ("step_fn/while", "step_fn/fusion")
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_plane_without_ops_is_busy_while_its_modules_run():
    only_modules = {"devices": {"/device:TPU:0": {
        "ops": [], "modules": [["jit_f(1)", 0, 10]]}},
        "host": {}}
    assert T.busy_seconds(only_modules) == pytest.approx(10e-9)
    assert T.busy_seconds({"devices": {}, "host": {}}) == 0.0
    assert T.idle_gaps({"devices": {}, "host": {}}) == []


@pytest.fixture(scope="module")
def moe_step():
    with open(os.path.join(HERE, "data", "moe_step_trace.json")) as f:
        return json.load(f)


def test_recorded_step_busy_and_programs(moe_step):
    """One fsdp=4 train step of the Mixtral cell as two of the chips
    recorded it: 755.96 ms in the module event, of which the core ran
    an op all but 46 us."""
    lo, hi = T.window(moe_step)
    assert (hi - lo) / 1e9 == pytest.approx(0.755956, abs=1e-6)
    assert T.program_runs(moe_step) == {"step_fn": [0.755956263]}
    busy = T.busy_seconds(moe_step)
    assert busy == pytest.approx(0.755910, abs=1e-6)
    assert busy <= (hi - lo) / 1e9
    # Self times of all ops add up to the busy time: nothing is counted
    # twice under the `while` that encloses the layers.
    ops = T.op_seconds(moe_step)
    assert sum(ops.values()) == pytest.approx(0.755912, abs=1e-6)
    assert all(k.startswith("step_fn/") for k in ops)
    top = T.breakdown(moe_step)["device_ops"]
    assert top[0][0] == "step_fn/fusion" and top[0][1] > 0.59
    # The idle 46 us fell to what the host was doing then.
    gaps = T.idle_gaps(moe_step)
    assert gaps and sum(s for _, s in gaps) == pytest.approx(46e-6, abs=3e-6)


def test_recorded_step_kernels_and_collectives(moe_step):
    # Two layers, each attention forward run twice (the layer is
    # recomputed in the backward pass), each backward kernel once.
    seen = T.kernel_seconds(
        moe_step, ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"])
    assert [seen[k][1] for k in ("flash_fwd", "flash_bwd_dq",
                                 "flash_bwd_dkv")] == [4, 2, 2]
    assert seen["flash_fwd"][0] == pytest.approx(5.295e-3, rel=1e-3)
    # Collectives the core ran or waited for, with nothing else running:
    # the sum of those ops on each chip, 13.3 % of the step.
    exposed = T.exposed_collective_seconds(moe_step)
    assert exposed == pytest.approx(0.100263, abs=1e-6)
    ops = T.op_seconds(moe_step)
    by_hand = sum(s for k, s in ops.items()
                  if T.is_collective(k.split("/")[1]))
    assert by_hand == pytest.approx(0.100258, abs=1e-6)  # the first chip's
    assert {"step_fn/collective-permute-done", "step_fn/all-reduce",
            "step_fn/async-collective-done", "step_fn/all-gather",
            "step_fn/all-to-all"} <= set(ops)
