"""The runners end to end at debug widths on the CPU mesh: a training
cell through `JaxTrainer` and the Dataset ingest, a serving cell through
the proxy, the replica and the load generator's process, each reduced to
its metrics by the readers its files name. No time read here is a
device's."""

import os
import time

import jax
import pytest

import ray_tpu
from benchmark import run as bench_run
from benchmark.harness import device as hw
from benchmark.harness.manifest import Cell, manifest, model_adapter

CELLS = [w["name"] for w in manifest()["workloads"]]


def cells_of_kind(kind):
    return [name for name in CELLS if Cell(name).config["kind"] == kind]


def cut_train(config, mix):
    config["train"]["loss_tolerance"] = 0.02  # bf16 on 64 tokens a batch
    mix.update(seq=32, trace_s=1)


def cut_serve(config, mix):
    config["serve"].update(max_batch_size=4, max_seq_len=128,
                           reference_prompt_lens=[16, 12, 7, 3],
                           reference_decode_steps=3,
                           logit_tolerance=0.05,  # bf16 at hidden 64
                           probe_prompt_lens=[9, 7, 5, 3], probe_total=12,
                           served_token_margin=0.05)
    mix["pairs"] = [[8 + 7 * (i % 9), 4 + (5 * i) % 13] for i in range(16)]
    mix.update(ramp_s=1, drain_s=5, trace_s=1)


# By the configuration's `kind`: the plan and the traffic cut to what the
# model's debug widths hold.
CUTS = {"train": cut_train, "serve": cut_serve}


def debug_cell(cell):
    """The cell with its model cut to debug widths by its family's
    adapter and its plan and traffic to lengths those hold. Never a
    benchmark configuration: a test's."""
    cell.config = model_adapter(cell.config).debug(cell.config)
    CUTS[cell.config["kind"]](cell.config, cell.traffic)
    return cell


def drive(cell, tmp_path, seconds):
    devices = jax.devices()[:cell.chips]
    compiles = hw.CompileLog()
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=len(devices))
    try:
        run = cell.runner().run(
            cell, seed=2 ** 31 + 77, seconds=seconds, trace_dir=None,
            devices=devices, run_dir=str(tmp_path))
    finally:
        ray_tpu.shutdown()
    t0 = run["window"][0]
    ctx = {"cell": cell, "run": run, "compiles": compiles, "trace": None,
           "setup_s": hw.process_age_s() - (time.perf_counter() - t0),
           "device": {"kind": "TPU v5 lite", "count": len(devices),
                      "peaks": hw.peaks("TPU v5 lite")}}
    return run, {g: bench_run.read_metrics(cell, g, ctx)
                 for g in ("end_to_end", "per_layer")}


@pytest.mark.parametrize("name", cells_of_kind("train"))
def test_training_cell_at_debug_width(name, tmp_path):
    cell = debug_cell(Cell(name))
    run, metrics = drive(cell, tmp_path, seconds=1.5)
    assert all(run["checks"].values()), run["log"]
    assert run["failed"] == 0 and run["attempted"] == run["steps"] >= 8
    assert run["tokens"] == run["steps"] * 32 * \
        cell.config["train"]["sequences_per_chip"] * cell.chips
    e2e, per = metrics["end_to_end"], metrics["per_layer"]
    assert set(e2e) == {"train_tokens_per_s_per_chip", "setup_s"}
    t0, t1 = run["window"]
    assert e2e["train_tokens_per_s_per_chip"]["value"] == pytest.approx(
        run["tokens"] / (t1 - t0) / cell.chips)
    # Without a trace the readers that need one report nothing.
    assert {"ingest.wait_share", "step.mfu", "setup.compile_s",
            "setup.compiles_in_window"} <= set(per)
    assert "step.train_device_ms" not in per
    assert per["setup.compiles_in_window"]["value"] == 0
    assert 0 <= per["ingest.wait_share"]["value"] < 100


@pytest.mark.parametrize("name", cells_of_kind("serve"))
def test_serving_cell_at_debug_width(name, tmp_path):
    cell = debug_cell(Cell(name))
    run, metrics = drive(cell, tmp_path, seconds=3.0)
    assert all(run["checks"].values()), run["log"]
    assert run["failed"] == 0 and run["attempted"] >= 5
    assert os.path.exists(tmp_path / "records.json")
    e2e, per = metrics["end_to_end"], metrics["per_layer"]
    want = {m["name"] for m in cell.metrics["end_to_end"]}
    assert set(e2e) == want and "setup_s" in want
    assert all(m["value"] > 0 for m in e2e.values())
    # The program's own spans were read: every admitted request has one.
    slot_wait = [k for k in per if k.startswith("service.slot_wait")]
    assert slot_wait and per[slot_wait[0]]["value"] >= 0
    assert len(run["stages"]["llm.admit"]) >= 5
    assert per["setup.compiles_in_window"]["value"] == 0
