"""`BENCHMARK.json` against the contract it is written to, and against
the files it names; and the command's refusal to run without a chip."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark.harness.manifest import (BENCH_DIR, ROOT, Cell, manifest,
                                        metric_spec, plugin)

BENCH = manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["paths"]) <= 16
    for path in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", path)
        assert not path.startswith("/") and ".." not in path.split("/")
    assert 1 <= len(BENCH["command"]) <= 32
    assert all(one_line(word) for word in BENCH["command"])
    # A full check of 24 cells fits the driver's 43200 seconds.
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_texts():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"),
                          group, entry["name"]))
    metrics = [n for is_metric, _, n in names if is_metric]
    assert len(metrics) == len(set(metrics))
    for group in ("configs", "workloads"):
        own = [n for _, g, n in names if g == group]
        assert len(own) == len(set(own))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and one_line(w["why"]), w
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["source"]) and one_line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])


def test_end_to_end_metrics():
    assert 1 <= len(E2E) <= 16 and "setup_s" in E2E
    for m in E2E.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert "workloads" not in E2E["setup_s"]
    for cell in CELLS:
        reported = [m for m in E2E.values() if cell in cells_of(m)]
        assert len(reported) >= 2  # set-up time and one other


def test_per_layer_metrics_move_what_their_cells_report():
    assert 1 <= len(BENCH["per_layer"]) <= 128
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert one_line(m["layer"])
        moved = E2E[m["moves"]]
        assert cells_of(m) and set(cells_of(m)) <= set(cells_of(moved)), m
    for cell in CELLS:
        assert any(cell in cells_of(m) for m in BENCH["per_layer"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(cells_of(m)) <= set(CELLS)


def test_cells_and_configurations():
    assert 1 <= len(CELLS) <= 24
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert len(four) <= max(1, len(CELLS) // 4)
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert 1 <= len(configs) <= 24
    assert {w["config"] for w in BENCH["workloads"]} == set(configs)
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    width = re.compile(r"(_size$|intermediate|latent|proj|_dim$|_rank$"
                       r"|expansion|experts_per_tok)")
    for c in configs.values():
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        body = json.load(open(os.path.join(ROOT, c["file"])))
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        assert not any(width.search(k) for k in c["reduced"])
        for key in c["reduced"]:  # what was cut, and from what
            assert body[key] != body["published"][key]


@pytest.mark.parametrize("name", CELLS)
def test_every_file_a_cell_names_exists(name):
    cell = Cell(name, BENCH)
    assert cell.traffic["loop"] in ("job", "closed", "open")
    assert callable(cell.runner().run)
    plugin("references", cell.config["reference"]).hyper(cell.config)
    assert callable(plugin("flops", cell.config["flops"])
                    .train_flops_per_token)
    groups = cell.metrics
    assert groups["end_to_end"] and groups["per_layer"]
    for m in groups["end_to_end"] + groups["per_layer"]:
        assert callable(plugin("readers", m["reader"]).read), m
        assert isinstance(m["args"], dict)


def test_metric_files_and_entries_agree():
    listed = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    on_disk = {f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, "metrics"))}
    # Every metric has a file, its own or that of a dotted prefix of its
    # name (`x.serve` and `x.train` share `x.json`); no file lies unused.
    used = set()
    for name in listed:
        parts = name.split(".")
        own = [".".join(parts[:n]) for n in range(len(parts), 0, -1)
               if ".".join(parts[:n]) in on_disk]
        assert own, name
        used.add(own[0])
        assert metric_spec(name) == json.load(open(os.path.join(
            BENCH_DIR, "metrics", own[0] + ".json")))
    assert used == on_disk
    for name in on_disk:
        body = json.load(open(os.path.join(BENCH_DIR, "metrics",
                                           name + ".json")))
        assert set(body) == {"reader", "args"}
    with pytest.raises(SystemExit):
        metric_spec("no.such.metric")
    for directory in ("configs", "traffic", "metrics"):
        for f in os.listdir(os.path.join(BENCH_DIR, directory)):
            assert re.fullmatch(r"[A-Za-z0-9_.\-]+\.json", f), f


def test_without_a_chip_the_command_fails_and_prints_no_result():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0],
         "--seed", str(2 ** 31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={**env, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "3"})
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not [line for line in proc.stdout.splitlines()
                if line.startswith("{")]
