"""The per-layer metrics of PR 53: what a pass of the engine's loop cost
the host, the wave's closing stretch, and the two witnesses of a stall
(the recorder's beat, the front's channel). Seven data files over two
readers that were there; nothing here is code of the benchmark's. Each
file against its reader, its cells and its entry in `BENCHMARK.json`,
found by name wherever it stands; and each metric on a window as the
parent's program leaves it (none of the new records: it reads nothing
and does not raise) and as this program does. What the program records
under these names is held by `tests/serve/test_engine_spans.py`.
"""

import inspect

import pytest

from benchmark.harness.manifest import Cell, manifest, metric_spec, plugin

SIX = ["serve-batch-closed", "serve-glm52-long-closed",
       "serve-nemotron3s-reason-closed", "serve-cmdaplus-rag-closed",
       "serve-olmohybrid-eval-closed", "serve-sdar-eval-closed"]
# The cells with a prefix cache: those of `engine.kv_readback_share`.
CACHED = ["serve-batch-closed", "serve-glm52-long-closed",
          "serve-sdar-eval-closed"]
# name: (reader, its arguments, unit, layer, at least these cells)
NEW = {
    "engine.loop_host_share": (
        "stage_span_share", {"stage": "engine.loop_host"}, "%", "Engine",
        SIX),
    "engine.loop_host_p50_ms": (
        "stage_span_quantile", {"stage": "engine.loop_host", "q": 0.5},
        "ms", "Engine", SIX),
    "engine.loop_host_max_ms": (
        "stage_span_quantile", {"stage": "engine.loop_host", "q": 1.0},
        "ms", "Engine", SIX),
    "engine.prefix_admit_share": (
        "stage_span_share", {"stage": "engine.prefix_admit"}, "%", "Engine",
        CACHED),
    "engine.prefix_admit_max_ms": (
        "stage_span_quantile", {"stage": "engine.prefix_admit", "q": 1.0},
        "ms", "Engine", CACHED),
    "process.wake_late_max_ms": (
        "stage_span_quantile", {"stage": "process.wake_late", "q": 1.0},
        "ms", "Engine", SIX),
    "service.stream_channel_max_ms": (
        "stage_span_quantile", {"stage": "stream.channel", "q": 1.0},
        "ms", "Serve front and admission", SIX),
}
# The one of them whose stage the parent's program records too.
PARENT_READS = "service.stream_channel_max_ms"

# A half-window of 25.5 s as the parent's program leaves it in the ring:
# the loop's tiles, the gaps between blocks, the streams' two lags, a
# request's stages; none of this PR's records.
PARENT_STAGES = {
    "engine.admit_wave": [0.004] * 160, "engine.flush_pending": [2e-6] * 160,
    "engine.prefix_readback": [0.0006] * 340,
    "engine.decode_dispatch": [0.0011] * 1300,
    "engine.token_fetch": [0.0175] * 1300,
    "engine.consume_block": [0.0009] * 1300,
    "engine.block_gap.plain": [0.0188] * 1130,
    "engine.block_gap.wave": [0.046] * 160,
    "stream.wake": [0.0004] * 900, "stream.channel": [0.0002] * 880 + [0.131],
    "llm.prefill": [0.07] * 170, "llm.admit": [4.6] * 170,
}
# What this PR's program adds to it.
CHANGE_STAGES = dict(
    PARENT_STAGES, **{
        "engine.loop_host": [0.0021] * 1290 + [0.0105] * 9 + [0.031],
        "engine.prefix_admit": [0.0012] * 159 + [0.0052],
        "process.wake_late": [0.00012] * 254 + [0.0034]})


def _ctx(stages):
    return {"run": {"window": (100.0, 125.5), "stages": stages},
            "trace": None}


def _read(name, stages):
    spec = metric_spec(name)
    return plugin("readers", spec["reader"]).read(_ctx(stages),
                                                  **spec["args"])


def _entry(name):
    (entry,) = [m for m in manifest()["per_layer"] if m["name"] == name]
    return entry


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_file_names_a_reader_that_exists_and_takes_its_arguments(name):
    reader, args, _unit, _layer, _cells = NEW[name]
    spec = metric_spec(name)
    assert spec == {"reader": reader, "args": args}
    params = inspect.signature(plugin("readers", reader).read).parameters
    assert set(args) == set(params) - {"ctx"}


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_entry_is_found_by_name_with_cells_that_report_the_rate(name):
    _reader, _args, unit, layer, cells = NEW[name]
    bench = manifest()
    entry = _entry(name)
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == (unit, "lower", "program_span", layer,
                                "serve_out_tokens_per_s")
    # A layer the benchmark had, under the name it had.
    assert layer in {m["layer"] for m in bench["per_layer"]
                     if m["name"] not in NEW}
    (moved,) = [m for m in bench["end_to_end"] if m["name"] == entry["moves"]]
    known = {w["name"] for w in bench["workloads"]}
    assert set(cells) <= set(entry["workloads"]) <= known
    assert set(entry["workloads"]) <= set(moved["workloads"])
    assert len(entry["workloads"]) == len(set(entry["workloads"]))


def test_the_cached_cells_are_those_that_read_the_read_back():
    assert set(_entry("engine.kv_readback_share")["workloads"]) \
        >= set(CACHED)


@pytest.mark.parametrize("gap", [None, 3.0], ids=["steady", "gap-of-3s"])
@pytest.mark.parametrize("name", sorted(NEW))
def test_on_the_parents_window_it_reads_nothing_and_does_not_raise(name, gap):
    """What refused PR 52: the parent's program under this PR's files.
    A window without the new records, also one whose blocks stood 3 s
    apart once, which is the window that had something to explain."""
    stages = dict(PARENT_STAGES)
    if gap:
        stages["engine.block_gap.plain"] = \
            stages["engine.block_gap.plain"] + [gap]
    value = _read(name, stages)
    if name == PARENT_READS:
        assert value == pytest.approx(131.0)
    else:
        assert value is None


@pytest.mark.parametrize("stages", [
    {}, {"engine.loop_host": [], "engine.prefix_admit": [],
         "process.wake_late": [], "stream.channel": []}],
    ids=["empty-window", "empty-lists"])
@pytest.mark.parametrize("name", sorted(NEW))
def test_on_a_window_without_records_it_reads_nothing(name, stages):
    assert _read(name, stages) is None
    # Nor on a run that kept no stages at all.
    spec = metric_spec(name)
    assert plugin("readers", spec["reader"]).read(
        {"run": {"window": (0.0, 1.0)}, "trace": None}, **spec["args"]) is None


@pytest.mark.parametrize("name, value", [
    ("engine.loop_host_share",
     100 * (0.0021 * 1290 + 0.0105 * 9 + 0.031) / 25.5),
    ("engine.loop_host_p50_ms", 2.1),
    ("engine.loop_host_max_ms", 31.0),
    ("engine.prefix_admit_share", 100 * (0.0012 * 159 + 0.0052) / 25.5),
    ("engine.prefix_admit_max_ms", 5.2),
    ("process.wake_late_max_ms", 3.4),
    ("service.stream_channel_max_ms", 131.0),
])
def test_on_the_changes_window_it_reads_a_number(name, value):
    assert _read(name, CHANGE_STAGES) == pytest.approx(value)
    assert value > 0


def test_a_pass_that_lost_a_second_is_the_maximum():
    stages = dict(CHANGE_STAGES)
    stages["engine.loop_host"] = stages["engine.loop_host"] + [1.3]
    assert _read("engine.loop_host_max_ms", stages) == pytest.approx(1300.0)
    assert _read("engine.loop_host_p50_ms", stages) == pytest.approx(2.1)


@pytest.mark.parametrize("cell", SIX)
def test_a_serve_cells_per_layer_metrics_load(cell):
    loaded = {m["name"]: m for m in Cell(cell).metrics["per_layer"]}
    mine = {name for name in NEW if cell in _entry(name)["workloads"]}
    assert mine <= set(loaded)
    assert {name for name in NEW if cell in NEW[name][4]} <= mine
    for name in mine:
        assert loaded[name]["reader"] == NEW[name][0]
        assert loaded[name]["args"] == NEW[name][1]
    # Every metric of the cell, old or new, has its reader.
    for m in loaded.values():
        assert callable(plugin("readers", m["reader"]).read), m["name"]


def test_no_train_cell_lists_them():
    serve = set(SIX)
    for cell in (w["name"] for w in manifest()["workloads"]):
        if cell not in serve and cell.startswith("train-"):
            assert not set(NEW) & {
                m["name"] for m in Cell(cell).metrics["per_layer"]}
