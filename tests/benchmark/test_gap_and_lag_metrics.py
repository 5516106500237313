"""The per-layer metrics of PR 36 (the engine's block gaps by kind, the
prefill's wait, the stream's two hand-over lags, the kept share of a
block's slot-steps, the slots' filling): their one new reader on a
made-up window, and each metric's file against the readers, the cells
and `BENCHMARK.json`'s entry. What the program records under these
names is held by `tests/serve/test_engine_spans.py` and
`tests/serve/test_streaming.py`.
"""

import inspect

import pytest

from benchmark.harness.manifest import Cell, manifest, metric_spec, plugin
from benchmark.readers import stage_count_share, stage_span_quantile

WAVE, PLAIN = "engine.block_gap.wave", "engine.block_gap.plain"
SERVE = ["serve-batch-closed", "serve-glm52-long-closed",
         "serve-nemotron3s-reason-closed"]
# name: (reader, what it reads, cells, the end-to-end metric it moves)
NEW = {
    "engine.plain_block_gap_p50_ms": (
        "stage_span_quantile", PLAIN, SERVE, "serve_tpot_p50_ms"),
    "engine.wave_block_gap_p50_ms": (
        "stage_span_quantile", WAVE, SERVE, "serve_tpot_p50_ms"),
    "engine.wave_block_share": (
        "stage_count_share", WAVE, SERVE, "serve_tpot_p50_ms"),
    "engine.prefill_to_token_p50_ms": (
        "stage_span_quantile", "llm.prefill", SERVE,
        "serve_out_tokens_per_s"),
    "service.stream_wake_p50_ms": (
        "stage_span_quantile", "stream.wake", SERVE, "serve_tpot_p50_ms"),
    "service.stream_channel_p50_ms": (
        "stage_span_quantile", "stream.channel", SERVE,
        "serve_tpot_p50_ms"),
    "engine.kept_token_share": (
        "span_stat_ratio", "engine.consume_block", SERVE,
        "serve_out_tokens_per_s"),
    "kv.slot_fill_share": (
        "span_stat_ratio", "engine.decode_dispatch", SERVE[:2],
        "serve_out_tokens_per_s"),
}


def test_count_share_of_two_names():
    ctx = {"run": {"window": (0.0, 25.5), "stages": {
        WAVE: [0.045, 0.050, 0.047], PLAIN: [0.0188] * 9,
        "engine.consume_block": [0.001] * 13}}}
    assert stage_count_share.read(ctx, stage=WAVE, beside=PLAIN) == \
        pytest.approx(25.0)
    assert stage_count_share.read(ctx, stage=PLAIN, beside=WAVE) == \
        pytest.approx(75.0)
    # The gaps' medians come through the reader that was there.
    assert stage_span_quantile.read(ctx, stage=WAVE, q=0.5) == \
        pytest.approx(47.0)
    assert stage_span_quantile.read(ctx, stage=PLAIN, q=0.5) == \
        pytest.approx(18.8)


@pytest.mark.parametrize("stages", [
    {PLAIN: [0.0188] * 9},                        # one name missing
    {WAVE: [0.045]},                              # the other one
    {WAVE: [], PLAIN: [0.0188]},                  # there, and empty
    {"engine.consume_block": [0.001]},            # the parent's ring
    {},                                           # an empty window
], ids=["no-wave", "no-plain", "empty-list", "parent", "empty-window"])
def test_count_share_without_both_names_is_none(stages):
    ctx = {"run": {"window": (0.0, 25.5), "stages": stages}}
    assert stage_count_share.read(ctx, stage=WAVE, beside=PLAIN) is None


def test_count_share_of_a_run_without_stages_is_none():
    assert stage_count_share.read({"run": {"window": (0.0, 1.0)}},
                                  stage=WAVE, beside=PLAIN) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_file_names_a_reader_and_cells_that_exist(name):
    reader, reads, cells, moves = NEW[name]
    spec = metric_spec(name)
    assert set(spec) == {"reader", "args"} and spec["reader"] == reader
    # The reader exists and takes exactly the file's arguments.
    read = plugin("readers", reader).read
    params = inspect.signature(read).parameters
    assert set(spec["args"]) <= set(params) - {"ctx"}
    assert all(p in spec["args"] for p, v in params.items()
               if p != "ctx" and v.default is inspect.Parameter.empty)
    assert reads in spec["args"].values()
    # Its entry: at the end of the list with the cells it is read in,
    # each of which exists and reports the metric it moves.
    bench = manifest()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == cells and entry["moves"] == moves
    assert entry["source"] == "program_span"
    assert entry["layer"] == ("Serve front and admission"
                              if name.startswith("service.") else "Engine")
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"]
                              if m["name"] not in NEW}
    assert entry["unit"] == ("ms" if name.endswith("_ms") else "%")
    assert entry["better"] == ("higher" if name in (
        "engine.kept_token_share", "kv.slot_fill_share") else "lower")
    (moved,) = [m for m in bench["end_to_end"] if m["name"] == moves]
    known = {w["name"] for w in bench["workloads"]}
    assert set(cells) <= known and set(cells) <= set(moved["workloads"])
    for cell in cells:
        assert name in {m["name"] for m in Cell(cell).metrics["per_layer"]}


def test_new_entries_stand_at_the_end_of_the_list():
    names = [m["name"] for m in manifest()["per_layer"]]
    assert set(names[-len(NEW):]) == set(NEW)
    assert len(names) == len(set(names))
