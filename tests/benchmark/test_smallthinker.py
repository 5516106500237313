"""The family `smallthinker` as files alone: the configuration keeps
every published number but the three it says it cut, its adapter builds
the program's config and a debug copy whose window a debug sequence
crosses, `flops/smallthinker.py` agrees with counts by hand, the reader
of the windowed flash kernels' roofline reads a hand-made trace, and
the cell's files are found by name. Membership only: nothing here pins
an entry's place in a list."""

import json

import jax.numpy as jnp
import pytest

from benchmark.harness import device as hw
from benchmark.harness import spans as sp
from benchmark.harness.manifest import (ROOT, Cell, load_json, manifest,
                                        model_adapter, plugin)
from benchmark.readers import flash_window_roofline as reader
from benchmark.runners import train as train_runner

NAME = "smallthinker-21b-a3b-train"
CELL = "train-smallthinker-16k-1chip"
FILE = load_json(ROOT, "benchmark", "configs", NAME + ".json")
ADAPTER = model_adapter(FILE, train_runner.NEEDS)
FLOPS = plugin("flops", FILE["flops"])


def test_the_configuration_keeps_every_published_number():
    entry = next(c for c in manifest()["configs"] if c["name"] == NAME)
    assert entry["reduced"] == FILE["reduced"]
    assert set(FILE["reduced"]) == {"num_hidden_layers",
                                    "moe_num_primary_experts", "vocab_size"}
    assert FILE["published"] == {"num_hidden_layers": 52,
                                 "moe_num_primary_experts": 64,
                                 "vocab_size": 151936}
    assert entry["source"] == FILE["source"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    widths = {"hidden_size": 2560, "head_dim": 128,
              "num_attention_heads": 28, "num_key_value_heads": 4,
              "moe_ffn_hidden_size": 768,
              "moe_num_active_primary_experts": 6,
              "sliding_window_size": 4096, "max_position_embeddings": 16384,
              "rope_theta": 1500000, "rms_norm_eps": 1e-6,
              "norm_topk_prob": True,
              "moe_primary_router_apply_softmax": True,
              "tie_word_embeddings": False}
    assert {k: FILE[k] for k in widths} == widths
    assert (FILE["num_hidden_layers"], FILE["moe_num_primary_experts"],
            FILE["vocab_size"]) == (4, 16, 37984)
    assert FILE["vocab_size"] * 4 == 151936
    # The published layouts whole; the layers held are their head.
    assert FILE["sliding_window_layout"] == FILE["rope_layout"] \
        == [0, 1, 1, 1] * 13
    share = FILE["deployment"]
    assert share["chips"] == 4 and share["layers_held"] == [0, 1, 2, 3]
    assert share["experts_held"] == [0, 16] and share["router_width"] == 64
    assert share["sequences_per_step"] == FILE["train"]["sequences_per_chip"]
    assert len(FILE["assumed"]) >= 6
    plan = FILE["train"]
    assert plan["mesh"] == {"data": 1, "fsdp": 1} and plan["remat"] is True
    assert plan["blocks"] == 4 and plan["sequences_per_chip"] in (2, 4)
    assert plan["learning_rate_why"] and plan["loss_tolerance_why"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(line) for line in open(catalog)]
    except OSError:
        pytest.skip("the catalog is not beside this checkout")
    row = next(r for r in rows if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert FILE["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if FILE.get(k) != v)
    assert differs == sorted(FILE["reduced"])


def test_the_adapter_builds_the_programs_config():
    cfg = ADAPTER.program_config(FILE)
    assert type(cfg).__name__ == "SmallThinkerConfig"
    assert (cfg.dim, cfg.n_layers, cfg.vocab_size, cfg.dtype) == (
        2560, 4, 37984, jnp.bfloat16)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (28, 4, 128)
    assert (cfg.n_experts, cfg.experts_held, cfg.n_experts_per_token,
            cfg.hidden_dim, cfg.expert_kind) == (64, (0, 16), 6, 768, "reglu")
    assert cfg.layer_kinds == ("full", "window", "window", "window")
    assert cfg.runs() == [("full", 1), ("window", 3)]
    assert (cfg.sliding_window, cfg.rope_theta, cfg.norm_eps,
            cfg.max_seq_len) == (4096, 1.5e6, 1e-6, 16384)
    assert cfg.norm_topk_prob and not cfg.tie_embeddings
    assert cfg.num_params() == 656_529_920
    assert ADAPTER.with_remat(cfg, False).remat is False
    # Two periods are two more runs; the whole file's experts are no share.
    deeper = ADAPTER.program_config({**FILE, "num_hidden_layers": 8})
    assert deeper.runs() == [("full", 1), ("window", 3)] * 2
    whole = ADAPTER.program_config({**FILE, "moe_num_primary_experts": 64})
    assert whole.experts_held is None and whole.n_experts == 64


def test_debug_keeps_the_familys_shape():
    small = ADAPTER.debug(FILE)
    assert FILE["hidden_size"] == 2560  # `debug` cut a copy
    cfg = ADAPTER.program_config(small)
    assert (cfg.dim, cfg.n_layers, cfg.vocab_size) == (64, 4, 512)
    assert cfg.layer_kinds == ("full", "window", "window", "window")
    # `test_runners.py` trains it on sequences of 32: a row's window
    # ends inside them, and the head size is not hidden over heads.
    assert cfg.sliding_window < 32
    assert cfg.head_dim == 32 != cfg.dim // cfg.n_heads
    assert cfg.experts_held == (0, 4) and cfg.n_experts == 8
    hp = plugin("references", small["reference"]).hyper(small)
    assert hp["held"] == (0, 4) and hp["n_experts"] == 8
    assert hp["layers"] == ((False, False),) + ((True, True),) * 3


def test_operations_and_bytes_by_hand():
    d, hd, h, hkv, f = 2560, 128, 28, 4, 768
    seq, window = 16384, 4096
    full = seq * (seq + 1) // 2
    windowed = window * (window + 1) // 2 + (seq - window) * window
    assert FLOPS.pairs_seen(seq) == full == 134_225_920
    assert FLOPS.pairs_seen(seq, window) == windowed == 58_722_304
    assert FLOPS.pairs_seen(100, 4096) == FLOPS.pairs_seen(100) == 5050
    assert FLOPS.pairs_seen(seq, 1) == seq
    assert FLOPS.held_pairs_per_token(FILE) == 6 * 16 / 64
    projections = 2 * d * h * hd + 2 * d * hkv * hd
    assert FLOPS.matmul_params_per_token(FILE) == 4 * (
        projections + d * 64 + 1.5 * 3 * d * f) + d * 37984
    attention = 4 * h * hd * (full + 3 * windowed) / seq
    assert FLOPS.attention_flops_per_token(FILE, seq) == attention
    forward = 2 * FLOPS.matmul_params_per_token(FILE) + attention
    assert FLOPS.train_flops_per_token(FILE, seq) == 3 * forward
    # ISSUE 47's count: 706 M a token forward, attention 38 % of it, a
    # windowed layer's 44 % of a full one's, the sliced head 28 %.
    assert round(forward / 1e6) == 706
    assert round(100 * attention / forward) == 38
    assert round(100 * windowed / full) == 44
    assert round(100 * 2 * d * 37984 / forward) == 28
    for kernel, products, q_like, k_like, rows in (
            ("flash_fwd", 2, 2, 2, 1), ("flash_bwd_dq", 3, 3, 2, 2),
            ("flash_bwd_dkv", 4, 2, 4, 2)):
        shapes = dict(batch=4, seq=seq, n_heads=h, n_kv_heads=hkv,
                      head_dim=hd)
        ops, moved = FLOPS.flash_ops_and_bytes(kernel, **shapes)
        assert ops == products * 2 * 4 * h * hd * full
        assert moved == 4 * seq * (q_like * h * hd * 2 + k_like * hkv * hd * 2
                                   + rows * h * 4)
        assert FLOPS.flash_ops_and_bytes(kernel, window=window, **shapes) \
            == (products * 2 * 4 * h * hd * windowed, moved)
        # Without a window it is the other train cells' count, within
        # the diagonal's half (theirs takes seq^2 / 2 pairs).
        theirs, same = plugin("flops", "decoder").flash_ops_and_bytes(
            kernel, **shapes)
        assert same == moved and 0 < ops - theirs < ops / seq
    ops, moved = FLOPS.grouped_matmul_ops_and_bytes(FILE, 4 * seq)
    assert ops == 2 * 98304 * d * f
    assert moved == 2 * (98304 * (d + f) + 16 * d * f)
    assert FLOPS.least_seconds(ops, moved, hw.peaks("TPU v5 lite"))[1] \
        == "compute"


def test_flash_window_roofline_of_a_hand_made_trace(monkeypatch, capsys):
    """One step: the full layer's three kernels and the windowed
    layers', told apart by the scope path, forward and backward."""
    ms = 1_000_000
    path = "jit(step_fn)/{}while/body/closed_call/attn/{}{}/pallas_call"
    names, calls = {}, {}
    at = 0
    for kernel, back in (("flash_fwd", "jvp()/"),
                         ("flash_bwd_dq", "transpose(jvp())/"),
                         ("flash_bwd_dkv", "transpose(jvp())/")):
        calls[kernel] = []
        for i, scope in enumerate(("", "window/")):
            name = f"{kernel}.{i}"
            names[(9, name)] = path.format(back, scope, kernel)
            calls[kernel].append((name, at, (60 - 20 * i) * ms,
                                  (4, 28, 16384, 128)))
            at += 100 * ms
    events = {"devices": {"/device:TPU:0": {"ops": [], "modules": [
        ["jit_step_fn(9)", 0, at]]}}, "host": {}}
    monkeypatch.setattr(sp, "xplane_path", lambda ctx: "unused")
    monkeypatch.setattr(sp, "op_names", lambda path: names)
    monkeypatch.setattr(reader, "kernel_calls",
                        lambda path, kernel: calls[kernel])
    cell = Cell(CELL)
    ctx = {"trace": events, "cell": cell,
           "device": {"count": 1, "peaks": hw.peaks("TPU v5 lite")}}
    args = {m["name"]: m for m in cell.metrics["per_layer"]}[
        "kernel.flash_window_roofline"]["args"]
    shapes = dict(batch=4, seq=16384, n_heads=28, n_kv_heads=4, head_dim=128)
    least = sum(FLOPS.flash_ops_and_bytes(k, window=w, **shapes)[0]
                for k in calls for w in (None, 4096)) / 197e12
    assert reader.read(ctx, **args) == pytest.approx(
        100 * least / 0.300, rel=1e-6)
    out = capsys.readouterr().out
    assert out.count("a window") == out.count("every key before") == 3
    # A program without the kernels, a file without a window (the
    # parent's cells), no trace: nothing, and nothing raised.
    monkeypatch.setattr(reader, "kernel_calls", lambda path, kernel: [])
    assert reader.read(ctx, **args) is None
    assert reader.read({**ctx, "cell": Cell("train-olmoe-1chip")},
                       **args) is None
    assert reader.read({"trace": None}, **args) is None


def test_the_cells_files_are_found_by_name():
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.config["family"] == "smallthinker"
    assert cell.entry["traffic"] == "pretrain-16k"
    assert cell.traffic == {"loop": "job", "seq": 16384, "trace_s": 6,
                            "why": cell.traffic["why"]}
    assert cell.config["max_position_embeddings"] == cell.traffic["seq"]
    assert cell.config["sliding_window_size"] < cell.traffic["seq"]
    assert cell.runner() is train_runner
    assert plugin("references", cell.config["reference"]).__name__ \
        == "benchmark.references.smallthinker"
    reported = {m["name"]: m for group in cell.metrics.values()
                for m in group}
    assert {"setup_s", "train_tokens_per_s_per_chip", "ingest.wait_share",
            "ingest.batch_wait_share", "step.train_device_ms", "step.mfu",
            "device.hbm_peak_share.train", "step.train_optimizer_share",
            "step.train_expert_share", "moe.dispatch_share",
            "moe.expert_load_max_over_mean", "setup.compile_s",
            "setup.compiles_in_window", "kernel.flash_window_roofline",
            "kernel.grouped_matmul_roofline.held", "step.train_window_share",
            "step.train_attention_share", "step.train_router_share",
            "moe.held_pair_share.train"} <= set(reported)
    # Their readers would misread this cell (ISSUE 47 says how).
    for unfit in ("kernel.flash_roofline", "kernel.grouped_matmul_roofline",
                  "step.train_backward_share"):
        assert unfit not in reported
    assert reported["kernel.grouped_matmul_roofline.held"]["args"]["flops"] \
        == "smallthinker"
    assert reported["moe.held_pair_share.train"]["args"] == {
        "span": "train.step_dispatch", "num": "pairs_held",
        "den": "pairs_routed"}
    assert reported["step.train_attention_share"]["args"]["any_of"] \
        == ["window", "attn"]
    bench = manifest()
    new = {m["name"] for m in bench["per_layer"]
           if m.get("workloads") == [CELL]}
    assert new == {"kernel.flash_window_roofline",
                   "kernel.grouped_matmul_roofline.held",
                   "step.train_window_share", "step.train_attention_share",
                   "step.train_router_share", "moe.held_pair_share.train"}
    assert all(m["moves"] == "train_tokens_per_s_per_chip"
               for m in bench["per_layer"] if m["name"] in new)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert CELL in {w["name"] for w in bench["workloads"]}
    assert len(cell.entry["why"]) <= 200
