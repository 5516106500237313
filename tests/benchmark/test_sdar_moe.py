"""The files of the cell `serve-sdar-eval-closed`: the configuration
against the catalog's numbers, the adapter, the plain reference (its
one-pass reading of many denoising steps against a pass each, its
generation loop against its own replay), the operations and bytes, the
runner's checks (a) and (c) on the CPU at the debug size with stand-ins
that carry each fault, the cell driven at debug width through its own
runner, and the new readers on a small recorded span set."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.flops import sdar_moe as flops
from benchmark.harness import spans as sp
from benchmark.harness.manifest import (ROOT, Cell, load_json, manifest,
                                        model_adapter)
from benchmark.readers import span_stat_quotient, span_stat_ratio
from benchmark.references import sdar_moe as reference
from benchmark.runners import serve_blocks
from tests.benchmark.test_runners import drive
from tests.models.test_sdar_moe import _faults as model_faults

CELL = "serve-sdar-eval-closed"
FILE = load_json(ROOT, "benchmark", "configs", "sdar-30b-a3b-serve.json")
ADAPTER = model_adapter(FILE)
# The catalog's `config` of SDAR-30B-A3B-Chat beside the model-configs
# guide, every key.
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


def debug_config():
    config = ADAPTER.debug(FILE)
    config["serve"] = {
        **config["serve"], "max_batch_size": 4, "max_seq_len": 128,
        "reference_prompt_lens": [44, 36, 24, 12],
        "probe_prompt_lens": [9, 6, 7, 4], "probe_total": 16}
    return config


CONFIG = debug_config()
CFG = ADAPTER.program_config(CONFIG)
HP = reference.hyper(CONFIG)


@pytest.fixture(scope="module")
def params():
    return ADAPTER.init(CFG, jax.random.PRNGKey(4))


def test_the_configuration_keeps_every_published_number():
    assert FILE["reduced"] == ["num_hidden_layers"]
    assert FILE["published"] == {"num_hidden_layers": 48}
    for key, value in CATALOG.items():
        assert FILE[key] == (6 if key == "num_hidden_layers" else value), key
    assert FILE["kind"] == "serve_blocks" and FILE["torch_dtype"] == "bfloat16"
    assert FILE["generation"]["block_length"] == 4
    assert FILE["generation"]["denoising_steps"] == 2
    assert FILE["generation"]["mask_token_id"] == 151669 < FILE["vocab_size"]
    assert FILE["deployment"]["chips"] == 8
    assert FILE["deployment"]["layers_held"] == list(range(6))
    assert 48 % FILE["num_hidden_layers"] == 0
    for key in ("logit_tolerance", "served_token_margin",
                "confidence_margin"):
        assert key + "_why" in FILE["serve"], key


def test_the_adapter_builds_the_programs_config():
    cfg = ADAPTER.program_config(FILE)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) \
        == (2048, 32, 4, 128)
    assert (cfg.n_experts, cfg.n_experts_per_token, cfg.hidden_dim) \
        == (128, 8, 768)
    assert cfg.experts_held is None and cfg.scoring == "softmax"
    assert cfg.norm_topk_prob and not cfg.tie_embeddings
    assert (cfg.block_length, cfg.denoising_steps, cfg.mask_token_id) \
        == (4, 2, 151669)
    assert ADAPTER.with_layers(cfg, 2) == dataclasses.replace(cfg, n_layers=2)
    small = ADAPTER.program_config(ADAPTER.debug(FILE))
    assert (small.dim, small.n_layers, small.vocab_size) == (64, 2, 512)
    assert FILE["hidden_size"] == 2048  # `debug` cut a copy


def test_parameters_and_resident_bytes_are_counted_from_shapes():
    cfg = ADAPTER.program_config(FILE)
    shapes = jax.eval_shape(lambda: ADAPTER.init(cfg, jax.random.PRNGKey(0)))
    counted = sum(x.size for x in jax.tree.leaves(shapes))
    assert counted == 4_361_055_744
    assert "4,361,055,744" in FILE["parameters"]
    layer = flops.attention_params(FILE) + 4352 + 2048 * 128 \
        + 128 * flops.expert_params(FILE)
    assert layer == 623_120_640 and "623,120,640" in FILE["parameters"]
    plan = FILE["serve"]
    cache = jax.eval_shape(lambda: ADAPTER.init_cache(
        cfg, plan["max_batch_size"], plan["max_seq_len"]))
    rows = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert rows == 805_306_368 and "805,306,368" in FILE["parameters"]
    # Over a quarter of a v5e's 16 GB by weights and cache alone.
    assert 2 * counted + rows > 0.5 * 16e9


def test_operations_and_bytes_are_counted_from_the_files_shapes():
    per_layer = 2 * 18_874_368 + 2 * 2048 * 128 + 2 * 8 * 4_718_592
    assert flops.matmul_flops_per_token(FILE) == 6 * per_layer
    # A token at position 5 sees the 8 keys up to its block's end.
    assert flops.prefill_flops_per_token(FILE, 6) \
        == 6 * per_layer + 6 * 4 * 8 * 32 * 128
    ops, nbytes = flops.flash_prefill_ops_and_bytes(FILE, 1, 1024, False)
    pairs = sum((i // 4 + 1) * 4 for i in range(1024))
    assert ops == 4 * pairs * 32 * 128
    assert nbytes == 1024 * 128 * (2 * 32 + 2 * 4) * 2
    whole = flops.decode_step_bytes(FILE, 32, 900)
    weights = 6 * (18_874_368 + 2048 * 128 + 128 * 4_718_592) \
        + 2048 * 151936
    assert whole == 2 * (weights + 32 * 6 * 2 * 4 * 128 * 900)
    assert flops.decode_step_bytes(FILE, 32, 900, touched=16) < whole / 4
    assert flops.train_flops_per_token(FILE, 1024) > 3 * 6 * per_layer


def test_one_pass_over_noised_copies_equals_a_pass_each(params):
    """`noised_logits`: two blocks of one sequence noised differently,
    read in one pass, against each run alone behind its own prefix."""
    tokens = np.random.default_rng(0).integers(1, 500, 20).astype(np.int32)
    mask = HP["mask_token_id"]
    first, second = tokens[8:12].copy(), tokens[12:16].copy()
    first[[0, 2]] = mask
    second[[1, 2, 3]] = mask
    with jax.default_matmul_precision("highest"):
        both = reference.noised_logits(params, tokens, [8, 12],
                                       [first, second], HP)
        layered = reference.noised_logits(params, tokens, [8, 12],
                                          [first, second], HP,
                                          layer_by_layer=True)
        alone = [reference.sequence_logits(
            params, jnp.asarray(np.concatenate([tokens[:start], block])),
            HP)[start:] for start, block in ((8, first), (12, second))]
    np.testing.assert_allclose(both, np.stack(alone), atol=1e-5)
    np.testing.assert_allclose(layered, both, atol=1e-5)


def test_replay_rebuilds_what_generate_was_shown(params):
    prompt = np.random.default_rng(1).integers(1, 500, 10).tolist()
    with jax.default_matmul_precision("highest"):
        tokens, steps = reference.generate(params, prompt, 14, HP)
        passes = reference.replay(params, prompt, tokens, steps, HP,
                                  layer_by_layer=False, room=9)
    # 10 = 8 + 2 known: a first block of 2 (one step), then three whole
    # blocks of two steps; every pass fixed the most confident open ones.
    assert [p["start"] for p in passes] == [8, 12, 12, 16, 16, 20, 20]
    assert serve_blocks.steps_follow_the_schedule(passes, 2)
    for p in passes:
        x0, fixed = reference.fix_most_confident(p["logits"], p["open"], 2)
        assert (fixed == p["fixed"]).all()
        assert (x0[fixed] == p["tokens"][fixed]).all()


def test_check_a_passes_the_program_and_fails_a_fault():
    """At the benchmark's weights; `tests/models/test_sdar_moe.py` holds
    every named fault at the plain ones."""
    seed = 2 ** 31 + 9
    err, positions = serve_blocks.check_against_reference(CONFIG, seed)
    assert err < 1e-5 and positions == 4 * (44 + 2 * 2 * 4)
    faults = model_faults()
    for name in ("logits shifted by one",
                 "a commit that keeps the denoising pass's keys"):
        assert serve_blocks.check_against_reference(
            CONFIG, seed, served=faults[name])[0] > 1e-3, name


@pytest.fixture(scope="module")
def answered(params):
    asked = serve_blocks.probes(CONFIG, 5)
    with jax.default_matmul_precision("highest"):
        answers = [list(zip(*reference.generate(
            params, body["prompt_ids"], body["max_tokens"], HP)))
            for body in asked]
    return asked, answers


def test_check_c_passes_the_references_own_answers(params, answered):
    asked, answers = answered
    assert [len(body["prompt_ids"]) % 4 for body in asked] == [1, 2, 3, 0]
    got = serve_blocks.check_served_blocks(CONFIG, params, asked, answers)
    assert got["schedule"] and got["same"] == 1.0
    assert got["token_short"] < 1e-6 and got["confidence_short"] < 1e-6
    assert got["compared"] == sum(body["max_tokens"] for body in asked)


def _with(answers, probe, at, token=None, step=None):
    changed = [list(a) for a in answers]
    old = changed[probe][at]
    changed[probe][at] = (old[0] if token is None else token,
                          old[1] if step is None else step)
    return changed


def test_check_c_fails_a_wrong_token_a_wrong_order_and_a_wrong_schedule(
        params, answered):
    asked, answers = answered
    check = lambda a: serve_blocks.check_served_blocks(  # noqa: E731
        CONFIG, params, asked, a)
    plan = FILE["serve"]  # the cell's own margins, not the cut run's
    # A token that is not the reference's choice lies far under it.
    wrong = check(_with(answers, 3, 5, token=(answers[3][5][0] + 1) % 500))
    assert wrong["token_short"] > plan["served_token_margin"]
    assert wrong["same"] < 1.0
    # Every whole block fixed in the wrong order, the least confident
    # positions first: the passes then fixed positions the reference is
    # less sure of than those they passed over, by more than the cell
    # allows, and the steps are still the schedule's.
    heads = [-len(body["prompt_ids"]) % 4 for body in asked]
    swapped = check([a[:h] + [(t, 1 - s) for t, s in a[h:]]
                     for a, h in zip(answers, heads)])
    assert swapped["confidence_short"] > plan["confidence_margin"]
    assert swapped["schedule"]
    block = answers[3][4:8]
    late = next(i for i, (_, s) in enumerate(block) if s == 1) + 4
    # Three positions in one step is not the schedule.
    assert not check(_with(answers, 3, late, step=0))["schedule"]
    # Nor is a pass that fixed nothing (a block all of step 1): it is
    # judged, not a crash.
    none = check([a[:h] + [(t, 1) for t, _ in a[h:]]
                  for a, h in zip(answers, heads)])
    assert not none["schedule"] and none["compared"] > 0
    # An answer cut short, or an error in its place, fails everything.
    short = [a[:-1] if i == 1 else a for i, a in enumerate(answers)]
    assert check(short)["compared"] == 0
    assert check(["RuntimeError('x')"] + answers[1:])["token_short"] \
        == float("inf")


def cut_serve_blocks(config, mix):
    config["serve"].update(
        max_batch_size=4, max_seq_len=128,
        reference_prompt_lens=[16, 12, 8, 4], reference_block_steps=2,
        logit_tolerance=1e-4, probe_prompt_lens=[9, 6, 7, 4], probe_total=16,
        served_token_margin=1e-4, confidence_margin=1e-4)
    mix["pairs"] = [[8 + 7 * (i % 9), 4 + (5 * i) % 13] for i in range(16)]
    mix.update(ramp_s=1, drain_s=5, trace_s=1)


def test_the_cell_at_debug_width_through_its_own_runner(tmp_path):
    cell = Cell(CELL)
    cell.config = ADAPTER.debug(cell.config)
    cut_serve_blocks(cell.config, cell.traffic)
    run, metrics = drive(cell, tmp_path, seconds=3.0)
    assert all(run["checks"].values()), run["log"]
    assert len(run["checks"]) == 8
    assert run["failed"] == 0 and run["attempted"] >= 5
    e2e, per = metrics["end_to_end"], metrics["per_layer"]
    assert set(e2e) == {"serve_out_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in e2e.values())
    assert per["service.slot_wait_p50_ms.closed"]["value"] >= 0
    assert per["diffusion.prefill_to_block_p50_ms"]["value"] > 0
    assert per["diffusion.block_gap_p50_ms"]["value"] > 0
    assert per["setup.compiles_in_window"]["value"] == 0
    totals = run["totals"]
    forwards = totals["slot_forwards_denoise"] + totals["slot_forwards_commit"]
    # (4/3 at length; the mix's answers of a few blocks end on a block
    # that no commit follows.)
    assert 1.2 < totals["tokens_fixed"] / forwards < 1.6


def test_the_cells_files_are_what_the_issue_names():
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.config["family"] == "sdar_moe"
    assert cell.entry["traffic"] == "closed-eval-unshared"
    assert cell.entry["config"] == "sdar-30b-a3b-serve"
    assert len(cell.entry["why"]) <= 200
    assert cell.runner() is serve_blocks
    plan = cell.config["serve"]
    assert plan["max_batch_size"] == 32 and plan["max_seq_len"] == 2048
    from ray_tpu.serve.llm import prefill_bucket
    for n in plan["reference_prompt_lens"]:
        assert n % 4 == 0 and prefill_bucket(n) != n
    assert sorted(n % 4 for n in plan["probe_prompt_lens"]) == [0, 1, 2, 3]
    assert plan["probe_total"] % 4 == 0
    assert max(plan["probe_prompt_lens"]) < plan["probe_total"] \
        < plan["max_seq_len"]
    e2e = {m["name"] for m in cell.metrics["end_to_end"]}
    assert e2e == {"serve_out_tokens_per_s", "setup_s"}
    reported = {m["name"] for m in cell.metrics["per_layer"]}
    joined = {
        "service.slot_wait_p50_ms.closed", "engine.tokens_per_decode_step",
        "step.prefill_device_ms", "device.hbm_peak_share.serve",
        "engine.admit_share", "engine.flush_wait_share",
        "device.idle_in_admit_share", "device.idle_in_decode_loop_share",
        "device.idle_in_idle_wait_share", "engine.decode_slot_occupancy",
        "engine.prefill_pad_share", "service.front_ttft_self_p50_ms",
        "engine.kv_readback_share"}
    # Three lists the benchmark's own tests hold to the cells of PR 36
    # (`test_gap_and_lag_metrics.py`) stay as they are; two of them
    # have twins here, `diffusion.prefill_to_block_p50_ms` and
    # `diffusion.kept_token_share`.
    assert not {"engine.prefill_to_token_p50_ms", "engine.kept_token_share",
                "kv.slot_fill_share"} & reported
    new = ["diffusion.tokens_per_forward", "diffusion.commit_forward_share",
           "diffusion.block_gap_p50_ms", "diffusion.prefill_to_block_p50_ms",
           "diffusion.kept_token_share", "step.block_device_ms",
           "step.block_attention_share", "step.block_expert_share",
           "step.block_experts_read_share",
           "kernel.flash_block_prefill_roofline"]
    assert joined | set(new) <= reported
    # Nothing that moves the token gap, which the cell does not report.
    bench = manifest()
    assert not [m["name"] for m in bench["per_layer"]
                if m["moves"] == "serve_tpot_p50_ms"
                and CELL in m.get("workloads", [])]
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(new):] == new
    assert all(m["workloads"] == [CELL] and
               m["moves"] == "serve_out_tokens_per_s"
               for m in bench["per_layer"][-len(new):])
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "sdar-30b-a3b-serve"
    cells = bench["workloads"]
    assert 4 * sum(c["chips"] == 4 for c in cells) <= len(cells) <= 24


def test_the_new_readers_on_a_recorded_span_set(monkeypatch):
    """Three hand-overs of a block engine, as the trace's host plane
    holds them, and one of the parent's, which has no such sums."""
    ms = 1_000_000
    seen = [("engine.consume_block", 0, ms, {
                "slot_forwards": 32, "slot_forwards_commit": 10,
                "slot_forwards_denoise": 22, "tokens_fixed": 44,
                "experts_touched": 700, "experts_held_steps": 768}),
            ("engine.consume_block", 2 * ms, ms, {
                "slot_forwards": 32, "slot_forwards_commit": 12,
                "slot_forwards_denoise": 20, "tokens_fixed": 40,
                "experts_touched": 720, "experts_held_steps": 768}),
            ("engine.consume_block", 4 * ms, ms, {
                "slot_forwards": 32, "slot_forwards_commit": 10,
                "slot_forwards_denoise": 22, "tokens_fixed": 44,
                "experts_touched": 740, "experts_held_steps": 768}),
            ("engine.consume_block", 6 * ms, ms, {"kept": 32})]
    monkeypatch.setattr(sp, "xplane_path", lambda ctx: "a.xplane.pb")
    monkeypatch.setattr(
        sp, "annotations",
        lambda path, names: [a for a in seen if a[0] in names])
    ctx = {"trace": {}}
    spec = {m["name"]: m for m in Cell(CELL).metrics["per_layer"]}
    tokens = spec["diffusion.tokens_per_forward"]
    assert tokens["reader"] == "span_stat_quotient"
    assert span_stat_quotient.read(ctx, **tokens["args"]) \
        == pytest.approx(128 / 96)
    commits = spec["diffusion.commit_forward_share"]
    assert commits["reader"] == "span_stat_ratio"
    assert span_stat_ratio.read(ctx, **commits["args"]) \
        == pytest.approx(100 * 32 / 96)
    read = spec["step.block_experts_read_share"]
    assert span_stat_ratio.read(ctx, **read["args"]) \
        == pytest.approx(100 * 2160 / 2304)
    # The parent's program has the span and none of the sums; a run
    # without a trace has nothing to read.
    monkeypatch.setattr(sp, "annotations", lambda path, names: seen[3:])
    assert span_stat_quotient.read(ctx, **tokens["args"]) is None
    assert span_stat_ratio.read(ctx, **commits["args"]) is None
    assert span_stat_quotient.read({"trace": None}, **tokens["args"]) is None


def test_a_traced_stretch_is_never_shorter_than_its_profile(monkeypatch):
    """The device's events span 6.02 s where the host's marks lie 6 s
    apart: the stretch grows to the profile, and the seconds an
    operation ran (`busy_seconds`) fit into it; a profile that spans
    less leaves the host's marks alone."""
    from benchmark.harness import trace as tr

    ns = 1_000_000_000
    events = {"devices": {"/device:TPU:0": {"modules": [], "ops": [
        ["fusion", 5 * ns, 3 * ns], ["gmm", 8 * ns, int(3.02 * ns)]]}},
        "host": {}}
    monkeypatch.setattr(tr, "find_xplane", lambda trace_dir: "a.xplane.pb")
    monkeypatch.setattr(tr, "load_xplane", lambda path: events)
    out = {"trace_t0": 100.0, "trace_t1": 106.0}
    widened = serve_blocks.widen_to_the_profile(out, "trace")
    assert widened == pytest.approx(0.02, abs=1e-5)
    assert out["trace_t0"] == 100.0
    assert out["trace_t1"] == pytest.approx(106.02, abs=1e-5)
    assert tr.busy_seconds(events) <= out["trace_t1"] - out["trace_t0"]
    out = {"trace_t0": 100.0, "trace_t1": 107.0}
    assert serve_blocks.widen_to_the_profile(out, "trace") == 0.0
    assert out == {"trace_t0": 100.0, "trace_t1": 107.0}
    # A profile that overruns the marks by more than the profiler's
    # slack is a mismatched stretch, and fails the run.
    with pytest.raises(AssertionError, match="more than the traced"):
        serve_blocks.widen_to_the_profile(
            {"trace_t0": 100.0, "trace_t1": 105.9}, "trace")
