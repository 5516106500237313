"""The family `olmo_hybrid` as files alone: its configuration against
the catalog's row, its adapter building the program's config, its
reference agreeing with the program at debug widths through the serving
runner's own check, its counts against the program's own shapes, its
traffic regenerated from what the file states, the delta rule's
roofline reader on a hand-made trace, and the cell's files what
`BENCHMARK.json` and the issue say."""

import json
import math
import os
import statistics

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import traffic
from benchmark.harness.manifest import (ROOT, Cell, load_json, manifest,
                                        model_adapter, plugin)
from benchmark.runners import serve as serve_runner

NAME = "olmo-hybrid-7b-serve"
CELL = "serve-olmohybrid-eval-closed"
FILE = load_json(ROOT, "benchmark", "configs", NAME + ".json")
ADAPTER = model_adapter(FILE, serve_runner.NEEDS)
FLOPS = plugin("flops", FILE["flops"])
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# The published widths, as the catalog's row has them.
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_attention_heads": 30,
    "num_key_value_heads": 30, "hidden_act": "silu",
    "max_position_embeddings": 65536, "attention_bias": False,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
    "layer_types": ["linear_attention"] * 3 + ["full_attention"]}


def nbytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def debug_config(lens=(45, 39, 26, 19)):
    config = ADAPTER.debug(FILE)
    config["serve"] = {**config["serve"], "max_seq_len": 128,
                       "reference_prompt_lens": list(lens),
                       "reference_decode_steps": 8}
    return config


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's row under the same key, but the one
    in `reduced`, which is no width."""
    entry = next(c for c in manifest()["configs"] if c["name"] == NAME)
    assert entry["reduced"] == FILE["reduced"] == ["num_hidden_layers"]
    assert FILE["published"] == {"num_hidden_layers": 32}
    assert FILE["num_hidden_layers"] == 12
    assert entry["source"] == FILE["source"] \
        == "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    want = {**PUBLISHED, "layer_types": PUBLISHED["layer_types"] * 8}
    assert {k: FILE[k] for k in want} == want
    share = FILE["deployment"]
    assert share["layers_held"] == list(range(12)) and share["chips"] == 3
    assert FILE["not_served"] == {}
    assert all(isinstance(FILE[k], str) and FILE[k]
               for k in ("parameters",)) and len(FILE["assumed"]) >= 6
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Olmo-Hybrid-7B")
        assert row["source_url"] == FILE["source"]
        for key, value in row["config"].items():
            if key not in FILE["reduced"]:
                assert FILE[key] == value, key
        assert row["config"]["num_hidden_layers"] \
            == FILE["published"]["num_hidden_layers"]


def test_the_adapter_builds_the_programs_config():
    cfg = ADAPTER.program_config(FILE)
    assert type(cfg).__name__ == "OlmoHybridConfig"
    assert (cfg.dim, cfg.n_layers, cfg.vocab_size, cfg.dtype) == (
        3840, 12, 100352, jnp.bfloat16)
    assert cfg.layer_types == ("linear", "linear", "linear", "full") * 3
    assert cfg.chunk_size == type(cfg)().chunk_size  # the program's own
    small = ADAPTER.program_config(ADAPTER.debug(FILE))
    assert (small.dim, small.n_layers, small.vocab_size,
            small.chunk_size) == (60, 8, 512, 8)
    assert small.delta_key_dim != small.delta_value_dim
    assert FILE["hidden_size"] == 3840  # `debug` cut a copy
    args, kwargs = ADAPTER.deployment_args(cfg, len)
    assert args == (cfg, len) and kwargs == {}


def test_prefill_and_decode_through_the_cache_match_the_reference():
    err, positions = serve_runner.check_against_reference(
        debug_config(), seed=2 ** 31 + 9)
    assert positions == 4 * 53 and err < 2e-6


@pytest.mark.parametrize("fault", ["pad absorbed", "beta without its 2",
                                   "gate before the norm",
                                   "norm on the input"])
def test_the_runners_check_fails_a_fault(fault):
    from tools import glm_logit_check
    served = glm_logit_check.olmo_faults(
        ADAPTER.cached_forward, ADAPTER.init_cache)[fault]
    err, _ = serve_runner.check_against_reference(
        debug_config(), seed=2 ** 31 + 9, served=served)
    assert err > 1e-3


def test_the_reference_steps_a_sequence_layer_by_layer():
    config = debug_config()
    reference = plugin("references", config["reference"])
    cfg = ADAPTER.program_config(config)
    params = ADAPTER.init(cfg, jax.random.PRNGKey(3))
    hp = reference.hyper(config)
    sequences = [jnp.asarray(np.random.default_rng(i).integers(
        0, 512, n), jnp.int32) for i, n in enumerate((17, 30))]
    by_layer = reference.logits_layer_by_layer(params, sequences, hp)
    for tokens, got in zip(sequences, by_layer):
        np.testing.assert_allclose(
            got, reference.sequence_logits(params, tokens, hp), atol=1e-5)
    assert len(list(reference.blocks_of(params))) == cfg.n_layers == 8
    # Plain `jax.numpy`: nothing of the program is imported.
    with open(reference.__file__) as f:
        assert "ray_tpu" not in f.read().split('"""', 2)[2]


def test_parameters_and_resident_bytes_are_counted_from_shapes():
    """The published depth lands on 7.43 B; the cut is 3.268 B, 6.54 GB,
    and with the cell's cache 10.21 GB resident."""
    from ray_tpu.models import olmo_hybrid
    whole = jax.eval_shape(lambda: olmo_hybrid.init_params(
        olmo_hybrid.OlmoHybridConfig(), jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(whole)) == 7_430_870_688
    cfg = ADAPTER.program_config(FILE)
    params = jax.eval_shape(lambda: ADAPTER.init(cfg, jax.random.PRNGKey(0)))
    held = sum(x.size for x in jax.tree.leaves(params))
    assert held == 3_268_268_508 and nbytes(params) == 6_536_538_096
    assert "3,268,268,508" in FILE["parameters"]
    assert "7,430,870,688" in FILE["parameters"]
    # The matrices a token is multiplied with, a layer of each kind.
    linear, full = params["runs"][0], params["runs"][1]
    matrices = ("wq", "wk", "wv", "wg", "wa", "wb", "wo")
    assert FLOPS.delta_params(FILE) == sum(
        linear[k].size // 3 for k in matrices) == 88_704_000
    assert FLOPS.attention_params(FILE) == sum(
        full[k].size for k in ("wq", "wk", "wv", "wo")) == 4 * 3840 ** 2
    assert FLOPS.swiglu_params(FILE) == sum(
        full[k].size for k in ("w1", "w2", "w3"))
    plan = FILE["serve"]
    slots, rows = plan["max_batch_size"], plan["max_seq_len"]
    assert (slots, rows) == (32, 2048)
    cache = jax.eval_shape(
        lambda: olmo_hybrid.init_cache(cfg, slots, rows))
    state = [x for x, s in zip(jax.tree.leaves(cache), jax.tree.leaves(
        olmo_hybrid.state_leaves(cache))) if s]
    keys = [x for x, s in zip(jax.tree.leaves(cache), jax.tree.leaves(
        olmo_hybrid.state_leaves(cache))) if not s]
    assert sorted(x.shape[2:] for x in state) == sorted(
        3 * [(30, 96, 192), (3, 2880), (3, 2880), (3, 5760)])
    assert [x.shape for x in keys] == 6 * [(1, 32, 2048, 3840)]
    # 20.5 MB of state a slot, 46.08 KB of keys and values a token.
    assert nbytes(state) // slots == 9 * FLOPS.state_bytes_per_slot(FILE) \
        == 9 * (30 * 96 * 192 * 4 + 3 * 11520 * 2) == 20_528_640
    assert nbytes(keys) // (slots * rows) \
        == 3 * FLOPS.kv_bytes_per_token(FILE) == 46_080
    resident = nbytes(params) + nbytes(cache)
    assert resident == 10_213_353_456 and round(resident / 1e9, 2) == 10.21


def test_operations_and_bytes_are_counted_from_the_files_shapes():
    # A decode step reads every matrix held once (all parameters but
    # the embedding, the norms, the convolutions, A and dt's bias),
    # reads and writes every slot's state, and reads its keys.
    vectors = 9 * (2 * 3840 + 4 * 11520 + 2 * 30 + 192) \
        + 3 * (2 * 3840 + 2 * 3840) + 3840
    matrices = 3_268_268_508 - 100352 * 3840 - vectors
    assert FLOPS.decode_step_bytes(FILE, 32, 0) \
        == 2 * matrices + 32 * 2 * 20_528_640
    assert FLOPS.decode_step_bytes(FILE, 32, 930) \
        - FLOPS.decode_step_bytes(FILE, 32, 0) == 32 * 930 * 46_080
    assert FLOPS.delta_update_bytes(FILE, 32) \
        == 2 * 32 * 9 * 4 * 30 * 96 * 192 == 1_274_019_840
    # Three full layers' two products a key.
    near, far = (FLOPS.prefill_flops_per_token(FILE, n) for n in (500, 1500))
    assert far - near == 3 * 2 * 2 * 1000 * 3840
    assert FLOPS.train_flops_per_token(FILE, 2048) \
        == 3 * FLOPS.prefill_flops_per_token(FILE, 1024)
    # The delta rule over nine layers: 7 x dk x dv a head and token; q,
    # k, v, a, b in and o out a token, the state in and out a call.
    ops, moved = FLOPS.delta_scan_ops_and_bytes(FILE, 1000, 2)
    assert ops == 9 * 1000 * 7 * 30 * 96 * 192
    assert moved == 9 * (1000 * ((2 * 2880 + 2 * 5760) * 2 + 2 * 30 * 4)
                         + 2 * 2 * 30 * 96 * 192 * 4)
    assert FLOPS.delta_scan_ops_and_bytes(FILE, 0, 0) == (0, 0)
    # Neither knows the program's chunk: the file has none.
    assert "chunk" not in json.dumps({k: v for k, v in FILE.items()
                                      if k != "serve"})


def _quantiles(median, sigma, lo, hi, n=64):
    """The n stratified quantiles of a log-normal's mass inside
    [lo, hi]."""
    normal = statistics.NormalDist()
    mu = math.log(median)
    a, b = (normal.cdf((math.log(x) - mu) / sigma) for x in (lo, hi))
    return [round(math.exp(mu + sigma * normal.inv_cdf(
        a + (i + 0.5) / n * (b - a)))) for i in range(n)]


@pytest.mark.parametrize("mix", ["closed-eval-unshared",
                                 "closed-reason-unshared",
                                 "closed-rag-unshared"])
def test_the_traffic_files_pairs_are_what_it_states(mix):
    """The pairs regenerated from the file's `stated` block: the new
    mix, and the two it was built as."""
    mix = load_json(ROOT, "benchmark", "traffic", mix + ".json")
    said = mix["stated"]
    prompts = _quantiles(**said["prompt_len"])
    answers = _quantiles(**said["output_len"])
    assert mix["pairs"] == [
        [p, min(answers[i * said["pair_stride"] % 64],
                said["max_total"] - p)] for i, p in enumerate(prompts)]


def test_the_cells_files_are_what_the_issue_names():
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.config["family"] == "olmo_hybrid"
    assert cell.entry["traffic"] == "closed-eval-unshared"
    assert len(cell.entry["why"]) <= 200
    mix = cell.traffic
    assert mix["loop"] == "closed" and mix["clients_per_slot"] == 2
    assert mix["stated"]["prompt_len"] == {
        "median": 704, "sigma": 0.3, "lo": 384, "hi": 1280}
    assert mix["stated"]["output_len"] == {
        "median": 448, "sigma": 0.3, "lo": 256, "hi": 768}
    assert (mix["stated"]["pair_stride"], mix["stated"]["max_total"]) \
        == (27, 2040)
    assert (mix["ramp_s"], mix["drain_s"], mix["trace_s"]) == (10, 2, 6)
    plan = cell.config["serve"]
    assert plan["max_batch_size"] == 32 and plan["max_seq_len"] == 2048
    assert traffic.longest_prompt(mix) + max(
        o for _, o in traffic.length_pairs(mix)) < plan["max_seq_len"]
    # Prompt lengths of the check: no multiple of the chunk, no bucket.
    from ray_tpu.serve.llm import prefill_bucket
    chunk = ADAPTER.program_config(FILE).chunk_size
    for n in plan["reference_prompt_lens"]:
        assert n % chunk and prefill_bucket(n) != n
    assert plan["reference_layers"] == 4
    assert plan["reference_decode_steps"] >= 8
    assert max(plan["probe_prompt_lens"]) < plan["probe_total"] \
        < plan["max_seq_len"]
    reported = {m["name"] for group in cell.metrics.values() for m in group}
    new = ["step.decode_delta_share", "step.decode_delta_state_share",
           "step.prefill_delta_share", "kernel.delta_scan_roofline",
           "kernel.delta_update_roofline"]
    assert {"setup_s", "serve_out_tokens_per_s", "serve_tpot_p50_ms",
            "step.decode_attention_share", "step.decode_device_ms",
            "step.prefill_device_ms", "engine.prefill_pad_share",
            "service.front_ttft_self_p50_ms", *new} <= reported
    assert "step.decode_ssm_share" not in reported
    assert "step.decode_expert_share" not in reported
    # The five new metrics stand together, in this order, behind every
    # metric the benchmark had (52 of them), and list this cell. No
    # count of cells or metrics here: the next cell would fail it.
    names = [m["name"] for m in manifest()["per_layer"]]
    first = names.index(new[0])
    assert first >= 52 and names[first:first + 5] == new
    assert all(m["workloads"][0] == CELL
               for m in manifest()["per_layer"][first:first + 5])
    cells = manifest()["workloads"]
    assert 4 * sum(c["chips"] == 4 for c in cells) <= len(cells)


def test_delta_roofline_of_a_hand_made_trace(monkeypatch, capsys):
    """Three prefills dispatched, of which the trace saw two run (the
    first run in it was dispatched before it began), and four decode
    blocks of two steps."""
    from benchmark.harness import device as hw
    from benchmark.harness import spans as sp
    from benchmark.readers import delta_roofline as reader

    ms = 1_000_000
    names = {(7, "fusion.1"): "jit(_prefill_impl)/while/body/delta/"
                              "delta_scan/dot_general:",
             (7, "fusion.2"): "jit(_prefill_impl)/while/body/delta/"
                              "delta_norm/mul:",
             (8, "fusion.1"): "jit(_decode_impl)/while/body/delta/"
                              "delta_update/mul:",
             (8, "fusion.2"): "jit(_decode_impl)/while/body/attn/dot:"}
    prefills = [["jit__prefill_impl(7)", t * ms, 40 * ms]
                for t in (5, 100, 200)]
    blocks = [["jit__decode_impl(8)", t * ms, 30 * ms]
              for t in (50, 150, 250, 300)]
    ops = [["fusion.1", m[1] + ms, 10 * ms] for m in prefills] \
        + [["fusion.2", m[1] + 20 * ms, 5 * ms] for m in prefills] \
        + [["fusion.1", m[1] + ms, 6 * ms] for m in blocks] \
        + [["fusion.2", m[1] + 10 * ms, 9 * ms] for m in blocks]
    events = {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": prefills + blocks}}, "host": {}}
    assert reader.scope_seconds_by_run(
        events, names, "_prefill_impl", "delta_scan") == [
        (5 * ms, pytest.approx(0.010)), (100 * ms, pytest.approx(0.010)),
        (200 * ms, pytest.approx(0.010))]
    # The first run started before any dispatch the trace holds; the
    # third dispatch never ran inside it.
    seen = [("engine.prefill_dispatch", 60 * ms, ms,
             {"real": 700, "bucket": 1024}),
            ("engine.prefill_dispatch", 160 * ms, ms,
             {"real": 1100, "bucket": 2048}),
            ("engine.prefill_dispatch", 260 * ms, ms,
             {"real": 500, "bucket": 512}),
            ("engine.consume_block", 90 * ms, ms, {"slot_steps": 64})]
    assert reader.match([(s, a["real"]) for _, s, _, a in seen[:3]],
                        [(m[1], 0.01) for m in prefills]) \
        == [(700, 0.01), (1100, 0.01)]
    monkeypatch.setattr(sp, "xplane_path", lambda ctx: "unused")
    monkeypatch.setattr(sp, "op_names", lambda path: names)
    monkeypatch.setattr(
        sp, "annotations",
        lambda path, wanted: [a for a in seen if a[0] in wanted])
    ctx = {"trace": events, "cell": Cell(CELL),
           "device": {"count": 1, "peaks": hw.peaks("TPU v5 lite")}}
    args = {m["name"]: m["args"] for m in ctx["cell"].metrics["per_layer"]}
    ops, moved = FLOPS.delta_scan_ops_and_bytes(FILE, 1800, 2)
    assert reader.read(ctx, **args["kernel.delta_scan_roofline"]) \
        == pytest.approx(100 * max(ops / 197e12, moved / 819e9) / 0.020)
    assert reader.read(ctx, **args["kernel.delta_update_roofline"]) \
        == pytest.approx(
            100 * 4 * 2 * FLOPS.delta_update_bytes(FILE, 32) / 819e9 / 0.024)
    assert capsys.readouterr().out.count("memory-bound") == 2
    # A program without the scopes (the parent's), no trace: nothing.
    monkeypatch.setattr(sp, "op_names", lambda path: {})
    assert reader.read(ctx, **args["kernel.delta_scan_roofline"]) is None
    assert reader.read(ctx, **args["kernel.delta_update_roofline"]) is None
    assert reader.read({"trace": None},
                       **args["kernel.delta_scan_roofline"]) is None
