"""The family `lfm2_moe` as files alone: its configuration against the
catalog's row, its adapter building the program's config, its reference
agreeing with the program at debug widths through the serving runner's
own check, its parameters against the initialised tree, its counts
against a hand count of one layer of each kind, the benchmark's weights
against the initialiser's, and the cell's files what `BENCHMARK.json`
and the issue say. Written by membership: nothing here counts the
benchmark's cells or metrics, nor asks that an entry stand last."""

import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import traffic
from benchmark.harness.manifest import (ROOT, Cell, load_json, manifest,
                                        metric_spec, model_adapter, plugin)
from benchmark.runners import serve as serve_runner

NAME = "lfm2-8b-a1b-serve"
CELL = "serve-lfm2-eval-closed"
FILE = load_json(ROOT, "benchmark", "configs", NAME + ".json")
ADAPTER = model_adapter(FILE, serve_runner.NEEDS)
FLOPS = plugin("flops", FILE["flops"])
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
LAYER_TYPES = ["conv", "conv", "full_attention"] \
    + ["conv", "conv", "conv", "full_attention"] * 4 \
    + ["conv", "conv", "full_attention", "conv", "conv"]
# The catalog's `config` of LFM2-8B-A1B, every key.
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "layer_types": LAYER_TYPES,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}


def nbytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def debug_config(lens=(45, 33, 12, 5)):
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
    assert entry["source"] == FILE["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert len(entry["why"]) <= 200
    assert FILE["published"] == {"num_hidden_layers": 24}
    assert FILE["num_hidden_layers"] == 14
    assert len(LAYER_TYPES) == 24
    for key, value in PUBLISHED.items():
        assert FILE[key] == (14 if key == "num_hidden_layers" else value), key
    # What the file adds to the published keys, each said under `assumed`.
    assert (FILE["head_dim"], FILE["tie_word_embeddings"],
            FILE["torch_dtype"]) == (64, True, "bfloat16")
    assert FILE["head_dim"] * FILE["num_attention_heads"] \
        == FILE["hidden_size"]
    share = FILE["deployment"]
    assert share["chips"] == 2 and share["layers_held"] == list(range(14))
    assert all(share[k] for k in ("layout", "this_chip", "not_here"))
    assert FILE["not_served"] == {} and len(FILE["assumed"]) >= 8
    assumed = " ".join(FILE["assumed"])
    for said in ("tied", "1e-6", "B, C and u", "[64]", "rope.py", "float32",
                 "expert_bias", "ROUTED_OUT_SCALE", "ROUTER_BIAS_SCALE",
                 "final_norm_signs"):
        assert said in assumed, said
    for key in ("logit_tolerance", "served_token_margin", "tool_checks",
                "reference_prompt_lens", "reference_layers", "probes"):
        assert FILE["serve"][key + "_why"], key
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-8B-A1B")
        assert row["source_url"] == FILE["source"]
        assert row["config"] == PUBLISHED
        for key, value in row["config"].items():
            if key not in FILE["reduced"]:
                assert FILE[key] == value, key


def test_the_adapter_builds_the_programs_config():
    cfg = ADAPTER.program_config(FILE)
    assert type(cfg).__name__ == "Lfm2MoeConfig"
    assert (cfg.dim, cfg.n_layers, cfg.vocab_size, cfg.dtype) == (
        2048, 14, 65536, jnp.bfloat16)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 8, 64)
    assert cfg.layer_types == ("conv", "conv") + ("full", "conv", "conv",
                                                  "conv") * 3
    assert cfg.n_dense_layers == 2 and cfg.conv_kernel == 3
    assert (cfg.n_experts, cfg.n_experts_per_token, cfg.hidden_dim,
            cfg.dense_hidden_dim) == (32, 4, 1792, 7168)
    # The compared stack: published layers 1 to 4, a layer of each kind.
    shallow = ADAPTER.with_layers(cfg, FILE["serve"]["reference_layers"])
    assert shallow.kinds == (("dense", "conv"), ("sparse", "full"),
                             ("sparse", "conv"), ("sparse", "conv"))
    small = ADAPTER.program_config(ADAPTER.debug(FILE))
    assert (small.dim, small.n_layers, small.vocab_size, small.head_dim,
            small.n_dense_layers) == (64, 8, 512, 8, 2)
    assert FILE["hidden_size"] == 2048  # `debug` cut a copy
    args, kwargs = ADAPTER.deployment_args(cfg, len)
    assert args == (cfg, len) and kwargs == {}


def test_a_program_without_the_family_is_refused_at_set_up(monkeypatch):
    """The parent of the PR that brought the family has no
    `ray_tpu.models.lfm2_moe`: the adapter then lacks the served names,
    and the runner's way in ends with the harness's own line."""
    import ray_tpu.models
    from ray_tpu.models import lfm2_moe  # noqa: F401 (bound, then hidden)
    name = "benchmark.models.lfm2_moe"
    monkeypatch.delattr(ray_tpu.models, "lfm2_moe")
    monkeypatch.setitem(sys.modules, "ray_tpu.models.lfm2_moe", None)
    monkeypatch.delitem(sys.modules, name)
    try:
        with pytest.raises(SystemExit) as refusal:
            model_adapter(FILE, serve_runner.NEEDS)
    finally:
        sys.modules[name] = ADAPTER
        importlib.import_module("benchmark.models").lfm2_moe = ADAPTER
    message = str(refusal.value)
    assert "'lfm2_moe'" in message and "'serve'" in message
    assert "benchmark/models/lfm2_moe.py" in message
    for piece in ("program_config", "init", "cached_forward", "init_cache",
                  "deployment_args"):
        assert piece in message


def test_prefill_and_decode_through_the_cache_match_the_reference():
    """The runner's own check over the benchmark's weights (the routed
    experts' scale and the final norm's signs with them)."""
    err, positions = serve_runner.check_against_reference(
        debug_config(), seed=2 ** 31 + 9)
    assert positions == 4 * 53 and err < 1e-6


@pytest.mark.parametrize("fault", ["silu in the conv", "no B gate",
                                   "no C gate", "pad absorbed",
                                   "no q and k norm",
                                   "experts in the dense layers"])
def test_the_runners_check_fails_a_fault(fault):
    from tools import glm_logit_check
    served = glm_logit_check.lfm2_faults(
        ADAPTER.cached_forward, ADAPTER.init_cache)[fault]
    err, _ = serve_runner.check_against_reference(
        debug_config(), seed=2 ** 31 + 9, served=served)
    assert err > 1e-4


def test_the_reference_steps_a_sequence_layer_by_layer():
    config = debug_config()
    reference = plugin("references", config["reference"])
    cfg = ADAPTER.program_config(config)
    params = ADAPTER.init(cfg, jax.random.PRNGKey(3))
    hp = reference.hyper(config)
    assert hp["kernel"] == 3 and hp["top_k"] == 3 and hp["gate_scale"] == 1.0
    sequences = [jnp.asarray(np.random.default_rng(i).integers(
        0, 512, n), jnp.int32) for i, n in enumerate((17, 30))]
    by_layer = reference.logits_layer_by_layer(params, sequences, hp)
    for tokens, got in zip(sequences, by_layer):
        np.testing.assert_allclose(
            got, reference.sequence_logits(params, tokens, hp), atol=1e-5)
    assert len(list(reference.layers_of(params))) == cfg.n_layers == 8
    # Plain `jax.numpy`: nothing of the program is imported.
    with open(reference.__file__) as f:
        assert "ray_tpu" not in f.read().split('"""', 2)[2]


def test_the_benchmarks_weights_are_the_programs_but_two_scales():
    """And but the final norm's signs: +1 or -1 a channel by the seed,
    about as many of each, so that the tied head does not answer every
    token with itself."""
    from ray_tpu.models import lfm2_moe
    cfg = ADAPTER.program_config(ADAPTER.debug(FILE))
    key = jax.random.PRNGKey(4)
    plain, drawn = lfm2_moe.init_params(cfg, key), ADAPTER.init(cfg, key)
    for a, b in zip(plain["runs"], drawn["runs"]):
        assert set(a) == set(b)
        for name in a:
            scale = {"we2": ADAPTER.ROUTED_OUT_SCALE,
                     "router_bias": ADAPTER.ROUTER_BIAS_SCALE}.get(name, 1)
            np.testing.assert_array_equal(a[name] * scale, b[name])
    assert sum("we2" in run for run in drawn["runs"]) == 4
    np.testing.assert_array_equal(plain["embed"], drawn["embed"])
    assert set(plain) == set(drawn) == {"embed", "runs", "final_norm"}
    signs = np.asarray(drawn["final_norm"])
    assert signs.dtype == plain["final_norm"].dtype
    np.testing.assert_array_equal(np.abs(signs), plain["final_norm"])
    assert 0.25 < (signs < 0).mean() < 0.75
    other = np.asarray(ADAPTER.init(cfg, jax.random.PRNGKey(5))["final_norm"])
    assert (other != signs).any()


@pytest.mark.parametrize("weights,wanders", [("benchmark", True),
                                             ("plain", False)])
def test_greedy_decoding_wanders_under_the_benchmarks_weights(weights,
                                                              wanders):
    """Under the initialiser's weights the tied head's largest logit is
    the input token's own and greedy decoding repeats a prompt's last
    token; under the benchmark's signs no token follows itself, so a
    request's routing changes from step to step (`test_cohere2_moe.py`
    has the same of Command A+, and why a pair of tokens that answer
    each other can hold for a while: here, four layers deep on a
    vocabulary of 4,096 with the routed experts at 1/32, one holds the
    whole answer). At a quarter of the published widths, published
    layers 1 to 4."""
    from ray_tpu.models import lfm2_moe
    config = ADAPTER.debug(FILE)
    config.update(hidden_size=512, intermediate_size=512,
                  moe_intermediate_size=256, vocab_size=4096, head_dim=64)
    cfg = ADAPTER.with_layers(ADAPTER.program_config(config), 4)
    init = {"benchmark": ADAPTER.init, "plain": lfm2_moe.init_params}
    params = init[weights](cfg, jax.random.PRNGKey(6))
    prompt = np.random.default_rng(2).integers(0, cfg.vocab_size, (2, 24))
    cache = lfm2_moe.init_cache(cfg, 2, 64)
    step = jax.jit(lambda tokens, cache, start: lfm2_moe.forward(
        params, tokens, cfg, cache, start, tokens.shape[1] - 1)[:2])
    logits, cache = step(jnp.asarray(prompt, jnp.int32), cache,
                         jnp.zeros(2, jnp.int32))
    answer = []
    for i in range(16):
        answer.append(np.asarray(logits.argmax(-1)))
        logits, cache = step(jnp.asarray(answer[-1][:, None], jnp.int32),
                             cache, jnp.full(2, 24 + i, jnp.int32))
    answer = np.stack(answer, 1)
    for row, last in zip(answer, prompt[:, -1]):
        if wanders:
            assert len(set(row)) >= 2 and (row[1:] != row[:-1]).all(), row
        else:
            assert set(row) == {last}, row


def test_parameters_and_resident_bytes_are_counted_from_shapes():
    """The published depth lands on 8.34 B (the published 8.3B); the
    cut is 4,667,077,376 parameters, 9.33 GB, and with the cell's cache
    10.15 GB resident."""
    from ray_tpu.models import lfm2_moe
    whole = jax.eval_shape(lambda: lfm2_moe.init_params(
        lfm2_moe.Lfm2MoeConfig(), jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(whole)) == 8_339_930_560
    cfg = ADAPTER.program_config(FILE)
    params = jax.eval_shape(lambda: ADAPTER.init(cfg, jax.random.PRNGKey(0)))
    held = sum(x.size for x in jax.tree.leaves(params))
    assert held == 4_667_077_376 and nbytes(params) \
        == 2 * held + 2 * 12 * 32  # the router's biases are float32
    assert "4,667,077,376" in FILE["parameters"]
    assert "8,339,930,560" in FILE["parameters"]
    # A layer of each kind, as the file's sum has them.
    def layer(run):
        return sum(x.size // x.shape[0] for x in jax.tree.leaves(run))
    dense_conv, full, conv = (layer(params["runs"][i]) for i in range(3))
    assert (dense_conv, conv, full) == (60_827_648, 369_174_560, 362_877_088)
    assert 2 * dense_conv + 9 * conv + 3 * full + 65536 * 2048 + 2048 == held
    for said in ("60,827,648", "369,174,560", "362,877,088"):
        assert said in FILE["parameters"], said
    plan = FILE["serve"]
    slots, rows = plan["max_batch_size"], plan["max_seq_len"]
    assert (slots, rows) == (64, 2048)
    cache = jax.eval_shape(lambda: lfm2_moe.init_cache(cfg, slots, rows))
    flags = jax.tree.leaves(lfm2_moe.state_leaves(cache))
    state = [x for x, s in zip(jax.tree.leaves(cache), flags) if s]
    keys = [x for x, s in zip(jax.tree.leaves(cache), flags) if not s]
    assert sorted(x.shape for x in state) == sorted(
        [(2, 64, 2, 2048)] + 3 * [(3, 64, 2, 2048)])
    assert [x.shape for x in keys] == 6 * [(1, 64, 2048, 512)]
    # 8 KB of carried rows a slot and conv layer, 2 KB of keys and
    # values a token and full layer.
    assert nbytes(state) == 11 * slots * FLOPS.state_bytes_per_slot(FILE) \
        == 11 * 64 * 8192 == 5_767_168
    assert nbytes(keys) == 3 * slots * rows * FLOPS.kv_bytes_per_token(FILE) \
        == 805_306_368
    resident = nbytes(params) + nbytes(cache)
    assert round(resident / 1e9, 2) == 10.15
    assert resident / 16e9 > 0.25  # the driver's floor, by the model alone


def test_operations_and_bytes_are_counted_by_hand_a_layer_of_each_kind():
    d, f, e, k, v = 2048, 7168, 1792, 4, 65536
    assert FLOPS.layers(FILE) == {"conv": 11, "full": 3, "dense": 2,
                                  "sparse": 12}
    # A conv mixer: in 2048 x 6144, out 2048 x 2048.
    assert FLOPS.conv_params(FILE) == d * 3 * d + d * d == 16_777_216
    # Its elementwise part a token: two gates, three taps.
    assert FLOPS.conv_elementwise_flops(FILE) == (2 + 2 * 3) * d
    # A full mixer at heads of 64: wq and wo 2048 x 2048, wk and wv
    # 2048 x 512.
    assert FLOPS.attention_params(FILE) == 2 * d * 32 * 64 + 2 * d * 8 * 64 \
        == 10_485_760
    assert FLOPS.dense_params(FILE) == 3 * d * f == 44_040_192
    assert FLOPS.expert_params(FILE) == 3 * d * e == 11_010_048
    assert FLOPS.router_params(FILE) == d * 32
    conv_dense = 2 * (16_777_216 + 44_040_192)
    conv_sparse = 2 * (16_777_216 + d * 32 + k * 11_010_048)
    full_sparse = 2 * (10_485_760 + d * 32 + k * 11_010_048)
    assert FLOPS.matmul_flops_per_token(FILE) \
        == 2 * conv_dense + 9 * conv_sparse + 3 * full_sparse
    assert FLOPS.head_flops(FILE) == 2 * d * v
    # Three full layers' two products a key, 32 heads of 64.
    near, far = (FLOPS.prefill_flops_per_token(FILE, n) for n in (500, 1500))
    assert far - near == 3 * 2 * 2 * 1000 * 32 * 64
    assert near == FLOPS.matmul_flops_per_token(FILE) + 11 * 8 * d \
        + 3 * 2 * 2 * 500 * 32 * 64
    assert FLOPS.train_flops_per_token(FILE, 2048) == 3 * (
        FLOPS.prefill_flops_per_token(FILE, 1024) + 2 * d * v)
    # The flash kernel's call in a prefill of 1,024 rows: the causal
    # pairs at 32 heads of 64, q and o once, k and v once.
    ops, moved = FLOPS.flash_prefill_ops_and_bytes(FILE, 1, 1024)
    assert ops == 2 * 2 * (1024 * 1025 // 2) * 32 * 64
    assert moved == 1024 * 64 * (2 * 32 + 2 * 8) * 2
    # The accepted reader of `kernel.flash_prefill_roofline` counts by
    # another family's file: the same numbers for this configuration.
    assert plugin("flops", metric_spec("kernel.flash_prefill_roofline")[
        "args"]["flops"]).flash_prefill_ops_and_bytes(
            FILE, 1, 1024, False) == (ops, moved)
    # A decode step: every matrix held once, the head among them, the
    # convolutions' taps; a slot's keys in three layers, its carried
    # rows in eleven, in and out.
    weights = 11 * (16_777_216 + 3 * d) + 3 * 10_485_760 + 2 * 44_040_192 \
        + 12 * (d * 32 + 32 * 11_010_048) + d * v
    assert FLOPS.decode_step_bytes(FILE, 64, 0) \
        == 2 * weights + 64 * 11 * 2 * 8192
    assert FLOPS.decode_step_bytes(FILE, 64, 900) \
        - FLOPS.decode_step_bytes(FILE, 64, 0) == 64 * 900 * 3 * 2048
    # An expert no pair fell on is not read.
    assert FLOPS.decode_step_bytes(FILE, 64, 0) \
        - FLOPS.decode_step_bytes(FILE, 64, 0, touched=30) \
        == 12 * 2 * 11_010_048 * 2
    # 9.3 GB of weights a step and 0.35 GB of keys at contexts of 900:
    # no chip at 819 GB/s takes it in under 11.2 ms.
    assert 9.3e9 < FLOPS.decode_step_bytes(FILE, 64, 0) < 9.4e9
    step = FLOPS.decode_step_bytes(FILE, 64, 900)
    assert 9.6e9 < step < 9.8e9 and step / 819e9 > 11.2e-3


def test_the_cells_files_are_what_the_issue_names():
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.config["family"] == "lfm2_moe"
    assert cell.entry["config"] == NAME
    assert cell.entry["traffic"] == "closed-eval-unshared"
    assert len(cell.entry["why"]) <= 200
    assert cell.config["kind"] == "serve"
    mix = cell.traffic
    assert mix["loop"] == "closed" and mix["clients_per_slot"] == 2
    pairs = traffic.length_pairs(mix)
    assert (min(p for p, _ in pairs), max(p for p, _ in pairs)) == (399, 1234)
    assert (min(o for _, o in pairs), max(o for _, o in pairs)) == (263, 749)
    plan = cell.config["serve"]
    assert plan["max_batch_size"] == 64 and plan["max_seq_len"] == 2048
    assert traffic.longest_prompt(mix) + max(o for _, o in pairs) \
        < plan["max_seq_len"]
    # Prompt lengths of the check: none a bucket.
    from ray_tpu.serve.llm import prefill_bucket
    for n in plan["reference_prompt_lens"]:
        assert prefill_bucket(n) != n
    assert plan["reference_layers"] == 4
    assert plan["reference_decode_steps"] >= 8
    assert max(plan["probe_prompt_lens"]) < plan["probe_total"] \
        < plan["max_seq_len"]
    assert set(plan["tool_checks"]) == {"benchmark", "plain"}
    reported = {m["name"] for group in cell.metrics.values() for m in group}
    new = {"step.decode_conv_share", "step.prefill_conv_share"}
    assert {"setup_s", "serve_out_tokens_per_s", "serve_tpot_p50_ms",
            "service.slot_wait_p50_ms.closed",
            "engine.tokens_per_decode_step", "step.decode_device_ms",
            "step.prefill_device_ms", "device.hbm_peak_share.serve",
            "engine.admit_share", "engine.flush_wait_share",
            "device.idle_in_admit_share", "device.idle_in_decode_loop_share",
            "device.idle_in_idle_wait_share", "engine.decode_slot_occupancy",
            "engine.prefill_pad_share", "service.front_ttft_self_p50_ms",
            "engine.loop_host_share", "engine.loop_host_p50_ms",
            "engine.loop_host_max_ms", "process.wake_late_max_ms",
            "service.stream_channel_max_ms", "setup.compile_s",
            "setup.compiles_in_window", "step.decode_attention_share",
            "step.decode_expert_share", "kv.read_key_share",
            "moe.held_experts_read_share", "kernel.flash_prefill_roofline",
            *new} <= reported
    # What it must not report: another mixer's shares, a prefix cache's
    # spans (the model has a state leaf, so it is served with none), a
    # share of the experts held (all are), a block engine's metrics, a
    # trained cell's.
    for name in reported:
        assert not name.startswith(("diffusion.", "step.block_",
                                    "step.train_", "ingest.", "dsa.",
                                    "swa.", "mesh.")), name
    assert not reported & {
        "step.decode_ssm_share", "step.decode_ssm_state_share",
        "step.prefill_ssm_share", "step.decode_delta_share",
        "step.decode_delta_state_share", "step.prefill_delta_share",
        "kernel.delta_scan_roofline", "kernel.delta_update_roofline",
        "step.decode_window_share", "step.prefill_window_share",
        "step.decode_indexer_share", "step.decode_shared_expert_share",
        "moe.held_pair_share", "engine.kv_readback_share",
        "engine.prefix_admit_share", "engine.prefix_admit_max_ms",
        "kv.slot_fill_share", "train_tokens_per_s_per_chip"}
    # The two new metrics: the accepted reader over the scope the conv
    # mixer opens, in the two programs, this cell alone.
    entries = {m["name"]: m for m in manifest()["per_layer"]}
    for name, program, moves in (
            ("step.decode_conv_share", "_decode_impl", "serve_tpot_p50_ms"),
            ("step.prefill_conv_share", "_prefill_impl",
             "serve_out_tokens_per_s")):
        assert metric_spec(name) == {
            "reader": "scope_device_share",
            "args": {"program": program, "any_of": ["conv"]}}
        entry = entries[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == moves
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"]) == ("%", "lower", "device_trace",
                                    "Model step, serve")
    # The scope is the one the program's mixer names.
    from ray_tpu.models import lfm2_moe
    mixer = lfm2_moe._conv_mixer(
        ADAPTER.program_config(ADAPTER.debug(FILE)),
        jnp.zeros(1, jnp.int32), 0)
    assert mixer.scope == "conv"
    cells = manifest()["workloads"]
    assert 4 * sum(c["chips"] == 4 for c in cells) <= len(cells) <= 24
