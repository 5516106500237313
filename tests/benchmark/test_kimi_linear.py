"""The family `kimi_linear` as files alone: its configuration against
the catalog's row, its adapter building the program's config, its
reference agreeing with the program at debug widths through the serving
runner's own check, its parameters against the initialised tree, its
counts against a hand count of one layer of each kind and of one decode
step's state bytes, and the cell's files what `BENCHMARK.json` and the
issue say. Written by membership: nothing here counts the benchmark's
cells or metrics, nor asks that an entry stand last."""

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

NAME = "kimi-linear-48b-a3b-serve"
CELL = "serve-kimilinear-reason-closed"
FILE = load_json(ROOT, "benchmark", "configs", NAME + ".json")
ADAPTER = model_adapter(FILE, serve_runner.NEEDS)
FLOPS = plugin("flops", FILE["flops"])
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/" \
    "blob/main/config.json"
REDUCED = {"num_hidden_layers": (12, 27), "num_experts": (32, 256),
           "vocab_size": (20480, 163840)}
# The catalog's `config` of Kimi-Linear-48B-A3B-Instruct, every key.
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
# One layer of each part, counted by hand from the published widths.
KDA = 3 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32 \
    + 4096 * 2304                  # the matrices a token is multiplied with
KDA_SMALL = 3 * 4096 * 4 + 32 + 4096 + 128   # convolutions, A, dt_bias, norm
MLA = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304
EXPERT = 3 * 2304 * 1024
DENSE = 3 * 2304 * 9216
ROUTER = 2304 * 256


def debug_config(lens=(45, 39, 26, 19)):
    config = ADAPTER.debug(FILE)
    config["serve"] = {**config["serve"], "max_seq_len": 128,
                       "reference_prompt_lens": list(lens),
                       "reference_decode_steps": 8}
    return config


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's row under the same key, but the three
    in `reduced`, none of them a width."""
    entry = next(c for c in manifest()["configs"] if c["name"] == NAME)
    assert entry["reduced"] == FILE["reduced"] == list(REDUCED)
    assert entry["source"] == FILE["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert FILE["published"] == {k: v[1] for k, v in REDUCED.items()}
    for key, value in PUBLISHED.items():
        assert FILE[key] == (REDUCED[key][0] if key in REDUCED else value), key
    assert (FILE["torch_dtype"], FILE["state_dtype"]) == ("bfloat16",
                                                          "float32")
    share = FILE["deployment"]
    assert share["chips"] == 16 and share["layers_held"] == list(range(12))
    assert share["router_width"] == 256 and share["experts_held"] == [0, 32]
    assert all(share[k] for k in ("layout", "this_chip", "not_here"))
    assert "2.25" in share["not_here"]
    assert "3,176,867,744" in FILE["parameters"]
    assert len(FILE["assumed"]) >= 9
    assumed = " ".join(FILE["assumed"])
    for said in ("silu", "unit length", "1/sqrt(head_dim)", "A_log",
                 "dt_bias", "no bias", "sigmoid", "RMSNorm", "selection bias",
                 "state_dtype", "log-uniform", "mla_use_nope",
                 "ROUTED_OUT_SCALE", "ROUTER_BIAS_SCALE"):
        assert said in assumed, said
    for key in ("logit_tolerance", "served_token_margin", "tool_checks",
                "reference_prompt_lens", "reference_layers", "probes"):
        assert FILE["serve"][key + "_why"], key
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert row["source_url"] == FILE["source"]
        assert row["config"] == PUBLISHED
        for key, value in row["config"].items():
            if key not in FILE["reduced"]:
                assert FILE[key] == value, key


def test_the_adapter_builds_the_programs_config():
    cfg = ADAPTER.program_config(FILE)
    assert type(cfg).__name__ == "KimiLinearConfig"
    assert (cfg.dim, cfg.n_layers, cfg.vocab_size, cfg.dtype) == (
        2304, 12, 20480, jnp.bfloat16)
    period = (("sparse", "kda"),) * 3 + (("sparse", "mla"),)
    assert cfg.kinds == (("dense", "kda"),) + period[1:] + period * 2
    assert (cfg.n_experts, cfg.experts_held, cfg.n_experts_per_token,
            cfg.hidden_dim, cfg.dense_hidden_dim, cfg.shared_hidden_dim,
            cfg.gate_scale) == (256, (0, 32), 8, 1024, 9216, 1024, 2.446)
    assert (cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim,
            cfg.conv_kernel, cfg.gate_rank) == (32, 128, 128, 4, 128)
    # The compared stack: a layer of each kind.
    shallow = ADAPTER.with_layers(cfg, FILE["serve"]["reference_layers"])
    assert shallow.kinds == (("dense", "kda"), ("sparse", "kda"),
                             ("sparse", "mla"))
    small = ADAPTER.program_config(ADAPTER.debug(FILE))
    assert (small.dim, small.n_layers, small.vocab_size, small.n_experts,
            small.experts_held) == (48, 8, 512, 16, (4, 4))
    assert FILE["hidden_size"] == 2304  # `debug` cut a copy
    args, kwargs = ADAPTER.deployment_args(cfg, len)
    assert args == (cfg, len) and kwargs == {}


def test_a_program_without_the_family_is_refused_at_set_up(monkeypatch):
    """The parent of the PR that brought the family has no
    `ray_tpu.models.kimi_linear`: the adapter then lacks the served
    names, and the runner's way in ends with the harness's own line."""
    import ray_tpu.models
    from ray_tpu.models import kimi_linear  # noqa: F401 (bound, then hidden)
    name = "benchmark.models.kimi_linear"
    monkeypatch.delattr(ray_tpu.models, "kimi_linear")
    monkeypatch.setitem(sys.modules, "ray_tpu.models.kimi_linear", None)
    monkeypatch.delitem(sys.modules, name)
    try:
        with pytest.raises(SystemExit) as refusal:
            model_adapter(FILE, serve_runner.NEEDS)
    finally:
        sys.modules[name] = ADAPTER
        importlib.import_module("benchmark.models").kimi_linear = ADAPTER
    message = str(refusal.value)
    assert "'kimi_linear'" in message and "'serve'" in message
    assert "benchmark/models/kimi_linear.py" in message
    for piece in ("program_config", "init", "cached_forward", "init_cache",
                  "deployment_args"):
        assert piece in message


def test_prefill_and_decode_through_the_cache_match_the_reference():
    """The runner's own check over the benchmark's weights (the routed
    experts' scale and the bias's with them)."""
    err, positions = serve_runner.check_against_reference(
        debug_config(), seed=2 ** 31 + 9)
    assert positions == 4 * 53 and err < 2e-6


@pytest.mark.parametrize("fault", ["one decay a head", "rotary turn"])
def test_the_runners_check_fails_a_fault(fault):
    from tools import glm_logit_check
    served = glm_logit_check.kimi_faults(
        ADAPTER.cached_forward, ADAPTER.init_cache)[fault]
    err, _ = serve_runner.check_against_reference(
        debug_config(), seed=2 ** 31 + 9, served=served)
    assert err > 1e-4


def test_the_reference_steps_a_sequence_layer_by_layer():
    config = debug_config()
    reference = plugin("references", config["reference"])
    cfg = ADAPTER.with_layers(ADAPTER.program_config(config), 3)
    params = jax.jit(lambda key: ADAPTER.init(cfg, key))(
        jax.random.PRNGKey(3))
    hp = reference.hyper(config)
    assert (hp["top_k"], hp["gate_scale"], hp["first_expert"],
            hp["scoring"]) == (2, 2.446, 4, "sigmoid")
    sequences = [jnp.asarray(np.random.default_rng(i).integers(
        0, 512, n), jnp.int32) for i, n in enumerate((17, 30))]
    by_layer = reference.logits_layer_by_layer(params, sequences, hp)
    whole = jax.jit(lambda p, t: reference.sequence_logits(p, t, hp))
    for tokens, got in zip(sequences, by_layer):
        np.testing.assert_allclose(got, whole(params, tokens), atol=1e-5)
    assert len(list(reference.blocks_of(params))) == cfg.n_layers == 3
    # Plain `jax.numpy`: nothing of the program is imported.
    with open(reference.__file__) as f:
        assert "ray_tpu" not in f.read().split('"""', 2)[2]


def test_parameters_and_resident_bytes_are_counted_from_shapes():
    """The published depth lands on 49.12 B (the published 48B); the
    cut is 3,176,867,744 parameters, 6.35 GB, and with the cell's cache
    8.6 GB resident, 54 % of the chip."""
    from ray_tpu.models import kimi_linear
    whole = jax.eval_shape(lambda: kimi_linear.init_params(
        kimi_linear.KimiLinearConfig(), jax.random.PRNGKey(0)))
    assert 49.1e9 < sum(x.size for x in jax.tree.leaves(whole)) < 49.2e9
    cfg = ADAPTER.program_config(FILE)
    tree = jax.eval_shape(lambda: ADAPTER.init(cfg, jax.random.PRNGKey(0)))
    n = sum(x.size for x in jax.tree.leaves(tree))
    kda_layer = KDA + KDA_SMALL + 33 * EXPERT + ROUTER + 256 + 2 * 2304
    mla_layer = MLA + 512 + 33 * EXPERT + ROUTER + 256 + 2 * 2304
    dense_layer = KDA + KDA_SMALL + DENSE + 2 * 2304
    assert (dense_layer, kda_layer, mla_layer) == (103_219_872, 273_679_264,
                                                   263_279_872)
    assert n == dense_layer + 8 * kda_layer + 3 * mla_layer \
        + 2 * 20480 * 2304 + 2304 == 3_176_867_744
    assert f"{n:,}" in FILE["parameters"]
    plan = FILE["serve"]
    cache = jax.eval_shape(lambda: kimi_linear.init_cache(
        cfg, plan["max_batch_size"], plan["max_seq_len"]))
    state = sum(x.size * x.dtype.itemsize for run in cache["runs"]
                for name, x in run.items() if name in kimi_linear.FAMILY.state)
    rows = sum(x.size * x.dtype.itemsize for run in cache["runs"]
               for name, x in run.items() if name in ("latent", "rope"))
    assert state == 9 * 64 * (2_097_152 + 73_728)
    # The 64 shared key channels lie in rows of 128 lanes.
    assert rows == 3 * 64 * 4096 * (512 + 128) * 2
    assert [run["state"].shape for run in cache["runs"] if "state" in run] \
        == [(layers, 64, 32, 128, 128) for layers in (1, 2, 3, 3)]
    resident = 2 * n + state + rows
    assert 8.5e9 < resident < 8.7e9 and resident / 16e9 > 0.5


def test_operations_and_bytes_are_counted_from_the_files_shapes():
    """Against a hand count of one layer of each kind and of one decode
    step's state bytes."""
    assert FLOPS.layers(FILE) == {"kda": 9, "mla": 3, "dense": 1,
                                  "sparse": 11}
    assert (FLOPS.kda_params(FILE), FLOPS.mla_params(FILE),
            FLOPS.dense_params(FILE), FLOPS.expert_params(FILE),
            FLOPS.router_params(FILE)) == (KDA, MLA, DENSE, EXPERT, ROUTER)
    assert FLOPS.held_share(FILE) == 1 / 8
    # A prefilled token: the matrices, the taps, the recurrence as
    # written (7 x dk x dv a head); the latent layers' two products
    # against the keys before it; one pair in eight on this chip.
    kda = 2 * KDA + 2 * 4 * 3 * 4096 + 7 * 32 * 128 * 128
    mla = 2 * MLA
    ffn = 2 * DENSE + 11 * 2 * (ROUTER + EXPERT * (1 + 8 / 8))
    head = 2 * 2304 * 20480
    assert FLOPS.prefill_flops_per_token(FILE, 0) \
        == 9 * kda + 3 * mla + ffn + head
    assert FLOPS.prefill_flops_per_token(FILE, 1000) \
        - FLOPS.prefill_flops_per_token(FILE, 0) \
        == 3 * 2 * 1000 * 32 * (2 * 512 + 64)
    assert FLOPS.train_flops_per_token(FILE, 2000) \
        == 3 * FLOPS.prefill_flops_per_token(FILE, 1000)
    # A decode step's state: nine layers of 32 x 128 x 128 float32 a
    # slot, read and written; 2.42 GB at 64 slots, 2.9 ms at 819 GB/s.
    assert FLOPS.delta_update_bytes(FILE, 64) \
        == 2 * 64 * 9 * 2_097_152 == 2_415_919_104
    assert FLOPS.state_bytes_per_slot(FILE) == 2_097_152 + 73_728
    assert FLOPS.latent_bytes_per_token(FILE) == 1152
    assert FLOPS.latent_bytes_per_step(FILE, 64, 1000) == 3 * 64 * 1000 * 1152
    # The experts' bytes by how many are hit: the router and the shared
    # expert always, a routed one only if a pair fell on it.
    assert FLOPS.experts_bytes_per_step(FILE, 0) == 11 * 2 * (ROUTER + EXPERT)
    assert FLOPS.experts_bytes_per_step(FILE, 32) \
        - FLOPS.experts_bytes_per_step(FILE, 0) == 11 * 32 * EXPERT * 2
    assert FLOPS.experts_bytes_per_step(FILE, 40) \
        == FLOPS.experts_bytes_per_step(FILE, 32)
    weights = 9 * (KDA + 3 * 4096 * 4) + 3 * MLA + DENSE + 2304 * 20480
    assert FLOPS.decode_step_bytes(FILE, 64, 0, touched=0) \
        == 2 * weights + FLOPS.experts_bytes_per_step(FILE, 0) \
        + 64 * 9 * 2 * (2_097_152 + 73_728)
    # 64 slots x 8 pairs x 1/8 = 64 pairs on 32 experts: all of them at
    # the most, and no chip at 819 GB/s takes the step in under 9.8 ms
    # even at the 86 % of them a uniform router hits.
    assert FLOPS.decode_step_bytes(FILE, 64, 1200) \
        == FLOPS.decode_step_bytes(FILE, 64, 1200, touched=32)
    step = FLOPS.decode_step_bytes(FILE, 64, 1200, touched=0.86 * 32)
    assert 8.0e9 < step < 8.6e9 and step / 819e9 > 9.8e-3
    # The scan: the recurrence's operations a real token, its inputs
    # and output once a token, the state in and out once a call.
    ops, nbytes = FLOPS.delta_scan_ops_and_bytes(FILE, 1000, 2)
    assert ops == 9 * 1000 * 7 * 32 * 128 * 128
    assert nbytes == 9 * (1000 * (5 * 4096 * 2 + (4096 + 32) * 4)
                          + 2 * 2 * 2_097_152)
    peaks = load_json(ROOT, "benchmark", "peaks.json")[
        "device_kinds"]["TPU v5 lite"]
    t, bound = FLOPS.least_seconds(ops, nbytes, peaks)
    assert bound == "memory" and t == nbytes / peaks["hbm_bytes_per_s"]


def test_the_cells_files_are_what_the_issue_names():
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.config["family"] == "kimi_linear"
    assert cell.entry["config"] == NAME
    assert cell.entry["traffic"] == "closed-reason-unshared"
    assert len(cell.entry["why"]) <= 200
    assert cell.config["kind"] == "serve"
    mix = cell.traffic
    assert mix["loop"] == "closed" and mix["clients_per_slot"] == 2
    pairs = traffic.length_pairs(mix)
    assert (min(p for p, _ in pairs), max(p for p, _ in pairs)) == (263, 1503)
    assert (min(o for _, o in pairs), max(o for _, o in pairs)) == (400, 1474)
    plan = cell.config["serve"]
    assert plan["max_batch_size"] == 64 and plan["max_seq_len"] == 4096
    assert traffic.longest_prompt(mix) + max(o for _, o in pairs) \
        < plan["max_seq_len"]
    from ray_tpu.serve.llm import prefill_bucket
    for n in plan["reference_prompt_lens"]:
        assert prefill_bucket(n) != n and n % 64 and n % 16
    assert plan["reference_layers"] == 3
    assert plan["reference_decode_steps"] >= 8
    assert max(plan["probe_prompt_lens"]) < plan["probe_total"] \
        < plan["max_seq_len"]
    assert set(plan["tool_checks"]) == {"benchmark", "plain"}
    reported = {m["name"] for group in cell.metrics.values() for m in group}
    new = {"kernel.kda_update_roofline", "kernel.kda_scan_roofline",
           "step.decode_latent_share", "step.prefill_latent_share"}
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
            "step.decode_expert_share", "step.decode_shared_expert_share",
            "moe.held_pair_share", "moe.held_experts_read_share",
            "step.decode_delta_share", "step.decode_delta_state_share",
            "step.prefill_delta_share", *new} <= reported
    # What it must not report: another mixer's shares, Olmo-Hybrid's
    # count of the delta rule's work, a prefix cache's spans (the model
    # has a state leaf, so it is served with none), the rows a slot's
    # length would bound (a step's latent layers read by the longest
    # slot's), a block engine's metrics, a trained cell's.
    for name in reported:
        assert not name.startswith(("diffusion.", "step.block_",
                                    "step.train_", "ingest.", "dsa.",
                                    "swa.", "mesh.")), name
    assert not reported & {
        "step.decode_ssm_share", "step.decode_ssm_state_share",
        "step.prefill_ssm_share", "kernel.delta_scan_roofline",
        "kernel.delta_update_roofline", "step.decode_window_share",
        "step.prefill_window_share", "step.decode_indexer_share",
        "step.prefill_indexer_share", "step.decode_conv_share",
        "step.prefill_conv_share", "kv.read_key_share",
        "engine.kv_readback_share", "engine.prefix_admit_share",
        "engine.prefix_admit_max_ms", "kv.slot_fill_share",
        "kernel.flash_prefill_roofline", "train_tokens_per_s_per_chip"}
    # The four new metrics: accepted readers over the scopes the
    # program's mixers open, this family's counts, this cell alone.
    entries = {m["name"]: m for m in manifest()["per_layer"]}
    for name, program, scope, work, moves in (
            ("kernel.kda_update_roofline", "_decode_impl", "delta_update",
             "update", "serve_tpot_p50_ms"),
            ("kernel.kda_scan_roofline", "_prefill_impl", "delta_scan",
             "scan", "serve_out_tokens_per_s")):
        assert metric_spec(name) == {
            "reader": "delta_roofline",
            "args": {"program": program, "scope": scope,
                     "flops": "kimi_linear", "work": work}}
        entry = entries[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == moves
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"]) == ("%", "higher", "device_trace", "Kernels")
    for name, program, moves in (
            ("step.decode_latent_share", "_decode_impl",
             "serve_tpot_p50_ms"),
            ("step.prefill_latent_share", "_prefill_impl",
             "serve_out_tokens_per_s")):
        assert metric_spec(name) == {
            "reader": "scope_device_share",
            "args": {"program": program, "any_of": ["latent_attn"]}}
        entry = entries[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == moves
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"]) == ("%", "lower", "device_trace",
                                    "Model step, serve")
    # Olmo-Hybrid's two rooflines keep its count and its cell.
    for name in ("kernel.delta_update_roofline", "kernel.delta_scan_roofline"):
        assert metric_spec(name)["args"]["flops"] == "olmo_hybrid"
        assert entries[name]["workloads"] == ["serve-olmohybrid-eval-closed"]
    for m in manifest()["end_to_end"]:
        if m["name"] in ("serve_out_tokens_per_s", "serve_tpot_p50_ms"):
            assert CELL in m["workloads"]
    cells = manifest()["workloads"]
    assert 4 * sum(c["chips"] == 4 for c in cells) <= len(cells) <= 24
