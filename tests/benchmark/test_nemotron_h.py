"""The family `nemotron_h` as files alone: its adapter builds the
program's config from the configuration file and cuts it as the runner
asks, its reference agrees with the program at debug widths through the
serving runner's own check, and the cell's files are what
`BENCHMARK.json` and the issue say."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import traffic
from benchmark.harness.manifest import (ROOT, Cell, load_json, manifest,
                                        model_adapter, plugin)
from benchmark.runners import serve as serve_runner

NAME = "nemotron-3-super-serve"
CELL = "serve-nemotron3s-reason-closed"
FILE = load_json(ROOT, "benchmark", "configs", NAME + ".json")
ADAPTER = model_adapter(FILE, serve_runner.NEEDS)


def debug_config(lens=(45, 39, 26, 19)):
    config = ADAPTER.debug(FILE)
    config["serve"] = {**config["serve"], "max_seq_len": 128,
                       "reference_prompt_lens": list(lens),
                       "reference_decode_steps": 8}
    return config


def test_the_adapter_builds_the_programs_config():
    cfg = ADAPTER.program_config(FILE)
    assert type(cfg).__name__ == "NemotronHConfig"
    assert (cfg.dim, cfg.n_layers, cfg.vocab_size, cfg.dtype) == (
        4096, 11, 32768, jnp.bfloat16)
    assert cfg.state_dtype == jnp.float32
    assert ADAPTER.with_layers(cfg, 5) == dataclasses.replace(
        cfg, n_layers=5, pattern="MEM*E")
    small = ADAPTER.program_config(ADAPTER.debug(FILE))
    assert (small.dim, small.n_layers, small.vocab_size) == (64, 7, 512)
    assert small.experts_held == (4, 4) and small.n_experts == 16
    assert small.latent_dim < small.dim and small.expert_kind == "relu2"
    assert {kind for kind, _ in small.runs()} == {
        ("ssm", "moe"), ("ssm", None), ("attn", "moe")}
    assert FILE["hidden_size"] == 4096  # `debug` cut a copy
    args, kwargs = ADAPTER.deployment_args(cfg, len)
    assert args == (cfg, len) and kwargs == {}


@pytest.mark.parametrize("weights", ["benchmark", "plain"])
def test_prefill_and_decode_through_the_cache_match_the_reference(
        weights, monkeypatch):
    if weights == "plain":
        from tools import glm_logit_check
        monkeypatch.setattr(
            ADAPTER, "init", glm_logit_check.FAMILIES["nemotron_h"][2]())
    err, positions = serve_runner.check_against_reference(
        debug_config(), seed=2 ** 31 + 9)
    assert positions == 4 * 53 and err < 1e-6


@pytest.mark.parametrize("fault", ["pad absorbed", "state in bfloat16",
                                   "no conv bias"])
def test_the_runners_check_fails_a_fault(fault):
    from tools import glm_logit_check
    served = glm_logit_check.nemotron_faults(
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
    sequences = [jnp.asarray(np.random.default_rng(i).integers(
        0, 512, n), jnp.int32) for i, n in enumerate((17, 30))]
    by_layer = reference.logits_layer_by_layer(params, sequences, hp)
    for tokens, got in zip(sequences, by_layer):
        np.testing.assert_allclose(
            got, reference.sequence_logits(params, tokens, hp), atol=1e-5)
    assert len(list(reference.blocks_of(params))) == len(cfg.blocks) == 4


def test_the_cells_files_are_what_the_issue_names():
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.config["family"] == "nemotron_h"
    assert cell.entry["traffic"] == "closed-reason-unshared"
    mix = cell.traffic
    assert mix["loop"] == "closed" and mix["clients_per_slot"] == 2
    assert mix["stated"]["prompt_len"] == {
        "median": 512, "sigma": 0.45, "lo": 256, "hi": 2048}
    assert mix["stated"]["output_len"] == {
        "median": 768, "sigma": 0.35, "lo": 384, "hi": 1536}
    assert (mix["stated"]["pair_stride"], mix["stated"]["max_total"]) \
        == (27, 4088)
    assert (mix["ramp_s"], mix["drain_s"], mix["trace_s"]) == (10, 2, 6)
    plan = cell.config["serve"]
    assert plan["max_batch_size"] == 64 and plan["max_seq_len"] == 4096
    assert traffic.longest_prompt(mix) + max(
        o for _, o in traffic.length_pairs(mix)) < plan["max_seq_len"]
    # Prompt lengths of the check: no multiple of the chunk, no bucket.
    from ray_tpu.serve.llm import prefill_bucket
    for n in plan["reference_prompt_lens"]:
        assert n % cell.config["chunk_size"] and prefill_bucket(n) != n
    assert plan["reference_decode_steps"] >= 8
    reported = {m["name"] for group in cell.metrics.values() for m in group}
    assert {"setup_s", "serve_out_tokens_per_s", "serve_tpot_p50_ms",
            "step.decode_ssm_share", "step.decode_ssm_state_share",
            "step.prefill_ssm_share", "step.prefill_device_ms",
            "engine.prefill_pad_share", "moe.held_experts_read_share",
            "step.decode_attention_share", "step.decode_expert_share",
            "moe.held_pair_share"} <= reported
    assert "step.decode_indexer_share" not in reported
    # No prefix cache, so no read-back span for its reader to find.
    assert "engine.kv_readback_share" not in reported


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog's entry under the same key, but the
    three in `reduced`, none of them a width."""
    entry = next(c for c in manifest()["configs"] if c["name"] == NAME)
    assert entry["reduced"] == FILE["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert FILE["published"] == {"num_hidden_layers": 88,
                                 "n_routed_experts": 512,
                                 "vocab_size": 131072}
    assert entry["source"] == FILE["source"]
    widths = {"hidden_size": 4096, "mamba_num_heads": 128,
              "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
              "moe_latent_size": 1024, "moe_intermediate_size": 2688,
              "moe_shared_expert_intermediate_size": 5376,
              "num_experts_per_tok": 22, "num_attention_heads": 32,
              "num_key_value_heads": 2, "head_dim": 128, "conv_kernel": 4,
              "chunk_size": 128, "expand": 2, "intermediate_size": 2688}
    assert {k: FILE[k] for k in widths} == widths
    assert len(FILE["hybrid_override_pattern"]) == 88
    share = FILE["deployment"]
    assert share["layers_held"] == list(range(27, 38))
    assert share["experts_held"] == [0, 128] and share["chips"] == 4
    assert set(FILE["not_served"]) >= {"num_nextn_predict_layers",
                                       "mtp_hybrid_override_pattern"}
