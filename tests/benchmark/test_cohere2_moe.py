"""The family `cohere2_moe` as files alone: its adapter builds the
program's config from the configuration file and cuts it as the runner
asks, its reference agrees with the program at debug widths through the
serving runner's own check and every named fault fails it, the counts
from shapes are the file's, and the cell's files are what
`BENCHMARK.json` and ISSUE 39 say."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import traffic
from benchmark.harness.manifest import (ROOT, Cell, load_json, manifest,
                                        model_adapter, plugin)
from benchmark.runners import serve as serve_runner

NAME = "command-a-plus-serve"
CELL = "serve-cmdaplus-rag-closed"
FILE = load_json(ROOT, "benchmark", "configs", NAME + ".json")
ADAPTER = model_adapter(FILE, serve_runner.NEEDS)
# The program reads 2.2e-7 to 2.4e-7 under the runner's check at these
# widths in float32 at the benchmark's weights (1.4e-7 at the plain
# ones, whose largest logit is a token's own) and the smallest named
# fault 1.6e-3: the limit lies 40 times over the one and 160 times under
# the other.
LIMIT = 1e-5
NAMED = ["lower precision", "window ignored", "rope on the full layer",
         "shared experts summed", "sequential block", "pad enters the ring"]


def debug_config(lens=(45, 39, 26, 19)):
    config = ADAPTER.debug(FILE)
    config["serve"] = {**config["serve"], "max_seq_len": 128,
                       "reference_prompt_lens": list(lens),
                       "reference_decode_steps": 8}
    return config


def nbytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def test_the_adapter_builds_the_programs_config():
    cfg = ADAPTER.program_config(FILE)
    assert type(cfg).__name__ == "Cohere2MoeConfig"
    assert (cfg.dim, cfg.n_layers, cfg.vocab_size, cfg.dtype) == (
        4096, 4, 32768, jnp.bfloat16)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (128, 8, 128)
    assert (cfg.n_experts, cfg.experts_held, cfg.n_experts_per_token) == (
        128, (0, 16), 8)
    assert (cfg.hidden_dim, cfg.shared_hidden_dim, cfg.n_shared_experts,
            cfg.shared_combination) == (4096, 16384, 4, "average")
    assert cfg.layer_types == ("sliding", "sliding", "sliding", "full")
    assert (cfg.sliding_window, cfg.rope_theta, cfg.norm_eps) == (
        4096, 50000.0, 1e-5)
    assert cfg.parallel_block and cfg.norm_kind == "layer"
    assert cfg.tie_embeddings and not cfg.selection_bias
    assert ADAPTER.with_layers(cfg, 2) == dataclasses.replace(
        cfg, n_layers=2, layer_types=("sliding", "full"))
    small = ADAPTER.program_config(ADAPTER.debug(FILE))
    assert (small.dim, small.n_layers, small.vocab_size) == (64, 4, 512)
    assert small.experts_held == (4, 4) and small.n_experts == 16
    assert small.sliding_window == 8 and small.shared_hidden_dim == 128
    assert FILE["hidden_size"] == 4096  # `debug` cut a copy
    args, kwargs = ADAPTER.deployment_args(cfg, len)
    assert args == (cfg, len) and kwargs == {}


def test_prefill_and_ring_decode_match_the_reference():
    err, positions = serve_runner.check_against_reference(
        debug_config(), seed=2 ** 31 + 9)
    assert positions == 4 * 53 and err < LIMIT / 25


@pytest.mark.parametrize("fault", NAMED)
def test_the_runners_check_fails_a_fault(fault):
    from tools import glm_logit_check
    served = glm_logit_check.cohere_faults(
        ADAPTER.cached_forward, ADAPTER.init_cache)[fault]
    err, _ = serve_runner.check_against_reference(
        debug_config(), seed=2 ** 31 + 9, served=served)
    assert err > 50 * LIMIT


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
        assert isinstance(got, np.ndarray)
        np.testing.assert_allclose(
            got, reference.sequence_logits(params, tokens, hp), atol=1e-5)
    kinds = [kind for _, _, kind in reference.layers_of(params, hp)]
    assert kinds == ["sliding"] * 3 + ["full"]
    # A shallow copy holds the share's top layers.
    shallow = ADAPTER.init(ADAPTER.with_layers(cfg, 2), jax.random.PRNGKey(3))
    assert [kind for _, _, kind in reference.layers_of(shallow, hp)] == [
        "sliding", "full"]


def test_the_benchmarks_weights_are_the_programs_but_one_scale():
    """And but the final norm's signs: +1 or -1 a channel by the seed,
    about as many of each, so that the tied head does not answer every
    token with itself."""
    from ray_tpu.models import cohere2_moe
    cfg = ADAPTER.program_config(ADAPTER.debug(FILE))
    key = jax.random.PRNGKey(4)
    plain, drawn = cohere2_moe.init_params(cfg, key), ADAPTER.init(cfg, key)
    for a, b in zip(plain["runs"], drawn["runs"]):
        for name in a:
            scale = ADAPTER.ROUTED_OUT_SCALE if name == "we2" else 1
            np.testing.assert_array_equal(a[name] * scale, b[name])
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
    token; under the benchmark's signs it does not, so a request's
    routing changes from token to token. (Any weight of the norm leaves
    the head's likeness of two embeddings the same from either side,
    which pulls greedy decoding towards pairs of tokens that answer
    each other until the layers' outputs break a pair: on a vocabulary
    of 4,096 a pair can hold for a dozen steps, on the chip's 32,768 a row
    of 160 tokens holds 60 to 100 distinct ones.) At a quarter of the
    published widths, one period of layers."""
    from ray_tpu.models import cohere2_moe
    config = ADAPTER.debug(FILE)
    config.update(hidden_size=1024, intermediate_size=1024, vocab_size=4096,
                  head_dim=128)
    cfg = ADAPTER.program_config(config)
    init = {"benchmark": ADAPTER.init, "plain": cohere2_moe.init_params}
    params = init[weights](cfg, jax.random.PRNGKey(6))
    prompt = np.random.default_rng(2).integers(0, cfg.vocab_size, (2, 24))
    cache = cohere2_moe.init_cache(cfg, 2, 64)
    step = jax.jit(lambda tokens, cache, start: cohere2_moe.forward(
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
            assert len(set(row)) >= 3 and (row[1:] != row[:-1]).all(), row
        else:
            assert set(row) == {last}, row


def test_parameters_and_resident_bytes_are_counted_from_shapes():
    """The published depth and width land on the published 218 B (the
    vision tower is not counted); the cut is 4.733 B parameters, and
    with the cell's cache 11.35 GB resident, the rings a fifth of what
    four full layers would hold less."""
    from ray_tpu.models import cohere2_moe
    full = cohere2_moe.Cohere2MoeConfig()
    count = sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda: cohere2_moe.init_params(full, jax.random.PRNGKey(0)))))
    assert count == 32 * 6_786_912_256 + 262144 * 4096 + 4096
    assert abs(count / 218e9 - 1) < 0.002          # 218.25 B
    cfg = ADAPTER.program_config(FILE)
    params = jax.eval_shape(lambda: ADAPTER.init(cfg, jax.random.PRNGKey(0)))
    held = sum(x.size for x in jax.tree.leaves(params))
    assert held == 4 * 1_149_767_680 + 134_217_728 + 4096 == 4_733_292_544
    plan = FILE["serve"]
    cache = jax.eval_shape(lambda: cohere2_moe.init_cache(
        cfg, plan["max_batch_size"], plan["max_seq_len"]))
    assert [x.shape for x in jax.tree.leaves(cache)] == [
        (3, 16, 4096, 8, 128)] * 2 + [(1, 16, 16384, 8, 128)] * 2
    rings = nbytes(cache["runs"][0])
    rows = nbytes(cache["runs"][1])
    assert (rings, rows) == (805_306_368, 1_073_741_824)
    resident = nbytes(params) + rings + rows
    assert resident == 11_345_633_280 and round(resident / 1e9, 2) == 11.35
    assert "4,733,292,544" in FILE["parameters"]


def test_operations_and_bytes_are_counted_from_the_files_shapes():
    flops = plugin("flops", FILE["flops"])
    assert flops.attention_params(FILE) == 142_606_336
    assert flops.expert_params(FILE) == 50_331_648
    # A sliding layer's token attends min(position, 4096) keys.
    near, far = (flops.prefill_flops_per_token(FILE, n) for n in (1000, 3000))
    assert far - near == 4 * 2 * 2 * 2000 * 128 * 128
    near, far = (flops.prefill_flops_per_token(FILE, n) for n in (5000, 9000))
    assert far - near == 1 * 2 * 2 * 4000 * 128 * 128
    assert flops.matmul_flops_per_token(FILE) == 4 * 2 * (
        142_606_336 + 524_288 + 50_331_648 * (4 + 8 * 16 / 128)) \
        + 2 * 4096 * 32768
    # A decode step reads every matrix held once and, a slot, three
    # rings and the full layer's rows to the row's length.
    matrices = 4_733_292_544 - 5 * 4096
    assert flops.decode_step_bytes(FILE, 16, 0) == 2 * matrices
    assert flops.decode_step_bytes(FILE, 16, 0) \
        - flops.decode_step_bytes(FILE, 16, 0, touched=10) \
        == 2 * 4 * 6 * 50_331_648
    assert flops.decode_step_bytes(FILE, 16, 8500) \
        - flops.decode_step_bytes(FILE, 16, 0) \
        == 16 * (3 * 4096 + 8500) * 2 * 8 * 128 * 2
    assert flops.train_flops_per_token(FILE, 4096) \
        == 3 * flops.prefill_flops_per_token(FILE, 2048)
    # A call of the flash kernel over a prefill from position 0: two
    # products over the pairs of a row and a key it sees, which are what
    # the per-token count adds up to over the rows.
    for windowed, kind in ((True, "sliding_attention"),
                           (False, "full_attention")):
        ops, moved = flops.flash_prefill_ops_and_bytes(FILE, 1, 9216,
                                                       windowed)
        assert ops == sum(flops.attention_flops(FILE, kind, n + 1)
                          for n in range(9216))
        assert moved == 9216 * 128 * (2 * 128 + 2 * 8) * 2
    assert flops.flash_prefill_ops_and_bytes(FILE, 4, 2048, True) \
        == flops.flash_prefill_ops_and_bytes(FILE, 4, 2048, False)


def test_flash_prefill_roofline_of_a_hand_made_trace(monkeypatch, capsys):
    """Two prefills, of 9,216 and of 6,144 rows, each a windowed call in
    the sliding layers' scan and a full one, and a call of the same
    kernel in another program; rows read from the HLO line, the window
    from the scope path."""
    from benchmark.harness import device as hw
    from benchmark.harness import spans as sp
    from benchmark.readers import flash_prefill_roofline as reader

    ms = 1_000_000
    names = {(7, "flash_fwd.1"): "jit(_prefill_impl)/while/body/attn/"
                                 "window/cond/branch_1_fun/flash_fwd:",
             (7, "flash_fwd.2"): "jit(_prefill_impl)/while/body/attn/"
                                 "cond/branch_1_fun/flash_fwd:",
             (8, "flash_fwd.1"): "jit(_prefill_impl)/attn/window/flash_fwd:",
             (8, "flash_fwd.2"): "jit(_prefill_impl)/attn/flash_fwd:"}
    events = {"devices": {"/device:TPU:0": {"ops": [], "modules": [
        ["jit__prefill_impl(7)", 0, 100 * ms],
        ["jit__prefill_impl(8)", 200 * ms, 100 * ms],
        ["jit_step_fn(9)", 400 * ms, 100 * ms]]}}, "host": {}}
    calls = [("flash_fwd.1", 10 * ms, 20 * ms, (1, 128, 9216, 128)),
             ("flash_fwd.2", 40 * ms, 30 * ms, (1, 128, 9216, 128)),
             ("flash_fwd.1", 210 * ms, 12 * ms, (1, 128, 6144, 128)),
             ("flash_fwd.2", 240 * ms, 14 * ms, (1, 128, 6144, 128)),
             ("flash_fwd.1", 410 * ms, 50 * ms, (8, 32, 4096, 128))]
    assert reader.calls_seen(events, names, calls, "_prefill_impl",
                             "window") == {
        (1, 9216, True): [1, pytest.approx(0.020)],
        (1, 9216, False): [1, pytest.approx(0.030)],
        (1, 6144, True): [1, pytest.approx(0.012)],
        (1, 6144, False): [1, pytest.approx(0.014)]}
    monkeypatch.setattr(sp, "xplane_path", lambda ctx: "unused")
    monkeypatch.setattr(sp, "op_names", lambda path: names)
    monkeypatch.setattr(reader, "kernel_calls", lambda path, kernel: calls)
    ctx = {"trace": events, "cell": Cell(CELL),
           "device": {"count": 1, "peaks": hw.peaks("TPU v5 lite")}}
    args = {m["name"]: m for m in ctx["cell"].metrics["per_layer"]}[
        "kernel.flash_prefill_roofline"]["args"]
    flops = plugin("flops", FILE["flops"])
    least = sum(flops.flash_prefill_ops_and_bytes(FILE, 1, rows, w)[0]
                for rows in (9216, 6144) for w in (True, False)) / 197e12
    assert reader.read(ctx, **args) == pytest.approx(
        100 * least / 0.076, rel=1e-6)
    assert capsys.readouterr().out.count("compute-bound") == 4
    # A program without the kernel (the parent's), no trace: nothing.
    monkeypatch.setattr(reader, "kernel_calls", lambda path, kernel: [])
    assert reader.read(ctx, **args) is None
    assert reader.read({"trace": None}, **args) is None
    # The HLO line's result, alone or first of a tuple.
    line = "%flash_fwd.3 = bf16[1,128,9216,128]{3,2,1,0:T(8,128)(2,1)} " \
           "custom-call(bf16[1,128,9216,128]{3,2,1,0} %a)"
    assert reader._RESULT.search(line).groups() == (
        "1", "128", "9216", "128")
    assert reader._RESULT.search(line.replace("= bf16", "= (bf16")).groups() \
        == ("1", "128", "9216", "128")


def test_the_cells_files_are_what_the_issue_names():
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.config["family"] == "cohere2_moe"
    assert cell.entry["traffic"] == "closed-rag-unshared"
    mix = cell.traffic
    assert mix["loop"] == "closed" and mix["clients_per_slot"] == 2
    assert mix["stated"]["prompt_len"] == {
        "median": 8192, "sigma": 0.15, "lo": 5120, "hi": 14336}
    assert mix["stated"]["output_len"] == {
        "median": 384, "sigma": 0.35, "lo": 192, "hi": 768}
    assert (mix["stated"]["pair_stride"], mix["stated"]["max_total"]) \
        == (27, 16376)
    assert (mix["ramp_s"], mix["drain_s"], mix["trace_s"]) == (10, 2, 6)
    plan = cell.config["serve"]
    assert plan["max_batch_size"] == 16 and plan["max_seq_len"] == 16384
    window = cell.config["sliding_window"]
    # Every prompt of the mix, of the check and of the probes is past
    # the window; the check's are no bucket, so padding is compared.
    from ray_tpu.serve.llm import prefill_bucket
    assert min(p for p, _ in traffic.length_pairs(mix)) > window
    assert all(n > window and prefill_bucket(n) != n
               for n in plan["reference_prompt_lens"])
    assert all(n > window for n in plan["probe_prompt_lens"])
    assert plan["reference_decode_steps"] >= 8
    assert plan["reference_layers"] >= 2
    reported = {m["name"] for group in cell.metrics.values() for m in group}
    assert {"setup_s", "serve_out_tokens_per_s", "serve_tpot_p50_ms",
            "step.decode_window_share", "step.prefill_window_share",
            "swa.attended_key_share", "step.decode_shared_expert_share",
            "step.decode_attention_share", "step.decode_expert_share",
            "moe.held_pair_share", "moe.held_experts_read_share",
            "step.prefill_device_ms", "engine.prefill_pad_share",
            "device.hbm_peak_share.serve"} <= reported
    # No prefix cache and no reserved rows a ring could be counted in.
    assert "engine.kv_readback_share" not in reported
    assert "kv.slot_fill_share" not in reported
    assert "step.decode_indexer_share" not in reported
    bench = manifest()
    assert len(bench["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == [
        "step.decode_window_share", "step.prefill_window_share",
        "swa.attended_key_share", "step.decode_shared_expert_share",
        "kernel.flash_prefill_roofline"]


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog's entry under the same key, but the
    three in `reduced`, none of them a width."""
    entry = next(c for c in manifest()["configs"] if c["name"] == NAME)
    assert entry["reduced"] == FILE["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert FILE["published"] == {"num_hidden_layers": 32,
                                 "num_experts": 128, "vocab_size": 262144}
    assert entry["source"] == FILE["source"]
    widths = {"hidden_size": 4096, "intermediate_size": 4096,
              "num_attention_heads": 128, "num_key_value_heads": 8,
              "head_dim": 128, "num_experts_per_tok": 8,
              "num_shared_experts": 4, "sliding_window": 4096,
              "prefix_dense_intermediate_size": 16384,
              "max_position_embeddings": 200000, "rope_theta": 50000,
              "layer_norm_eps": 1e-5, "logit_scale": 1}
    assert {k: FILE[k] for k in widths} == widths
    assert len(FILE["layer_types"]) == 32
    assert FILE["layer_types"][:4] == ["sliding_attention"] * 3 + [
        "full_attention"]
    share = FILE["deployment"]
    assert share["layers_held"] == [0, 1, 2, 3]
    assert share["experts_held"] == [0, 16] and share["chips"] == 8
    assert share["router_width"] == 128
    assert "vision_tower" in FILE["not_served"]
    assert len(FILE["assumed"]) >= 10
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(line) for line in open(catalog)]
    except OSError:
        pytest.skip("the catalog is not beside this checkout")
    published = next(r for r in rows
                     if r["name"] == "command-a-plus-05-2026")["config"]
    differs = sorted(k for k, v in published.items() if FILE.get(k) != v)
    assert differs == sorted(FILE["reduced"])
