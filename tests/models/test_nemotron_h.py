"""Nemotron 3 Super's decoder at debug widths on the CPU, in float32,
seeded random weights: the served path (a prefill padded to its bucket,
then decode through the cache) against the plain reference, each fault
of `tools/glm_logit_check.py` failing where the program passes; what a
state leaf demands of a forward pass (padding kept out of the state, a
prefill in two calls, rows of different lengths, a slot used before);
the held shares of the experts adding up to the uncut layer; and the
engine, which knows no model, serving a cache with state leaves and no
prefix cache."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.manifest import ROOT, load_json, model_adapter
from benchmark.references import nemotron_h as reference
from ray_tpu._private import flight_recorder
from ray_tpu.models import moe, nemotron_h
from ray_tpu.models.serving import served_model
from ray_tpu.serve.llm import LLMEngine, SamplingParams
from tools import glm_logit_check

FILE = load_json(ROOT, "benchmark", "configs", "nemotron-3-super-serve.json")
ADAPTER = model_adapter(FILE)


def debug_config():
    config = ADAPTER.debug(FILE)
    # 45 is no multiple of the 8-token chunk and no bucket: the check
    # pads it to 64; the shorter rows decode from their own lengths.
    config["serve"] = {**config["serve"], "max_seq_len": 128,
                       "reference_prompt_lens": [45, 39, 26, 19],
                       "reference_decode_steps": 8}
    return config


CONFIG = debug_config()
CFG = ADAPTER.program_config(CONFIG)
FAULTS = glm_logit_check.nemotron_faults(ADAPTER.cached_forward,
                                         ADAPTER.init_cache)


@pytest.fixture(scope="module")
def errors():
    """Of the program and of each fault, the statistics of its
    positions' errors, at the program's own (plain) weights."""
    small, params, lens, tokens = glm_logit_check.weights_and_tokens(
        CONFIG, 2 ** 31 + 5, ADAPTER, nemotron_h.init_params)
    return glm_logit_check.distances(
        CONFIG, small, params, lens, tokens, ADAPTER, reference,
        {"program": ADAPTER.cached_forward, **FAULTS})


@pytest.fixture(scope="module")
def distances(errors):
    return {name: row["max"] for name, row in errors.items()}


@pytest.fixture(scope="module")
def params():
    return nemotron_h.init_params(CFG, jax.random.PRNGKey(2))


def _tokens(shape, seed=1):
    return jnp.asarray(np.random.default_rng(seed).integers(
        1, CFG.vocab_size, shape, dtype=np.int32))


def _state(cache):
    return [x for x, is_state in zip(
        jax.tree.leaves(cache),
        jax.tree.leaves(nemotron_h.state_leaves(cache))) if is_state]


def test_the_file_builds_the_published_model():
    cfg = ADAPTER.program_config(FILE)
    assert cfg.pattern == "MEMEMEMEM*E" == nemotron_h.PUBLISHED_PATTERN[27:38]
    assert FILE["hybrid_override_pattern"] == nemotron_h.PUBLISHED_PATTERN
    assert cfg.runs() == [(("ssm", "moe"), 4), (("ssm", None), 1),
                          (("attn", "moe"), 1)]
    assert (cfg.dim, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
            cfg.ssm_state, cfg.conv_kernel, cfg.chunk_size) == (
                4096, 128, 64, 8, 128, 4, 128)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.n_experts, cfg.n_experts_per_token, cfg.latent_dim,
            cfg.hidden_dim, cfg.shared_hidden_dim, cfg.experts_held,
            cfg.expert_kind, cfg.gate_scale) == (
                512, 22, 1024, 2688, 5376, (0, 128), "relu2", 5.0)
    # The defaults are the published model's.
    assert dataclasses.replace(
        cfg, vocab_size=131072, n_layers=88, experts_held=None,
        pattern=nemotron_h.PUBLISHED_PATTERN) == nemotron_h.NemotronHConfig()
    held = jax.eval_shape(
        lambda: nemotron_h.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree.leaves(held)) == 4_648_163_712
    whole = jax.eval_shape(lambda: nemotron_h.init_params(
        nemotron_h.NemotronHConfig(), jax.random.PRNGKey(0)))
    assert round(sum(x.size for x in jax.tree.leaves(whole)) / 1e8) == 1207
    cache = jax.eval_shape(lambda: nemotron_h.init_cache(cfg, 64, 4096))
    leaves = dict(zip(("state", "rows"), (
        [x for x, s in zip(jax.tree.leaves(cache), jax.tree.leaves(
            nemotron_h.state_leaves(cache))) if s is want]
        for want in (True, False))))
    assert sorted(x.shape[2:] for x in leaves["state"]) == sorted(
        2 * [(128, 64, 128), (3, 10240)])
    assert [x.shape for x in leaves["rows"]] == 2 * [(1, 64, 4096, 2, 128)]
    # 21.3 MB of state a slot, 1 KB of keys and values a token.
    assert sum(x.size * x.dtype.itemsize for x in leaves["state"]) // 64 \
        == 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert sum(x.size * x.dtype.itemsize for x in leaves["rows"]) \
        // (64 * 4096) == 1024
    assert ADAPTER.with_layers(cfg, 5).runs() == [
        (("ssm", "moe"), 1), (("ssm", None), 1), (("attn", "moe"), 1)]


@pytest.mark.parametrize("pattern, blocks", [
    ("MEM*E", (("ssm", "moe"), ("ssm", None), ("attn", "moe"))),
    ("EM", ((None, "moe"), ("ssm", None))),
    ("M*", (("ssm", None), ("attn", None))),
    ("EE*EM", ((None, "moe"), (None, "moe"), ("attn", "moe"),
               ("ssm", None))),
])
def test_a_published_layer_is_a_mixer_or_an_ffn_alone(pattern, blocks):
    """Blocks pair a mixer with the expert layer that follows it; every
    other layer is a block of one half. Each such stack runs."""
    cfg = dataclasses.replace(CFG, pattern=pattern, n_layers=len(pattern))
    assert cfg.blocks == blocks
    params = nemotron_h.init_params(cfg, jax.random.PRNGKey(0))
    logits, cache = nemotron_h.forward_with_cache(
        params, _tokens((2, 9)), cfg, nemotron_h.init_cache(cfg, 2, 16),
        jnp.zeros(2, jnp.int32))
    assert logits.shape == (2, 9, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert len(cache["runs"]) == len(cfg.runs())


def test_operations_and_bytes_are_counted_from_the_files_shapes():
    from benchmark.flops import nemotron_h as flops

    assert flops.mamba_params(FILE) == 4096 * 18560 + 8192 * 4096
    assert flops.attention_params(FILE) == 35_651_584
    assert flops.expert_params(FILE) == 5_505_024
    assert flops.expert_layer_params(FILE) == 4096 * (512 + 2 * 1024
                                                      + 2 * 5376)
    # A decode step reads every matrix held once (all parameters but
    # the norms, the convolutions, A, D and the biases) and reads and
    # writes every slot's state.
    vectors = 5 * (4096 + 8192 + 5 * 10240 + 3 * 128) + 4096 \
        + 5 * (4096 + 512) + 4096
    matrices = 4_648_163_712 - vectors
    assert flops.decode_step_bytes(FILE, 64, 0) == 2 * matrices + 64 * 2 \
        * 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    # 120 of the 128 held experts touched leaves eight a layer unread.
    assert flops.decode_step_bytes(FILE, 64, 0) \
        - flops.decode_step_bytes(FILE, 64, 0, touched=120) \
        == 2 * 5 * 8 * 5_505_024
    assert flops.decode_step_bytes(FILE, 64, 1000) \
        - flops.decode_step_bytes(FILE, 64, 0) == 64 * 1000 * 1024
    assert flops.ssm_update_bytes(FILE, 64) == 2 * 64 * 5 * 4 * 128 * 64 * 128
    # One attention layer's two products a key.
    near, far = (flops.prefill_flops_per_token(FILE, n) for n in (500, 1500))
    assert far - near == 2 * 2 * 1000 * 32 * 128
    assert flops.train_flops_per_token(FILE, 4096) \
        == 3 * flops.prefill_flops_per_token(FILE, 2048)


def test_the_served_path_agrees_with_the_reference(distances):
    assert distances["program"] < 1e-6


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails(distances, fault):
    assert distances[fault] > 1e-5 > 20 * distances["program"]


def test_the_tool_takes_the_family_by_its_configurations_name(errors):
    family_faults, unseen, plain_init, _ = glm_logit_check.FAMILIES[
        FILE["family"]]
    assert family_faults is glm_logit_check.nemotron_faults
    assert plain_init() is nemotron_h.init_params
    assert set(glm_logit_check.FAMILIES) >= {"glm_dsa", "nemotron_h",
                                             "cohere2_moe", "olmo_hybrid"}
    # What one set of weights cannot show on the chip is named, and is
    # a fault; each limit of the file names a statistic the tool gives.
    checks = FILE["serve"]["tool_checks"]
    assert set(checks) == set(unseen) == {"benchmark", "plain"}
    assert all(set(names) < set(FAULTS) for names in unseen.values())
    assert all(name in errors["program"] for limits in checks.values()
               for name in limits)
    for row in errors.values():
        assert 0 <= row["q50"] <= row["q99"] <= row["q99.9"] <= row["max"]
    assert glm_logit_check.within(errors["program"], checks["plain"])
    assert not glm_logit_check.within(errors["no D term"], checks["plain"])


def test_the_benchmarks_weights_are_the_programs_but_two_scales():
    key = jax.random.PRNGKey(4)
    plain, drawn = nemotron_h.init_params(CFG, key), ADAPTER.init(CFG, key)
    scales = {"we2": ADAPTER.ROUTED_OUT_SCALE,
              "router_bias": ADAPTER.ROUTER_BIAS_SCALE}
    scaled = dict.fromkeys(scales, 0)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(plain),
                            jax.tree.leaves(drawn)):
        name = getattr(path[-1], "key", None)
        scaled[name] = scaled.get(name, 0) + 1
        assert np.array_equal(np.asarray(a) * scales.get(name, 1),
                              np.asarray(b)), name
    sparse = sum(kind[1] == "moe" for kind, _ in CFG.runs())
    assert scaled["we2"] == scaled["router_bias"] == sparse


def test_a_padded_prompt_leaves_the_same_logits_and_state(params):
    """13 tokens in a bucket of 16, neither a multiple of the 8-token
    chunk: the padding changes no logit of the prompt and nothing of
    the state the prompt leaves."""
    tokens = _tokens((2, 13))
    start = jnp.zeros(2, jnp.int32)
    want, left = nemotron_h.forward_with_cache(
        params, tokens, CFG, nemotron_h.init_cache(CFG, 2, 32), start)
    padded = jnp.pad(tokens, ((0, 0), (0, 3)), constant_values=7)
    got, state = nemotron_h.forward_with_cache(
        params, padded, CFG, nemotron_h.init_cache(CFG, 2, 32), start, at=12)
    np.testing.assert_allclose(got[:, :13], want, atol=1e-6)
    for a, b in zip(_state(state), _state(left)):
        np.testing.assert_allclose(a, b, atol=1e-6)
    # Without `at` the padding is absorbed.
    _, absorbed = nemotron_h.forward_with_cache(
        params, padded, CFG, nemotron_h.init_cache(CFG, 2, 32), start)
    assert max(float(jnp.abs(a - b).max())
               for a, b in zip(_state(absorbed), _state(left))) > 1e-3
    # The engine's `forward` gives the logits of position `at` itself.
    last, _, _ = nemotron_h.forward(
        params, padded, CFG, nemotron_h.init_cache(CFG, 2, 32), start,
        jnp.int32(12))
    np.testing.assert_allclose(last, want[:, 12], atol=1e-6)


def test_a_prefill_in_two_calls_equals_one(params):
    tokens = _tokens((2, 21), seed=3)
    start = jnp.zeros(2, jnp.int32)
    want, left = nemotron_h.forward_with_cache(
        params, tokens, CFG, nemotron_h.init_cache(CFG, 2, 32), start)
    first, cache = nemotron_h.forward_with_cache(
        params, tokens[:, :11], CFG, nemotron_h.init_cache(CFG, 2, 32),
        start)
    second, cache = nemotron_h.forward_with_cache(
        params, tokens[:, 11:], CFG, cache, start + 11)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), want,
                               atol=1e-6)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(left)):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_rows_of_different_lengths_in_one_decode_batch_equal_each_alone(
        params):
    """Each row is prefilled alone into its slot, as the engine admits
    it; the two then decode together from their own positions."""
    lens, steps = (17, 9), 4
    tokens = _tokens((2, max(lens) + steps), seed=4)
    cache = nemotron_h.init_cache(CFG, 2, 32)
    alone = []
    for row, n in enumerate(lens):
        slot = jax.tree.map(lambda x: x[:, row:row + 1], cache)
        _, slot = nemotron_h.forward_with_cache(
            params, tokens[row:row + 1, :n], CFG, slot,
            jnp.zeros(1, jnp.int32))
        cache = jax.tree.map(
            lambda x, new: x.at[:, row:row + 1].set(new), cache, slot)
        logits = []
        for i in range(steps):
            out, slot = nemotron_h.forward_with_cache(
                params, tokens[row:row + 1, n + i:n + i + 1], CFG, slot,
                jnp.full(1, n + i, jnp.int32))
            logits.append(out[0, 0])
        alone.append(logits)
    at = np.arange(2)
    for i in range(steps):
        pos = np.asarray(lens) + i
        out, cache = nemotron_h.forward_with_cache(
            params, tokens[at, pos][:, None], CFG, cache,
            jnp.asarray(pos, jnp.int32))
        for row in range(2):
            np.testing.assert_allclose(out[row, 0], alone[row][i], atol=1e-6)


def _sparse_layer(seed=3):
    cfg = dataclasses.replace(CFG, experts_held=None)
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    lp = moe.expert_init(cfg, keys[:4])
    y = jax.random.normal(keys[4], (2, 24, cfg.dim))
    return cfg, lp, y


def test_the_shares_add_up_to_the_uncut_layer():
    """4 shares of 4 of the 16 experts, each up-projected on its own
    chip, the shared expert counted once, against the reference given
    all 16."""
    cfg, lp, y = _sparse_layer()
    hp = {**reference.hyper(CONFIG), "first_expert": 0}
    want = jax.vmap(lambda rows: reference.experts(rows, lp, hp))(y)
    shared = moe._add_shared_expert(cfg, lp, y, jnp.zeros_like(y))
    total, held, touched = shared, 0, 0
    for first in range(0, 16, 4):
        share = dataclasses.replace(cfg, experts_held=(first, 4))
        part = {**lp, **{k: lp[k][first:first + 4] for k in ("we1", "we2")}}
        out, _, counts, counted = moe._moe_ffn(share, part, y, None, None)
        assert int(counted["pairs_held"]) == int(
            counts[first:first + 4].sum())
        assert int(counted["pairs_routed"]) == 2 * 24 * 3
        assert int(counted["experts_touched"]) == int(
            (counts[first:first + 4] > 0).sum())
        assert int(counted["experts_held_steps"]) == 4
        total = total + (out - shared)
        held += int(counted["pairs_held"])
        touched += int(counted["experts_touched"])
        np.testing.assert_allclose(
            out, jax.vmap(lambda rows: reference.experts(
                rows, part, {**hp, "first_expert": first}))(y), atol=2e-6)
    assert held == 2 * 24 * 3 and 0 < touched <= 16
    np.testing.assert_allclose(total, want, atol=5e-6)
    whole, _, counts, counted = moe._moe_ffn(cfg, lp, y, None, None)
    np.testing.assert_allclose(whole, want, atol=5e-6)
    assert int(counted["experts_touched"]) == int((counts > 0).sum())
    assert int(counted["experts_held_steps"]) == 16


def test_the_contract_of_a_model_with_state_leaves(params):
    model = served_model(CFG)
    assert model.forward is nemotron_h.forward
    cache = model.init_cache(CFG, 2, 16)
    kinds = jax.tree.leaves(model.state_leaves(cache))
    assert kinds == [True, True, True, True, False, False]
    tokens = _tokens((2, 6))
    logits, new, counts = model.forward(params, tokens, CFG, cache,
                                        jnp.zeros(2, jnp.int32), 5)
    assert logits.shape == (2, CFG.vocab_size)
    assert logits.dtype == jnp.float32
    assert jax.tree.structure(new) == jax.tree.structure(cache)
    assert sorted(counts) == ["experts_held_steps", "experts_touched",
                              "pair_overflows", "pairs_held", "pairs_routed"]
    assert all(x.dtype == jnp.int32 and x.shape == () for x in
               counts.values())
    # Three expert layers of four held experts each.
    assert int(counts["experts_held_steps"]) == 12
    assert int(counts["pairs_routed"]) == 3 * 2 * 6 * 3
    # Every other served model's leaves are rows.
    from ray_tpu.models import llama
    dense = llama.LlamaConfig.debug()
    assert not any(jax.tree.leaves(served_model(dense).state_leaves(
        served_model(dense).init_cache(dense, 2, 16))))


def test_each_mixer_is_scoped_by_its_kind(params):
    """The Mamba-2 layers' ops lie under `ssm`, never under `attn`: a
    trace's attention share reads the attention layer alone."""
    def lowered(t):
        return jax.jit(lambda p, c: nemotron_h.forward(
            p, _tokens((2, t)), CFG, c, jnp.ones(2, jnp.int32), t - 1)
        ).lower(params, nemotron_h.init_cache(CFG, 2, 32)).as_text(
            debug_info=True)

    decode, prefill = lowered(1), lowered(16)
    for scope in ("ssm/ssm_conv", "ssm/ssm_update", "mlp/latent_down",
                  "mlp/latent_up", "mlp/shared_expert", "mlp/router",
                  "attn/"):
        assert scope in decode, scope
    assert "ssm/ssm_scan" in prefill and "ssm/ssm_update" not in prefill
    assert "ssm/ssm_scan" not in decode
    for text in (decode, prefill):
        assert "attn/ssm" not in text and "ssm/attn" not in text


# -- the engine over a cache with state leaves -------------------------------


def _greedy(params, prompt, n):
    """Greedy decoding by the reference's full forward pass."""
    hp = reference.hyper(CONFIG)
    tokens = list(prompt)
    for _ in range(n):
        logits = reference.sequence_logits(
            params, jnp.asarray(tokens, jnp.int32), hp)
        tokens.append(int(logits[-1].argmax()))
    return tokens[len(prompt):]


def _prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n)]


def test_the_engine_serves_it_with_no_prefix_cache(params):
    engine = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=64)
    assert engine.prefix_cache is None and engine.prefix_digests() is None
    assert engine._is_state == [True, True, True, True, False, False]
    # Blocks and read-backs are sized by the rows alone: one attention
    # layer's keys and values.
    assert engine._block_nbytes == engine.block_tokens * 2 * 2 * 16 * 4
    prompt = _prompt(21, 5)
    first = engine.generate(prompt, SamplingParams(max_tokens=6))
    second = engine.generate(prompt, SamplingParams(max_tokens=6))
    engine.stop()
    assert first == second == _greedy(params, prompt, 6)
    assert "kv_cache" not in engine.metrics()
    assert not engine._read_rows_exec and not engine._kv_store
    totals = engine.metrics()["totals"]
    assert totals["kv_blocks_read_back"] == 0
    assert 0 < totals["pairs_held"] < totals["pairs_routed"]
    assert 0 < totals["experts_touched"] <= totals["experts_held_steps"]
    assert totals["experts_held_steps"] % 12 == 0


def test_a_retired_slot_admitted_again_equals_a_fresh_engine(params):
    """One slot: the second request gets the slot the first one left,
    whose state kept stepping after it was retired."""
    engine = LLMEngine(CFG, params, max_batch_size=1, max_seq_len=64,
                       decode_steps=2)
    engine.generate(_prompt(19, 6), SamplingParams(max_tokens=5))
    before = [np.asarray(x) for x in _state(engine.cache)]
    assert any(np.abs(x).max() > 0 for x in before)
    prompt = _prompt(11, 7)
    again = engine.generate(prompt, SamplingParams(max_tokens=7))
    engine.stop()
    fresh = LLMEngine(CFG, params, max_batch_size=1, max_seq_len=64,
                      decode_steps=2)
    want = fresh.generate(prompt, SamplingParams(max_tokens=7))
    fresh.stop()
    assert again == want == _greedy(params, prompt, 7)


def test_requests_beside_each_other_keep_their_own_state(params):
    import threading

    engine = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=64)
    engine.warmup(32)
    prompts = [_prompt(23, 8), _prompt(9, 9), _prompt(14, 10)]
    answers = [None] * 3

    def ask(i):
        answers[i] = engine.generate(prompts[i],
                                     SamplingParams(max_tokens=6))

    threads = [threading.Thread(target=ask, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    engine.stop()
    assert answers == [_greedy(params, p, 6) for p in prompts]


def test_decode_spans_carry_the_models_counts(params):
    engine = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=64)
    engine.generate(list(range(1, 14)), SamplingParams(max_tokens=4))
    engine.stop()
    consumed = [s["attrs"] for s in
                flight_recorder.local_snapshot()["spans"]
                if s.get("attrs") and s["stage"] == "engine.consume_block"
                and "experts_touched" in s["attrs"]]
    assert consumed
    assert all(0 < a["experts_touched"] <= a["experts_held_steps"] == 12
               and a["pairs_held"] <= a["pairs_routed"] for a in consumed)
