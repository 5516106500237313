"""LFM2's decoder at debug widths on the CPU, in float32, seeded random
weights: the served path (a prefill padded to its bucket, then decode
through the cache, rows of different lengths) against the plain
reference, each fault of `tools/glm_logit_check.py` failing where the
program passes; what a state leaf demands of a forward pass (padding
kept out of the carried rows, a prefill in two calls, a slot used
before); the two mixers' scopes and kernels; and the engine, which
knows no model, serving it with no prefix cache."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.manifest import ROOT, load_json, model_adapter
from benchmark.references import lfm2_moe as reference
from ray_tpu._private import flight_recorder
from ray_tpu.models import lfm2_moe, mamba2
from ray_tpu.models.serving import served_model
from ray_tpu.serve.llm import LLMEngine, SamplingParams
from tests.models.test_cached_attention import through_the_kernel
from tools import glm_logit_check

FILE = load_json(ROOT, "benchmark", "configs", "lfm2-8b-a1b-serve.json")
ADAPTER = model_adapter(FILE)


def debug_config():
    config = ADAPTER.debug(FILE)
    # 45 is no bucket: the check pads it to 64; the shorter rows decode
    # from their own lengths, the shortest from 5, where the first two
    # positions (what a carry that was not zeroed moves) still weigh.
    config["serve"] = {**config["serve"], "max_seq_len": 128,
                       "reference_prompt_lens": [45, 33, 12, 5],
                       "reference_decode_steps": 8}
    return config


CONFIG = debug_config()
CFG = ADAPTER.program_config(CONFIG)
FAULTS = glm_logit_check.lfm2_faults(ADAPTER.cached_forward,
                                     ADAPTER.init_cache)


@pytest.fixture(scope="module")
def distances():
    """Of the program and of each fault, the largest logit error over
    the largest |reference| logit, at the program's own weights."""
    small, params, lens, tokens = glm_logit_check.weights_and_tokens(
        CONFIG, 2 ** 31 + 5, ADAPTER, lfm2_moe.init_params)
    rows = glm_logit_check.distances(
        CONFIG, small, params, lens, tokens, ADAPTER, reference,
        {"program": ADAPTER.cached_forward, **FAULTS})
    return {name: row["max"] for name, row in rows.items()}


@pytest.fixture(scope="module")
def params():
    return lfm2_moe.init_params(CFG, jax.random.PRNGKey(2))


def _tokens(shape, seed=1):
    return jnp.asarray(np.random.default_rng(seed).integers(
        1, CFG.vocab_size, shape, dtype=np.int32))


def _state(cache):
    return [x for x, is_state in zip(
        jax.tree.leaves(cache),
        jax.tree.leaves(lfm2_moe.state_leaves(cache))) if is_state]


def _cache(rows=2, max_seq=32):
    return lfm2_moe.init_cache(CFG, rows, max_seq)


def test_the_file_builds_the_published_model():
    cfg = ADAPTER.program_config(FILE)
    assert cfg.layer_types == lfm2_moe.PUBLISHED_LAYER_TYPES[:14]
    assert cfg.runs() == [(("dense", "conv"), 2)] + [
        (("sparse", "full"), 1), (("sparse", "conv"), 3)] * 3
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.hidden_dim, cfg.dense_hidden_dim, cfg.vocab_size) == (
                2048, 32, 8, 64, 1792, 7168, 65536)
    assert (cfg.n_experts, cfg.n_experts_per_token, cfg.scoring,
            cfg.selection_bias, cfg.norm_topk_prob, cfg.gate_scale,
            cfg.shared_hidden_dim, cfg.experts_held) == (
                32, 4, "sigmoid", True, True, 1.0, 0, None)
    assert (cfg.conv_kernel, cfg.n_dense_layers, cfg.tie_embeddings,
            cfg.rope_theta, cfg.norm_eps) == (3, 2, True, 1e6, 1e-5)
    assert cfg.dtype == jnp.bfloat16
    # The defaults are the published model's.
    assert dataclasses.replace(
        cfg, n_layers=24, layer_types=lfm2_moe.PUBLISHED_LAYER_TYPES) \
        == lfm2_moe.Lfm2MoeConfig()
    assert lfm2_moe.PUBLISHED_LAYER_TYPES == tuple(
        {"conv": "conv", "full_attention": "full"}[kind]
        for kind in FILE["layer_types"])
    # The compared stack: published layers 1 to 4, every kind of layer.
    assert ADAPTER.with_layers(cfg, 4).runs() == [
        (("dense", "conv"), 1), (("sparse", "full"), 1),
        (("sparse", "conv"), 2)]
    assert CFG == lfm2_moe.Lfm2MoeConfig.debug_lfm2()
    assert CFG.n_heads // CFG.n_kv_heads == cfg.n_heads // cfg.n_kv_heads


def test_the_served_path_agrees_with_the_reference(distances):
    assert distances["program"] < 1e-6


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails(distances, fault):
    """Every fault reads at least ten times the limit the program keeps
    (the weakest, a carry not zeroed, moves two positions of a row's
    second prefill and reaches the compared decode steps through the
    keys of those two alone)."""
    assert distances[fault] > 1e-5 > 10 * distances["program"]


def test_the_tool_takes_the_family_by_its_configurations_name():
    family_faults, unseen, plain_init, _ = glm_logit_check.FAMILIES[
        FILE["family"]]
    assert family_faults is glm_logit_check.lfm2_faults
    assert plain_init() is lfm2_moe.init_params
    checks = FILE["serve"]["tool_checks"]
    assert set(checks) == set(unseen) == {"benchmark", "plain"}
    assert all(set(names) < set(FAULTS) for names in unseen.values())
    assert set(FAULTS) == {
        "lower precision", "silu in the conv", "no B gate", "no C gate",
        "pad absorbed", "carry not zeroed", "no q and k norm",
        "bias in the gates", "gates not renormalised",
        "experts in the dense layers"}


def test_the_convolution_is_mamba2s_with_no_activation():
    """`mamba2._conv` told `activation=None` hands back the sum itself;
    with its default it is what it was, silu of that sum."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 7, 6)), jnp.float32)
    carry = jnp.asarray(rng.normal(size=(2, 2, 6)), jnp.float32)
    lp = {"conv_w": jnp.asarray(rng.normal(size=(6, 3)), jnp.float32)}
    at = jnp.asarray([6, 3], jnp.int32)
    plain, rows = mamba2._conv(CFG, lp, carry, x, at, bias=False,
                               activation=None)
    window = np.concatenate([carry, x], 1)
    want = sum(window[:, j:j + 7] * np.asarray(lp["conv_w"])[:, j]
               for j in range(3))
    np.testing.assert_allclose(plain, want, atol=1e-6)
    np.testing.assert_array_equal(rows[0], x[0, 5:7])
    np.testing.assert_array_equal(rows[1], x[1, 2:4])
    activated, same_rows = mamba2._conv(CFG, lp, carry, x, at, bias=False)
    np.testing.assert_allclose(activated, jax.nn.silu(plain), atol=1e-6)
    np.testing.assert_array_equal(same_rows, rows)


def test_a_padded_prompt_leaves_the_same_logits_and_carries(params):
    """13 tokens in a bucket of 16: the padding changes no logit of the
    prompt and nothing of the carried rows."""
    tokens = _tokens((2, 13))
    start = jnp.zeros(2, jnp.int32)
    want, left = lfm2_moe.forward_with_cache(params, tokens, CFG, _cache(),
                                             start)
    padded = jnp.pad(tokens, ((0, 0), (0, 3)), constant_values=7)
    got, state = lfm2_moe.forward_with_cache(params, padded, CFG, _cache(),
                                             start, at=12)
    np.testing.assert_allclose(got[:, :13], want, atol=1e-6)
    assert len(_state(state)) == 3  # one leaf a run of conv layers
    for a, b in zip(_state(state), _state(left)):
        np.testing.assert_allclose(a, b, atol=1e-6)
    # Without `at` the padding is what is carried.
    _, absorbed = lfm2_moe.forward_with_cache(params, padded, CFG, _cache(),
                                              start)
    for a, b in zip(_state(absorbed), _state(left)):
        assert float(jnp.abs(a - b).max()) > 1e-3
    # The engine's `forward` gives the logits of position `at` itself,
    # and counts the real tokens and the rows that started from zeros.
    last, _, counts = lfm2_moe.forward(params, padded, CFG, _cache(), start,
                                       jnp.int32(12))
    np.testing.assert_allclose(last, want[:, 12], atol=1e-6)
    assert int(counts["conv_prefill_tokens"]) == 2 * 13
    assert int(counts["conv_state_resets"]) == 2
    assert int(counts["pairs_routed"]) == 6 * 2 * 16 * 3  # six expert layers


def test_a_prefill_in_two_calls_equals_one(params):
    tokens = _tokens((2, 21), seed=3)
    start = jnp.zeros(2, jnp.int32)
    want, left = lfm2_moe.forward_with_cache(params, tokens, CFG, _cache(),
                                             start)
    first, cache = lfm2_moe.forward_with_cache(
        params, tokens[:, :11], CFG, _cache(), start)
    second, cache = lfm2_moe.forward_with_cache(
        params, tokens[:, 11:], CFG, cache, start + 11)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), want,
                               atol=1e-5)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(left)):
        np.testing.assert_allclose(a, b, atol=1e-5)
    _, _, counts = lfm2_moe.forward(params, tokens[:, 11:], CFG, cache,
                                    start + 11, 9)
    assert int(counts["conv_state_resets"]) == 0
    assert int(counts["conv_prefill_tokens"]) == 2 * 10


def test_a_row_that_starts_at_zero_starts_from_zeros(params):
    """A cache whose carried rows hold another request's: a prefill from
    position 0 reads none of them, one from a later position does."""
    tokens = _tokens((2, 9), seed=5)
    start = jnp.zeros(2, jnp.int32)
    want, _ = lfm2_moe.forward_with_cache(params, tokens, CFG, _cache(),
                                          start)
    _, used = lfm2_moe.forward_with_cache(params, _tokens((2, 12), seed=6),
                                          CFG, _cache(), start)
    assert all(float(jnp.abs(x).max()) > 0 for x in _state(used))
    got, _ = lfm2_moe.forward_with_cache(params, tokens, CFG, used, start)
    np.testing.assert_allclose(got, want, atol=1e-6)
    later, _ = lfm2_moe.forward_with_cache(params, tokens, CFG, used,
                                           start + 12)
    fresh, _ = lfm2_moe.forward_with_cache(params, tokens, CFG, _cache(),
                                           start + 12)
    assert float(jnp.abs(later - fresh).max()) > 1e-3


def test_rows_of_different_lengths_in_one_batch_equal_the_reference(params):
    """Rows of 17 and 9 tokens prefilled in one call, each left after
    its own last token, then decoding together from their own
    positions: every logit is the reference's full forward pass's."""
    lens, steps = np.asarray((17, 9)), 4
    tokens = _tokens((2, 17 + steps), seed=4)
    hp = reference.hyper(CONFIG)
    with jax.default_matmul_precision("highest"):
        want = [np.asarray(reference.sequence_logits(
            params, tokens[row, :n + steps], hp))
            for row, n in enumerate(lens)]
    logits, cache = lfm2_moe.forward_with_cache(
        params, tokens[:, :17], CFG, _cache(), jnp.zeros(2, jnp.int32),
        at=jnp.asarray(lens - 1, jnp.int32))
    top = max(np.abs(w).max() for w in want)
    for row, n in enumerate(lens):
        np.testing.assert_allclose(logits[row, :n], want[row][:n],
                                   atol=3e-6 * top)
    at = np.arange(2)
    for i in range(steps):
        pos = lens + i
        # The short row's token at its own position, not the prefill's.
        fed = jnp.asarray(np.asarray(tokens)[at, pos][:, None])
        out, cache = lfm2_moe.forward_with_cache(
            params, fed, CFG, cache, jnp.asarray(pos, jnp.int32))
        for row in range(2):
            np.testing.assert_allclose(out[row, 0], want[row][pos[row]],
                                       atol=3e-6 * top)
    _, _, counts = lfm2_moe.forward(params, fed, CFG, cache,
                                    jnp.asarray(pos + 1, jnp.int32), 0)
    assert int(counts["conv_prefill_tokens"]) == 0
    assert int(counts["conv_state_resets"]) == 0


def test_the_contract_of_a_model_whose_only_state_is_a_carry(params):
    model = served_model(CFG)
    assert model.forward is lfm2_moe.forward
    cache = model.init_cache(CFG, 2, 16)
    kinds = jax.tree.leaves(model.state_leaves(cache))
    assert kinds == [True, False, False, True, False, False, True]
    assert [run["conv"].shape for run in cache["runs"] if "conv" in run] \
        == [(2, 2, 2, 64), (3, 2, 2, 64), (1, 2, 2, 64)]
    assert cache["runs"][1]["k"].shape == (1, 2, 16, 2 * 8)
    logits, new, counts = model.forward(params, _tokens((2, 6)), CFG, cache,
                                        jnp.zeros(2, jnp.int32), 5)
    assert logits.shape == (2, CFG.vocab_size)
    assert logits.dtype == jnp.float32
    assert jax.tree.structure(new) == jax.tree.structure(cache)
    assert {"conv_prefill_tokens", "conv_state_resets", "experts_touched",
            "experts_held_steps", "pairs_held"} <= set(counts)
    assert all(x.dtype == jnp.int32 and x.shape == () for x in
               counts.values())
    # The head is the embedding: the tree has no `out`.
    assert set(params) == {"embed", "runs", "final_norm"}


def test_each_mixer_is_scoped_by_its_kind(params):
    """The conv layers' ops lie under `conv`, never under `attn`: a
    trace's attention share reads the full layers alone."""
    def lowered(t):
        return jax.jit(lambda p, c: lfm2_moe.forward(
            p, _tokens((2, t)), CFG, c, jnp.ones(2, jnp.int32), t - 1)
        ).lower(params, _cache()).as_text(debug_info=True)

    for text in (lowered(1), lowered(16)):
        for scope in ("conv/conv_in", "attn/", "mlp/router",
                      "mlp/moe_dispatch", "/expert_matmul"):
            assert scope in text, scope
        assert "attn/conv" not in text and "conv/attn" not in text


@pytest.mark.parametrize("rows", [16, 256], ids=["blocks-of-16", "one-block"])
@pytest.mark.parametrize("lens", [(17, 9), (16, 1), (29, 15)],
                         ids=lambda lens: "-".join(map(str, lens)))
def test_a_decode_step_through_the_kernel_equals_the_plain_path(
        params, monkeypatch, lens, rows):
    """The full layers' decode step through
    `ops.attention.decode_attention` on the merged axis (two key heads
    side by side in a row, four query heads each), the kernel a TPU
    runs, interpreted here, against `llama._cached_attention` on the
    [rows, heads, head size] view, which the CPU takes."""
    lens, steps = np.asarray(lens), 3
    tokens = _tokens((2, lens.max() + steps), seed=int(lens.sum()))
    _, filled = lfm2_moe.forward_with_cache(
        params, tokens[:, :lens.max()], CFG, _cache(),
        jnp.zeros(2, jnp.int32), at=jnp.asarray(lens - 1, jnp.int32))

    def decoded():
        out, cache, at = [], filled, np.arange(2)
        for i in range(steps):
            fed = jnp.asarray(np.asarray(tokens)[at, lens + i][:, None])
            logits, cache = lfm2_moe.forward_with_cache(
                params, fed, CFG, cache, jnp.asarray(lens + i, jnp.int32))
            out.append(np.asarray(logits))
        return np.stack(out), cache

    want, plain_cache = decoded()
    through_the_kernel(monkeypatch, lfm2_moe, rows)
    got, cache = decoded()
    np.testing.assert_allclose(got, want, atol=3e-6 * np.abs(want).max())
    assert not np.array_equal(got, want)  # it did go another way
    for x, y in zip(jax.tree.leaves(cache), jax.tree.leaves(plain_cache)):
        np.testing.assert_allclose(x, y, atol=3e-6 * np.abs(y).max())


# 128 rows in two tiles of 64; 640, a multiple of 128 between two powers
# of two as `serve.llm.prefill_bucket`'s 384, 768 and 1,536 are, in the
# tile the kernel chooses.
@pytest.mark.parametrize("rows, tiles", [
    (128, {"block_q": 64, "block_k": 64}), (640, {})], ids=["128", "640"])
def test_a_prefill_through_the_flash_kernel_equals_the_plain_path(
        params, monkeypatch, rows, tiles):
    """A prefill from position 0 at a bucket the kernel tiles (any
    multiple of 128 rows) takes `flash_attention_forward` over the
    call's own keys on a TPU (interpreted here); one from a later
    position takes the plain path on the same program."""
    import types

    from jax import lax

    from ray_tpu.ops import attention

    tokens = _tokens((1, rows), seed=8)
    start = jnp.zeros(1, jnp.int32)
    want, plain_cache = lfm2_moe.forward_with_cache(
        params, tokens, CFG, _cache(1, 2 * rows), start, at=rows - 28)
    calls = []

    def flash(q, k, v):
        calls.append(q.shape)
        return attention.flash_attention_forward(
            q, k, v, interpret=True, **tiles)

    monkeypatch.setattr(lfm2_moe, "attention", types.SimpleNamespace(
        on_tpu=lambda: False, flash_attention_forward=flash))
    # `serving.own_keys` as it is on a TPU.
    monkeypatch.setattr(
        lfm2_moe, "own_keys", lambda tiled, start_pos, flash, plain:
        lax.cond(start_pos.max() == 0, flash, plain) if tiled else plain())
    got, cache = lfm2_moe.forward_with_cache(
        params, tokens, CFG, _cache(1, 2 * rows), start, at=rows - 28)
    assert calls == [(1, rows, 8, 8)] * 2  # a trace a run of full layers
    np.testing.assert_allclose(got, want, atol=3e-6 * np.abs(want).max())
    assert not np.array_equal(got, want)
    for x, y in zip(jax.tree.leaves(cache), jax.tree.leaves(plain_cache)):
        np.testing.assert_allclose(x, y, atol=3e-6 * np.abs(y).max())
    later, _ = lfm2_moe.forward_with_cache(
        params, tokens, CFG, cache, start + rows, at=rows - 28)
    assert later.shape == want.shape and bool(jnp.isfinite(later).all())


# -- the engine over a cache whose state is a carry ---------------------------

# Published layers 1 to 4 are enough for the engine: a conv layer with
# the dense FFN, a full layer and two conv layers with experts.
ONE = ADAPTER.with_layers(CFG, 4)


@pytest.fixture(scope="module")
def one_period():
    return lfm2_moe.init_params(ONE, jax.random.PRNGKey(2))


def _is_greedy(params, prompt, answer):
    """Whether `answer` is greedy decoding by the reference: each of
    its tokens the largest logit of the reference's full forward pass
    over what came before it."""
    logits = reference.sequence_logits(
        params, jnp.asarray((prompt + answer)[:-1], jnp.int32),
        reference.hyper(CONFIG))
    return answer == [int(t) for t in
                      logits[len(prompt) - 1:].argmax(-1)]


def _prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n)]


def test_the_engine_serves_it_with_no_prefix_cache(one_period):
    engine = LLMEngine(ONE, one_period, max_batch_size=2, max_seq_len=64)
    assert type(engine) is LLMEngine
    assert engine.prefix_cache is None and engine.prefix_digests() is None
    assert engine._is_state == [True, False, False, True]
    prompt = _prompt(21, 5)
    first = engine.generate(prompt, SamplingParams(max_tokens=6))
    second = engine.generate(prompt, SamplingParams(max_tokens=6))
    engine.stop()
    assert first == second and len(first) == 6
    assert _is_greedy(one_period, prompt, first)
    assert "kv_cache" not in engine.metrics()
    totals = engine.metrics()["totals"]
    assert totals["kv_blocks_read_back"] == 0
    # A decode step carries no prefill; the engine drops a prefill's
    # counts.
    assert totals["conv_prefill_tokens"] == 0
    assert totals["experts_touched"] > 0


def test_a_retired_slot_admitted_again_starts_from_zeros(one_period):
    """One slot: the second, shorter request gets the slot the first
    one left, whose carried rows kept stepping after it was retired."""
    engine = LLMEngine(ONE, one_period, max_batch_size=1, max_seq_len=64,
                       decode_steps=2)
    engine.generate(_prompt(19, 6), SamplingParams(max_tokens=5))
    before = [np.asarray(x) for x, state in zip(
        jax.tree.leaves(engine.cache), engine._is_state) if state]
    assert all(np.abs(x).max() > 0 for x in before)
    prompt = _prompt(11, 7)
    again = engine.generate(prompt, SamplingParams(max_tokens=7))
    engine.stop()
    assert len(again) == 7 and _is_greedy(one_period, prompt, again)


def test_requests_beside_each_other_keep_their_own_rows(one_period):
    import threading

    engine = LLMEngine(ONE, one_period, max_batch_size=2, max_seq_len=64)
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
    for prompt, answer in zip(prompts, answers):
        assert len(answer) == 6 and _is_greedy(one_period, prompt, answer)


def test_decode_spans_carry_the_models_counts(one_period):
    engine = LLMEngine(ONE, one_period, max_batch_size=2, max_seq_len=64)
    engine.generate(list(range(1, 14)), SamplingParams(max_tokens=4))
    engine.stop()
    consumed = [s["attrs"] for s in
                flight_recorder.local_snapshot()["spans"]
                if s.get("attrs") and s["stage"] == "engine.consume_block"
                and "conv_state_resets" in s["attrs"]]
    assert consumed
    # The slot that never held a request stands at position 0 and
    # starts from zeros at every step.
    assert all(a["conv_prefill_tokens"] == 0
               and 0 <= a["conv_state_resets"] <= 2
               and a["experts_held_steps"] == 3 * 8 for a in consumed)
