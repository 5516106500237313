"""Olmo Hybrid's decoder at debug widths on the CPU, in float32, seeded
random weights: the served path (a prefill padded to its bucket, then
decode through the cache) against the plain reference, each fault of
`tools/glm_logit_check.py` failing where the program passes; what a
state leaf demands of a forward pass (padding kept out of the state
and the three carries, a prefill in two calls, rows of different
lengths, a slot used before); the block's norm placement; and the
engine, which knows no model, serving it with no prefix cache."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.manifest import ROOT, load_json, model_adapter
from benchmark.references import olmo_hybrid as reference
from ray_tpu._private import flight_recorder
from ray_tpu.models import decoder, gated_delta, llama, olmo_hybrid
from ray_tpu.models.serving import served_model
from ray_tpu.serve.llm import LLMEngine, SamplingParams
from tests.models.test_cached_attention import through_the_kernel
from tools import glm_logit_check

FILE = load_json(ROOT, "benchmark", "configs", "olmo-hybrid-7b-serve.json")
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
FAULTS = glm_logit_check.olmo_faults(ADAPTER.cached_forward,
                                     ADAPTER.init_cache)


@pytest.fixture(scope="module")
def distances():
    """Of the program and of each fault, the largest logit error over
    the largest |reference| logit."""
    small, params, lens, tokens = glm_logit_check.weights_and_tokens(
        CONFIG, 2 ** 31 + 5, ADAPTER, olmo_hybrid.init_params)
    rows = glm_logit_check.distances(
        CONFIG, small, params, lens, tokens, ADAPTER, reference,
        {"program": ADAPTER.cached_forward, **FAULTS})
    return {name: row["max"] for name, row in rows.items()}


@pytest.fixture(scope="module")
def params():
    return olmo_hybrid.init_params(CFG, jax.random.PRNGKey(2))


def _tokens(shape, seed=1):
    return jnp.asarray(np.random.default_rng(seed).integers(
        1, CFG.vocab_size, shape, dtype=np.int32))


def _state(cache):
    return [x for x, is_state in zip(
        jax.tree.leaves(cache),
        jax.tree.leaves(olmo_hybrid.state_leaves(cache))) if is_state]


def _cache(rows=2, max_seq=32):
    return olmo_hybrid.init_cache(CFG, rows, max_seq)


def test_the_file_builds_the_published_model():
    cfg = ADAPTER.program_config(FILE)
    assert cfg.layer_types == olmo_hybrid.PUBLISHED_LAYER_TYPES[:12]
    assert cfg.runs() == [("linear", 3), ("full", 1)] * 3
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.hidden_dim, cfg.vocab_size) == (3840, 30, 30, 128, 11008,
                                                100352)
    assert (cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim,
            cfg.conv_kernel, cfg.allow_neg_eigval) == (30, 96, 192, 4, True)
    assert (cfg.norm_placement, cfg.qk_norm, cfg.tie_embeddings,
            cfg.norm_eps) == ("output", True, False, 1e-6)
    assert cfg.dtype == jnp.bfloat16 and cfg.state_dtype == jnp.float32
    # The defaults are the published model's.
    assert dataclasses.replace(
        cfg, n_layers=32, layer_types=olmo_hybrid.PUBLISHED_LAYER_TYPES) \
        == olmo_hybrid.OlmoHybridConfig()
    assert ADAPTER.with_layers(cfg, 4).runs() == [("linear", 3),
                                                  ("full", 1)]
    debug = olmo_hybrid.OlmoHybridConfig.debug_olmo_hybrid()
    assert debug.delta_key_dim != debug.delta_value_dim
    assert debug.delta_heads & (debug.delta_heads - 1)  # no power of two
    assert CFG == debug


def test_the_served_path_agrees_with_the_reference(distances):
    assert distances["program"] < 2e-6


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails(distances, fault):
    """Every fault reads at least 100 times the program's error."""
    assert distances[fault] > 2e-4 > 100 * distances["program"]


def test_the_tool_takes_the_family_by_its_configurations_name():
    family_faults, unseen, plain_init, _ = glm_logit_check.FAMILIES[
        FILE["family"]]
    assert family_faults is glm_logit_check.olmo_faults
    assert plain_init() is olmo_hybrid.init_params is ADAPTER.init
    checks = FILE["serve"]["tool_checks"]
    assert set(checks) == set(unseen) == {"benchmark", "plain"}
    assert all(set(names) < set(FAULTS) for names in unseen.values())
    assert {"lower precision", "beta without its 2", "gate before the norm",
            "k not normalised", "q without its scale", "no decay",
            "pad absorbed", "no q and k norm", "norm on the input"} \
        <= set(FAULTS)


def test_the_norm_stands_behind_each_half(params):
    """`decoder.block` with `norm_placement` "output" is x + norm(half(x))
    for both halves, written out; "input" is another function."""
    cfg = dataclasses.replace(CFG, n_layers=1, layer_types=("full",))
    lp = jax.tree.map(lambda x: x[0], params["runs"][1])
    lp = {**lp, "attn_norm": lp["attn_norm"] * 1.5,
          "mlp_norm": lp["mlp_norm"] * 0.5}
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, cfg.dim))

    def mixer(h, lp, rope, state, handed):
        return jnp.einsum("bsd,dhk->bshk", h, lp["wv"]), state, handed

    ffn = llama.swiglu()
    got, *_ = decoder.block(mixer, ffn, cfg, None, x, lp)
    mixed = jnp.einsum("bshk,hkd->bsd", mixer(x, lp, None, None, None)[0],
                       lp["wo"])
    mid = x + decoder.norm(cfg, mixed, lp["attn_norm"])
    want = mid + decoder.norm(cfg, ffn(mid, lp)[0], lp["mlp_norm"])
    np.testing.assert_allclose(got, want, atol=1e-6)
    other, *_ = decoder.block(
        mixer, ffn, dataclasses.replace(cfg, norm_placement="input"), None,
        x, lp)
    assert float(jnp.abs(other - want).max()) > 0.1
    with pytest.raises(AssertionError):
        decoder.block(mixer, ffn, dataclasses.replace(
            cfg, norm_placement="both"), None, x, lp)


def test_a_padded_prompt_leaves_the_same_logits_state_and_carries(params):
    """13 tokens in a bucket of 16, neither a multiple of the 8-token
    chunk: the padding changes no logit of the prompt, nothing of the
    delta state and nothing of the three convolutions' carries."""
    tokens = _tokens((2, 13))
    start = jnp.zeros(2, jnp.int32)
    want, left = olmo_hybrid.forward_with_cache(params, tokens, CFG,
                                                _cache(), start)
    padded = jnp.pad(tokens, ((0, 0), (0, 3)), constant_values=7)
    got, state = olmo_hybrid.forward_with_cache(params, padded, CFG,
                                                _cache(), start, at=12)
    np.testing.assert_allclose(got[:, :13], want, atol=1e-6)
    assert len(_state(state)) == 2 * 4  # S and three carries a run
    for a, b in zip(_state(state), _state(left)):
        np.testing.assert_allclose(a, b, atol=1e-6)
    # Without `at` the padding is absorbed, by the state and by each of
    # the carries.
    _, absorbed = olmo_hybrid.forward_with_cache(params, padded, CFG,
                                                 _cache(), start)
    for a, b in zip(_state(absorbed), _state(left)):
        assert float(jnp.abs(a - b).max()) > 1e-3
    # The engine's `forward` gives the logits of position `at` itself,
    # and counts the real tokens and the rows that started from zeros.
    last, _, counts = olmo_hybrid.forward(params, padded, CFG, _cache(),
                                          start, jnp.int32(12))
    np.testing.assert_allclose(last, want[:, 12], atol=1e-6)
    assert {k: int(v) for k, v in counts.items()} == {
        "delta_scan_tokens": 2 * 13, "delta_state_resets": 2}


def test_a_prefill_in_two_calls_equals_one(params):
    tokens = _tokens((2, 21), seed=3)
    start = jnp.zeros(2, jnp.int32)
    want, left = olmo_hybrid.forward_with_cache(params, tokens, CFG,
                                                _cache(), start)
    first, cache = olmo_hybrid.forward_with_cache(
        params, tokens[:, :11], CFG, _cache(), start)
    second, cache = olmo_hybrid.forward_with_cache(
        params, tokens[:, 11:], CFG, cache, start + 11)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), want,
                               atol=1e-5)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(left)):
        np.testing.assert_allclose(a, b, atol=1e-5)
    _, _, counts = olmo_hybrid.forward(params, tokens[:, 11:], CFG, cache,
                                       start + 11, 9)
    assert int(counts["delta_state_resets"]) == 0
    assert int(counts["delta_scan_tokens"]) == 2 * 10


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
    logits, cache = olmo_hybrid.forward_with_cache(
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
        out, cache = olmo_hybrid.forward_with_cache(
            params, fed, CFG, cache, jnp.asarray(pos, jnp.int32))
        for row in range(2):
            np.testing.assert_allclose(out[row, 0], want[row][pos[row]],
                                       atol=3e-6 * top)
    _, _, counts = olmo_hybrid.forward(params, fed, CFG, cache,
                                       jnp.asarray(pos + 1, jnp.int32), 0)
    assert {k: int(v) for k, v in counts.items()} == {
        "delta_scan_tokens": 0, "delta_state_resets": 0}


def test_the_contract_of_a_model_with_state_leaves(params):
    model = served_model(CFG)
    assert model.forward is olmo_hybrid.forward
    cache = model.init_cache(CFG, 2, 16)
    kinds = jax.tree.leaves(model.state_leaves(cache))
    assert kinds == ([True] * 4 + [False] * 2) * 2
    run = cache["runs"][0]
    assert run["state"].shape == (3, 2, 3, 8, 16)
    assert run["state"].dtype == jnp.float32
    assert [run[name].shape for name in gated_delta.CONVS] == [
        (3, 2, 3, 24), (3, 2, 3, 24), (3, 2, 3, 48)]
    assert cache["runs"][1]["k"].shape == (1, 2, 16, 3 * 20)
    logits, new, counts = model.forward(params, _tokens((2, 6)), CFG, cache,
                                        jnp.zeros(2, jnp.int32), 5)
    assert logits.shape == (2, CFG.vocab_size)
    assert logits.dtype == jnp.float32
    assert jax.tree.structure(new) == jax.tree.structure(cache)
    assert sorted(counts) == ["delta_scan_tokens", "delta_state_resets"]
    assert all(x.dtype == jnp.int32 and x.shape == () for x in
               counts.values())


def test_each_mixer_is_scoped_by_its_kind(params):
    """The delta layers' ops lie under `delta`, never under `attn`: a
    trace's attention share reads the full layers alone."""
    def lowered(t):
        return jax.jit(lambda p, c: olmo_hybrid.forward(
            p, _tokens((2, t)), CFG, c, jnp.ones(2, jnp.int32), t - 1)
        ).lower(params, _cache()).as_text(debug_info=True)

    decode, prefill = lowered(1), lowered(16)
    for scope in ("delta/delta_conv", "delta/delta_update",
                  "delta/delta_norm", "attn/", "mlp/"):
        assert scope in decode, scope
    assert "delta/delta_scan" in prefill
    assert "delta/delta_update" not in prefill
    assert "delta/delta_scan" not in decode
    for text in (decode, prefill):
        assert "attn/delta" not in text and "delta/attn" not in text


@pytest.mark.parametrize("rows", [16, 256], ids=["blocks-of-16", "one-block"])
@pytest.mark.parametrize("lens", [(17, 9), (16, 1), (29, 15)],
                         ids=lambda lens: "-".join(map(str, lens)))
def test_a_decode_step_through_the_kernel_equals_the_plain_path(
        params, monkeypatch, lens, rows):
    """The full layers' decode step through
    `ops.attention.decode_attention` on the merged axis, the kernel a
    TPU runs, interpreted here, against `llama._cached_attention` on the
    [rows, heads, head size] view, which the CPU takes: rows prefilled
    to their own lengths decode three steps together, the same logits
    and the same cache either way."""
    lens, steps = np.asarray(lens), 3
    tokens = _tokens((2, lens.max() + steps), seed=int(lens.sum()))
    _, filled = olmo_hybrid.forward_with_cache(
        params, tokens[:, :lens.max()], CFG, _cache(),
        jnp.zeros(2, jnp.int32), at=jnp.asarray(lens - 1, jnp.int32))

    def decoded():
        out, cache, at = [], filled, np.arange(2)
        for i in range(steps):
            fed = jnp.asarray(np.asarray(tokens)[at, lens + i][:, None])
            logits, cache = olmo_hybrid.forward_with_cache(
                params, fed, CFG, cache, jnp.asarray(lens + i, jnp.int32))
            out.append(np.asarray(logits))
        return np.stack(out), cache

    want, plain_cache = decoded()
    through_the_kernel(monkeypatch, olmo_hybrid, rows)
    got, cache = decoded()
    np.testing.assert_allclose(got, want, atol=3e-6 * np.abs(want).max())
    assert not np.array_equal(got, want)  # it did go another way
    for x, y in zip(jax.tree.leaves(cache), jax.tree.leaves(plain_cache)):
        np.testing.assert_allclose(x, y, atol=3e-6 * np.abs(y).max())


# -- the engine over a cache with state leaves -------------------------------


# One period is enough for the engine: three delta layers and the
# full one.
ONE = ADAPTER.with_layers(CFG, 4)


@pytest.fixture(scope="module")
def one_period():
    return olmo_hybrid.init_params(ONE, jax.random.PRNGKey(2))


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
    assert engine.prefix_cache is None and engine.prefix_digests() is None
    assert engine._is_state == [True] * 4 + [False] * 2
    prompt = _prompt(21, 5)
    first = engine.generate(prompt, SamplingParams(max_tokens=6))
    second = engine.generate(prompt, SamplingParams(max_tokens=6))
    engine.stop()
    assert first == second and len(first) == 6
    assert _is_greedy(one_period, prompt, first)
    assert "kv_cache" not in engine.metrics()
    totals = engine.metrics()["totals"]
    assert totals["kv_blocks_read_back"] == 0
    # A decode step scans nothing; the engine drops a prefill's counts.
    assert totals["delta_scan_tokens"] == 0


def test_a_retired_slot_admitted_again_starts_from_zeros(one_period):
    """One slot: the second, shorter request gets the slot the first
    one left, whose state kept stepping after it was retired."""
    engine = LLMEngine(ONE, one_period, max_batch_size=1, max_seq_len=64,
                       decode_steps=2)
    engine.generate(_prompt(19, 6), SamplingParams(max_tokens=5))
    before = [np.asarray(x) for x in _state(engine.cache)]
    assert all(np.abs(x).max() > 0 for x in before)
    prompt = _prompt(11, 7)
    again = engine.generate(prompt, SamplingParams(max_tokens=7))
    engine.stop()
    assert len(again) == 7 and _is_greedy(one_period, prompt, again)


def test_requests_beside_each_other_keep_their_own_state(one_period):
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
                and "delta_state_resets" in s["attrs"]]
    assert consumed
    # The slot that never held a request stands at position 0 and
    # starts from zeros at every step.
    assert all(a["delta_scan_tokens"] == 0
               and 0 <= a["delta_state_resets"] <= 2 for a in consumed)
