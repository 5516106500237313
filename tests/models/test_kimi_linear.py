"""Kimi Linear's decoder at debug widths on the CPU, in float32, seeded
random weights: the served path (a prefill padded to its bucket, a
chunk of two sub-blocks, then decode through the cache, rows at their
own lengths) against the plain reference, each fault of
`tools/glm_logit_check.py` failing where the program passes; the
chunked scan for a decay a key channel against the recurrence token by
token, decays near 0 and near 1 in one head; the state kernel through
the Pallas interpreter for a decay a head and a decay a channel; the
eight shares of an expert layer adding up to the uncut layer; what a
state leaf demands of a forward pass; and the engine, which knows no
model, serving it with no prefix cache."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.manifest import ROOT, load_json, model_adapter
from benchmark.references import kimi_linear as reference
from ray_tpu._private import flight_recorder
from ray_tpu.models import gated_delta, kimi_linear, moe
from ray_tpu.models.serving import served_model
from ray_tpu.ops import delta_update as du
from ray_tpu.serve.llm import LLMEngine, SamplingParams
from tools import glm_logit_check

FILE = load_json(ROOT, "benchmark", "configs",
                 "kimi-linear-48b-a3b-serve.json")
ADAPTER = model_adapter(FILE)


def debug_config():
    config = ADAPTER.debug(FILE)
    # 45 is no multiple of the 32-token chunk or of its 16-row
    # sub-block and no bucket: the check pads it to 64, two chunks of
    # two sub-blocks; the shorter rows decode from their own lengths.
    config["serve"] = {**config["serve"], "max_seq_len": 128,
                       "reference_prompt_lens": [45, 39, 26, 19],
                       "reference_decode_steps": 8}
    return config


CONFIG = debug_config()
CFG = ADAPTER.program_config(CONFIG)
FAULTS = glm_logit_check.kimi_faults(ADAPTER.cached_forward,
                                     ADAPTER.init_cache)
# Read on the CPU in float32: the program 4e-7, the quietest fault (the
# state rounded to bfloat16 after every call) 3e-4.
LIMIT = 1e-4


@pytest.fixture(scope="module")
def check():
    """The check's weights (the program's initialiser's: the routed
    experts at their own scale) and tokens, and `distance(served)`:
    the largest logit error over the largest |reference| logit."""
    small, params, lens, tokens = glm_logit_check.weights_and_tokens(
        CONFIG, 2 ** 31 + 5, ADAPTER, kimi_linear.init_params)

    def distance(name, served):
        return glm_logit_check.distances(
            CONFIG, small, params, lens, tokens, ADAPTER, reference,
            {name: served})[name]["max"]

    return distance


# A layer of each kind, the three the check keeps: KDA over the dense
# FFN, KDA over experts, latent attention over experts.
ONE = ADAPTER.with_layers(CFG, 3)
forward_with_cache = jax.jit(kimi_linear.forward_with_cache, static_argnums=2)
forward = jax.jit(kimi_linear.forward, static_argnums=2)


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda key: kimi_linear.init_params(ONE, key))(
        jax.random.PRNGKey(2))


def _tokens(shape, seed=1):
    return jnp.asarray(np.random.default_rng(seed).integers(
        1, CFG.vocab_size, shape, dtype=np.int32))


def _state(cache):
    return [x for x, is_state in zip(
        jax.tree.leaves(cache),
        jax.tree.leaves(kimi_linear.state_leaves(cache))) if is_state]


def _cache(rows=2, max_seq=64):
    return kimi_linear.init_cache(ONE, rows, max_seq)


def test_the_file_builds_the_published_model():
    cfg = ADAPTER.program_config(FILE)
    period = [("sparse", "kda")] * 3 + [("sparse", "mla")]
    assert list(cfg.kinds) == [("dense", "kda")] + period[1:] + period * 2
    assert cfg.kinds == kimi_linear.published_kinds()[:12]
    assert (cfg.dim, cfg.n_heads, cfg.hidden_dim, cfg.dense_hidden_dim,
            cfg.vocab_size) == (2304, 32, 1024, 9216, 20480)
    assert (cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim,
            cfg.conv_kernel, cfg.gate_rank, cfg.allow_neg_eigval) \
        == (32, 128, 128, 4, 128, False)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert (cfg.n_experts, cfg.experts_held, cfg.n_experts_per_token,
            cfg.scoring, cfg.selection_bias, cfg.norm_topk_prob,
            cfg.gate_scale, cfg.shared_hidden_dim) \
        == (256, (0, 32), 8, "sigmoid", True, True, 2.446, 1024)
    assert cfg.dtype == jnp.bfloat16 and cfg.state_dtype == jnp.float32
    # The defaults are the published model's, uncut.
    whole = kimi_linear.KimiLinearConfig()
    assert dataclasses.replace(
        cfg, n_layers=27, vocab_size=163840, experts_held=None,
        layer_kinds=()) == whole
    kinds = whole.kinds
    assert [i + 1 for i, (_, mixer) in enumerate(kinds) if mixer == "mla"] \
        == FILE["linear_attn_config"]["full_attn_layers"]
    assert [i + 1 for i, (_, mixer) in enumerate(kinds) if mixer == "kda"] \
        == FILE["linear_attn_config"]["kda_layers"]
    assert [ffn for ffn, _ in kinds] == ["dense"] + ["sparse"] * 26
    assert ADAPTER.with_layers(cfg, 3).kinds == (
        ("dense", "kda"), ("sparse", "kda"), ("sparse", "mla"))
    debug = kimi_linear.KimiLinearConfig.debug_kimi_linear()
    assert debug.delta_key_dim != debug.delta_value_dim
    assert debug.delta_heads & (debug.delta_heads - 1)  # no power of two
    assert debug.chunk_size > gated_delta._SUB_BLOCK
    assert CFG == debug


def test_the_served_path_agrees_with_the_reference(check):
    assert check("program", ADAPTER.cached_forward) < 2e-6


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails(check, fault):
    """Every fault passes the one limit, 50 times the program's
    error."""
    assert check(fault, FAULTS[fault]) > LIMIT


def test_the_tool_takes_the_family_by_its_configurations_name():
    family_faults, unseen, plain_init, _ = glm_logit_check.FAMILIES[
        FILE["family"]]
    assert family_faults is glm_logit_check.kimi_faults
    assert plain_init() is kimi_linear.init_params
    checks = FILE["serve"]["tool_checks"]
    assert set(checks) == set(unseen) == {"benchmark", "plain"}
    assert all(set(names) < set(FAULTS) for names in unseen.values())
    assert {"lower precision", "one decay a head",
            "decay after the correction", "beta doubled", "silu gate",
            "gate before the norm", "no dt bias", "k not normalised",
            "q without its scale", "conv without silu", "pad absorbed",
            "state not zeroed", "rotary turn", "score scaled by nope alone",
            "no latent norm", "no shared key channels", "no gate scale",
            "gates not renormalised", "bias in the gates",
            "no shared expert", "experts in the dense layer"} <= set(FAULTS)


def test_the_benchmarks_weights_are_the_programs_but_two_scales():
    key = jax.random.PRNGKey(4)
    plain = jax.jit(lambda key: kimi_linear.init_params(ONE, key))(key)
    drawn = jax.jit(lambda key: ADAPTER.init(ONE, key))(key)
    scales = {"we2": ADAPTER.ROUTED_OUT_SCALE,
              "router_bias": ADAPTER.ROUTER_BIAS_SCALE}
    scaled = dict.fromkeys(scales, 0)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(plain),
                            jax.tree.leaves(drawn)):
        name = getattr(path[-1], "key", None)
        scaled[name] = scaled.get(name, 0) + 1
        np.testing.assert_allclose(  # two programs round a product apart
            np.asarray(a, np.float32) * scales.get(name, 1),
            np.asarray(b, np.float32), rtol=1e-6, err_msg=name)
    sparse = sum(kind[0] == "sparse" for kind, _ in ONE.runs())
    assert scaled["we2"] == scaled["router_bias"] == sparse


# -- the chunked scan and the kernel for a decay a key channel ---------------

H, DK, DV = 3, 8, 16


def _scan_inputs(t, seed, gamma, batch=2):
    """q, k (unit length, q scaled), v, a log decay a key channel and
    beta in (0, 1) for `t` positions, and a carried state. "both ends":
    in every head, every other channel forgets all but 1e-30 to 1e-6 of
    its row a step and the rest keep 0.9999 of theirs, so that
    exp(-g_j) of the one overflows float32 within a chunk while
    exp(g_i) of the other stays 1."""
    rng = np.random.default_rng(seed)
    q = gated_delta._queries(jnp.asarray(
        rng.normal(size=(batch, t, H, DK)), jnp.float32))
    k = gated_delta._keys(jnp.asarray(
        rng.normal(size=(batch, t, H, DK)), jnp.float32))
    v = jnp.asarray(rng.normal(size=(batch, t, H, DV)), jnp.float32)
    if gamma == "any":
        decay = rng.uniform(0.2, 0.999, (batch, t, H, DK))
    else:
        decay = rng.uniform(0.9999, 1.0, (batch, t, H, DK))
        decay[..., ::2] = rng.uniform(1e-30, 1e-6, (batch, t, H, DK // 2))
    beta = jnp.asarray(rng.uniform(0, 1, (batch, t, H)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(batch, H, DK, DV)), jnp.float32)
    return s0, q, k, v, jnp.log(jnp.asarray(decay, jnp.float32)), beta


def _recurrence(s0, q, k, v, log_gamma, beta):
    """`_update` position by position: (o [B, T, H, dv], the last S)."""
    def position(s, now):
        q_t, k_t, v_t, g_t, b_t = now
        o, s = gated_delta._update(s, q_t, k_t, v_t, jnp.exp(g_t), b_t)
        return s, o

    last, o = jax.lax.scan(position, s0, tuple(
        x.swapaxes(0, 1) for x in (q, k, v, log_gamma, beta)))
    return o.swapaxes(0, 1), last


@pytest.mark.parametrize("t, chunk", [(19, 8), (150, 64), (70, 48)])
@pytest.mark.parametrize("gamma", ["any", "both ends"])
def test_the_chunked_form_for_a_decay_a_channel_is_the_recurrence(
        t, chunk, gamma):
    """Chunks of one sub-block, of several, and of three of 16 rows
    (48); every exponent stays non-positive, so the result is finite
    and right where a channel forgets everything beside one that
    forgets nothing."""
    args = _scan_inputs(t, seed=t + chunk, gamma=gamma)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = _recurrence(*args)
        got_o, got_s = gated_delta._scan(
            types.SimpleNamespace(chunk_size=chunk), *args)
    assert bool(jnp.isfinite(got_o).all() & jnp.isfinite(got_s).all())
    np.testing.assert_allclose(got_o, want_o,
                               atol=2e-5 * float(jnp.abs(want_o).max()))
    np.testing.assert_allclose(got_s, want_s,
                               atol=2e-5 * float(jnp.abs(want_s).max()))


def test_a_decay_that_every_channel_shares_is_the_scalar_one():
    """A decay a channel that repeats one number a head gives what the
    decay a head gives: the two shapes are one recurrence."""
    s0, q, k, v, log_gamma, beta = _scan_inputs(40, 3, "any")
    cfg = types.SimpleNamespace(chunk_size=32)
    with jax.default_matmul_precision("highest"):
        want = gated_delta._scan(cfg, s0, q, k, v, log_gamma[..., 0], beta)
        got = gated_delta._scan(
            cfg, s0, q, k, v,
            jnp.broadcast_to(log_gamma[..., :1], log_gamma.shape), beta)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.abs(b).max()))


def _kernel_inputs(shape, seed, by_channel):
    _, b, h, dk, dv = shape
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    gamma = rng.uniform(0.05, 0.999, (b, h, dk) if by_channel else (b, h))
    if by_channel:  # both ends in one head
        gamma[..., ::4], gamma[..., 1::4] = 1e-30, 1 - 2.0 ** -24
    return [jnp.asarray(x, jnp.float32) for x in (
        rng.normal(size=shape), unit(rng.normal(size=(b, h, dk))) * dk ** -0.5,
        unit(rng.normal(size=(b, h, dk))), rng.normal(size=(b, h, dv)),
        gamma, rng.uniform(0, 1, (b, h)))]


@pytest.mark.parametrize("by_channel", [False, True],
                         ids=["a-decay-a-head", "a-decay-a-channel"])
@pytest.mark.parametrize("shape", [(2, 3, 32, 128, 128), (3, 2, 17, 96, 192)],
                         ids=["kimi-linear", "heads-no-block-divides"])
def test_the_kernel_takes_either_decay(shape, by_channel):
    """The one kernel through the Pallas interpreter against
    `reference` on the layer sliced out of the stack, at this model's
    state of 32 heads of [128, 128] (two blocks of 16) and at a head
    count no block divides, a row starting from zeros over what its
    slot held."""
    stack, q, k, v, gamma, beta = _kernel_inputs(shape, sum(shape),
                                                 by_channel)
    layer = shape[0] - 1
    fresh = np.arange(shape[1]) == 1
    s0 = jnp.where(fresh[:, None, None, None], 0.0, stack[layer])
    want_o, want_s = du.reference(s0, q, k, v, gamma, beta)
    o, new = jax.jit(du.delta_update, static_argnames="interpret")(
        stack, np.int32(layer), jnp.asarray(fresh), q, k, v, gamma, beta,
        interpret=True)
    np.testing.assert_allclose(o, want_o,
                               atol=1e-6 * float(jnp.abs(want_o).max()))
    np.testing.assert_allclose(new[layer], want_s,
                               atol=1e-6 * float(jnp.abs(want_s).max()))
    np.testing.assert_array_equal(new[:layer], stack[:layer])
    # Off the TPU and uninterpreted it is `reference` itself.
    plain_o, plain = du.delta_update(stack, np.int32(layer),
                                     jnp.asarray(fresh), q, k, v, gamma, beta)
    np.testing.assert_array_equal(plain_o, want_o)
    np.testing.assert_array_equal(plain[layer], want_s)


# -- the share and the model -------------------------------------------------


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """8 shares of 2 of the 16 experts, their routed parts added and
    the shared expert counted once, against the reference given all
    16; and each share against the reference given the same share."""
    cfg = dataclasses.replace(CFG, experts_held=None)
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    lp = moe.expert_init(cfg, keys[:4])
    y = jax.random.normal(keys[4], (2, 24, cfg.dim))
    hp = {**reference.hyper(CONFIG), "first_expert": 0}
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda rows: reference.experts(rows, lp, hp))(y)
    shared = moe._add_shared_expert(cfg, lp, y, jnp.zeros_like(y))
    total, held = shared, 0
    for first in range(0, 16, 2):
        share = dataclasses.replace(cfg, experts_held=(first, 2))
        part = {**lp, **{k: lp[k][first:first + 2]
                         for k in ("we1", "we3", "we2")}}
        out, _, counts, counted = jax.jit(
            lambda part: moe._moe_ffn(share, part, y, None, None))(part)
        assert int(counted["pairs_held"]) == int(
            counts[first:first + 2].sum())
        total = total + (out - shared)
        held += int(counted["pairs_held"])
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(
                out, jax.vmap(lambda rows: reference.experts(
                    rows, part, {**hp, "first_expert": first}))(y),
                atol=2e-6)
    assert held == 2 * 24 * cfg.n_experts_per_token
    np.testing.assert_allclose(total, want, atol=5e-6)


# -- what a state leaf demands -----------------------------------------------


def test_a_padded_prompt_leaves_the_same_logits_state_and_carries(params):
    """45 tokens in a bucket of 64, neither a multiple of the 32-token
    chunk: the padding changes no logit of the prompt, nothing of the
    delta states and nothing of the carries, and no latent row of the
    prompt."""
    tokens = _tokens((2, 45))
    start = jnp.zeros(2, jnp.int32)
    want, left = forward_with_cache(params, tokens, ONE, _cache(), start)
    padded = jnp.pad(tokens, ((0, 0), (0, 19)), constant_values=7)
    got, state = forward_with_cache(params, padded, ONE, _cache(), start,
                                    at=44)
    np.testing.assert_allclose(got[:, :45], want, atol=2e-6)
    assert len(_state(state)) == 2 * 4  # S and three carries a KDA run
    for a, b in zip(_state(state), _state(left)):
        np.testing.assert_allclose(a, b, atol=1e-6)
    _, absorbed = forward_with_cache(params, padded, ONE, _cache(), start)
    for a, b in zip(_state(absorbed), _state(left)):
        assert float(jnp.abs(a - b).max()) > 1e-3
    last, _, counts = forward(params, padded, ONE, _cache(), start,
                              jnp.int32(44))
    np.testing.assert_allclose(last, want[:, 44], atol=2e-6)
    counts = {k: int(v) for k, v in counts.items()}
    assert (counts["delta_scan_tokens"], counts["delta_state_resets"],
            counts["latent_keys_read"]) == (2 * 45, 2, 0)


def test_a_prefill_in_two_calls_equals_one(params):
    tokens = _tokens((2, 41), seed=3)
    start = jnp.zeros(2, jnp.int32)
    want, left = forward_with_cache(params, tokens, ONE, _cache(), start)
    first, cache = forward_with_cache(params, tokens[:, :23], ONE,
                                      _cache(), start)
    second, cache = forward_with_cache(params, tokens[:, 23:], ONE, cache,
                                       start + 23)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), want,
                               atol=1e-5)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(left)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_rows_of_different_lengths_in_one_batch_equal_the_reference(params):
    """Rows of 37 and 9 tokens prefilled in one call, each left after
    its own last token, then decoding together from their own
    positions: every logit is the reference's full forward pass's, and
    a decode step's latent layer reads whole blocks of keys."""
    lens, steps = np.asarray((37, 9)), 4
    tokens = _tokens((2, 37 + steps), seed=4)
    hp = reference.hyper(CONFIG)
    with jax.default_matmul_precision("highest"):
        want = [np.asarray(jax.jit(
            lambda p, t: reference.sequence_logits(p, t, hp))(
                params, tokens[row, :n + steps]))
            for row, n in enumerate(lens)]
    logits, cache = forward_with_cache(
        params, tokens[:, :37], ONE, _cache(), jnp.zeros(2, jnp.int32),
        at=jnp.asarray(lens - 1, jnp.int32))
    top = max(np.abs(w).max() for w in want)
    for row, n in enumerate(lens):
        np.testing.assert_allclose(logits[row, :n], want[row][:n],
                                   atol=3e-6 * top)
    at = np.arange(2)
    for i in range(steps):
        pos = lens + i
        fed = jnp.asarray(np.asarray(tokens)[at, pos][:, None])
        out, cache = forward_with_cache(params, fed, ONE, cache,
                                        jnp.asarray(pos, jnp.int32))
        for row in range(2):
            np.testing.assert_allclose(out[row, 0], want[row][pos[row]],
                                       atol=3e-6 * top)
    _, _, counts = forward(params, fed, ONE, cache,
                           jnp.asarray(pos + 1, jnp.int32), 0)
    # One latent layer, two rows, the one block of 64 keys.
    assert int(counts["latent_keys_read"]) == 1 * 2 * 64
    assert int(counts["delta_scan_tokens"]) == 0


def test_the_contract_of_a_model_with_state_leaves(params):
    model = served_model(ONE)
    assert model.forward is kimi_linear.forward
    assert model.keys_read is None
    cache = model.init_cache(ONE, 2, 16)
    kinds = jax.tree.leaves(model.state_leaves(cache))
    assert kinds == [True] * 4 * 2 + [False] * 2
    run = cache["runs"][1]
    assert run["state"].shape == (1, 2, 3, 8, 16)
    assert run["state"].dtype == jnp.float32
    assert [run[name].shape for name in gated_delta.CONVS] == [
        (1, 2, 3, 24), (1, 2, 3, 24), (1, 2, 3, 48)]
    assert {k: v.shape for k, v in cache["runs"][2].items()} == {
        "latent": (1, 2, 16, 32), "rope": (1, 2, 16, 128)}
    logits, new, counts = forward(params, _tokens((2, 6)), ONE, cache,
                                  jnp.zeros(2, jnp.int32), 5)
    assert logits.shape == (2, ONE.vocab_size)
    assert logits.dtype == jnp.float32
    assert jax.tree.structure(new) == jax.tree.structure(cache)
    assert {"delta_scan_tokens", "delta_state_resets", "latent_keys_read",
            "pairs_held", "pairs_routed", "experts_touched",
            "experts_held_steps"} <= set(counts)
    assert all(x.dtype == jnp.int32 and x.shape == () for x in
               counts.values())


def test_each_mixer_is_scoped_by_its_kind(params):
    """The KDA layers' ops lie under `delta`, the two rank-`gate_rank`
    projections under `delta_gate` inside it, the latent layers' under
    `attn`, never one under the other."""
    def lowered(t):
        return jax.jit(lambda p, c: kimi_linear.forward(
            p, _tokens((2, t)), ONE, c, jnp.ones(2, jnp.int32), t - 1)
        ).lower(params, _cache()).as_text(debug_info=True)

    decode, prefill = lowered(1), lowered(16)
    for scope in ("delta/delta_conv", "delta/delta_gate",
                  "delta/delta_update", "delta/delta_norm", "attn/mla_proj",
                  "attn/latent_attn", "mlp/"):
        assert scope in decode, scope
    assert "delta/delta_scan" in prefill and "attn/latent_attn" in prefill
    assert "delta/delta_update" not in prefill
    assert "delta/delta_scan" not in decode
    for text in (decode, prefill):
        assert "attn/delta" not in text and "delta/attn" not in text
        assert "delta/latent_attn" not in text


# -- the engine over a cache with state leaves -------------------------------


def _is_greedy(params, prompt, answer):
    logits = jax.jit(lambda p, t: reference.sequence_logits(
        p, t, reference.hyper(CONFIG)))(
            params, jnp.asarray((prompt + answer)[:-1], jnp.int32))
    return answer == [int(t) for t in
                      logits[len(prompt) - 1:].argmax(-1)]


def _prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n)]


def test_the_engine_serves_it_with_no_prefix_cache(params):
    engine = LLMEngine(ONE, params, max_batch_size=2, max_seq_len=64)
    assert engine.prefix_cache is None and engine.prefix_digests() is None
    assert engine._is_state == [True] * 4 * 2 + [False] * 2
    prompt = _prompt(21, 5)
    first = engine.generate(prompt, SamplingParams(max_tokens=6))
    second = engine.generate(prompt, SamplingParams(max_tokens=6))
    engine.stop()
    assert first == second and len(first) == 6
    assert _is_greedy(params, prompt, first)
    totals = engine.metrics()["totals"]
    assert totals["kv_blocks_read_back"] == 0
    assert totals["delta_scan_tokens"] == 0
    assert totals["latent_keys_read"] > 0


def test_a_retired_slot_admitted_again_starts_from_zeros(params):
    """One slot: the second, shorter request gets the slot the first
    one left, whose state kept stepping after it was retired."""
    engine = LLMEngine(ONE, params, max_batch_size=1, max_seq_len=64,
                       decode_steps=2)
    engine.generate(_prompt(19, 6), SamplingParams(max_tokens=5))
    assert all(np.abs(np.asarray(x)).max() > 0
               for x in _state(engine.cache))
    prompt = _prompt(11, 7)
    again = engine.generate(prompt, SamplingParams(max_tokens=7))
    engine.stop()
    assert len(again) == 7 and _is_greedy(params, prompt, again)


def test_requests_beside_each_other_keep_their_own_state(params):
    import threading

    engine = LLMEngine(ONE, params, max_batch_size=2, max_seq_len=64)
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
        assert len(answer) == 6 and _is_greedy(params, prompt, answer)


def test_decode_spans_carry_the_models_counts(params):
    engine = LLMEngine(ONE, params, max_batch_size=2, max_seq_len=64)
    engine.generate(list(range(1, 14)), SamplingParams(max_tokens=4))
    engine.stop()
    consumed = [s["attrs"] for s in
                flight_recorder.local_snapshot()["spans"]
                if s.get("attrs") and s["stage"] == "engine.consume_block"
                and "latent_keys_read" in s["attrs"]]
    assert consumed
    assert all(a["delta_scan_tokens"] == 0
               and 0 <= a["delta_state_resets"] <= 2
               and a["latent_keys_read"] % 64 == 0
               and a["pairs_routed"] >= a["pairs_held"] for a in consumed)
