"""Command A+'s decoder (`ray_tpu/models/cohere2_moe.py`) in float32 at
debug widths on the CPU, against the plain reference's full forward
pass: a window of 8 keys, shorter than every prompt but one, so the
rings wrap several times, a prefill is longer than the ring, a prefill
is padded to its bucket, and slots of unlike lengths (one shorter than
the ring) decode beside each other; each fault the chip's check must
fail, and padding entering the ring, fails here by a stated factor."""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmark.harness.manifest import ROOT, load_json, model_adapter
from benchmark.references import cohere2_moe as reference
from ray_tpu._private import flight_recorder
from ray_tpu.models import cohere2_moe, moe, serving
from ray_tpu.models.serving import served_model
from ray_tpu.serve.llm import LLMEngine, SamplingParams
from tools import glm_logit_check

FILE = load_json(ROOT, "benchmark", "configs", "command-a-plus-serve.json")
ADAPTER = model_adapter(FILE)


def debug_config():
    config = ADAPTER.debug(FILE)
    # 45 is no bucket: the check pads it to 64, eight times the ring;
    # the shorter rows decode from their own lengths.
    config["serve"] = {**config["serve"], "max_seq_len": 128,
                       "reference_prompt_lens": [45, 39, 26, 19],
                       "reference_decode_steps": 8}
    return config


CONFIG = debug_config()
CFG = ADAPTER.program_config(CONFIG)
HP = reference.hyper(CONFIG)
FAULTS = glm_logit_check.cohere_faults(ADAPTER.cached_forward,
                                       ADAPTER.init_cache)
# The faults ISSUE 39 names for the chip's check, and the ring's own.
NAMED = ("lower precision", "window ignored", "rope on the full layer",
         "shared experts summed", "sequential block", "pad enters the ring")
# Every compared logit error is under this, every fault's largest over
# it: the program by a factor of 50 and more, the smallest named fault
# (rotary positions on the full layer, 5.8e-4) by 50.
LIMIT = 1e-5


@pytest.fixture(scope="module")
def errors():
    """Of the program and of each fault, the statistics of its
    positions' errors, at the program's own (plain) weights."""
    small, params, lens, tokens = glm_logit_check.weights_and_tokens(
        CONFIG, 2 ** 31 + 5, ADAPTER, cohere2_moe.init_params)
    return glm_logit_check.distances(
        CONFIG, small, params, lens, tokens, ADAPTER, reference,
        {"program": ADAPTER.cached_forward, **FAULTS})


@pytest.fixture(scope="module")
def params():
    return cohere2_moe.init_params(CFG, jax.random.PRNGKey(0))


def _tokens(shape, seed=1):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, CFG.vocab_size, shape), jnp.int32)


def test_the_config_is_the_published_model_cut_for_the_tests():
    full = cohere2_moe.Cohere2MoeConfig()
    assert (full.dim, full.n_heads, full.n_kv_heads, full.head_dim) == (
        4096, 128, 8, 128)
    assert full.runs() == [("sliding", 3), ("full", 1)] * 8
    assert (full.parallel_block, full.norm_kind) == (True, "layer")
    assert (CFG.sliding_window, CFG.n_layers, CFG.experts_held) == (
        8, 4, (4, 4))
    assert CFG.runs() == [("sliding", 3), ("full", 1)]


def test_the_served_path_agrees_with_the_reference(errors):
    assert errors["program"]["max"] < LIMIT / 50


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails(errors, fault):
    factor = 50 if fault in NAMED else 2
    assert errors[fault]["max"] > factor * LIMIT, errors[fault]


def test_the_named_faults_are_all_there():
    assert set(NAMED) <= set(FAULTS)
    family_faults, unseen, plain_init, _ = glm_logit_check.FAMILIES[
        FILE["family"]]
    assert family_faults is glm_logit_check.cohere_faults
    assert plain_init() is cohere2_moe.init_params
    checks = FILE["serve"]["tool_checks"]
    assert set(checks) == set(unseen) == {"benchmark", "plain"}
    assert all(set(names) < set(FAULTS) for names in unseen.values())


def _flash_as_on_a_tpu(monkeypatch, rows, block):
    """The prefill's choice as a TPU makes it, for calls of `rows` rows
    or a multiple: the kernel through the interpreter, tiles of `block`."""
    from ray_tpu.ops import attention
    kernel, used = attention._flash_fwd, []

    def interpreted(q, k, v, causal, scale, block_q, block_k, _, **kw):
        used.append(kw["window"])
        return kernel(q, k, v, causal, scale, block, block, True, **kw)

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(attention, "_flash_fwd", interpreted)
    monkeypatch.setattr(cohere2_moe, "_FLASH_ROWS", rows)
    return used


@pytest.mark.parametrize("query_block,key_block,flash", [
    (256, 1024, False), (16, 4, False), (16, 4, True)],
    ids=["one block", "blocks of queries and keys", "the flash kernel"])
def test_prefill_then_ring_decode_against_the_full_forward(
        params, monkeypatch, query_block, key_block, flash):
    """Four slots whose rings hold another request's keys: prompts of
    45, 39, 26 and 5 tokens in a bucket of 64 (the last shorter than
    the ring of 8), each slot left after its own last token, then 12
    decode steps side by side, the rings wrapping once more. With
    `flash` the prefill's rows attend through the flash kernel, tiles
    of 16 and a window of 8, and the decode steps by blocks as ever."""
    monkeypatch.setattr(serving, "QUERY_BLOCK", query_block)
    monkeypatch.setattr(cohere2_moe, "_KEY_BLOCK", key_block)
    used = _flash_as_on_a_tpu(monkeypatch, 16, 16) if flash else []
    lens = np.array([45, 39, 26, 5])
    tokens = _tokens((4, 45 + 12))
    want = reference.forward(params, tokens, HP)
    cache = jax.tree.map(lambda x: x + 3.0,
                         cohere2_moe.init_cache(CFG, 4, 64))
    padded = jnp.pad(tokens[:, :45], ((0, 0), (0, 19)))
    logits, cache = cohere2_moe.forward_with_cache(
        params, padded, CFG, cache, jnp.zeros(4, jnp.int32),
        at=jnp.asarray(lens - 1))
    for row, n in enumerate(lens):
        np.testing.assert_allclose(logits[row, :n], want[row, :n],
                                   atol=LIMIT)
    rows = np.arange(4)
    for i in range(12):
        logits, cache = cohere2_moe.forward_with_cache(
            params, tokens[rows, lens + i][:, None], CFG, cache,
            jnp.asarray(lens + i, jnp.int32))
        np.testing.assert_allclose(logits[:, 0], want[rows, lens + i],
                                   atol=LIMIT)
    # Three sliding layers in one scan and the full layer in another.
    assert used == ([CFG.sliding_window, None] if flash else [])


def test_the_rings_hold_the_last_window_of_real_rows_and_no_padding(params):
    """After a padded prefill the ring's row p mod 8 is position p's
    key for the last 8 real positions; a one-token call writes one row
    and leaves the rest where they lie."""
    tokens = _tokens((1, 29))
    cache = cohere2_moe.init_cache(CFG, 1, 32)
    _, exact = cohere2_moe.forward_with_cache(
        params, tokens, CFG, cache, jnp.zeros(1, jnp.int32))
    _, padded = cohere2_moe.forward_with_cache(
        params, jnp.pad(tokens, ((0, 0), (0, 3))), CFG, cache,
        jnp.zeros(1, jnp.int32), at=28)
    for a, b in zip(jax.tree.leaves(exact), jax.tree.leaves(padded)):
        if a.shape[2] == CFG.sliding_window:
            np.testing.assert_allclose(a, b, atol=1e-6)
    ring = exact["runs"][0]["ring_k"]
    assert ring.shape == (3, 1, 8, 2, 16)
    _, stepped = cohere2_moe.forward_with_cache(
        params, _tokens((1, 1), 2), CFG, exact, jnp.full(1, 29, jnp.int32))
    changed = np.abs(np.asarray(stepped["runs"][0]["ring_k"] - ring)).max(
        (0, 1, 3, 4)) > 0
    assert changed.tolist() == [r == 29 % 8 for r in range(8)]


def test_a_call_from_a_later_position_attends_the_ring_it_found(params):
    """A prefill in two calls equals one: the second call's rows see
    the first call's last keys in the ring, and their own beside them."""
    tokens = _tokens((2, 27))
    zeros = jnp.zeros(2, jnp.int32)
    whole, _ = cohere2_moe.forward_with_cache(
        params, tokens, CFG, cohere2_moe.init_cache(CFG, 2, 32), zeros)
    _, cache = cohere2_moe.forward_with_cache(
        params, tokens[:, :13], CFG, cohere2_moe.init_cache(CFG, 2, 32),
        zeros)
    rest, _ = cohere2_moe.forward_with_cache(
        params, tokens[:, 13:], CFG, cache, zeros + 13)
    np.testing.assert_allclose(rest, whole[:, 13:], atol=LIMIT)


def test_a_later_call_of_flash_size_still_attends_the_cache(
        params, monkeypatch):
    """A program that holds the flash kernel takes it only from
    position 0: a second call of the kernel's size, or one whose rows
    start at unlike positions, reads the ring and the rows it found."""
    _flash_as_on_a_tpu(monkeypatch, 8, 8)
    tokens = _tokens((2, 32))
    zeros = jnp.zeros(2, jnp.int32)
    want = reference.forward(params, tokens, HP)
    first, cache = cohere2_moe.forward_with_cache(
        params, tokens[:, :16], CFG, cohere2_moe.init_cache(CFG, 2, 32),
        zeros)
    rest, _ = cohere2_moe.forward_with_cache(
        params, tokens[:, 16:], CFG, cache, zeros + 16)
    np.testing.assert_allclose(first, want[:, :16], atol=LIMIT)
    np.testing.assert_allclose(rest, want[:, 16:], atol=LIMIT)


@pytest.mark.parametrize("t", [1, 16], ids=["decode", "prefill"])
def test_the_decode_steps_seam_moves_no_number(params, monkeypatch, t):
    """A call of one row a slot goes through the seam, a barrier ahead
    of the rotary turn on each `sliding` layer; a call of more is the
    program it was. Both give what the parent's program gives, which is
    this one with the barrier the identity: logits, rings, rows and
    counts, from slots of unlike lengths whose rings another request
    filled."""
    lens = np.array([24, 13, 5])
    tokens = _tokens((3, 24 + t))
    cache = jax.tree.map(lambda x: x + 3.0,
                         cohere2_moe.init_cache(CFG, 3, 64))
    _, cache = cohere2_moe.forward_with_cache(
        params, tokens[:, :24], CFG, cache, jnp.zeros(3, jnp.int32),
        at=jnp.asarray(lens - 1))

    def call():
        return cohere2_moe.forward(params, tokens[:, 24:], CFG, cache,
                                   jnp.asarray(lens, jnp.int32), t - 1)

    def barriers():  # (a function of its own: a trace is kept by function)
        return str(jax.make_jaxpr(lambda: call())()).count(
            "optimization_barrier")

    got, seams = call(), barriers()
    assert seams == (1 if t == 1 else 0)  # one scan body of sliding layers
    monkeypatch.setattr(lax, "optimization_barrier", lambda x: x)
    want = call()
    assert barriers() == 0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=0)


def _expert_layer(seed=3):
    cfg = dataclasses.replace(CFG, experts_held=None)
    lp = cohere2_moe._init_layer(cfg, jax.random.PRNGKey(seed))
    y = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 24, cfg.dim))
    return cfg, lp, y


def _reference_experts(y, lp, hp):
    run = jax.tree.map(lambda x: x[None], lp)
    return jax.vmap(lambda rows: reference.experts(rows, run, 0, hp))(y)


def test_the_shares_add_up_to_the_uncut_layer():
    """8 shares of 2 of the 16 experts, as the deployment's 8 chips
    hold 16 of 128 each, the four shared experts counted once, against
    the reference given all 16."""
    cfg, lp, y = _expert_layer()
    hp = {**HP, "first_expert": 0}
    want = _reference_experts(y, lp, hp)
    shared = moe._add_shared_expert(cfg, lp, y, jnp.zeros_like(y))
    total, held = shared, 0
    for first in range(0, 16, 2):
        share = dataclasses.replace(cfg, experts_held=(first, 2))
        part = {**lp, **{k: lp[k][first:first + 2]
                         for k in ("we1", "we2", "we3")}}
        out, _, counts, counted = moe._moe_ffn(share, part, y, None, None)
        assert int(counted["pairs_held"]) == int(
            counts[first:first + 2].sum())
        total = total + (out - shared)
        held += int(counted["pairs_held"])
        np.testing.assert_allclose(out, _reference_experts(
            y, part, {**hp, "first_expert": first}), atol=2e-6)
    assert held == 2 * 24 * 3
    np.testing.assert_allclose(total, want, atol=5e-6)


def test_the_fused_shared_expert_is_four_averaged_ones():
    cfg, lp, y = _expert_layer(5)
    fused = moe._add_shared_expert(cfg, lp, y, jnp.zeros_like(y))
    width = cfg.shared_hidden_dim // 4
    apart = [reference.swiglu(
        y, lp["ws1"][:, j * width:(j + 1) * width],
        lp["ws3"][:, j * width:(j + 1) * width],
        lp["ws2"][j * width:(j + 1) * width]) for j in range(4)]
    np.testing.assert_allclose(fused, sum(apart) / 4, atol=2e-6)
    summed = moe._add_shared_expert(
        dataclasses.replace(cfg, shared_combination="sum"), lp, y,
        jnp.zeros_like(y))
    np.testing.assert_allclose(summed, sum(apart), atol=5e-6)


def test_the_contract_of_a_model_with_rings(params):
    model = served_model(CFG)
    assert model.forward is cohere2_moe.forward
    cache = model.init_cache(CFG, 2, 32)
    assert jax.tree.leaves(model.state_leaves(cache)) == [True, True,
                                                          False, False]
    assert [x.shape for x in jax.tree.leaves(cache)] == [
        (3, 2, 8, 2, 16)] * 2 + [(1, 2, 32, 2, 16)] * 2
    logits, new, counts = model.forward(
        params, _tokens((2, 6)), CFG, cache, jnp.zeros(2, jnp.int32), 5)
    assert logits.shape == (2, CFG.vocab_size)
    assert logits.dtype == jnp.float32
    assert jax.tree.structure(new) == jax.tree.structure(cache)
    assert sorted(counts) == ["experts_held_steps", "experts_touched",
                              "pair_overflows", "pairs_held", "pairs_routed"]
    assert int(counts["experts_held_steps"]) == 16
    assert int(counts["pairs_routed"]) == 4 * 2 * 6 * 3
    # Of the keys four full layers would read, three windows and one
    # whole context: (3 min(L, 8) + L) / 4.
    got = model.keys_attended(CFG, np.array([3, 8, 20, 100]))
    assert got.tolist() == [3, 8, (24 + 20) // 4, (24 + 100) // 4]


def test_the_window_is_scoped_inside_attention(params):
    def lowered(t):
        return jax.jit(lambda p, c: cohere2_moe.forward(
            p, _tokens((2, t)), CFG, c, jnp.ones(2, jnp.int32), t - 1)
        ).lower(params, cohere2_moe.init_cache(CFG, 2, 32)).as_text(
            debug_info=True)

    for text in (lowered(1), lowered(16)):
        for scope in ("attn/window", "mlp/shared_expert", "mlp/router"):
            assert scope in text, scope
        assert "mlp/window" not in text


def test_the_other_families_blocks_are_what_they_were():
    """The two fields of the block's form default to the sequential
    RMSNorm block: a dense decoder's program has no mean taken off and
    reads both of its norms."""
    from ray_tpu.models import llama
    dense = llama.LlamaConfig.debug()
    assert (dense.norm_kind, dense.parallel_block) == ("rms", False)
    p = llama.init_params(dense, jax.random.PRNGKey(0))
    x = _tokens((1, 8)) % dense.vocab_size
    want = llama.forward(p, x, dense)
    other = {**p, "layers": {**p["layers"], "mlp_norm":
                             p["layers"]["mlp_norm"] * 2}}
    assert np.abs(np.asarray(llama.forward(other, x, dense) - want)).max() > 0


# -- the engine over a cache with rings --------------------------------------


_FULL = jax.jit(lambda params, tokens: reference.sequence_logits(
    params, tokens, HP))


def _greedy(params, prompt, n):
    """Greedy decoding by the reference's full forward pass, over the
    sequence padded to one length (a row sees nothing after itself)."""
    tokens = list(prompt)
    for _ in range(n):
        padded = jnp.asarray(tokens + [0] * (48 - len(tokens)), jnp.int32)
        tokens.append(int(_FULL(params, padded)[len(tokens) - 1].argmax()))
    return tokens[len(prompt):]


def _prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n)]


def test_the_engine_serves_it_with_no_prefix_cache(params):
    engine = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=64)
    assert engine.prefix_cache is None and engine.prefix_digests() is None
    assert engine._is_state == [True, True, False, False]
    # Blocks would be sized by the rows alone: the full layer's.
    assert engine._block_nbytes == engine.block_tokens * 2 * 2 * 16 * 4
    prompt = _prompt(21, 5)
    first = engine.generate(prompt, SamplingParams(max_tokens=6))
    second = engine.generate(prompt, SamplingParams(max_tokens=6))
    engine.stop()
    assert first == second == _greedy(params, prompt, 6)
    totals = engine.metrics()["totals"]
    assert totals["kv_blocks_read_back"] == 0
    assert 0 < totals["pairs_held"] < totals["pairs_routed"]
    assert 0 < totals["keys_attended"] < totals["keys_cached"]


def test_a_retired_slot_admitted_again_equals_a_fresh_engine(params):
    """One slot: a prompt shorter than the ring gets the slot a longer
    request left, whose rings are full of that request's keys."""
    engine = LLMEngine(CFG, params, max_batch_size=1, max_seq_len=64,
                       decode_steps=2)
    engine.generate(_prompt(19, 6), SamplingParams(max_tokens=5))
    prompt = _prompt(5, 7)
    again = engine.generate(prompt, SamplingParams(max_tokens=9))
    engine.stop()
    assert again == _greedy(params, prompt, 9)


def test_requests_beside_each_other_keep_their_own_rings(params):
    engine = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=64)
    engine.warmup(32)
    prompts = [_prompt(23, 8), _prompt(6, 9), _prompt(14, 10)]
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


def test_decode_spans_carry_the_keys_and_the_expert_counts(params):
    engine = LLMEngine(CFG, params, max_batch_size=2, max_seq_len=64)
    engine.generate(list(range(1, 30)), SamplingParams(max_tokens=4))
    engine.stop()
    spans = flight_recorder.local_snapshot()["spans"]
    dispatched = [s["attrs"] for s in spans if s.get("attrs")
                  and s["stage"] == "engine.decode_dispatch"
                  and s["attrs"].get("keys_cached", 0) >= 29]
    assert dispatched
    assert all(a["keys_attended"] == (3 * 8 + a["keys_cached"]) // 4
               for a in dispatched)
    consumed = [s["attrs"] for s in spans if s.get("attrs")
                and s["stage"] == "engine.consume_block"
                and s["attrs"].get("experts_held_steps") == 16]
    assert consumed and all(0 < a["experts_touched"] <= 16
                            for a in consumed)
