"""What every served family's tests held, each in a file of its own up
to PR 58, stated once and run for each family as a case: at debug widths
on the CPU, in float32, seeded random weights, the served path (a
prefill padded to its bucket, then decode through the cache, rows at
their own lengths) against the plain reference, each fault of
`tools/glm_logit_check.py` failing where the program passes; what a
state leaf demands of a forward pass (padding kept out of the state, a
prefill in two calls, rows of different lengths); the held shares of an
expert layer adding up to the uncut layer; the mixers' scopes; a decode
step through the kernel a TPU runs; and the engine, which knows no
model, serving each through its cache. The family's module, config and
expected numbers come from its row in `tests/models/families.py`; a
test some families never had runs for those that had it. What is a
family's alone stays in its `test_<family>.py`."""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private import flight_recorder
from ray_tpu.models import moe
from ray_tpu.models.serving import served_model
from ray_tpu.serve.llm import LLMEngine, SamplingParams
from tests.models import families
from tests.models.families import FAMILIES, ROWS, having
from tests.models.test_cached_attention import through_the_kernel
from tools import glm_logit_check

# The families the tool checks, which are those whose reference is
# causal (SDAR's rows see their whole block, and its check is the block
# runner's).
TOOL = having("tool_faults")


# -- the check ----------------------------------------------------------------


@pytest.mark.parametrize("name", FAMILIES)
def test_the_served_path_agrees_with_the_reference(name):
    assert families.errors(name)["max"] < ROWS[name].program


@pytest.mark.parametrize("name, fault", [
    (name, fault) for name in FAMILIES for fault in sorted(
        families.faults(name))])
def test_a_fault_fails(name, fault):
    """Every fault reads over its family's limit, which the program's
    error stays under by the factor the row states."""
    row, errors = ROWS[name], families.errors(name, fault)
    assert errors["max"] > row.faults.get(fault, row.fault), errors
    if name in TOOL:
        assert 0 <= errors["q50"] <= errors["q99"] <= errors["q99.9"] \
            <= errors["max"] and 0 <= errors["over"] <= 1


@pytest.mark.parametrize("name", TOOL)
def test_the_tool_takes_the_family_by_its_configurations_name(name):
    row, file = ROWS[name], families.file(name)
    family_faults, unseen, plain_init, _ = glm_logit_check.FAMILIES[
        file["family"]]
    assert family_faults is getattr(glm_logit_check, row.tool_faults)
    assert plain_init() is families.module(name).init_params
    # What one set of weights cannot show on the chip is named, and is
    # a fault; the faults a limit of their own is set for are there.
    faults = set(families.faults(name))
    checks = file["serve"]["tool_checks"]
    assert set(checks) == set(unseen) == {"benchmark", "plain"}
    assert all(set(names) < faults for names in unseen.values())
    assert row.named | set(row.faults) <= faults
    assert len(faults) == row.n_faults


@pytest.mark.parametrize("name", [
    "KimiLinearConfig", "NemotronHConfig", "GlmDsaConfig"])
def test_the_benchmarks_weights_are_the_programs_but_two_scales(name):
    cfg, model = families.stack(name), families.adapter(name)
    key = jax.random.PRNGKey(4)
    # Kimi Linear's two initialisers are two programs, which round a
    # product apart; the others' run eagerly and agree to the bit.
    jitted = name == "KimiLinearConfig"
    through = jax.jit if jitted else (lambda init: init)
    plain = through(lambda key: families.module(name).init_params(cfg, key))(
        key)
    drawn = through(lambda key: model.init(cfg, key))(key)
    scales = {"we2": model.ROUTED_OUT_SCALE,
              "router_bias": model.ROUTER_BIAS_SCALE}
    scaled = dict.fromkeys(scales, 0)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(plain),
                            jax.tree.leaves(drawn)):
        leaf = getattr(path[-1], "key", None)
        scaled[leaf] = scaled.get(leaf, 0) + 1
        if jitted:
            np.testing.assert_allclose(
                np.asarray(a, np.float32) * scales.get(leaf, 1),
                np.asarray(b, np.float32), rtol=1e-6, err_msg=leaf)
        else:
            assert np.array_equal(np.asarray(a) * scales.get(leaf, 1),
                                  np.asarray(b)), leaf
    sparse = sum("sparse" in kind or "moe" in kind for kind, _ in cfg.runs())
    assert scaled["we2"] == scaled["router_bias"] == sparse


# -- the shares of an expert layer ---------------------------------------------

@pytest.mark.parametrize("name", having("share"))
def test_the_shares_add_up_to_the_uncut_layer(name):
    """Shares of 2 or 4 of the 16 experts, their routed parts added and
    the shared expert counted once, against the reference given all
    16; each share against the reference given the same share; and the
    uncut layer through the program's own path."""
    reference = families.reference(name)
    cfg = dataclasses.replace(families.cfg(name), experts_held=None)
    hp = {**reference.hyper(families.config(name)), "first_expert": 0}
    if name == "Cohere2MoeConfig":
        lp = families.module(name)._init_layer(cfg, jax.random.PRNGKey(3))
        y = jax.random.normal(jax.random.PRNGKey(4), (2, 24, cfg.dim))

        def want_of(lp, hp):
            run = jax.tree.map(lambda x: x[None], lp)
            return jax.vmap(lambda rows: reference.experts(rows, run, 0, hp))(
                y)
    else:
        keys = jax.random.split(jax.random.PRNGKey(3), 5)
        lp = moe.expert_init(cfg, keys[:4])
        y = jax.random.normal(keys[4], (2, 24, cfg.dim))

        def want_of(lp, hp):
            return jax.vmap(lambda rows: reference.experts(rows, lp, hp))(y)

    size, pairs = ROWS[name].share, 2 * 24 * cfg.n_experts_per_token
    matrices = [k for k in ("we1", "we3", "we2") if k in lp]
    ffn = jax.jit(moe._moe_ffn, static_argnums=(0, 3, 4))
    with jax.default_matmul_precision("highest"):
        want = want_of(lp, hp)
    shared = moe._add_shared_expert(cfg, lp, y, jnp.zeros_like(y))
    total, held, touched = shared, 0, 0
    for first in range(0, 16, size):
        share = dataclasses.replace(cfg, experts_held=(first, size))
        part = {**lp, **{k: lp[k][first:first + size] for k in matrices}}
        out, _, counts, counted = ffn(share, part, y, None, None)
        ours = counts[first:first + size]
        assert int(counted["pairs_held"]) == int(ours.sum())
        assert int(counted["pairs_routed"]) == pairs
        assert int(counted["experts_touched"]) == int((ours > 0).sum())
        assert int(counted["experts_held_steps"]) == size
        total = total + (out - shared)
        held += int(counted["pairs_held"])
        touched += int(counted["experts_touched"])
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(
                out, want_of(part, {**hp, "first_expert": first}), atol=2e-6)
    assert held == pairs and 0 < touched <= 16
    np.testing.assert_allclose(total, want, atol=5e-6)
    whole, _, counts, counted = ffn(cfg, lp, y, None, None)
    np.testing.assert_allclose(whole, want, atol=5e-6)
    assert int(counted["experts_touched"]) == int((counts > 0).sum())
    assert int(counted["experts_held_steps"]) == 16


# -- what a state leaf demands of a forward pass -------------------------------


def _counted(counts, want):
    return {k: int(v) for k, v in counts.items() if k in want} == want


@pytest.mark.parametrize("name", having("two_calls"))
def test_a_padded_prompt_leaves_the_same_logits_and_state(name):
    """13 tokens in a bucket of 16 (Kimi Linear: 45 in 64), neither a
    multiple of the scan's chunk: the padding changes no logit of the
    prompt and nothing of the state and the carries the prompt
    leaves."""
    row, cfg = ROWS[name], families.stack(name)
    params = families.params(name, row.layers)
    forward_with_cache = families.forward_with_cache(name)
    real, bucket, atol = row.padded
    tokens = families.tokens(name, (2, real))
    start = jnp.zeros(2, jnp.int32)
    want, left = forward_with_cache(params, tokens, cfg,
                                    families.cache(name), start)
    padded = jnp.pad(tokens, ((0, 0), (0, bucket - real)), constant_values=7)
    got, state = forward_with_cache(params, padded, cfg,
                                    families.cache(name), start, at=real - 1)
    np.testing.assert_allclose(got[:, :real], want, atol=atol)
    assert len(families.state(name, state)) == sum(row.cache_state)
    for a, b in zip(families.state(name, state), families.state(name, left)):
        np.testing.assert_allclose(a, b, atol=1e-6)
    # Without `at` the padding is absorbed: by every leaf of the state
    # and the carries, or (Nemotron's) by the state.
    _, absorbed = forward_with_cache(params, padded, cfg,
                                     families.cache(name), start)
    moved = [float(jnp.abs(a - b).max()) > 1e-3 for a, b in zip(
        families.state(name, absorbed), families.state(name, left))]
    assert all(moved) if row.every_leaf else any(moved)
    # The engine's `forward` gives the logits of position `at` itself,
    # and counts the real tokens and the rows that started from zeros.
    last, _, counts = families.forward(name)(
        params, padded, cfg, families.cache(name), start,
        jnp.int32(real - 1))
    np.testing.assert_allclose(last, want[:, real - 1], atol=atol)
    assert _counted(counts, row.padded_counts), counts
    assert not row.counts_exact or set(counts) == row.counts


@pytest.mark.parametrize("name", having("two_calls"))
def test_a_prefill_in_two_calls_equals_one(name):
    row, cfg = ROWS[name], families.stack(name)
    params = families.params(name, row.layers)
    forward_with_cache = families.forward_with_cache(name)
    n, first, atol = row.two_calls
    tokens = families.tokens(name, (2, n), seed=3)
    start = jnp.zeros(2, jnp.int32)
    want, left = forward_with_cache(params, tokens, cfg,
                                    families.cache(name), start)
    head, cache = forward_with_cache(params, tokens[:, :first], cfg,
                                     families.cache(name), start)
    tail, cache = forward_with_cache(params, tokens[:, first:], cfg, cache,
                                     start + first)
    np.testing.assert_allclose(jnp.concatenate([head, tail], 1), want,
                               atol=atol)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(left)):
        np.testing.assert_allclose(a, b, atol=atol)
    if row.second_counts:
        _, _, counts = families.forward(name)(
            params, tokens[:, first:], cfg, cache, start + first, 9)
        assert _counted(counts, row.second_counts), counts


@pytest.mark.parametrize("name", having("decode_counts"))
def test_rows_of_different_lengths_in_one_batch_equal_the_reference(name):
    """Rows of 17 (Kimi Linear: 37) and 9 tokens prefilled in one call,
    each left after its own last token, then decoding together from
    their own positions: every logit is the reference's full forward
    pass's."""
    row, cfg = ROWS[name], families.stack(name)
    params = families.params(name, row.layers)
    forward_with_cache = families.forward_with_cache(name)
    lens, steps = np.asarray((row.long_row, 9)), 4
    tokens = families.tokens(name, (2, row.long_row + steps), seed=4)
    reference = families.reference(name)
    hp = reference.hyper(families.config(name))
    sequence_logits = jax.jit(
        lambda p, t: reference.sequence_logits(p, t, hp))
    with jax.default_matmul_precision("highest"):
        want = [np.asarray(sequence_logits(params, tokens[i, :n + steps]))
                for i, n in enumerate(lens)]
    logits, cache = forward_with_cache(
        params, tokens[:, :row.long_row], cfg, families.cache(name),
        jnp.zeros(2, jnp.int32), at=jnp.asarray(lens - 1, jnp.int32))
    top = max(np.abs(w).max() for w in want)
    for i, n in enumerate(lens):
        np.testing.assert_allclose(logits[i, :n], want[i][:n],
                                   atol=3e-6 * top)
    at = np.arange(2)
    for i in range(steps):
        pos = lens + i
        # The short row's token at its own position, not the prefill's.
        fed = jnp.asarray(np.asarray(tokens)[at, pos][:, None])
        out, cache = forward_with_cache(params, fed, cfg, cache,
                                        jnp.asarray(pos, jnp.int32))
        for r in range(2):
            np.testing.assert_allclose(out[r, 0], want[r][pos[r]],
                                       atol=3e-6 * top)
    _, _, counts = families.forward(name)(
        params, fed, cfg, cache, jnp.asarray(pos + 1, jnp.int32), 0)
    assert _counted(counts, row.decode_counts), counts
    assert not row.counts_exact or set(counts) == row.counts


@pytest.mark.parametrize("name", having("cache_state"))
def test_the_contract_of_a_model_with_state_leaves(name):
    row, cfg = ROWS[name], families.stack(name)
    params = families.params(name, row.layers)
    model = served_model(cfg)
    assert model.forward is families.module(name).forward
    assert "keys_read" in row.given or model.keys_read is None
    cache = model.init_cache(cfg, 2, row.contract_rows)
    assert tuple(jax.tree.leaves(model.state_leaves(cache))) \
        == row.cache_state
    for (run, leaf), shape in row.cache_leaves.items():
        assert cache["runs"][run][leaf].shape == shape, (run, leaf)
        assert leaf != "state" or cache["runs"][run][leaf].dtype \
            == jnp.float32
    logits, new, counts = families.forward(name)(
        params, families.tokens(name, (2, 6)), cfg, cache,
        jnp.zeros(2, jnp.int32), 5)
    assert logits.shape == (2, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert jax.tree.structure(new) == jax.tree.structure(cache)
    assert row.counts <= set(counts)
    assert not row.counts_exact or set(counts) == row.counts
    assert all(x.dtype == jnp.int32 and x.shape == () for x in
               counts.values())
    assert _counted(counts, row.contract_counts), counts
    assert not row.param_names or set(params) == row.param_names
    if row.keys_attended:
        lens, attended = row.keys_attended
        assert tuple(model.keys_attended(cfg, np.array(lens)).tolist()) \
            == attended


@pytest.mark.parametrize("name", having("scopes"))
def test_each_mixer_is_scoped_by_its_kind(name):
    """A mixer's ops lie under its own scope (`conv`, `delta`, `ssm`,
    `attn` with the window inside it), never under another's: a trace's
    attention share reads the attention layers alone."""
    row, cfg = ROWS[name], families.stack(name)
    params = families.params(name, row.layers)

    def lowered(t):
        return jax.jit(lambda p, c: families.module(name).forward(
            p, families.tokens(name, (2, t)), cfg, c, jnp.ones(2, jnp.int32),
            t - 1)).lower(params, families.cache(name)).as_text(
                debug_info=True)

    for step, text in (("decode", lowered(1)), ("prefill", lowered(16))):
        for scope in row.scopes[step]:
            assert scope in text, (step, scope)
        for scope in row.scopes["not " + step]:
            assert scope not in text, (step, scope)


@pytest.mark.parametrize("rows", [16, 256], ids=["blocks-of-16", "one-block"])
@pytest.mark.parametrize("lens", [(17, 9), (16, 1), (29, 15)],
                         ids=lambda lens: "-".join(map(str, lens)))
@pytest.mark.parametrize("name", ["OlmoHybridConfig", "Lfm2MoeConfig"])
def test_a_decode_step_through_the_kernel_equals_the_plain_path(
        monkeypatch, name, lens, rows):
    """The full layers' decode step through
    `ops.attention.decode_attention` on the merged axis (LFM2: two key
    heads side by side in a row, four query heads each), the kernel a
    TPU runs, interpreted here, against `llama._cached_attention` on the
    [rows, heads, head size] view, which the CPU takes: rows prefilled
    to their own lengths decode three steps together, the same logits
    and the same cache either way."""
    cfg, module = families.stack(name), families.module(name)
    params = families.params(name)
    lens, steps = np.asarray(lens), 3
    tokens = families.tokens(name, (2, lens.max() + steps),
                             seed=int(lens.sum()))
    _, filled = families.forward_with_cache(name)(
        params, tokens[:, :lens.max()], cfg, families.cache(name),
        jnp.zeros(2, jnp.int32), at=jnp.asarray(lens - 1, jnp.int32))

    def decoded(step):
        out, cache, at = [], filled, np.arange(2)
        for i in range(steps):
            fed = jnp.asarray(np.asarray(tokens)[at, lens + i][:, None])
            logits, cache = step(params, fed, cfg, cache,
                                 jnp.asarray(lens + i, jnp.int32))
            out.append(np.asarray(logits))
        return np.stack(out), cache

    want, plain_cache = decoded(families.forward_with_cache(name))
    through_the_kernel(monkeypatch, module, rows)
    # (A jit of its own: it is traced under the patch.)
    got, cache = decoded(jax.jit(
        lambda *args: module.forward_with_cache(*args), static_argnums=2))
    np.testing.assert_allclose(got, want, atol=3e-6 * np.abs(want).max())
    assert not np.array_equal(got, want)  # it did go another way
    for x, y in zip(jax.tree.leaves(cache), jax.tree.leaves(plain_cache)):
        np.testing.assert_allclose(x, y, atol=3e-6 * np.abs(y).max())


# -- the engine over each family's cache ---------------------------------------


@pytest.fixture(scope="session")
def engines():
    """`engine(name)`: the family's engine of two slots, built when its
    first test asks and shared by the tests below, which stand together
    so that a worker mostly gets them together; each stops its loop
    before it reads what the engine left, and the next `generate`
    starts it again."""
    built = {}

    def engine(name):
        if name not in built:
            row = ROWS[name]
            built[name] = LLMEngine(
                families.served_stack(name),
                families.params(name, row.engine_layers),
                max_batch_size=2, max_seq_len=64)
        return built[name]

    yield engine
    for each in built.values():
        each.stop()


@pytest.mark.parametrize("name", TOOL)
def test_a_padded_reference_gives_the_real_positions_their_logits(name):
    """What the engine tests' expected answers lean on: the reference's
    logits of a prompt's positions are the same from a call padded to
    `families.ANSWER_ROWS` tokens, to the float's last digits, and so is
    every position's largest."""
    row = ROWS[name]
    params = families.params(name, row.engine_layers)
    prompt = families.prompt(name, 21, 5)
    reference = families.reference(name)
    hp = reference.hyper(families.config(name))
    alone = np.asarray(jax.jit(
        lambda p, t: reference.sequence_logits(p, t, hp))(
            params, jnp.asarray(prompt, jnp.int32)))
    padded = np.asarray(families.reference_rows(name, params, prompt))
    np.testing.assert_allclose(padded, alone, atol=1e-5 * np.abs(alone).max())
    np.testing.assert_array_equal(padded.argmax(-1), alone.argmax(-1))


@pytest.mark.parametrize("name", having("engine_state"))
def test_the_engine_serves_it_with_no_prefix_cache(name, engines):
    row, engine = ROWS[name], engines(name)
    params = families.params(name, row.engine_layers)
    assert type(engine) is LLMEngine
    assert engine.prefix_cache is None and engine.prefix_digests() is None
    assert tuple(engine._is_state) == row.engine_state
    assert not row.block_token_bytes or engine._block_nbytes \
        == engine.block_tokens * row.block_token_bytes
    prompt = families.prompt(name, 21, 5)
    first = engine.generate(prompt, SamplingParams(max_tokens=6))
    second = engine.generate(prompt, SamplingParams(max_tokens=6))
    engine.stop()
    assert first == second and len(first) == 6
    assert families.is_greedy(name, params, prompt, first)
    assert "kv_cache" not in engine.metrics()
    assert not engine._read_rows_exec and not engine._kv_store
    totals = engine.metrics()["totals"]
    assert totals["kv_blocks_read_back"] == 0
    assert row.totals(totals), totals


@pytest.mark.parametrize("name", having("engine_state"))
def test_requests_beside_each_other_keep_their_own_state(name, engines):
    row, engine = ROWS[name], engines(name)
    params = families.params(name, row.engine_layers)
    if row.warms_up:
        engine.warmup(32)
    prompts = [families.prompt(name, n, 8 + i)
               for i, n in enumerate(row.beside)]
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
        assert len(answer) == 6 \
            and families.is_greedy(name, params, prompt, answer)


@pytest.mark.parametrize("name", having("consumed"))
def test_decode_spans_carry_the_models_counts(name, engines):
    row, engine = ROWS[name], engines(name)
    engine.generate(list(range(1, row.spans_prompt + 1)),
                    SamplingParams(max_tokens=4))
    engine.stop()
    spans = [s for s in flight_recorder.local_snapshot()["spans"]
             if s.get("attrs")]
    consumed = [s["attrs"] for s in spans
                if s["stage"] == "engine.consume_block"
                and row.consumed_with in s["attrs"]]
    assert consumed and all(row.consumed(a) for a in consumed), consumed
    if row.dispatched:
        which, hold = row.dispatched
        dispatched = [s["attrs"] for s in spans
                      if s["stage"] == "engine.decode_dispatch"
                      and which(s["attrs"])]
        cfg = families.served_stack(name)
        assert dispatched and all(hold(a, cfg) for a in dispatched)
    totals = engine.metrics()["totals"]
    assert row.totals(totals), totals


@pytest.mark.parametrize("name", having("engine_state"))
def test_a_retired_slot_admitted_again_starts_from_zeros(name):
    """One slot (an engine of its own: the tests above need two): the
    second, shorter request gets the slot the first one left, whose
    state kept stepping after it was retired."""
    row = ROWS[name]
    params = families.params(name, row.engine_layers)
    engine = LLMEngine(families.served_stack(name), params, max_batch_size=1,
                       max_seq_len=64, decode_steps=2)
    engine.generate(families.prompt(name, 19, 6),
                    SamplingParams(max_tokens=5))
    moved = [np.abs(np.asarray(x)).max() > 0 for x, is_state in zip(
        jax.tree.leaves(engine.cache), engine._is_state) if is_state]
    assert all(moved) if row.every_leaf else any(moved)
    n, max_tokens = row.retired
    prompt = families.prompt(name, n, 7)
    again = engine.generate(prompt, SamplingParams(max_tokens=max_tokens))
    engine.stop()
    assert len(again) == max_tokens \
        and families.is_greedy(name, params, prompt, again)
