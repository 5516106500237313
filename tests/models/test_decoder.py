"""The seam between `models/decoder.py` and the architectures composed
over it: every entry point runs the one block, what attention knows
(the q/k norm) works wherever attention runs, the one loss tail weighs
by the mask for every architecture, and the one parameter skeleton draws
the leaves the two it replaced drew."""

import dataclasses
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decoder, llama, moe
from ray_tpu.models import init_train_state, make_optimizer, make_train_step

DENSE = llama.LlamaConfig.debug()
MOE = moe.MoEConfig.debug_moe()


def _batch(cfg, b=2, s=16, seed=0):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0,
                                cfg.vocab_size)
    return {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}


def _pipeline_stage(params, batch):
    from ray_tpu.parallel.pipeline import llama_pp_parts

    stage_params, _, stage_fn, _, embed_fn = llama_pp_parts(
        DENSE, params, n_stages=2)
    return stage_fn(jax.tree.map(lambda a: a[0], stage_params),
                    embed_fn(params["embed"], batch["tokens"]))


ENTRY_POINTS = {
    "forward": (DENSE, lambda p, b: llama.forward(p, b["tokens"], DENSE)),
    "loss_fn": (DENSE, lambda p, b: llama.loss_fn(p, b, DENSE)),
    "forward_with_cache": (DENSE, lambda p, b: llama.forward_with_cache(
        p, b["tokens"], DENSE, llama.init_kv_cache(DENSE, 2, 32),
        jnp.zeros(2, jnp.int32))),
    "moe_forward": (MOE, lambda p, b: moe.moe_forward(p, b["tokens"], MOE)),
    "moe_loss_fn": (MOE, lambda p, b: moe.moe_loss_fn(p, b, MOE)),
    "llama_pp_parts.stage_fn": (DENSE, _pipeline_stage),
}


def _init(cfg, seed=0):
    init = moe.init_moe_params if isinstance(cfg, moe.MoEConfig) \
        else llama.init_params
    return init(cfg, jax.random.PRNGKey(seed))


@pytest.mark.parametrize("entry", ENTRY_POINTS)
def test_every_entry_point_runs_the_one_block(monkeypatch, entry):
    calls = []
    block = decoder.block

    def counted(*args, **kwargs):
        calls.append(1)
        return block(*args, **kwargs)

    monkeypatch.setattr(decoder, "block", counted)
    cfg, run = ENTRY_POINTS[entry]
    out = run(_init(cfg), _batch(cfg))
    assert calls and all(bool(jnp.all(jnp.isfinite(x)))
                         for x in jax.tree.leaves(out)
                         if jnp.issubdtype(x.dtype, jnp.floating))


@pytest.mark.parametrize("scope", ["attn", "mlp"])
def test_only_the_block_opens_the_two_layer_scopes(scope):
    opened = {
        path.name: len(re.findall(rf'named_scope\([^\n]*"{scope}"\)',
                                  path.read_text()))
        for path in pathlib.Path(decoder.__file__).parent.glob("*.py")}
    assert {name: n for name, n in opened.items() if n} == {"decoder.py": 1}


def test_a_dense_model_with_the_qk_norm_trains_and_serves():
    cfg = dataclasses.replace(DENSE, qk_norm=True)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    layer_axes = llama.param_logical_axes(cfg)["layers"]
    assert params["layers"]["q_norm"].shape == (2, 64)
    assert params["layers"]["k_norm"].shape == (2, 32)
    assert layer_axes["q_norm"] == layer_axes["k_norm"] == (None, "norm")
    assert "q_norm" not in llama.param_logical_axes(DENSE)["layers"]
    assert sum(x.size for x in jax.tree.leaves(params)) == cfg.num_params()

    # A step trains the two norm weights with the rest.
    tx = make_optimizer(1e-2, warmup_steps=0)
    step = make_train_step(lambda p, b: llama.loss_fn(p, b, cfg), tx,
                           donate=False)
    state, metrics = step(init_train_state(params, tx), _batch(cfg, b=4))
    assert np.isfinite(float(metrics["loss"]))
    assert not np.array_equal(state.params["layers"]["q_norm"],
                              params["layers"]["q_norm"])

    # Prefill, then decode token by token with the four rows at
    # different positions, against the uncached forward pass.
    lens = np.array([9, 7, 5, 3])
    rows, n_pre, n_dec = len(lens), int(lens.max()), 4
    tokens = np.random.default_rng(3).integers(
        0, cfg.vocab_size, (rows, n_pre + n_dec), dtype=np.int32)
    want = np.asarray(llama.forward(params, jnp.asarray(tokens), cfg))
    without = np.asarray(llama.forward(params, jnp.asarray(tokens), DENSE))
    assert np.abs(want - without).max() > 0.01 * np.abs(want).max()
    served = jax.jit(lambda p, t, c, s: llama.forward_with_cache(
        p, t, cfg, c, s))
    logits, cache = served(params, jnp.asarray(tokens[:, :n_pre]),
                           llama.init_kv_cache(cfg, rows, 32),
                           jnp.zeros(rows, jnp.int32))
    worst = np.abs(np.asarray(logits) - want[:, :n_pre]).max()
    at = np.arange(rows)
    for i in range(n_dec):
        logits, cache = served(
            params, jnp.asarray(tokens[at, lens + i][:, None]), cache,
            jnp.asarray(lens + i, jnp.int32))
        worst = max(worst, np.abs(np.asarray(logits[:, 0])
                                  - want[at, lens + i]).max())
    assert worst < 1e-4 * np.abs(want).max()


@pytest.mark.parametrize("cfg,loss,key", [
    (DENSE, llama.loss_fn, "loss"), (MOE, moe.moe_loss_fn, "ce_loss")],
    ids=["dense", "moe"])
def test_the_loss_is_the_mean_over_the_unmasked_tokens(cfg, loss, key):
    params = _init(cfg)
    batch = _batch(cfg, b=4)
    keep = jnp.array([0, 2])
    mask = jnp.zeros((4, 16)).at[keep].set(1.0)
    masked = loss(params, {**batch, "mask": mask}, cfg)[1][key]
    alone = loss(params, jax.tree.map(lambda a: a[keep], batch), cfg)[1][key]
    assert float(masked) == pytest.approx(float(alone), rel=1e-6)
    # Half of them padding: not the mean over all four rows.
    assert float(masked) != pytest.approx(
        float(loss(params, batch, cfg)[1][key]), rel=1e-4)


def test_the_moe_loss_without_a_mask_is_what_it_was():
    """Pinned from the tree before the two loss tails became one
    (commit 8bac51a), where the MoE's was a plain mean."""
    loss, metrics = moe.moe_loss_fn(_init(MOE), _batch(MOE), MOE)
    assert float(loss) == pytest.approx(6.241194725036621, rel=1e-6)
    assert float(metrics["ce_loss"]) == pytest.approx(6.221142292022705,
                                                      rel=1e-6)
    assert float(metrics["aux_loss"]) == pytest.approx(2.0052425861358643,
                                                       rel=1e-6)
    np.testing.assert_array_equal(metrics["expert_tokens"],
                                  [[12, 19, 16, 17], [19, 15, 16, 14]])


# Float64 sums of the leaves commit 8bac51a's two initialisers drew from
# PRNGKey(0), before they became compositions of one skeleton.
PINNED = {
    "dense": (DENSE, {
        ("embed",): -3.273028610547726, ("out",): 3.368426441520228,
        ("layers", "wq"): -3.262807725583116,
        ("layers", "wk"): -0.7264747314159195,
        ("layers", "wv"): -1.3577227717441929,
        ("layers", "wo"): -0.09920178299284999,
        ("layers", "w1"): -1.5520537649840662,
        ("layers", "w3"): 0.2743522636458806,
        ("layers", "w2"): -0.05360522038533588}),
    "moe": (MOE, {
        ("embed",): -3.273028610547726, ("out",): 3.368426441520228,
        ("layers", "wq"): -3.262807725583116,
        ("layers", "wo"): -0.09920178299284999,
        ("layers", "router"): 0.28121378573268885,
        ("layers", "we1"): -10.02939456119574,
        ("layers", "we3"): -5.349558385334376,
        ("layers", "we2"): -0.32426919763397777}),
}


@pytest.mark.parametrize("name", PINNED)
def test_the_skeleton_draws_the_leaves_the_two_initialisers_drew(name):
    cfg, sums = PINNED[name]
    params = _init(cfg)
    for path, want in sums.items():
        leaf = params
        for part in path:
            leaf = leaf[part]
        got = float(np.asarray(leaf).astype(np.float64).sum())
        assert got == pytest.approx(want, rel=1e-12, abs=1e-12), path
    ones = {"final_norm": params["final_norm"],
            **{k: params["layers"][k] for k in ("attn_norm", "mlp_norm")}}
    assert all(bool(jnp.all(v == 1)) for v in ones.values())


# ---------------------------------------------------------------------------
# Leaves handed whole (`layers`' contract for both halves)
# ---------------------------------------------------------------------------


def _halves_that_name(mixer_names, ffn_names, seen):
    """A mixer and a SwiGLU over `llama`'s leaves that read `wv`, and
    `w1` and `w2`, through `leaf_product`, naming in `whole` what they
    are told to; `seen` takes what each call was handed."""
    from ray_tpu.ops.stacked_product import leaf_product

    def mixer(h, lp, rope, state, handed, stacks=None):
        seen.append(("mixer", stacks, sorted(lp)))
        v = leaf_product("bsd,dhk->bshk", h, "wv", lp, stacks)
        return jnp.repeat(v, DENSE.n_heads // DENSE.n_kv_heads, 2), state, \
            handed

    def ffn(h, lp, stacks=None):
        seen.append(("ffn", stacks, sorted(lp)))
        up = jax.nn.silu(leaf_product("bsd,df->bsf", h, "w1", lp, stacks))
        return leaf_product("bsf,fd->bsd", up, "w2", lp, stacks), None

    mixer.scope = "ssm"
    if mixer_names:
        mixer.whole = mixer_names
    if ffn_names:
        ffn.whole = ffn_names
    return mixer, ffn


@pytest.mark.parametrize("mixer_names,ffn_names", [
    (("wv",), ()), ((), ("w1", "w2")), (("wv", "not_a_leaf"), ("w1", "w2")),
], ids=["mixer", "ffn", "both"])
def test_a_half_that_names_leaves_is_handed_them_whole(mixer_names,
                                                       ffn_names):
    """One rule for both halves: the leaves a half names in `whole` are
    no scanned input, the half is called with `stacks=(those leaves,
    layer)` and `lp` holds the rest, its other attributes (`scope`)
    kept; the hidden states are those of the same halves fed slices."""
    params, batch = _init(DENSE), _batch(DENSE)
    plain, handed = [], []
    want, _, _ = decoder.hidden(params, batch["tokens"], DENSE,
                                *_halves_that_name((), (), plain))
    text = jax.jit(lambda p: decoder.hidden(
        p, batch["tokens"], DENSE,
        *_halves_that_name(mixer_names, ffn_names, []))).lower(
            params).as_text(debug_info=True)
    got, _, _ = decoder.hidden(
        params, batch["tokens"], DENSE,
        *_halves_that_name(mixer_names, ffn_names, handed))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    assert '"ssm' in text and '"attn:' not in text and '"mlp' in text
    assert all(stacks is None for _, stacks, _ in plain)
    named = {name for name in mixer_names + ffn_names
             if name in params["layers"]}
    for half, stacks, lp in handed:
        names = {"mixer": mixer_names, "ffn": ffn_names}[half]
        assert not named & set(lp)
        if not names:
            assert stacks is None
            continue
        whole, layer = stacks
        assert set(whole) == named and layer.shape == ()
        assert all(whole[name].shape == params["layers"][name].shape
                   for name in named)


def _layers_as_they_were(mixer, ffn, cfg, rope, x, stacked, state=None,
                         handed=None, *, save=None, mesh=None,
                         rules=decoder.DEFAULT_RULES):
    """`decoder.layers` before a mixer could name leaves (PR 59's)."""
    import functools
    from jax import lax

    whole = {name: stacked[name] for name in getattr(ffn, "whole", ())
             if name in stacked}
    if whole:
        stacked = {name: leaf for name, leaf in stacked.items()
                   if name not in whole}

    def body(carry, scanned):
        x, handed, state = carry
        lp, layer = scanned
        half = ffn
        if whole:
            half = functools.partial(ffn, stacks=(whole, layer))
            half.stream = getattr(ffn, "stream", False)
        x, state, extras, handed = decoder.block(
            mixer, half, cfg, rope, x, lp,
            None if state is None else (state, layer), handed, mesh=mesh,
            rules=rules)
        return (x, handed, state), extras

    if save is not None:
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(*save))
    index = None if state is None and not whole else jnp.arange(
        jax.tree.leaves(stacked)[0].shape[0])
    (x, handed, state), extras = lax.scan(body, (x, handed, state),
                                          (stacked, index))
    return x, state, extras, handed


@pytest.mark.parametrize("entry", ["loss_fn", "moe_loss_fn", "forward",
                                   "forward_with_cache"])
def test_a_run_whose_halves_name_nothing_is_traced_as_it_was(monkeypatch,
                                                             entry):
    """The trained path names nothing: its jaxpr (and its gradient's)
    is, to the letter, what `layers` made of it before the contract
    reached the mixer, with every leaf a scanned input and no kernel;
    so is the served dense step's off the TPU."""
    cfg, fn = ENTRY_POINTS[entry]
    params, batch = _init(cfg), _batch(cfg)

    def traced():
        run = fn
        if entry.endswith("loss_fn"):
            run = jax.value_and_grad(lambda p, b: fn(p, b)[0])
        return str(jax.make_jaxpr(run)(params, batch))

    now = traced()
    monkeypatch.setattr(decoder, "layers", _layers_as_they_were)
    assert now == traced()
    assert "pallas_call" not in now
