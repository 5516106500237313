"""MoE model tests: routing, expert-parallel equivalence, training."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (
    init_train_state,
    make_optimizer,
    make_train_step,
)
from ray_tpu.models.moe import (
    MoEConfig,
    init_moe_params,
    init_moe_params_sharded,
    moe_forward,
    moe_loss_fn,
)
from ray_tpu.parallel import MeshConfig, create_mesh


def _batch(cfg, b=2, s=16, seed=0):
    key = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    return {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}


def test_moe_forward_finite_and_aux():
    cfg = MoEConfig.debug_moe()
    params = init_moe_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits, aux = moe_forward(params, batch["tokens"], cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    # Balanced-ish routing at init: aux near its floor of 1.0.
    assert 0.9 < float(aux) < 3.0


def test_moe_expert_parallel_matches_single_device():
    cfg = MoEConfig.debug_moe()
    params = init_moe_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    expected, aux0 = moe_forward(params, batch["tokens"], cfg)

    mesh = create_mesh(MeshConfig(data=2, expert=2, tensor=2))
    sharded = init_moe_params_sharded(cfg, mesh, jax.random.PRNGKey(0))
    got, aux1 = jax.jit(
        lambda p, t: moe_forward(p, t, cfg, mesh=mesh)
    )(sharded, batch["tokens"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux1), float(aux0), rtol=1e-4)


def test_moe_train_step_descends():
    cfg = MoEConfig.debug_moe()
    mesh = create_mesh(MeshConfig(data=2, expert=2, tensor=2))
    params = init_moe_params_sharded(cfg, mesh, jax.random.PRNGKey(0))
    tx = make_optimizer(5e-3, warmup_steps=0)
    state = init_train_state(params, tx)
    step = make_train_step(
        lambda p, b: moe_loss_fn(p, b, cfg, mesh=mesh), tx, mesh=mesh,
        batch_logical={"tokens": ("batch", "seq"),
                       "targets": ("batch", "seq")})
    batch = _batch(cfg, b=4, s=16)
    losses = []
    for _ in range(5):
        state, metrics = step(state, batch)
        losses.append(float(metrics["ce_loss"]))
    assert losses[-1] < losses[0], losses


def test_moe_topk_gating_selects_k_experts():
    """Exactly k experts a token, a tie going to the lower index (the
    dense-masked form took every expert at or above the k-th largest
    probability, so three here), gates as configured."""
    from ray_tpu.models.moe import _moe_ffn, _init_moe_layer

    from ray_tpu.parallel.sharding import DEFAULT_RULES

    cfg = MoEConfig.debug_moe()
    lp = _init_moe_layer(cfg, jax.random.PRNGKey(1))
    # Every token's router logits are (3, 1, 1, -3): experts 1 and 2 tie
    # for the second place.
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, cfg.dim))
    x = x.at[..., 0].set(1.0)
    lp["router"] = jnp.zeros_like(lp["router"]).at[0].set(
        jnp.array([3.0, 1.0, 1.0, -3.0]))
    out, aux, counts, share = _moe_ffn(cfg, lp, x, None, DEFAULT_RULES)
    assert out.shape == x.shape
    np.testing.assert_array_equal(counts, [8, 8, 0, 0])
    # Every expert is held: every pair routed is computed here.
    assert {k: int(v) for k, v in share.items()} == {
        "pairs_held": 16, "pairs_routed": 16, "pair_overflows": 0,
        "experts_touched": 2, "experts_held_steps": 4}

    def experts(weights):
        """The weighted sum of whole experts, one token at a time."""
        rows = []
        for t in x[0]:
            ffn = [(jax.nn.silu(t @ lp["we1"][e]) * (t @ lp["we3"][e]))
                   @ lp["we2"][e] for e in range(cfg.n_experts)]
            rows.append(sum(w * f for w, f in zip(weights, ffn)))
        return jnp.stack(rows)[None]

    p = jax.nn.softmax(jnp.array([3.0, 1.0, 1.0, -3.0]))
    renormalised = [p[0] / (p[0] + p[1]), p[1] / (p[0] + p[1]), 0.0, 0.0]
    np.testing.assert_allclose(out, experts(renormalised), rtol=1e-4,
                               atol=1e-6)
    # Gates as the softmax gives them (OLMoE's `norm_topk_prob` false).
    import dataclasses
    plain = dataclasses.replace(cfg, norm_topk_prob=False)
    out, _, counts, _ = _moe_ffn(plain, lp, x, None, DEFAULT_RULES)
    np.testing.assert_array_equal(counts, [8, 8, 0, 0])
    np.testing.assert_allclose(out, experts([p[0], p[1], 0.0, 0.0]),
                               rtol=1e-4, atol=1e-6)


def test_olmoe_preset_and_its_norm_weights_on_a_mesh():
    import dataclasses

    from ray_tpu.models.moe import moe_param_logical_axes

    preset = MoEConfig.olmoe_1b_7b()
    assert (preset.n_experts, preset.n_experts_per_token, preset.hidden_dim,
            preset.head_dim) == (64, 8, 1024, 128)
    assert preset.qk_norm and not preset.norm_topk_prob
    mixtral = MoEConfig.mixtral_8x7b()
    assert mixtral.norm_topk_prob and not mixtral.qk_norm
    # The two norm weights exist, and have axes, only with the norm on.
    cfg = dataclasses.replace(MoEConfig.debug_moe(), qk_norm=True)
    assert "q_norm" not in moe_param_logical_axes(
        MoEConfig.debug_moe())["layers"]
    mesh = create_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    params = init_moe_params_sharded(cfg, mesh, jax.random.PRNGKey(0))
    assert params["layers"]["q_norm"].shape == (2, 64)
    assert params["layers"]["k_norm"].shape == (2, 32)
    batch = _batch(cfg, b=4)
    want, _ = moe_forward(init_moe_params(cfg, jax.random.PRNGKey(0)),
                          batch["tokens"], cfg)
    got, _ = jax.jit(lambda p, t: moe_forward(p, t, cfg, mesh=mesh))(
        params, batch["tokens"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_fused_and_plain_cross_entropy_give_the_same_moe_loss():
    import dataclasses

    cfg = MoEConfig.debug_moe()
    params = init_moe_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    fused, _ = moe_loss_fn(params, batch, cfg)
    plain, _ = moe_loss_fn(params, batch,
                           dataclasses.replace(cfg, fused_ce=False))
    assert float(fused) == pytest.approx(float(plain), rel=1e-5)


def test_grouped_matmuls_take_each_row_to_its_groups_matrix():
    """`_sparse_experts` by hand on rows whose experts are given: an
    empty expert, and experts of very different loads."""
    from ray_tpu.models.moe import _sparse_experts

    t, k, d, f, e = 48, 2, 16, 8, 4
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(keys[0], (t, d))
    we1, we3 = (jax.random.normal(kk, (e, d, f)) for kk in keys[1:3])
    we2 = jax.random.normal(keys[3], (e, f, d))
    gates = jax.random.uniform(keys[4], (t, k))
    # Expert 1 gets nothing, expert 0 every token's first choice.
    top_i = jnp.stack([jnp.zeros(t, jnp.int32),
                       jnp.where(jnp.arange(t) < 40, 2, 3)], 1)
    got = _sparse_experts(x, gates, top_i, we1, we3, we2)
    want = jnp.zeros((t, d))
    for j in range(k):
        w1, w3, w2 = we1[top_i[:, j]], we3[top_i[:, j]], we2[top_i[:, j]]
        hidden = jax.nn.silu(jnp.einsum("td,tdf->tf", x, w1)) \
            * jnp.einsum("td,tdf->tf", x, w3)
        want += gates[:, j, None] * jnp.einsum("tf,tfd->td", hidden, w2)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_step_dispatch_span_carries_the_last_finished_steps_routing():
    from ray_tpu._private import critical_path, flight_recorder

    critical_path.reset()
    flight_recorder.reset()
    cfg = MoEConfig.debug_moe()
    mesh = create_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    params = init_moe_params_sharded(cfg, mesh, jax.random.PRNGKey(0))
    tx = make_optimizer(5e-3, warmup_steps=0)
    state = init_train_state(params, tx)
    step = make_train_step(
        lambda p, b: moe_loss_fn(p, b, cfg, mesh=mesh), tx, mesh=mesh)
    batch = _batch(cfg, b=4, s=16)
    seen = []
    for _ in range(3):
        state, metrics = step(state, batch)
        seen.append(metrics)
        jax.block_until_ready(metrics)
    assert seen[0]["expert_tokens"].shape == (2, 4)
    assert int(seen[0]["expert_tokens"].sum()) == 2 * 4 * 16 * 2
    critical_path.flush()
    spans = [s for s in flight_recorder.local_snapshot()["spans"]
             if s["stage"] == "train.step_dispatch"]
    critical_path.reset()
    flight_recorder.reset()
    assert len(spans) == 3
    # The first dispatch knows no finished step; each later one carries
    # the counts of the step before it.
    assert "attrs" not in spans[0]
    for span, before in zip(spans[1:], seen):
        assert span["attrs"] == {
            "expert_tokens_max": int(
                before["span_attrs"]["expert_tokens_max"]),
            "expert_tokens_mean": 2 * 4 * 16 // 4,
            # A chip holds 16 of the 64 tokens and takes in the 16 of
            # the other chip of its `fsdp` pair.
            "expert_rows_received": 16}


# -- a held share reads its layer's experts in the run's stack ----------------


def _held_run(kind, n_layers=3, seed=0):
    """A run of FFN-only blocks whose experts are a held share: config,
    parameters stacked by layer, activations. Swiglu experts as GLM-5.2
    has them (a shared expert at the full width), relu2 ones as Nemotron
    3 Super (in a latent). The middle layer's router never chooses a
    held expert."""
    import dataclasses

    from ray_tpu.models import moe

    cfg = dataclasses.replace(
        MoEConfig.debug_moe(), n_experts=16, n_experts_per_token=3,
        hidden_dim=24, scoring="sigmoid", selection_bias=True,
        gate_scale=2.5, experts_held=(4, 4), expert_kind=kind,
        shared_hidden_dim=40, latent_dim=32 if kind == "relu2" else 0)

    def layer(key):
        return {"mlp_norm": jnp.ones(cfg.dim, cfg.dtype),
                **moe.expert_init(cfg, jax.random.split(key, 4))}

    k_layers, k_x = jax.random.split(jax.random.PRNGKey(seed))
    stacked = jax.vmap(layer)(jax.random.split(k_layers, n_layers))
    stacked["router_bias"] = stacked["router_bias"].at[1, 4:8].set(-100.0)
    return cfg, stacked, jax.random.normal(k_x, (2, 24, cfg.dim))


def _share_by_hand(cfg, lp, h):
    """A held share's layer, every held expert on every token and the
    gates of the pairs the router did not send there zero."""
    from ray_tpu.models import moe

    _, gates, top_i = moe._route(cfg, lp, h)
    first, count = cfg.experts_held
    z = h @ lp["w_dn"] if cfg.latent_dim else h
    out = jnp.zeros_like(z)
    for e in range(count):
        hidden = z @ lp["we1"][e]
        hidden = (jax.nn.silu(hidden) * (z @ lp["we3"][e]) if "we3" in lp
                  else jnp.square(jax.nn.relu(hidden)))
        gate = jnp.where(top_i == first + e, gates, 0.0).sum(-1)
        out += gate[..., None] * (hidden @ lp["we2"][e])
    if cfg.latent_dim:
        out = out @ lp["w_up"]
    return moe._add_shared_expert(cfg, lp, h, out)


@pytest.mark.parametrize("kind", ["swiglu", "relu2"])
def test_a_row_that_is_nobodys_goes_through_no_expert(kind):
    """`served_ffn` with `live`: a row that is not live chooses no
    expert, so the held share computes and counts nothing for it (it
    keeps what every token gets, the shared expert); the live rows'
    output is what it is without the mask."""
    from ray_tpu.models import moe

    cfg, stacked, h = _held_run(kind, n_layers=1)
    lp = jax.tree.map(lambda x: x[0], stacked)
    live = jnp.arange(h.shape[1])[None, :] % 3 != jnp.arange(2)[:, None]
    masked, counted = moe.served_ffn(cfg, live)(h, lp)
    plain, all_counted = moe.served_ffn(cfg)(h, lp)
    np.testing.assert_allclose(masked[live], plain[live], atol=1e-6)
    np.testing.assert_allclose(
        masked[~live], moe._add_shared_expert(
            cfg, lp, h, jnp.zeros_like(h))[~live], atol=1e-6)
    _, _, top_i = moe._route(cfg, lp, h)
    first, count = cfg.experts_held
    held = (top_i >= first) & (top_i < first + count)
    assert int(counted["pairs_held"]) == int(held[live].sum()) \
        < int(all_counted["pairs_held"]) == int(held.sum())
    assert int(counted["experts_touched"]) == len(
        np.unique(np.asarray(top_i)[np.asarray(held & live[..., None])]))


@pytest.mark.parametrize("products", ["ragged_dot", "kernel"])
@pytest.mark.parametrize("crowded", [False, True], ids=["roomy", "crowded"])
@pytest.mark.parametrize("kind", ["swiglu", "relu2"])
def test_a_run_of_held_layers_reads_each_layers_experts_in_the_stack(
        kind, crowded, products, monkeypatch):
    """`decoder.layers` over three held expert layers, whose expert
    matrices ride whole: every layer's output is that of the layer's own
    slice (by hand, and through `_moe_ffn` given the slice), also where
    the share takes several buffers and where no pair falls on it; with
    the grouped products as they run off the TPU and as the kernel the
    TPU runs, here through the Pallas interpreter."""
    import functools

    from ray_tpu.models import decoder, moe
    from ray_tpu.ops import grouped_matmul
    from ray_tpu.ops.norms import rms_norm_reference

    if products == "kernel":
        monkeypatch.setattr(grouped_matmul, "plan", functools.partial(
            grouped_matmul.plan, interpret=True))
    if crowded:
        monkeypatch.setattr(moe, "_HELD_ROWS_MIN", 4)
        monkeypatch.setattr(moe, "_HELD_ROWS_SLACK", 0)
    cfg, stacked, x = _held_run(kind)
    served = moe.served_ffn(cfg)
    assert served.whole == ("we1", "we3", "we2")
    seen = []

    def ffn(h, lp, stacks):
        # What `layers` hands over: the run's stacks and a layer, and
        # an `lp` without the expert matrices.
        seen.append((sorted(stacks[0]), [w.shape[0]
                                         for w in stacks[0].values()]))
        assert not set(lp) & set(served.whole)
        out, counted = served(h, lp, stacks=stacks)
        return out, {**counted, "out": out}

    ffn.whole = served.whole
    got, _, extras, _ = jax.jit(
        lambda stacked, x: decoder.layers(None, ffn, cfg, None, x, stacked)
    )(stacked, x)
    names = sorted(n for n in served.whole if n in stacked)
    assert seen == [(names, [3] * len(names))]
    for layer in range(3):
        lp = jax.tree.map(lambda leaf: leaf[layer], stacked)
        h = rms_norm_reference(x, lp["mlp_norm"], cfg.norm_eps)
        np.testing.assert_allclose(extras["out"][layer],
                                   _share_by_hand(cfg, lp, h), atol=3e-6)
        out, _, _, counted = moe._moe_ffn(cfg, lp, h, None, None)
        np.testing.assert_allclose(extras["out"][layer], out, atol=1e-6)
        for name, value in counted.items():
            assert int(extras[name][layer]) == int(value), (name, layer)
        x = x + out
    np.testing.assert_allclose(got, x, atol=1e-6)
    held = [int(n) for n in extras["pairs_held"]]
    assert held[1] == 0 < min(held[0], held[2])
    assert int(extras["experts_touched"][1]) == 0
    over = [int(n) for n in extras["pair_overflows"]]
    assert over == ([-(-n // 4) - 1 if n else 0 for n in held] if crowded
                    else [0, 0, 0])


def _subjaxprs(jaxpr):
    """`jaxpr` and every jaxpr inside its equations' parameters."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _subjaxprs(sub)


def _expert_copies(jaxpr, matrix_shapes):
    """Where a program takes one layer's expert matrices out of a stack:
    the scanned inputs of a `scan` whose slice is a matrix's shape, and
    the `dynamic_slice`s that give one."""
    found = []
    for sub in _subjaxprs(jaxpr):
        for eqn in sub.eqns:
            if eqn.primitive.name == "scan":
                skip = eqn.params["num_consts"] + eqn.params["num_carry"]
                found += [("scan", v.aval.shape) for v in eqn.invars[skip:]
                          if v.aval.shape[1:] in matrix_shapes]
            elif eqn.primitive.name in ("dynamic_slice", "gather"):
                found += [(eqn.primitive.name, v.aval.shape)
                          for v in eqn.outvars
                          if v.aval.shape[-3:] in matrix_shapes]
    return found


def _served_engine(family):
    """An engine at debug widths whose model has a run of several held
    expert layers, and the shapes of a layer's expert matrices."""
    import dataclasses

    from ray_tpu.models import glm_dsa, nemotron_h
    from ray_tpu.serve.llm import LLMEngine

    if family == "glm_dsa":
        cfg = dataclasses.replace(
            glm_dsa.GlmDsaConfig.debug_glm(), n_layers=5, experts_held=(2, 4),
            layer_kinds=(("dense", "full"),) + (("sparse", "shared"),) * 3
            + (("sparse", "full"),))
        params = glm_dsa.init_params(cfg, jax.random.PRNGKey(0))
    else:
        cfg = nemotron_h.NemotronHConfig.debug_nemotron()  # MEMEM*E
        params = nemotron_h.init_params(cfg, jax.random.PRNGKey(0))
    runs = [run for run in params["runs"] if "we1" in run]
    assert max(run["we1"].shape[0] for run in runs) > 1
    shapes = {run[name].shape[1:] for run in runs
              for name in ("we1", "we3", "we2") if name in run}
    assert all(s[0] == cfg.n_experts_held for s in shapes)
    return LLMEngine(cfg, params, max_batch_size=2, max_seq_len=32), shapes


@pytest.mark.parametrize("products", ["ragged_dot", "kernel"])
@pytest.mark.parametrize("family", ["glm_dsa", "nemotron_h"])
def test_no_served_program_slices_expert_matrices_out_of_their_stack(
        family, products, monkeypatch):
    """The decode and the prefill program of a model that holds a share
    of the experts: no layer scan has an expert matrix among its scanned
    operands and nothing slices one out of the run's stack, so the
    grouped products read the stack itself (a scanned slice is a copy of
    every held expert's weights, a layer: PERF.md, PR 35), as
    `lax.ragged_dot` off the TPU and as the held path's Pallas kernel on
    it (the test says which backend it is on; nothing is lowered). The
    same reading finds the copies in the program as it was, the FFN
    naming nothing to stay whole."""
    from ray_tpu.models import moe
    from ray_tpu.ops import grouped_matmul

    if products == "kernel":
        monkeypatch.setattr(grouped_matmul, "on_tpu", lambda: True)
    eng, shapes = _served_engine(family)
    n = eng.n_slots
    ints = jnp.zeros(n, jnp.int32)

    def programs():
        return {
            "decode": jax.make_jaxpr(eng._decode_impl)(
                eng.params, eng.cache, ints, ints, jnp.zeros(n), ints,
                jax.random.PRNGKey(0)),
            "prefill": jax.make_jaxpr(
                lambda p, c, t: eng._prefill_impl(p, c, t, 0, 5, 0, 8))(
                eng.params, eng.cache, jnp.zeros((1, 8), jnp.int32))}

    for name, program in programs().items():
        assert _expert_copies(program.jaxpr, shapes) == [], name
        eqns = [eqn for sub in _subjaxprs(program.jaxpr) for eqn in sub.eqns]
        ragged = {eqn.invars[1].aval.shape[0] for eqn in eqns
                  if eqn.primitive.name == "ragged_dot_general"}
        if products == "kernel":
            # The kernel takes the run's stack as it lies, [layers,
            # experts held, K, N], and no other grouped product runs.
            stacks = {v.aval.shape for eqn in eqns
                      if eqn.primitive.name == "pallas_call"
                      for v in eqn.invars if v.aval.ndim == 4}
            assert not ragged and stacks, (name, ragged)
            assert {s[1:] for s in stacks} == shapes, (name, stacks)
            assert max(s[0] for s in stacks) > 1, (name, stacks)
        else:
            # `lax.ragged_dot` takes it as layers x experts groups.
            assert max(ragged) > eng.cfg.n_experts_held, (name, ragged)

    served = moe.served_ffn

    def scanned(cfg):
        ffn = served(cfg)
        return lambda h, lp: ffn(h, lp)

    monkeypatch.setattr(moe, "served_ffn", scanned)
    for name, program in programs().items():
        kinds = {kind for kind, _ in _expert_copies(program.jaxpr, shapes)}
        assert kinds == {"scan"}, name


def test_a_trained_expert_layer_still_takes_its_layers_slice():
    """The trained path is as it was: every leaf of the layers' stack,
    the expert matrices among them, is a scanned input of the forward
    scan and of no other kind (no layer index rides along), and the
    grouped products take one layer's [E, K, N] matrices, in the loss
    and in its gradient."""
    cfg = MoEConfig.debug_moe()
    params = init_moe_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    shapes = {params["layers"][name].shape[1:]
              for name in ("we1", "we3", "we2")}
    program = jax.make_jaxpr(jax.grad(
        lambda p: moe_loss_fn(p, batch, cfg)[0]))(params)
    scans = [eqn for sub in _subjaxprs(program.jaxpr) for eqn in sub.eqns
             if eqn.primitive.name == "scan"]
    forward = scans[0]
    skip = forward.params["num_consts"] + forward.params["num_carry"]
    assert sorted(v.aval.shape for v in forward.invars[skip:]) == sorted(
        leaf.shape for leaf in jax.tree.leaves(params["layers"]))
    assert len(_expert_copies(program.jaxpr, shapes)) >= 3
    # (A weight's gradient is a grouped product of two matrices of
    # rows; the others read expert matrices, as they lie or transposed.)
    read = {eqn.invars[1].aval.shape
            for sub in _subjaxprs(program.jaxpr) for eqn in sub.eqns
            if eqn.primitive.name == "ragged_dot_general"
            and eqn.invars[1].aval.ndim == 3}
    assert read and read <= shapes | {(e, n, k) for e, k, n in shapes}, read


_MESHES = {"fsdp": dict(data=2, fsdp=2, tensor=2),
           "expert": dict(data=2, expert=2, tensor=2),
           "fsdp4": dict(data=2, fsdp=4),
           "one_device_mesh": dict(data=1)}


def _on(place):
    """(mesh, parameters at debug widths) on one device, on one of the
    file's meshes (`fsdp`, `expert`), on four chips that share the
    experts' hidden width, or on a mesh of one device."""
    cfg = MoEConfig.debug_moe()
    if place == "one_device":
        return None, init_moe_params(cfg, jax.random.PRNGKey(0))
    axes = _MESHES[place]
    mesh = create_mesh(MeshConfig(**axes),
                       devices=jax.devices()[:math.prod(axes.values())])
    return mesh, init_moe_params_sharded(cfg, mesh, jax.random.PRNGKey(0))


def _collapsed(params):
    """`params` with a router collapsed as the four-chip cell's is: the
    first feature of every embedding is large, so it leads the stream
    every layer reads, and every router scores it (3, 1, 1, -3): expert
    0 is every token's first choice, the second is between 1 and 2, and
    expert 3 gets no pair."""
    router = params["layers"]["router"]
    score = jnp.array([3.0, 1.0, 1.0, -3.0], router.dtype)
    return {**params,
            "embed": params["embed"].at[:, 0].set(1.0),
            "layers": {**params["layers"],
                       "router": router.at[:, 0].set(score)}}


@pytest.mark.parametrize("place", ["fsdp", "expert", "fsdp4"])
def test_a_mesh_changes_no_number(place):
    """Chips that each hold a slice of every expert's hidden width and
    are sent one another's tokens (`fsdp`, 2 and 4), and chips that
    gather the matrices whole (`expert`, `tensor`), give the single
    device's loss and every gradient leaf, also where one expert gets
    no pair and one gets half of them."""
    cfg = MoEConfig.debug_moe()
    batch = _batch(cfg, b=8)

    def run(place):
        mesh, params = _on(place)
        params = jax.jit(_collapsed, donate_argnums=0)(params)
        return jax.jit(jax.value_and_grad(lambda p: moe_loss_fn(
            p, batch, cfg, mesh=mesh), has_aux=True))(params)

    (want, metrics), want_grads = run("one_device")
    counts = np.asarray(metrics["expert_tokens"])
    assert (counts[:, 0] == 8 * 16).all() and (counts[:, 3] == 0).all()
    assert (counts[:, 1:3] > 0).all()
    (got, on_mesh), got_grads = run(place)
    np.testing.assert_array_equal(on_mesh["expert_tokens"], counts)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    leaves = jax.tree_util.tree_leaves_with_path(want_grads)
    assert len(leaves) == 3 + 10  # ten leaves a layer
    for (path, a), b in zip(leaves, jax.tree.leaves(got_grads)):
        assert float(jnp.abs(a).max()) > 0, path
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-4,
                                   atol=2e-4 * float(jnp.abs(a).max()),
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("place,received", [
    ("one_device", 0), ("one_device_mesh", 0), ("expert", 0),
    ("fsdp", 32), ("fsdp4", 3 * 16)])
def test_the_step_says_what_rows_its_expert_layer_took_in(place, received):
    """`span_attrs["expert_rows_received"]`: n - 1 times a chip's own
    tokens where n chips share the experts' hidden width (128 tokens on
    2 x 2 and on 2 x 4 holders of tokens), 0 where none do. Traced, not
    run: the count is a constant of the program
    (`test_step_dispatch_span_carries_the_last_finished_steps_routing`
    reads it off a step's span)."""
    cfg = MoEConfig.debug_moe()
    mesh, params = _on(place)
    program = jax.make_jaxpr(lambda p, b: moe_loss_fn(
        p, b, cfg, mesh=mesh)[1]["span_attrs"]["expert_rows_received"])(
        params, _batch(cfg, b=8))
    assert program.jaxpr.outvars[0].val == received


def test_on_a_mesh_of_one_device_the_step_holds_no_collective():
    """The train step as it is lowered for a mesh of one device (the
    one-chip training cells build one): the expert layer gathers
    nothing and exchanges nothing. What the lowered text still holds
    are the sums `shard_map`'s own transpose makes over the mesh's
    axes, each over one group of one device, which the TPU's compiler
    drops (`test_moe_compile_tpu.py` reads OLMoE's compiled step: no
    collective of any kind); on the file's `fsdp` mesh the same reading
    finds the expert layer's."""
    cfg = MoEConfig.debug_moe()
    tx = make_optimizer(5e-3, warmup_steps=0)
    ops = (r"(all_gather|all_reduce|reduce_scatter|all_to_all|"
           r"collective_permute|collective_broadcast)")

    def lowered(place):
        mesh, params = _on(place)
        step = make_train_step(
            lambda p, b: moe_loss_fn(p, b, cfg, mesh=mesh), tx, mesh=mesh,
            batch_logical={"tokens": ("batch", "seq"),
                           "targets": ("batch", "seq")})
        return step.lower(init_train_state(params, tx), _batch(cfg, b=4))

    one = lowered("one_device_mesh")
    found = re.findall(ops + r"[^\n]*?replica_groups = (dense<[^>]*> : "
                       r"tensor<\w+>)", one.as_text())
    assert set(found) <= {("all_reduce", "dense<0> : tensor<1x1xi64>")}
    assert set(re.findall(ops, lowered("fsdp").as_text())) >= {
        "all_gather", "reduce_scatter"}


@pytest.mark.parametrize("place", ["one_device", "fsdp"])
def test_remat_changes_no_number(place):
    """What a rematerialised layer keeps (`moe._SAVED`) are the values
    it would compute again: the loss and every gradient leaf are those
    of a layer that keeps everything."""
    import dataclasses

    cfg = MoEConfig.debug_moe()
    mesh, params = _on(place)
    batch = _batch(cfg, b=4)

    def run(cfg):
        return jax.jit(jax.value_and_grad(lambda p: moe_loss_fn(
            p, batch, cfg, mesh=mesh)[0]))(params)

    plain, remat = run(cfg), run(dataclasses.replace(cfg, remat=True))
    assert float(plain[0]) == float(remat[0])
    leaves = jax.tree_util.tree_leaves_with_path(plain[1])
    assert len(leaves) == 3 + 10  # ten leaves a layer
    for (path, a), b in zip(leaves, jax.tree.leaves(remat[1])):
        assert float(jnp.abs(a).max()) > 0, path
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7,
                                   err_msg=jax.tree_util.keystr(path))


def grouped_products(loss, params):
    """How many `ragged_dot_general` equations the program of `loss`'s
    value and gradient holds, wherever they lie."""
    program = jax.make_jaxpr(jax.value_and_grad(loss))(params)
    return sum(eqn.primitive.name == "ragged_dot_general"
               for sub in _subjaxprs(program.jaxpr) for eqn in sub.eqns)


@pytest.mark.parametrize("place", ["one_device", "fsdp"])
@pytest.mark.parametrize("kind,kept,nothing_kept", [
    ("swiglu", 9, 12), ("relu2", 6, 8)])
def test_a_rematerialised_layer_computes_each_grouped_product_once(
        place, kind, kept, nothing_kept, monkeypatch):
    """The layer body's grouped products with `remat` on: 3 forward and
    6 backward for gated experts, 2 and 4 for relu2 ones (`expert_up`
    is absent there), on one device and inside the mesh's `shard_map`
    alike, as where every activation is kept. One more a product means
    that its name fell out of `moe._SAVED`, or off the product, and the
    backward pass computes it again: with no name kept it is all of
    the forward ones."""
    import dataclasses

    from ray_tpu.models import moe

    cfg = dataclasses.replace(MoEConfig.debug_moe(), expert_kind=kind)
    mesh, params = _on(place)
    if kind == "relu2":
        del params["layers"]["we3"]
    batch = _batch(cfg, b=4)

    def products(cfg):
        return grouped_products(
            lambda p: moe_loss_fn(p, batch, cfg, mesh=mesh)[0], params)

    assert products(cfg) == kept
    remat = dataclasses.replace(cfg, remat=True)
    assert products(remat) == kept
    monkeypatch.setattr(moe, "_SAVED", ())
    assert products(remat) == nothing_kept


def test_every_kept_name_is_one_the_layer_sets(monkeypatch):
    """`moe._SAVED` against the `checkpoint_name`s in the traced loss,
    with the flash kernels in (as on a TPU: nothing is lowered): a
    name nobody sets is kept in silence, and its array recomputed."""
    from ray_tpu.models import llama, moe
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(llama, "on_tpu", lambda: True)
    cfg = MoEConfig.debug_moe()
    params = init_moe_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, s=128)
    program = jax.make_jaxpr(jax.grad(
        lambda p: moe_loss_fn(p, batch, cfg)[0]))(params)
    set_names = {eqn.params["name"] for sub in _subjaxprs(program.jaxpr)
                 for eqn in sub.eqns if eqn.primitive.name == "name"}
    assert set(moe._SAVED) <= set_names, set(moe._SAVED) - set_names
