"""MoE model tests: routing, expert-parallel equivalence, training."""

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
            "expert_tokens_mean": 2 * 4 * 16 // 4}
