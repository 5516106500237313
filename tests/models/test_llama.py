"""Llama model tests: correctness, sharded equivalence, train step."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (
    LlamaConfig,
    TrainState,
    forward,
    init_params,
    init_params_sharded,
    init_train_state,
    loss_fn,
    make_optimizer,
    make_train_step,
    param_logical_axes,
)
from ray_tpu.parallel import MeshConfig, create_mesh


def _batch(cfg, b=2, s=32, seed=0):
    key = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    return {"tokens": tokens, "targets": targets}


def test_forward_shapes_and_finite():
    cfg = LlamaConfig.debug()
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits = forward(params, batch["tokens"], cfg)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_num_params_matches_tree():
    cfg = LlamaConfig.debug()
    params = init_params(cfg, jax.random.PRNGKey(0))
    actual = sum(x.size for x in jax.tree.leaves(params))
    assert actual == cfg.num_params()


def test_param_logical_axes_structure_matches():
    cfg = LlamaConfig.debug()
    params = init_params(cfg, jax.random.PRNGKey(0))
    axes = param_logical_axes(cfg)
    jax.tree.map(
        lambda p, a: None if p.ndim == len(a) else (_ for _ in ()).throw(
            AssertionError(f"{p.shape} vs {a}")),
        params, axes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            y is None or isinstance(y, str) for y in x),
    )


@pytest.mark.parametrize("attention", ["auto", "flash"])
def test_sharded_forward_matches_single_device(attention):
    # "flash" takes the shard_map around the kernel call (batch over
    # data x fsdp, GQA heads over tensor); off the TPU the function under
    # it is the reference, so this checks the partitioning alone.
    cfg = dataclasses.replace(LlamaConfig.debug(), attention=attention)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, b=4)  # shard_map wants batch % (data*fsdp) == 0
    expected = forward(params, batch["tokens"], cfg)

    mesh = create_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    sharded_params = init_params_sharded(cfg, mesh, jax.random.PRNGKey(0))
    got = jax.jit(
        lambda p, t: forward(p, t, cfg, mesh=mesh)
    )(sharded_params, batch["tokens"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


def test_context_parallel_forward_matches():
    cfg = LlamaConfig.debug()
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, b=2, s=64)
    expected = forward(params, batch["tokens"], cfg)

    mesh = create_mesh(MeshConfig(data=2, seq=4))
    sharded = init_params_sharded(cfg, mesh, jax.random.PRNGKey(0))
    cfg_ring = LlamaConfig.debug()
    cfg_ring = cfg_ring.__class__(**{**cfg_ring.__dict__,
                                     "attention": "ring"})
    # Global positions must be provided under context parallelism.
    positions = jnp.broadcast_to(jnp.arange(64), (2, 64))
    got = jax.jit(
        lambda p, t, pos: forward(p, t, cfg_ring, mesh=mesh, positions=pos)
    )(sharded, batch["tokens"], positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


def test_train_step_descends():
    cfg = LlamaConfig.debug()
    mesh = create_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    params = init_params_sharded(cfg, mesh, jax.random.PRNGKey(0))
    tx = make_optimizer(1e-2, warmup_steps=0)
    state = init_train_state(params, tx)
    # Moments start where their parameters are — not on the first
    # device, to be cut up at the first step — and the whole state
    # starts in the layout the step returns it in, or the step would
    # compile a second time for its own output.
    assert jax.tree.map(lambda m: m.sharding, state.opt_state[1].mu) == \
        jax.tree.map(lambda p: p.sharding, params)
    layout = jax.tree.map(lambda x: x.sharding, state)

    step = make_train_step(
        lambda p, b: loss_fn(p, b, cfg, mesh=mesh), tx, mesh=mesh,
        batch_logical={"tokens": ("batch", "seq"),
                       "targets": ("batch", "seq")},
    )
    batch = _batch(cfg, b=4, s=32)
    losses = []
    for _ in range(5):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses
    assert int(state.step) == 5
    assert jax.tree.map(lambda x: x.sharding, state) == layout


def test_positions_shift_changes_logits():
    cfg = LlamaConfig.debug()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = _batch(cfg, b=1, s=16)["tokens"]
    base = forward(params, tokens, cfg)
    shifted = forward(params, tokens, cfg,
                      positions=jnp.arange(16)[None, :] + 5)
    assert not np.allclose(np.asarray(base), np.asarray(shifted))
