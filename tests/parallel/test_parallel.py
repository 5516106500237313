"""Parallel-layer tests on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.parallel import (
    DEFAULT_RULES,
    MeshConfig,
    create_mesh,
    logical_to_mesh_axes,
    named_sharding,
    pipeline_apply,
    ring_attention,
    shard_pytree,
    ulysses_attention,
)
from ray_tpu.parallel.ring_attention import reference_attention
from jax.sharding import PartitionSpec as P


def test_mesh_config_auto_fill():
    cfg = MeshConfig(data=-1, tensor=2)
    assert cfg.shape(8) == (4, 1, 1, 1, 1, 2)
    with pytest.raises(ValueError):
        MeshConfig(data=3, tensor=2).shape(8)


def test_create_mesh_axes():
    mesh = create_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    assert mesh.shape == {"data": 2, "fsdp": 2, "expert": 1, "pipe": 1,
                          "seq": 1, "tensor": 2}


def test_logical_rules():
    assert logical_to_mesh_axes(("batch", "seq", "embed")) == P(
        ("data", "fsdp"), "seq", "fsdp")
    assert logical_to_mesh_axes((None, "mlp")) == P(None, "tensor")


def test_shard_pytree():
    mesh = create_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    params = {"w": np.ones((8, 16), np.float32), "b": np.zeros(16, np.float32)}
    logical = {"w": ("embed", "mlp"), "b": ("mlp",)}
    sharded = shard_pytree(params, mesh, logical)
    assert sharded["w"].sharding.spec == P("fsdp", "tensor")
    np.testing.assert_allclose(np.asarray(sharded["w"]), params["w"])


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(causal):
    mesh = create_mesh(MeshConfig(data=1, seq=4, tensor=2))
    b, s, h, d = 2, 32, 4, 16
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, h, d), jnp.float32)
    expected = reference_attention(q, k, v, causal=causal)
    got = ring_attention(q, k, v, mesh=mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_matches_reference(causal):
    mesh = create_mesh(MeshConfig(data=2, seq=4))
    b, s, h, d = 2, 32, 4, 16
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, h, d), jnp.float32)
    expected = reference_attention(q, k, v, causal=causal)
    got = ulysses_attention(q, k, v, mesh=mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_jit_grad():
    mesh = create_mesh(MeshConfig(data=1, seq=8))

    def loss(q, k, v):
        return ring_attention(q, k, v, mesh=mesh).sum()

    b, s, h, d = 1, 16, 2, 8
    q = jnp.ones((b, s, h, d)) * 0.1
    k = jnp.ones((b, s, h, d)) * 0.2
    v = jnp.ones((b, s, h, d)) * 0.3
    g = jax.jit(jax.grad(loss))(q, k, v)
    assert g.shape == q.shape
    assert bool(jnp.all(jnp.isfinite(g)))


def test_pipeline_matches_sequential():
    mesh = create_mesh(MeshConfig(data=2, pipe=4))
    n_stages, n_mb, mb, dim = 4, 8, 2, 16
    key = jax.random.PRNGKey(2)
    ws = jax.random.normal(key, (n_stages, dim, dim)) / np.sqrt(dim)
    x = jax.random.normal(jax.random.PRNGKey(3), (n_mb, mb, dim))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    got = pipeline_apply(stage_fn, ws, x, mesh=mesh)

    expected = x
    for i in range(n_stages):
        expected = jax.vmap(lambda h: stage_fn(ws[i], h))(expected)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-6)


def test_collectives_roundtrip():
    from ray_tpu.parallel import collectives as col

    mesh = create_mesh(MeshConfig(data=8))

    def body(x):
        s = col.allreduce(x, "data")
        g = col.allgather(x, "data")
        b = col.broadcast(x, "data", root=3)
        return s, g, b

    x = jnp.arange(8.0).reshape(8, 1)
    fn = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                   out_specs=(P("data"), P("data"), P("data")),
                   check_vma=False)
    s, g, b = fn(x)
    np.testing.assert_allclose(np.asarray(s).ravel(), [28.0] * 8)
    np.testing.assert_allclose(np.asarray(g).ravel(),
                               np.tile(np.arange(8.0), 8))
    np.testing.assert_allclose(np.asarray(b).ravel(), [3.0] * 8)
