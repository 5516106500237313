"""Component-level timing of the flagship train step on the real chip.

Times each piece with a host fetch of an on-device scalar reduction as
the barrier. Not part of the test suite; run manually to find the MFU
bottleneck.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def _fetch(out):
    """Wait for `out`. Reduce to a scalar on-device first: fetching a
    big array would time the transfer, not the computation."""
    leaf = jax.tree_util.tree_leaves(out)[0]
    float(jnp.sum(leaf.astype(jnp.float32)))


def timeit(fn, *args, iters=5, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    _fetch(out)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        _fetch(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e3


def main():
    from ray_tpu.models import (LlamaConfig, init_params_sharded,
                                init_train_state, loss_fn, make_optimizer,
                                make_train_step)
    from ray_tpu.ops.attention import flash_attention
    from ray_tpu.ops.cross_entropy import softmax_cross_entropy
    from ray_tpu.parallel import MeshConfig, create_mesh

    cfg = LlamaConfig.llama3_1b()
    batch, seq = 4, 2048
    mesh = create_mesh(MeshConfig(data=-1, fsdp=1))
    key = jax.random.PRNGKey(1)

    # -- small isolated kernels first (low memory) ---------------------
    hd = cfg.head_dim
    q = jax.random.normal(key, (batch, seq, cfg.n_heads, hd), jnp.bfloat16)
    k = jax.random.normal(key, (batch, seq, cfg.n_kv_heads, hd), jnp.bfloat16)
    v = jax.random.normal(key, (batch, seq, cfg.n_kv_heads, hd), jnp.bfloat16)
    fa = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    t = timeit(lambda: fa(q, k, v))
    print(f"flash fwd  (1 layer): {t:8.2f} ms  x{cfg.n_layers} = "
          f"{t * cfg.n_layers:.1f}")

    fab = jax.jit(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, causal=True)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    t = timeit(lambda: fab(q, k, v))
    print(f"flash f+b  (1 layer): {t:8.2f} ms  x{cfg.n_layers} = "
          f"{t * cfg.n_layers:.1f}")

    # final projection + CE at bench shapes
    x = jax.random.normal(key, (batch * seq, cfg.dim), jnp.bfloat16)
    w = jax.random.normal(key, (cfg.dim, cfg.vocab_size), jnp.bfloat16)
    lbl = jax.random.randint(key, (batch * seq,), 0, cfg.vocab_size)

    proj = jax.jit(lambda x, w: x @ w)
    t = timeit(lambda: proj(x, w))
    print(f"vocab proj fwd:       {t:8.2f} ms")

    ce = jax.jit(lambda x, w, l: softmax_cross_entropy(x @ w, l).mean())
    t = timeit(lambda: ce(x, w, lbl))
    print(f"proj+CE fwd:          {t:8.2f} ms")

    ceb = jax.jit(jax.grad(
        lambda x, w, l: softmax_cross_entropy(x @ w, l).mean(),
        argnums=(0, 1)))
    t = timeit(lambda: ceb(x, w, lbl))
    print(f"proj+CE fwd+bwd:      {t:8.2f} ms")

    # one transformer layer fwd at bench shapes (no vocab proj)
    from ray_tpu.models.llama import DEFAULT_RULES, _init_layer, layer_fn
    from ray_tpu.ops.rope import rope_frequencies
    lp = _init_layer(cfg, key)
    cos, sin = rope_frequencies(cfg.head_dim, seq, cfg.rope_theta)
    xact = jax.random.normal(key, (batch, seq, cfg.dim), jnp.bfloat16)
    layer_f = jax.jit(lambda x, lp: layer_fn(
        cfg, None, DEFAULT_RULES, cos, sin, x, lp, None))
    t = timeit(lambda: layer_f(xact, lp))
    print(f"layer fwd (1 layer):  {t:8.2f} ms  x{cfg.n_layers} = "
          f"{t * cfg.n_layers:.1f}")

    layer_b = jax.jit(jax.grad(lambda x, lp: layer_fn(
        cfg, None, DEFAULT_RULES, cos, sin, x, lp, None)
        .astype(jnp.float32).sum(), argnums=(0, 1)))
    t = timeit(lambda: layer_b(xact, lp))
    print(f"layer f+b (1 layer):  {t:8.2f} ms  x{cfg.n_layers} = "
          f"{t * cfg.n_layers:.1f}")
    del lp, xact, q, k, v, x, w

    # -- full model ----------------------------------------------------
    params = init_params_sharded(cfg, mesh, jax.random.PRNGKey(0))
    tokens = jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)
    bd = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}

    lf = jax.jit(lambda p, b: loss_fn(p, b, cfg, mesh=mesh)[0])
    fwd = timeit(lambda: lf(params, bd))
    print(f"forward (loss only):  {fwd:8.1f} ms")

    gf = jax.jit(jax.grad(lambda p, b: loss_fn(p, b, cfg, mesh=mesh)[0]))
    bwd = timeit(lambda: gf(params, bd))
    print(f"fwd+bwd (grads):      {bwd:8.1f} ms")
    gf.clear_cache()
    lf.clear_cache()
    jax.clear_caches()

    tx = make_optimizer(3e-4, warmup_steps=0, moment_dtype=jnp.bfloat16)
    state = init_train_state(params, tx)
    del params
    step = make_train_step(
        lambda p, b: loss_fn(p, b, cfg, mesh=mesh), tx, mesh=mesh,
        batch_logical={"tokens": ("batch", "seq"),
                       "targets": ("batch", "seq")})
    # The train step donates `state`, so time it with rebinding (the
    # generic timeit would reuse a donated/deleted buffer).
    state, m = step(state, bd)
    float(m["loss"])
    full = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(5):
            state, m = step(state, bd)
        float(m["loss"])
        full = min(full, (time.perf_counter() - t0) / 5)
    full *= 1e3
    print(f"full step:            {full:8.1f} ms")


if __name__ == "__main__":
    main()
