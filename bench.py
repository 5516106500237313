"""Llama pretrain step throughput, tokens/sec/chip, on the local TPU.

Prints exactly one JSON line and fails without a chip. On a single v5e
chip (16G HBM) the largest Llama-3-family config that fits a full AdamW
train step is ~1B with bf16 optimizer moments; multi-chip runs shard with
the same code via MeshConfig (fsdp/tensor/seq axes).
"""

from __future__ import annotations

import dataclasses
import json
import time


def _measure_llama_train_step():
    import jax
    import jax.numpy as jnp

    from ray_tpu._private.compile_cache import enable_persistent_cache
    from ray_tpu.models import (
        LlamaConfig,
        init_params_sharded,
        init_train_state,
        loss_fn,
        make_optimizer,
        make_train_step,
    )
    from ray_tpu.parallel import MeshConfig, create_mesh
    from ray_tpu.util.accelerators import chip_spec, require_tpu

    devices = require_tpu()
    n = len(devices)
    enable_persistent_cache()

    # remat="gate" saves the silu(w1) MLP activation across the remat
    # boundary (the largest recompute the HBM budget allows next to
    # AdamW bf16 moments); fused CE (cfg default) keeps the [tokens,
    # vocab] logits unmaterialized.
    cfg = dataclasses.replace(LlamaConfig.llama3_1b(), remat="gate")
    batch, seq = 4, 2048
    steps = 10

    # One chip → trivial mesh; more chips → fsdp-shard the params.
    mesh = create_mesh(MeshConfig(data=-1, fsdp=min(n, 4) if n > 1 else 1))

    params = init_params_sharded(cfg, mesh, jax.random.PRNGKey(0))
    tx = make_optimizer(3e-4, warmup_steps=0, moment_dtype=jnp.bfloat16)
    state = init_train_state(params, tx)
    step = make_train_step(
        lambda p, b: loss_fn(p, b, cfg, mesh=mesh), tx, mesh=mesh,
        batch_logical={"tokens": ("batch", "seq"),
                       "targets": ("batch", "seq")},
    )

    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)
    batch_data = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}

    # Warmup (compile) then timed windows, best of 3. Each window ends
    # with a host fetch of the loss, which waits for every step in it.
    state, metrics = step(state, batch_data)
    float(metrics["loss"])
    dt = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, batch_data)
        float(metrics["loss"])
        dt = min(dt, (time.perf_counter() - t0) / steps)

    per_chip = batch * seq / dt / n
    flops_per_token = 6 * cfg.num_params() + 12 * cfg.n_layers * cfg.dim * seq
    return {
        "config": f"llama-{cfg.num_params() / 1e9:.2f}B",
        "value": per_chip,
        "mfu": per_chip * flops_per_token / chip_spec().peak_bf16_flops,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": n},
        "batch": batch,
        "seq": seq,
        "n_chips": n,
        "step_ms": dt * 1e3,
    }


def main():
    result = _measure_llama_train_step()
    print(json.dumps({
        "metric": f"train_tokens_per_sec_per_chip[{result['config']}]",
        "value": round(result["value"], 1),
        "unit": "tokens/s/chip",
        "detail": {k: (round(v, 4) if isinstance(v, float) else v)
                   for k, v in result.items() if k != "value"},
    }))


if __name__ == "__main__":
    main()
