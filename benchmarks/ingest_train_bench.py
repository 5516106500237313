"""Ingest→train end-to-end benchmark (reference analog:
`release/air_tests/air_benchmarks/workloads/pytorch_training_e2e.py` —
the BASELINE.md "Dataset → trainer images/s" row).

Dataset (synthetic token blocks) → `iter_jax_batches` (background block
prefetch + async host→device staging one batch ahead) → sharded llama
train step. Reports tokens/s end to end, the data-wait fraction (how
much of wall time the step loop spent BLOCKED on ingest — ~0 means the
prefetch pipeline fully hides data behind compute), and writes a Chrome
trace (`--trace out.json`) where the overlap is visible as near-zero
`data_wait` slices between `train_step` slices.

Usage: python benchmarks/ingest_train_bench.py [--steps 30] [--trace f]
Writes one JSON line to stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--batch", type=int, default=4)
    parser.add_argument("--seq", type=int, default=512)
    parser.add_argument("--blocks", type=int, default=24)
    parser.add_argument("--trace", default=None,
                        help="write a Chrome trace of the loop here")
    args = parser.parse_args()

    import numpy as np

    import jax
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu import data as rt_data
    from ray_tpu.models import (
        LlamaConfig,
        init_params_sharded,
        init_train_state,
        loss_fn,
        make_optimizer,
        make_train_step,
    )
    from ray_tpu._private.compile_cache import enable_persistent_cache
    from ray_tpu.parallel import MeshConfig, create_mesh
    from ray_tpu.util.accelerators import require_tpu

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)

    require_tpu()
    enable_persistent_cache()
    cfg = LlamaConfig.llama3_1b()
    seq = min(args.seq, cfg.max_seq_len)
    mesh = create_mesh(MeshConfig(data=-1))
    params = init_params_sharded(cfg, mesh, jax.random.PRNGKey(0))
    tx = make_optimizer(1e-4, warmup_steps=0)
    state = init_train_state(params, tx)
    step = make_train_step(lambda p, b: loss_fn(p, b, cfg, mesh=mesh),
                           tx, mesh=mesh)

    rows_per_block = max(args.batch * 4, 16)

    def gen(batch):
        i = int(batch["id"][0])
        rng = np.random.RandomState(i)
        return {"tokens": rng.randint(
            0, cfg.vocab_size, (rows_per_block, seq)).astype(np.int32)}

    ds = rt_data.range(args.blocks, parallelism=args.blocks) \
        .map_batches(gen, batch_size=None)

    def epoch_batches():
        while True:  # loop the dataset so --steps sets the budget
            yield from ds.iter_jax_batches(
                batch_size=args.batch, prefetch_batches=2,
                drop_last=True)

    events = []  # chrome trace
    t_origin = time.perf_counter()

    def mark(name, t0, t1):
        events.append({
            "name": name, "ph": "X", "pid": 0, "tid": 0,
            "ts": (t0 - t_origin) * 1e6,
            "dur": (t1 - t0) * 1e6,
        })

    it = epoch_batches()
    # Warmup: first batch + first step (compile).
    batch = next(it)
    tokens = jnp.asarray(np.asarray(batch["tokens"]))
    state, metrics = step(state, {
        "tokens": tokens, "targets": jnp.roll(tokens, -1, 1)})
    float(jax.device_get(metrics["loss"]))  # barrier

    data_wait = 0.0
    t_start = time.perf_counter()
    for i in range(args.steps):
        t0 = time.perf_counter()
        batch = next(it)  # blocks only if ingest lags compute
        t1 = time.perf_counter()
        data_wait += t1 - t0
        mark("data_wait", t0, t1)
        tokens = jnp.asarray(np.asarray(batch["tokens"]))
        state, metrics = step(state, {
            "tokens": tokens, "targets": jnp.roll(tokens, -1, 1)})
        t2 = time.perf_counter()
        mark("dispatch_step", t1, t2)
    loss = float(jax.device_get(metrics["loss"]))  # honest end barrier
    wall = time.perf_counter() - t_start

    tokens_total = args.steps * args.batch * seq
    ray_tpu.shutdown()

    if args.trace:
        with open(args.trace, "w") as f:
            json.dump({"traceEvents": events}, f)

    print(json.dumps({
        "metric": "ingest_train_tokens_per_s",
        "value": round(tokens_total / wall, 1),
        "unit": "tokens/s",
        "detail": {
            "config": "llama-1.24B",
            "steps": args.steps, "batch": args.batch, "seq": seq,
            "data_wait_fraction": round(data_wait / wall, 4),
            "data_wait_ms_per_step": round(
                data_wait / args.steps * 1e3, 2),
            "step_ms": round(wall / args.steps * 1e3, 1),
            "loss": round(loss, 3),
            "pipeline": "Dataset blocks -> prefetch thread -> "
                        "device_put one batch ahead -> train step",
        },
    }))


if __name__ == "__main__":
    main()
