"""A training cell: `JaxTrainer` drives the sharded step, fed by the
Dataset ingest, as `chip_smoke.train_phase` showed it runs on the chip.

Set-up: the cell's chips, parameters made on the devices in one jitted
call from the seed, the reference's loss on the first batch (before the
optimizer state takes its room), the optimizer state, two steps (the
first compiles). Then the window: steps until `seconds` have passed,
the loss fetched after every one. A traced run measures half the
window untraced, for what is a rate, and then a few traced steps.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.harness import device as hw
from benchmark.harness.manifest import model_adapter, plugin


# What a training cell takes from its family's model adapter.
NEEDS = ("program_config", "with_remat", "init_sharded", "loss")


def prng_key(seed):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed >> 31),
                              seed & 0x7FFFFFFF)


def run(cell, *, seed, seconds, trace_dir, devices, run_dir):
    import ray_tpu
    from ray_tpu import data as rt_data
    from ray_tpu.air import session
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.parallel.mesh import create_mesh
    from ray_tpu.train.jax_trainer import JaxTrainer

    config, mix = cell.config, cell.traffic
    plan = config["train"]
    n = len(devices)
    seq = mix["seq"]
    batch = plan["sequences_per_chip"] * n
    model = model_adapter(config, NEEDS)
    cfg = model.with_remat(model.program_config(config), plan["remat"])
    reference = plugin("references", config["reference"])
    hp = reference.hyper(config)

    phases = hw.Phases()
    phases.mark("imports")

    def seeded_block(ids):
        rng = np.random.default_rng([seed, int(ids["id"][0])])
        tokens = rng.integers(0, config["vocab_size"], (batch, seq + 1),
                              dtype=np.int32)
        return {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}

    dataset = rt_data.range(plan["blocks"], parallelism=plan["blocks"]) \
        .map_batches(seeded_block, batch_size=None)
    scaling = ScalingConfig(
        num_workers=1, use_tpu=True, resources_per_worker={"TPU": n},
        mesh={**plan["mesh"], "fsdp": n})

    def train_loop():
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import (init_train_state, make_optimizer,
                                    make_train_step)
        from ray_tpu.parallel import named_sharding

        phases.mark("trainer start")
        mesh = create_mesh(scaling.mesh_config(), devices=devices)
        params = model.init_sharded(cfg, mesh, prng_key(seed))
        shard = session.get_dataset_shard("train")
        batch_sharding = named_sharding(mesh, "batch", "seq")

        def batches():
            while True:
                yield from shard.iter_jax_batches(
                    batch_size=batch, sharding=batch_sharding,
                    drop_last=True)

        it = batches()
        first = next(it)
        jax.block_until_ready(params)
        phases.mark("parameters and first batch")
        with jax.default_matmul_precision("highest"):
            want = float(jax.jit(
                lambda p, b: reference.loss(p, b["tokens"], b["targets"],
                                            hp))(params, first))
        phases.mark("reference loss")
        tx = make_optimizer(plan["learning_rate"], warmup_steps=0,
                            moment_dtype=jnp.bfloat16)
        state = init_train_state(params, tx)
        del params
        step = make_train_step(
            lambda p, b: model.loss(p, b, cfg, mesh=mesh), tx, mesh=mesh,
            batch_logical={"tokens": ("batch", "seq"),
                           "targets": ("batch", "seq")})
        state, metrics = step(state, first)
        got = float(metrics["loss"])
        phases.mark("first step")
        state, metrics = step(state, next(it))
        float(metrics["loss"])
        phases.mark("second step")

        report = {"reference_loss": want, "first_loss": got,
                  "tokens_per_step": batch * seq}
        untraced = seconds / 2 if trace_dir else seconds
        losses, wait_s = [], 0.0
        t0 = now = time.perf_counter()
        while now - t0 < untraced:
            t_wait = time.perf_counter()
            b = next(it)
            wait_s += time.perf_counter() - t_wait
            state, metrics = step(state, b)
            losses.append(float(metrics["loss"]))  # the step's barrier
            now = time.perf_counter()
        report.update(t0=t0, t1=now, wait_s=wait_s, losses=losses)
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
            t_trace = now = time.perf_counter()
            traced = 0
            while now - t_trace < mix["trace_s"] or traced < 2:
                state, metrics = step(state, next(it))
                losses.append(float(metrics["loss"]))
                traced += 1
                now = time.perf_counter()
            jax.profiler.stop_trace()
            report.update(trace_t0=t_trace, trace_t1=now,
                          traced_steps=traced)
        report["memory"] = [hw.memory(d) for d in mesh.devices.flat]
        session.report({"bench": report})

    if not ray_tpu.is_initialized():  # a CPU rehearsal names its devices
        ray_tpu.init()
    try:
        result = JaxTrainer(train_loop, scaling_config=scaling,
                            datasets={"train": dataset}).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise result.error
    report = result.metrics["bench"]

    losses = report["losses"]
    want, got = report["reference_loss"], report["first_loss"]
    tol = plan["loss_tolerance"]
    cycle = plan["blocks"]  # the same batches come round every `cycle` steps
    checks = {
        "first loss within tolerance of the reference":
            abs(got - want) <= tol * abs(want),
        "every loss finite": bool(np.isfinite(losses).all()),
        "mean loss of the last cycle of batches below the first's":
            bool(len(losses) >= 2 * cycle and
                 np.mean(losses[-cycle:]) < np.mean(losses[:cycle])),
    }
    steps = len(losses) - report.get("traced_steps", 0)
    report.update(
        kind="train", checks=checks, steps=steps,
        attempted=len(losses), failed=int((~np.isfinite(losses)).sum()),
        window=(report["t0"], report["t1"]),
        tokens=steps * report["tokens_per_step"],
        seq=seq, batch=batch,
        log=(f"reference loss {want:.6f}, first step {got:.6f} (rel "
             f"{abs(got - want) / abs(want):.2e}, tolerance {tol}); "
             f"{steps} steps in {report['t1'] - report['t0']:.3f} s, loss "
             f"{np.mean(losses[:cycle]):.4f} -> "
             f"{np.mean(losses[-cycle:]):.4f} (means over a cycle of "
             f"{cycle} batches), waited for data {report['wait_s']:.4f} s; "
             f"set-up: {phases}"))
    return report
