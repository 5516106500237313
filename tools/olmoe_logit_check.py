"""OLMoE's logits on the chip against the plain reference, at the
published widths: `python tools/olmoe_logit_check.py [seed ...]`.

Outside the benchmark and its timed window (PERF.md, PR 27, has the
readings): for each seed the parameters of
`benchmark/configs/olmoe-1b-7b-0125-train.json` (its own widths and
depth) from the program's initialiser, one seeded sequence of the
model's whole context, `moe_forward`'s logits in bfloat16 against
`benchmark/references/olmoe.py` in float32 under
`jax.default_matmul_precision("highest")`. Two distances: `rms`, the
root mean square of the error over that of the reference's logits,
which is what is held to TOLERANCE, and `max`, the largest error over
the largest |logit|, which a few tokens set: where two experts' router
probabilities lie closer than bfloat16 tells apart, program and
reference choose differently, and that token's logits move by percents.
Beside the program, four that must not pass (and, under `loss_rel`,
the benchmark's own check for the first two: the loss of one step
against the reference's, relative): the weights cut to the
three mantissa bits of float8 e4m3 (the nearest precision below the one
the configuration states), the gates renormalised over the chosen
experts, the gates cut to bfloat16, and seven experts a token instead
of eight. (The roundings are done on the bits: the TPU compiler drops a
convert to a narrower type and back.) Exit code 1 if the program is
over TOLERANCE, or the float8 weights, the renormalised gates or the
seven experts are under it; bfloat16 gates are printed and not judged:
in a program whose activations are bfloat16 they cannot show, and it is
the float32 test on the CPU (`tests/benchmark/test_olmoe.py`) that
fails them. Needs a TPU.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# Of `rms`. The program computes in bfloat16 (float32 router softmax,
# gates, norms and accumulation) and the reference in float32; PERF.md,
# PR 27, lists what the chip read for the program and for each fault,
# and the limit lies between the two groups.
TOLERANCE = 0.015
JUDGED = ("float8 weights", "gates renormalised", "seven experts")


def main(seeds):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness.manifest import load_json, model_adapter, plugin
    from ray_tpu.models import moe

    if jax.default_backend() != "tpu":
        raise SystemExit(f"needs a TPU, found {jax.default_backend()}")
    config = load_json(ROOT, "benchmark", "configs",
                       "olmoe-1b-7b-0125-train.json")
    cfg = model_adapter(config).program_config(config)
    reference = plugin("references", config["reference"])
    hp = reference.hyper(config)
    seq = config["max_position_embeddings"]

    program = jax.jit(lambda p, t, c: moe.moe_forward(p, t, c)[0]
                      .astype(jnp.float32), static_argnums=2)

    @jax.jit
    def want_logits(params, tokens):
        with jax.default_matmul_precision("highest"):
            return reference.forward(params, tokens, hp)[0]

    # The benchmark's own check (`loss_tolerance` of the file): the
    # first step's loss on random targets against the reference's.
    step_loss = jax.jit(lambda p, b: moe.moe_loss_fn(p, b, cfg)[0])

    @jax.jit
    def want_loss(params, batch):
        with jax.default_matmul_precision("highest"):
            return reference.loss(params, batch["tokens"], batch["targets"],
                                  hp)

    def cut(x, bits):
        """Round to nearest at `bits` fewer mantissa bits."""
        whole = {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        u = jax.lax.bitcast_convert_type(x, whole)
        u = (u + whole(1 << (bits - 1))) & ~whole((1 << bits) - 1)
        return jax.lax.bitcast_convert_type(u, x.dtype)

    @jax.jit
    def through_float8(params):  # bfloat16 keeps 7 mantissa bits, e4m3 3
        return jax.tree.map(lambda x: cut(x, 4) if x.ndim > 1 else x,
                            params)

    @jax.jit
    def distance(got, want):
        err = got - want
        per_token = jnp.abs(err).max(-1)
        return {"rms": jnp.sqrt(jnp.mean(err * err) / jnp.mean(want * want)),
                "max": per_token.max() / jnp.abs(want).max(),
                "tokens_over_half_max":
                    (per_token > per_token.max() / 2).sum()}

    top_k = moe.lax.top_k

    def rounded_gates(x, k):
        p, i = top_k(x, k)
        return cut(p, 16), i

    def seven(x, k):
        p, i = top_k(x, k)
        return p.at[..., -1].set(0.0), i

    ok = True
    for seed in seeds:
        key = jax.random.fold_in(jax.random.PRNGKey(seed >> 31),
                                 seed & 0x7FFFFFFF)
        params = jax.jit(lambda k: moe.init_moe_params(cfg, k))(key)
        tokens = jnp.asarray(np.random.default_rng([seed, 0]).integers(
            0, config["vocab_size"], (1, seq + 1), dtype=np.int32))
        batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
        tokens = batch["tokens"]
        want = want_logits(params, tokens)
        loss = float(want_loss(params, batch))
        row = {"seed": seed, "max_logit": float(jnp.abs(want).max()),
               "loss_rel": {
                   "program": abs(float(step_loss(params, batch)) - loss)
                   / loss,
                   "float8 weights": abs(float(step_loss(
                       through_float8(params), batch)) - loss) / loss},
               "program": distance(program(params, tokens, cfg), want),
               "float8 weights": distance(
                   program(through_float8(params), tokens, cfg), want),
               "gates renormalised": distance(program(
                   params, tokens,
                   dataclasses.replace(cfg, norm_topk_prob=True)), want)}
        for name, fault in (("bfloat16 gates", rounded_gates),
                            ("seven experts", seven)):
            moe.lax.top_k = fault
            try:  # a new function, so that the fault is traced
                row[name] = distance(jax.jit(
                    lambda p, t: moe.moe_forward(p, t, cfg)[0]
                    .astype(jnp.float32))(params, tokens), want)
            finally:
                moe.lax.top_k = top_k
        row = jax.tree.map(float, row)
        print(json.dumps(row), flush=True)
        ok = ok and row["program"]["rms"] <= TOLERANCE < min(
            row[name]["rms"] for name in JUDGED)
        del params, want
    print(json.dumps({"tolerance": TOLERANCE, "ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main([int(a) for a in sys.argv[1:]] or [2147483747]))
