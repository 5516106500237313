"""SmallThinker's logits and gradients on the chip against the plain
reference, at the published widths:
`python tools/smallthinker_logit_check.py [seed ...]`.

Outside the benchmark and its timed window (PERF.md, PR 47, has the
readings): for each seed the parameters of
`benchmark/configs/smallthinker-21b-a3b-train.json` (its own widths,
depth and share of the experts) from the program's initialiser and one
seeded sequence of the model's whole context, 16,384 tokens. (Under
`loss_rel`, printed and not judged here, the benchmark's own check on
a step's batch of the cell's size, that sequence the first of it: the
step's loss against the reference's, relative, for the program and for
the weights cut to float8's mantissa.)

Logits: `smallthinker.forward`'s in bfloat16 against
`benchmark/references/smallthinker.py` in float32 under
`jax.default_matmul_precision("highest")`, by `rms`, the root mean
square of the error over that of the reference's logits, held to
TOLERANCE; `max`, the largest error over the largest |logit|, is
printed and not judged: a few tokens set it, those where two experts'
router logits lie closer than bfloat16 activations tell apart. Beside
the program, the faults that must not pass: the weights cut to the
three mantissa bits of float8 e4m3 (the nearest precision below the one
the configuration states; the rounding is done on the bits, because the
TPU compiler drops a convert to a narrower type and back), a window of
4095 keys, the rotary turn on the full layer, none on the windowed
ones, the router fed the normed stream behind attention, silu in place
of relu, five experts a token, and pairs on held experts dropped: every
held expert keeps the pairs an even load would deal it (6,144) and
drops the rest, what a capacity factor of one does. (One dropped pair
moves one token of 16,384 and cannot show in an rms over bfloat16's
own error; it is the float32 test on the CPU,
`tests/models/test_smallthinker.py`, that fails a single pair.)

A window of 4095 keys is printed there and not judged: a row past the
window loses one key of 4,096, which moves the logits by a hundredth of
what bfloat16 against float32 moves them (the readings are in PERF.md).
What holds the window's far edge on the chip is the comparison one
level down (`edge`): the first windowed layer's attention alone, the
program's mixer (rotary turn, the flash kernel with its window) and
`wo` against the reference's `attention` on the same normed
activations, the output's rows past the window by rms and the gradient
of a fixed random weighting of it by `wq`, `wk` and `wv`, where the
other layers' rounding is not in the way; EDGE_TOLERANCE holds the
program, and a window of 4095 must not pass, output or gradients.

Gradients of the step: at a sequence of GRAD_SEQ tokens, past the
window, the gradient of the training loss (`smallthinker.loss_fn`,
bfloat16, the flash kernels forward and backward, the held share's
buffers) by the first windowed layer's `wq`, `wk` and `wv` and by one
held expert's three matrices in that layer, against `jax.grad` of the
reference's loss, by relative rms, held to GRAD_TOLERANCE, a limit for the
projections and one for the expert's matrices; the weights cut to
float8's mantissa must not pass either; a window of 4095 is printed
(the edge check above is what fails it).

Exit code 1 if the program is over a tolerance or a judged fault under
it. Needs a TPU.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# Of the logits' `rms` and of a gradient's relative rms: the program
# computes in bfloat16 (float32 router, softmax, norms and
# accumulation) and the reference in float32. PERF.md, PR 47, lists
# what the chip read for the program and for each fault; each limit
# lies between the two groups.
TOLERANCE = 0.045
GRAD_TOLERANCE = {"wq": 0.06, "wk": 0.06, "wv": 0.06,
                  "we1": 0.14, "we3": 0.14, "we2": 0.14}
EDGE_TOLERANCE = 0.0078
GRAD_SEQ = 8192
GRAD_EXPERT = 5


def main(seeds):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness.manifest import load_json, model_adapter, plugin
    from ray_tpu.models import decoder, moe
    from ray_tpu.models import smallthinker as st
    from ray_tpu.ops.norms import rms_norm_reference

    if jax.default_backend() != "tpu":
        raise SystemExit(f"needs a TPU, found {jax.default_backend()}")
    config = load_json(ROOT, "benchmark", "configs",
                       "smallthinker-21b-a3b-train.json")
    cfg = model_adapter(config).program_config(config)
    reference = plugin("references", config["reference"])
    hp = reference.hyper(config)
    seq = config["max_position_embeddings"]
    short = dataclasses.replace(cfg, sliding_window=cfg.sliding_window - 1)

    def program(cfg):
        return jax.jit(lambda p, t: st.forward(p, t, cfg)[0]
                       .astype(jnp.float32))

    @jax.jit
    def want_logits(params, tokens):
        with jax.default_matmul_precision("highest"):
            return reference.forward(params, tokens, hp)[0]

    # The benchmark's own check (`loss_tolerance` of the file): a
    # step's loss on random targets against the reference's, relative.
    step_loss = jax.jit(lambda p, b: st.loss_fn(p, b, cfg)[0])

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
                "max": per_token.max() / jnp.abs(want).max()}

    mixer, ffn, held = (st.llama.self_attention, moe._moe_ffn,
                        moe._held_experts_trained)

    def capacity_of_one(cfg, x, gates, top_i, *ws):
        """Every expert keeps the first `even` of its pairs."""
        flat = top_i.reshape(-1)
        even = flat.size // cfg.n_experts
        order = jnp.argsort(flat, stable=True)
        counts = moe._expert_counts(top_i, cfg.n_experts)
        rank = jnp.argsort(order) - (jnp.cumsum(counts) - counts)[flat]
        return held(cfg, x, jnp.where(rank.reshape(gates.shape) < even,
                                      gates, 0.0), top_i, *ws)

    # name -> (config, {(module, attribute): patched}).
    faults = {
        "window of 4095": (short, {}),
        "rotary on the full layer": (cfg, {(st.llama, "self_attention"):
            lambda *a, **kw: mixer(*a, **{**kw, "turned": True})}),
        "no rotary on the windowed": (cfg, {(st.llama, "self_attention"):
            lambda *a, **kw: mixer(*a, **{**kw, "turned": False})}),
        "router behind the norm": (cfg, {(moe, "_moe_ffn"):
            lambda *a, **kw: ffn(*a, **{**kw, "routed": None})}),
        "silu for relu": (dataclasses.replace(cfg, expert_kind="swiglu"),
                          {}),
        "five experts": (dataclasses.replace(cfg, n_experts_per_token=5),
                         {}),
        "held pairs dropped": (cfg, {(moe, "_held_experts_trained"):
                                     capacity_of_one}),
    }

    def faulty(name, params, tokens):
        fault_cfg, patches = faults[name]
        for (module, attribute), patched in patches.items():
            setattr(module, attribute, patched)
        try:  # a new jitted function, so that the fault is traced
            return program(fault_cfg)(params, tokens)
        finally:
            st.llama.self_attention = mixer
            moe._moe_ffn, moe._held_experts_trained = ffn, held

    # -- gradients ---------------------------------------------------------

    def picked(params):
        """The leaves the gradient is taken by: the first windowed
        layer's projections and its experts' matrices."""
        return {name: params["runs"][1][name]
                for name in ("wq", "wk", "wv", "we1", "we3", "we2")}

    def with_picked(params, leaves):
        runs = list(params["runs"])
        runs[1] = {**runs[1], **leaves}
        return {**params, "runs": runs}

    def one_layer_one_expert(grads):
        out = {name: grads[name][0] for name in ("wq", "wk", "wv")}
        out.update({name: grads[name][0, GRAD_EXPERT]
                    for name in ("we1", "we3", "we2")})
        return out

    def program_grads(cfg):
        return jax.jit(lambda params, batch: one_layer_one_expert(jax.grad(
            lambda leaves: st.loss_fn(with_picked(params, leaves), batch,
                                      cfg)[0])(picked(params))))

    @jax.jit
    def want_grads(params, batch):
        with jax.default_matmul_precision("highest"):
            return one_layer_one_expert(jax.grad(
                lambda leaves: reference.loss(
                    with_picked(params, leaves), batch["tokens"],
                    batch["targets"], hp))(picked(params)))

    @jax.jit
    def relative(got, want):
        return jax.tree.map(
            lambda a, b: jnp.sqrt(
                jnp.sum(jnp.square(a.astype(jnp.float32)
                                   - b.astype(jnp.float32)))
                / jnp.sum(jnp.square(b.astype(jnp.float32)))), got, want)

    # -- the window's far edge, one layer's attention alone ----------------

    def edge_inputs(params, tokens):
        """The first windowed layer's leaves and the normed embeddings
        of the sequence, the activations both sides are handed."""
        lp = jax.tree.map(lambda leaf: leaf[0], params["runs"][1])
        h = rms_norm_reference(params["embed"][tokens], lp["attn_norm"],
                               cfg.norm_eps)
        weight = jax.random.normal(jax.random.PRNGKey(7), h.shape[1:])
        return lp, h, weight

    def edge_program(cfg):
        mixer = st.llama.self_attention(cfg, window=cfg.sliding_window)

        def out(qkv, lp, h):
            attn, _, _ = mixer(h, {**lp, **qkv}, decoder.rope_tables(cfg),
                               None, None)
            return jnp.einsum("bshk,hkd->bsd", attn.astype(cfg.dtype),
                              lp["wo"])[0].astype(jnp.float32)

        return jax.jit(lambda lp, h, weight: jax.value_and_grad(
            lambda qkv: (out(qkv, lp, h) * weight).sum(),
            has_aux=False)({n: lp[n] for n in ("wq", "wk", "wv")})
            + (out({}, lp, h),))

    @jax.jit
    def edge_wanted(lp, h, weight):
        def out(qkv):
            return reference.attention(h[0].astype(jnp.float32),
                                       {**lp, **qkv}, hp, True, True)

        with jax.default_matmul_precision("highest"):
            grads = jax.grad(lambda qkv: (out(qkv) * weight).sum())(
                {n: lp[n] for n in ("wq", "wk", "wv")})
            return grads, out({})

    def edge(cfg, lp, h, weight, wanted):
        _, grads, got = edge_program(cfg)(lp, h, weight)
        want_grads, want = wanted
        past = slice(cfg.sliding_window, None)  # the rows a window cuts
        return {"output": distance(got[past], want[past])["rms"],
                **relative(grads, want_grads)}

    ok = True
    for seed in seeds:
        key = jax.random.fold_in(jax.random.PRNGKey(seed >> 31),
                                 seed & 0x7FFFFFFF)
        params = jax.jit(lambda k: st.init_params(cfg, k))(key)
        # A step's batch as the cell has it; its first sequence for the
        # logits and the gradients.
        step = jnp.asarray(np.random.default_rng([seed, 0]).integers(
            0, config["vocab_size"],
            (config["train"]["sequences_per_chip"], seq + 1),
            dtype=np.int32))
        whole = {"tokens": step[:, :-1], "targets": step[:, 1:]}
        loss = float(want_loss(params, whole))
        tokens = step[:1]
        inputs = tokens[:, :-1]
        want = want_logits(params, inputs)
        row = {"seed": seed, "max_logit": float(jnp.abs(want).max()),
               "loss_rel": {
                   "program": abs(float(step_loss(params, whole)) - loss)
                   / loss,
                   "float8 weights": abs(float(step_loss(
                       through_float8(params), whole)) - loss) / loss},
               "program": distance(program(cfg)(params, inputs), want),
               "float8 weights": distance(
                   program(cfg)(through_float8(params), inputs), want)}
        for name in faults:
            row[name] = distance(faulty(name, params, inputs), want)
        row = jax.tree.map(float, row)
        print(json.dumps(row), flush=True)
        ok = ok and row["program"]["rms"] <= TOLERANCE < min(
            row[name]["rms"] for name in ("float8 weights", *faults)
            if name != "window of 4095")
        del want

        lp, h, weight = edge_inputs(params, inputs)
        wanted = edge_wanted(lp, h, weight)
        edges = jax.tree.map(float, {
            "seed": seed, "edge_rows_past": cfg.sliding_window,
            "program": edge(cfg, lp, h, weight, wanted),
            "window of 4095": edge(short, lp, h, weight, wanted)})
        print(json.dumps(edges), flush=True)
        ok = ok and max(edges["program"].values()) <= EDGE_TOLERANCE \
            < min(edges["window of 4095"].values())
        del wanted

        batch = {"tokens": tokens[:, :GRAD_SEQ],
                 "targets": tokens[:, 1:GRAD_SEQ + 1]}
        wanted = want_grads(params, batch)
        grads = {"seed": seed, "seq": GRAD_SEQ,
                 "program": relative(program_grads(cfg)(params, batch),
                                     wanted),
                 "float8 weights": relative(
                     program_grads(cfg)(through_float8(params), batch),
                     wanted),
                 "window of 4095": relative(
                     program_grads(short)(params, batch), wanted)}
        grads = jax.tree.map(float, grads)
        print(json.dumps(grads), flush=True)
        ok = ok and all(
            grads["program"][name] <= limit < grads["float8 weights"][name]
            for name, limit in GRAD_TOLERANCE.items())
        del params, wanted
    print(json.dumps({"tolerance": TOLERANCE,
                      "grad_tolerance": GRAD_TOLERANCE,
                      "edge_tolerance": EDGE_TOLERANCE, "ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main([int(a) for a in sys.argv[1:]] or [2147483747]))
