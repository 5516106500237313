"""A served model's path through the cache against its plain reference,
with the faults that must not pass:
`python tools/glm_logit_check.py [--config NAME] [--weights
benchmark|plain] [--hit] [seed ...]`. NAME is a configuration under
`benchmark/configs/`, `glm-5.2-serve` unless given; the file keeps the
name it had when GLM-5.2 was the one model it knew, which
`glm-5.2-serve.json` cites. `FAMILIES` has, by the configuration's
family, its faults, the faults a set of weights cannot show and the
program's own initialiser; below, GLM-5.2's at length, then
`nemotron_faults` for `nemotron-3-super-serve`, `cohere_faults` for
`command-a-plus-serve`, `olmo_faults` for `olmo-hybrid-7b-serve`,
`lfm2_faults` for `lfm2-8b-a1b-serve` and `kimi_faults` for
`kimi-linear-48b-a3b-serve`.

Outside the benchmark and its timed window (PERF.md, PR 32, has the
readings). For each seed, what `benchmark/runners/serve.py`'s
`check_against_reference` does for `benchmark/configs/glm-5.2-serve.json`
(the shallow copy at the published widths, one row a slot, prefill then
decode with the rows at their own positions, contexts past
`index_topk`), with the reference computed once and then, beside the
program, each of `FAULTS`: the weights cut to float8 e4m3's three
mantissa bits (the nearest precision below the one the file states; on
the bits, because the TPU compiler drops a convert to a narrower type
and back), every key attended (no selection), 1024 keys, a `shared`
layer selecting anew (run as a `full` layer with the indexer of the
`full` layer above it), softmax scores in place of sigmoid, the bias
weighing in the gates, the gate scale left out, the shared expert left
out, the routed pairs dropped. A position's error is its largest logit
error over the largest |reference| logit; of each variant the line
gives the largest over all positions (what the runner holds under
`logit_tolerance`), the median, the 99th and the 99.9th percentile and
`over`, the share of positions over `logit_tolerance`.

`--weights benchmark` (the default) draws the weights as the benchmark
does (`benchmark/models/glm_dsa.py` `init`: the routed experts'
down-projection `ROUTED_OUT_SCALE` of the program's and the router's
selection bias `ROUTER_BIAS_SCALE` of it), `--weights plain` as the
program's initialiser does. At the plain weights a router that
chooses another expert than the float32 reference (the 8th and 9th of
256 scores closer than bfloat16 resolves) moves one position in a
hundred by a tenth of the largest logit and more, in the program and
in every fault alike, so the largest error tells nothing there. The
verdict: the program keeps every limit of the file's
`tool_checks[weights]` (statistics by name) and, at the benchmark's
weights, the runner's own (`max` within `logit_tolerance`); every fault
breaks one of them, but for those in `UNSEEN[weights]`, which the other
set of weights shows.

With `--hit`, after the first seed, the prefix cache's path. `hit
against miss`: the last row's logits of a prompt prefilled from its
last whole block on (what a hit runs, over the rows a miss wrote)
against those of the whole prefill. `engine hit`: an `LLMEngine` over
the same shallow copy asked the same prompt twice, so that the second
answer goes through the read-back's payloads and the copy-in, leaf by
leaf: how many tokens it matched, whether the two answers are the same
tokens (they need not be: the two prefills are two compiled programs),
and the hit's tokens against the reference as the runner holds served
tokens (`served_token_margin`).

Exit code 1 if a verdict fails. `tests/models/test_glm_dsa.py` runs the
same faults at debug widths on the CPU in float32; this needs a TPU
(`--rehearse` runs it at the adapter's debug widths wherever it is, for
the control flow alone).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def cut(x, bits):
    """Round to nearest at `bits` fewer mantissa bits."""
    import jax
    import jax.numpy as jnp

    whole = {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
    u = jax.lax.bitcast_convert_type(x, whole)
    u = (u + whole(1 << (bits - 1))) & ~whole((1 << bits) - 1)
    return jax.lax.bitcast_convert_type(u, x.dtype)


def _with_cfg(forward, **changes):
    """`forward` under a config with `changes`."""
    def served(params, tokens, cfg, cache, start_pos):
        return forward(params, tokens, dataclasses.replace(cfg, **changes),
                       cache, start_pos)
    return served


def _patched(forward, module, name, other):
    """`forward` while `module.name` is `other(what it was)`."""
    def served(params, tokens, cfg, cache, start_pos):
        real = getattr(module, name)
        setattr(module, name, other(real))
        try:
            return forward(params, tokens, cfg, cache, start_pos)
        finally:
            setattr(module, name, real)
    return served


def _with_leaf(forward, name, change):
    """`forward` with the leaf `name` of every run that has one made
    `change(leaf)`."""
    def served(params, tokens, cfg, cache, start_pos):
        runs = [{**run, name: change(run[name])} if name in run else run
                for run in params["runs"]]
        return forward({**params, "runs": runs}, tokens, cfg, cache,
                       start_pos)
    return served


def _gate_before_norm(gate):
    """A `_gated_norm` that gates first and norms what is left."""
    def gated_norm(cfg, o, z, weight):
        import jax
        import jax.numpy as jnp

        o = o.astype(jnp.float32) * gate(z.astype(jnp.float32))
        return o * jax.lax.rsqrt(
            jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps) \
            * weight.astype(jnp.float32)
    return gated_norm


def _state_in_bfloat16(forward, state_leaves):
    """`forward` with every float32 state leaf of the cache it returns
    (`state_leaves(cache)`) rounded to bfloat16's mantissa."""
    def served(params, tokens, cfg, cache, start_pos):
        import jax
        import jax.numpy as jnp

        logits, cache = forward(params, tokens, cfg, cache, start_pos)
        return logits, jax.tree.map(
            lambda x, is_state: cut(x, 16)
            if is_state and x.dtype == jnp.float32 else x, cache,
            state_leaves(cache))
    return served


def _to_the_end(real):
    """A `forward_with_cache` whose state is left as after the call's
    last token, padding and all: `at` is dropped."""
    def forward_with_cache(params, tokens, cfg, cache, start_pos, at=None,
                           **rest):
        return real(params, tokens, cfg, cache, start_pos, **rest)
    return forward_with_cache


def faults(forward, init_cache):
    """{name: served}: `forward` (the program's cached forward pass,
    `(params, tokens, cfg, cache, start_pos)`) with one fault each, as
    the serving runner's check calls it (`served(params, tokens, cfg=,
    cache=, start_pos=)`)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import moe

    with_cfg = functools.partial(_with_cfg, forward)

    def lower_precision(params, tokens, cfg, cache, start_pos):
        # bfloat16 keeps 7 mantissa bits and float32 23, e4m3 keeps 3.
        bits = {2: 4, 4: 20}
        params = jax.tree.map(
            lambda x: cut(x, bits[x.dtype.itemsize]) if x.ndim > 1 else x,
            params)
        return forward(params, tokens, cfg, cache, start_pos)

    def shared_selects_anew(params, tokens, cfg, cache, start_pos):
        """Every `shared` layer becomes a `full` one with the indexer
        of the last layer (a `full` one). Its cache is made here, at the
        prefill, because it has leaves the program's lacks."""
        kinds = tuple((ffn, "full") for ffn, _ in cfg.kinds)
        faulty = dataclasses.replace(cfg, layer_kinds=kinds)
        last = params["runs"][-1]
        indexer = {k: last[k][-1:] for k in
                   ("wiq", "wik", "ik_norm", "ik_bias", "wiw")}
        layers = []
        for run in params["runs"]:
            for i in range(run["wqa"].shape[0]):
                layer = jax.tree.map(lambda x: x[i:i + 1], run)
                layers.append({**indexer, **layer})
        runs, at = [], 0
        for _, n in faulty.runs():
            runs.append(jax.tree.map(lambda *xs: jnp.concatenate(xs),
                                     *layers[at:at + n]))
            at += n
        if tokens.shape[1] > 1:
            cache = init_cache(faulty, tokens.shape[0],
                               cache["runs"][0]["latent"].shape[2])
        return forward({**params, "runs": runs}, tokens, faulty, cache,
                       start_pos)

    def bias_in_the_gates(params, tokens, cfg, cache, start_pos):
        route = moe._route

        def biased(cfg, lp, x):
            fair = dataclasses.replace(cfg, norm_topk_prob=False,
                                       gate_scale=1.0)
            probs, gates, top_i = route(fair, lp, x)
            gates = gates + jnp.take_along_axis(
                jnp.broadcast_to(lp["router_bias"], probs.shape), top_i, -1)
            if cfg.norm_topk_prob:
                gates = gates / gates.sum(-1, keepdims=True)
            return probs, gates * cfg.gate_scale, top_i

        moe._route = biased
        try:
            return forward(params, tokens, cfg, cache, start_pos)
        finally:
            moe._route = route

    def routed_pairs_dropped(params, tokens, cfg, cache, start_pos):
        held = moe._held_experts

        def none(*args):
            out, *counted = held(*args)
            return jnp.zeros_like(out), *counted

        moe._held_experts = none
        try:
            return forward(params, tokens, cfg, cache, start_pos)
        finally:
            moe._held_experts = held

    return {
        "lower precision": lower_precision,
        "every key": with_cfg(index_topk=1 << 30),
        "half the keys": lambda p, t, cfg, cache, start_pos: forward(
            p, t, dataclasses.replace(cfg, index_topk=cfg.index_topk // 2),
            cache, start_pos),
        "shared selects anew": shared_selects_anew,
        "softmax scores": with_cfg(scoring="softmax"),
        "bias in the gates": bias_in_the_gates,
        "no gate scale": with_cfg(gate_scale=1.0),
        "no shared expert": with_cfg(shared_hidden_dim=0),
        "routed pairs dropped": routed_pairs_dropped,
    }


def nemotron_faults(forward, init_cache):
    """{name: served} for the family `nemotron_h`, as `faults` for
    GLM-5.2: the weights cut to float8 e4m3's mantissa and the
    recurrent state rounded to bfloat16's after every call (the two
    lower precisions), a term of the Mamba-2 layer left out (D, dt's
    bias, the convolution's bias), the group norm before the gate, relu
    in place of relu^2, the gate scale left out, the bias weighing in
    the gates, the latent's down-projection replaced by a cut of the
    first `latent_dim` channels, and a prefill whose bucket padding
    enters the state."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import mamba2, moe, nemotron_h

    glm = faults(forward, init_cache)

    with_leaf = functools.partial(_with_leaf, forward)

    patched = functools.partial(_patched, forward)

    def norm_first(real):
        def gated_norm(cfg, y, z, weight):
            b, t, h, p = y.shape
            groups = y.astype(jnp.float32).reshape(b, t, cfg.ssm_groups, -1)
            groups = groups * jax.lax.rsqrt(
                jnp.mean(groups * groups, -1, keepdims=True) + cfg.norm_eps)
            return groups.reshape(b, t, h * p) * weight.astype(jnp.float32) \
                * jax.nn.silu(z.astype(jnp.float32)).reshape(b, t, h * p)
        return gated_norm

    def cut_to_the_latent(w_dn):
        eye = jnp.eye(w_dn.shape[-2], w_dn.shape[-1], dtype=w_dn.dtype)
        return jnp.broadcast_to(eye, w_dn.shape)

    return {
        "lower precision": glm["lower precision"],
        "state in bfloat16": _state_in_bfloat16(
            forward, nemotron_h.state_leaves),
        "no D term": with_leaf("D", jnp.zeros_like),
        "no dt bias": with_leaf("dt_bias", jnp.zeros_like),
        "no conv bias": with_leaf("conv_b", jnp.zeros_like),
        "norm before the gate": patched(mamba2, "_gated_norm", norm_first),
        "relu for relu2": patched(moe, "_relu2",
                                  lambda real: jax.nn.relu),
        "no gate scale": glm["no gate scale"],
        "bias in the gates": glm["bias in the gates"],
        "no latent projection": with_leaf("w_dn", cut_to_the_latent),
        "pad absorbed": patched(nemotron_h, "forward_with_cache",
                                _to_the_end),
    }


def cohere_faults(forward, init_cache):
    """{name: served} for the family `cohere2_moe`, as `faults` for
    GLM-5.2: the weights cut to float8 e4m3's mantissa, the window
    ignored on the sliding layers (a prefill's rows attend every key
    before them), rotary positions on the full layer too, the shared
    experts summed and not averaged, a sequential block in place of the
    parallel one (the expert layer behind a norm of its own, after
    attention's residual), RMSNorm in place of LayerNorm, the chosen
    gates left as the sigmoid gave them, and a prefill whose bucket
    padding enters the rings."""
    from ray_tpu.models import cohere2_moe

    glm = faults(forward, init_cache)

    with_cfg = functools.partial(_with_cfg, forward)

    patched = functools.partial(_patched, forward, cohere2_moe)

    def sequential_block(params, tokens, cfg, cache, start_pos):
        runs = [{**run, "mlp_norm": run["attn_norm"]}
                for run in params["runs"]]
        return forward({**params, "runs": runs}, tokens,
                       dataclasses.replace(cfg, parallel_block=False),
                       cache, start_pos)

    def to_the_end(real):
        """The rings are left as after the call's last token, padding
        and all."""
        def forward_with_cache(params, tokens, cfg, cache, start_pos,
                               at=None, keep=None):
            return real(params, tokens, cfg, cache, start_pos, keep=keep)
        return forward_with_cache

    return {
        "lower precision": glm["lower precision"],
        "window ignored": with_cfg(sliding_window=1 << 30),
        "rope on the full layer": patched(
            "_ROTATED", lambda real: ("sliding", "full")),
        "shared experts summed": with_cfg(shared_combination="sum"),
        "sequential block": sequential_block,
        "rms norm": with_cfg(norm_kind="rms"),
        "gates not renormalised": with_cfg(norm_topk_prob=False),
        "pad enters the ring": patched("forward_with_cache", to_the_end),
    }


def olmo_faults(forward, init_cache):
    """{name: served} for the family `olmo_hybrid`, as `faults` for
    GLM-5.2: the weights cut to float8 e4m3's mantissa and the delta
    state rounded to bfloat16's after every call (the two lower
    precisions), beta without its factor 2, the gate before the output
    norm, k not normalised, q without 1 / sqrt(dk), no decay (gamma =
    1), the full layer's q and k norm left out, the block's norms on a
    half's input and not its output, and a prefill whose bucket padding
    enters the state and the carries."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gated_delta, olmo_hybrid

    with_cfg = functools.partial(_with_cfg, forward)
    patched = functools.partial(_patched, forward)

    def no_decay(params, tokens, cfg, cache, start_pos):
        runs = [{**run, "A_log": jnp.full_like(run["A_log"], -jnp.inf)}
                if "A_log" in run else run for run in params["runs"]]
        return forward({**params, "runs": runs}, tokens, cfg, cache,
                       start_pos)

    return {
        "lower precision": faults(forward, init_cache)["lower precision"],
        "state in bfloat16": _state_in_bfloat16(
            forward, olmo_hybrid.state_leaves),
        "beta without its 2": with_cfg(allow_neg_eigval=False),
        "gate before the norm": patched(
            gated_delta, "_gated_norm",
            lambda real: _gate_before_norm(jax.nn.silu)),
        "k not normalised": patched(
            gated_delta, "_keys",
            lambda real: lambda k: k.astype(jnp.float32)),
        "q without its scale": patched(
            gated_delta, "_queries",
            lambda real: gated_delta._l2_normalise),
        "no decay": no_decay,
        "no q and k norm": with_cfg(qk_norm=False),
        "norm on the input": with_cfg(norm_placement="input"),
        "pad absorbed": patched(olmo_hybrid, "forward_with_cache",
                                _to_the_end),
    }


def lfm2_faults(forward, init_cache):
    """{name: served} for the family `lfm2_moe`, as `faults` for
    GLM-5.2: the weights cut to float8 e4m3's mantissa, `silu` left in
    the short convolution, the B gate or the C gate left out, a prefill
    whose bucket padding enters the carried rows, a carry not zeroed
    for a row that starts at position 0 (the check's second prefill
    runs over what the first one left), the q and k norm a head left
    out, the selection bias weighing in the gates, the chosen gates
    left as the sigmoid gave them, and the leading dense layers given
    experts (those of the topmost layer)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import lfm2_moe, mamba2

    glm = faults(forward, init_cache)
    with_cfg = functools.partial(_with_cfg, forward)
    patched = functools.partial(_patched, forward)

    def silu_kept(real):
        def conv(cfg, lp, carry, x, at, bias=True, activation=None):
            return real(cfg, lp, carry, x, at, bias, jax.nn.silu)
        return conv

    def without(gate):
        def short_conv(real):
            def faulty(cfg, lp, carry, bcu, fresh, at):
                ones = jnp.ones_like(bcu[..., :cfg.dim])
                b_gate, c_gate, u = jnp.split(bcu, 3, -1)
                parts = {"B": (ones, c_gate, u), "C": (b_gate, ones, u)}
                return real(cfg, lp, carry, jnp.concatenate(parts[gate], -1),
                            fresh, at)
            return faulty
        return short_conv

    def never_fresh(real):
        def faulty(cfg, lp, carry, bcu, fresh, at):
            return real(cfg, lp, carry, bcu, jnp.zeros_like(fresh), at)
        return faulty

    def experts_in_the_dense_layers(params, tokens, cfg, cache, start_pos):
        ours = ("router", "router_bias", "we1", "we2", "we3")
        top = params["runs"][-1]
        runs = [{**{k: v for k, v in run.items()
                    if k not in ("w1", "w2", "w3")},
                 **{k: jnp.repeat(top[k][-1:], run["w1"].shape[0], 0)
                    for k in ours}} if "w1" in run else run
                for run in params["runs"]]
        return forward({**params, "runs": runs}, tokens,
                       dataclasses.replace(cfg, n_dense_layers=0), cache,
                       start_pos)

    return {
        "lower precision": glm["lower precision"],
        "silu in the conv": patched(mamba2, "_conv", silu_kept),
        "no B gate": patched(lfm2_moe, "_short_conv", without("B")),
        "no C gate": patched(lfm2_moe, "_short_conv", without("C")),
        "pad absorbed": patched(lfm2_moe, "forward_with_cache", _to_the_end),
        "carry not zeroed": patched(lfm2_moe, "_short_conv", never_fresh),
        "no q and k norm": with_cfg(qk_norm=False),
        "bias in the gates": glm["bias in the gates"],
        "gates not renormalised": with_cfg(norm_topk_prob=False),
        "experts in the dense layers": experts_in_the_dense_layers,
    }


def kimi_faults(forward, init_cache):
    """{name: served} for the family `kimi_linear`, as `faults` for
    GLM-5.2: the weights cut to float8 e4m3's mantissa and the delta
    state rounded to bfloat16's after every call (the two lower
    precisions); of the KDA mixer, the decay taken as one number a head
    (the channels' mean), the decay applied after the correction and
    not before it, beta doubled, the output gate a silu, the gate
    before the norm, `dt_bias` left out, k not normalised, q without
    1 / sqrt(dk), a convolution without its silu, a prefill whose
    bucket padding enters the state and the carries, a state and
    carries not zeroed for a row that starts at position 0 (the
    check's second prefill runs over what the first one left); of the
    latent layer, a rotary turn applied to the shared key channels and
    the queries' share of them, the score scaled by `qk_nope_head_dim`
    alone, the latent's norm left out, the shared key channels left out
    of the scores; of the expert layer, the gate scale left out, the
    chosen gates not renormalised, the selection bias weighing in the
    gates, the shared expert left out, and the leading dense layer
    given experts (those of the topmost layer)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import (decoder, gated_delta, kda, kimi_linear, llama,
                                mamba2, moe)
    from ray_tpu.models.serving import rotate_pairs

    glm = faults(forward, init_cache)
    with_cfg = functools.partial(_with_cfg, forward)
    patched = functools.partial(_patched, forward)

    with_leaf = functools.partial(_with_leaf, forward)

    def one_decay_a_head(real):
        def gates(cfg, a, lp):
            log_gamma, z = real(cfg, a, lp)
            return log_gamma.mean(-1), z
        return gates

    def decay_after(s, q, k, v, gamma, beta):
        """One token with the state decayed after it is corrected."""
        u = beta[..., None] * (v - (s * k[..., None]).sum(-2))
        s = (s + k[..., None] * u[..., None, :]) \
            * gamma.reshape(gamma.shape[:2] + (-1, 1))
        return (s * q[..., None]).sum(-2), s

    def update_decays_after(real):
        def delta_update(stack, layer, fresh, q, k, v, gamma, beta):
            s0 = jnp.where(fresh[:, None, None, None], 0.0,
                           stack[layer].astype(jnp.float32))
            o, s = decay_after(s0, q, k, v, gamma, beta)
            return o, stack.at[layer].set(s.astype(stack.dtype))
        return delta_update

    def scan_decays_after(real):
        def scan(cfg, s0, q, k, v, log_gamma, beta):
            def position(s, now):
                q_t, k_t, v_t, g_t, b_t = now
                o, s = decay_after(s, q_t.astype(jnp.float32),
                                   k_t.astype(jnp.float32),
                                   v_t.astype(jnp.float32), jnp.exp(g_t),
                                   b_t)
                return s, o
            last, o = jax.lax.scan(position, s0, tuple(
                x.swapaxes(0, 1) for x in (q, k, v, log_gamma, beta)))
            return o.swapaxes(0, 1), last
        return scan

    def decays_after(params, tokens, cfg, cache, start_pos):
        return _patched(
            patched(gated_delta, "_scan", scan_decays_after), gated_delta,
            "delta_update", update_decays_after)(
                params, tokens, cfg, cache, start_pos)

    def no_silu(real):
        def conv(cfg, lp, carry, x, at, bias=True, activation=None):
            return real(cfg, lp, carry, x, at, bias, None)
        return conv

    def never_fresh(real):
        def mixer(cfg, start_pos, at, **parts):
            return real(cfg, jnp.ones_like(start_pos), at, **parts)
        return mixer

    def attend(change):
        """`kimi_linear.latent_attention` with its arguments changed."""
        def other(real):
            def faulty(q_lat, q_rope, latent, rope_keys, mask, positions,
                       scale, *block):
                return real(*change(q_lat, q_rope, latent, rope_keys, mask,
                                    positions, scale), *block)
            return faulty
        return patched(kimi_linear, "latent_attention", other)

    def turned(q_lat, q_rope, latent, rope_keys, mask, positions, scale):
        """Rotary positions on the queries' shared channels and on every
        cached row of them, a row's position being its index."""
        stack, layer = rope_keys
        cfg = kimi_linear.KimiLinearConfig(
            qk_rope_head_dim=q_rope.shape[-1])
        rows = jnp.broadcast_to(jnp.arange(stack.shape[2]),
                                stack.shape[1:3])
        keys = rotate_pairs(stack[layer], *decoder.rope_tables(cfg, rows))
        q_rope = rotate_pairs(q_rope, *decoder.rope_tables(cfg, positions))
        return (q_lat, q_rope, latent, (stack.at[layer].set(keys), layer),
                mask, positions, scale)

    def nope_alone(params, tokens, cfg, cache, start_pos):
        grow = (1 + cfg.qk_rope_head_dim / cfg.qk_nope_head_dim) ** 0.5
        return attend(lambda *args: args[:-1] + (args[-1] * grow,))(
            params, tokens, cfg, cache, start_pos)

    def experts_in_the_dense_layer(params, tokens, cfg, cache, start_pos):
        """The dense layer's FFN is the expert layer's, over the
        topmost layer's experts."""
        ours = ("router", "router_bias", "we1", "we2", "we3", "ws1", "ws2",
                "ws3")
        top = params["runs"][-1]
        runs = [{**run, **{k: jnp.repeat(top[k][-1:], run["w1"].shape[0], 0)
                           for k in ours}} if "w1" in run else run
                for run in params["runs"]]
        return _patched(forward, llama, "swiglu",
                        lambda real: lambda: moe.served_ffn(cfg))(
            {**params, "runs": runs}, tokens, cfg, cache, start_pos)

    return {
        "lower precision": glm["lower precision"],
        "state in bfloat16": _state_in_bfloat16(
            forward, kimi_linear.state_leaves),
        "one decay a head": patched(kda, "_gates", one_decay_a_head),
        "decay after the correction": decays_after,
        "beta doubled": with_cfg(allow_neg_eigval=True),
        "silu gate": patched(kda, "_gated_norm",
                             lambda real: gated_delta._gated_norm),
        "gate before the norm": patched(
            kda, "_gated_norm",
            lambda real: _gate_before_norm(jax.nn.sigmoid)),
        "no dt bias": with_leaf("dt_bias", jnp.zeros_like),
        "k not normalised": patched(
            gated_delta, "_keys",
            lambda real: lambda k: k.astype(jnp.float32)),
        "q without its scale": patched(
            gated_delta, "_queries",
            lambda real: gated_delta._l2_normalise),
        "conv without silu": patched(mamba2, "_conv", no_silu),
        "pad absorbed": patched(kimi_linear, "forward_with_cache",
                                _to_the_end),
        "state not zeroed": patched(gated_delta, "mixer", never_fresh),
        "rotary turn": attend(turned),
        "score scaled by nope alone": nope_alone,
        "no latent norm": patched(
            kimi_linear, "rms_norm_reference",
            lambda real: lambda x, weight, eps: x),
        "no shared key channels": attend(
            lambda q_lat, q_rope, *rest: (q_lat, jnp.zeros_like(q_rope),
                                          *rest)),
        "no gate scale": glm["no gate scale"],
        "gates not renormalised": with_cfg(norm_topk_prob=False),
        "bias in the gates": glm["bias in the gates"],
        "no shared expert": glm["no shared expert"],
        "experts in the dense layer": experts_in_the_dense_layer,
    }


# The faults a set of weights cannot show on the chip (each is seen at
# the other; PERF.md section 6, PR 32, has the readings). The
# benchmark's weights make the routed experts 32 times quieter, so what
# only changes their gates stays inside the program's own error there.
# At the plain weights one position in a hundred is moved by a router's
# near-tie, and a `shared` layer that selects anew moves fewer than that.
UNSEEN = {"benchmark": ("bias in the gates", "no gate scale"),
          "plain": ("shared selects anew",)}
# The same for `nemotron_h` (PERF.md section 6, PR 34). The benchmark's
# weights make the routed experts 32 times quieter, as GLM-5.2's do.
# The state rounded to bfloat16 after every call reads the program's
# own digits on the chip at either set, at the check's eight decode
# steps and at 512 (0.79-0.83 % beside the program's 0.79-0.83 %); the
# float32 test on the CPU holds it (`tests/models/test_nemotron_h.py`).
NEMOTRON_UNSEEN = {
    "benchmark": ("state in bfloat16", "no gate scale",
                  "bias in the gates", "no latent projection"),
    "plain": ("state in bfloat16",)}


# The same for `cohere2_moe` (PERF.md section 6, PR 39): nothing. At the
# benchmark's weights even the one fault of the routed experts' gates
# reads 4.7 % against the runner's 2 %; at the plain weights every
# fault breaks the median or the 99th percentile.
COHERE_UNSEEN = {"benchmark": (), "plain": ()}


# The same for `olmo_hybrid` (PERF.md section 6, PR 41): both sets of
# weights are the program's initialiser's.
OLMO_UNSEEN = {"benchmark": ("state in bfloat16",),
               "plain": ("state in bfloat16",)}


# The same for `lfm2_moe` (PERF.md section 6, PR 55). The benchmark's
# weights make the routed experts 32 times quieter, as GLM-5.2's do:
# the bias in the gates reads the program's digits there, and the gates
# not renormalised 2.3 to 2.6 % against the runner's 2 %, over it but
# too near to count on; the plain weights show both. A carry not zeroed
# for a row that starts at 0 moves two positions of a row's second
# prefill, 1e-5 of the largest logit in float32 and nothing in
# bfloat16; the float32 test on the CPU holds it
# (`tests/models/test_lfm2_moe.py`).
LFM2_UNSEEN = {
    "benchmark": ("carry not zeroed", "bias in the gates",
                  "gates not renormalised"),
    "plain": ("carry not zeroed",)}


# The same for `kimi_linear` (PERF.md section 6, PR 57). The benchmark's
# weights make the routed experts 32 times quieter, as GLM-5.2's do:
# the gate scale left out reads 0.79 to 0.89 % and the bias in the
# gates the program's digits against the runner's 1.5 %; the plain
# weights show both by the median. At the plain weights a router's
# near-tie moves two positions in a hundred by up to 13 %, and the two
# faults of the latent layer that move every position a little (the
# score's scale, the latent's norm: medians 0.61 to 0.64 % beside the
# program's 0.50 to 0.51) stay inside the median's room; the
# benchmark's weights show both by the largest error (2.04 to 2.18 %).
# The state rounded to bfloat16 reads the program's digits at either
# set, as Olmo-Hybrid's, and so does a state not zeroed for a row that
# starts at 0 (0.95 % against 0.71: the check's second prefill runs
# over what the first left, and 1,000 tokens of decay have forgotten
# it); the float32 test on the CPU holds both
# (`tests/models/test_kimi_linear.py`).
KIMI_UNSEEN = {
    "benchmark": ("state in bfloat16", "state not zeroed", "no gate scale",
                  "bias in the gates"),
    "plain": ("state in bfloat16", "state not zeroed",
              "score scaled by nope alone", "no latent norm")}


def _plain_init(family):
    """A call that hands back the initialiser of the program's module
    of that name (the plain weights), imported when it is made."""
    def init():
        import importlib
        return importlib.import_module(f"ray_tpu.models.{family}").init_params
    return init


# By a configuration's family, which is the name of the program's
# module: its faults, the faults a set of weights cannot show, the
# program's own initialiser (the plain weights), and the prompt lengths
# of a rehearsal at debug widths.
FAMILIES = {
    family: (family_faults, unseen, _plain_init(family), rehearsal_lens)
    for family, (family_faults, unseen, rehearsal_lens) in {
        "glm_dsa": (faults, UNSEEN, [40, 33, 26, 19]),
        "nemotron_h": (nemotron_faults, NEMOTRON_UNSEEN, [45, 39, 26, 19]),
        "cohere2_moe": (cohere_faults, COHERE_UNSEEN, [45, 39, 26, 19]),
        "olmo_hybrid": (olmo_faults, OLMO_UNSEEN, [45, 39, 26, 19]),
        "lfm2_moe": (lfm2_faults, LFM2_UNSEEN, [45, 33, 12, 5]),
        "kimi_linear": (kimi_faults, KIMI_UNSEEN, [45, 39, 26, 19]),
    }.items()}


def within(row, limits):
    """Whether a variant's statistics keep every limit: `limits` names
    statistics of `distances` and the most each may be."""
    return all(row[name] <= most for name, most in limits.items())


def weights_and_tokens(config, seed, model, init):
    """The shallow copy, its weights and the check's tokens, as the
    serving runner makes them from the seed."""
    import jax
    import numpy as np

    from benchmark.runners.train import prng_key

    plan = config["serve"]
    small = model.with_layers(model.program_config(config),
                              plan["reference_layers"])
    params = jax.jit(functools.partial(init, small))(prng_key(seed))
    lens = np.asarray(plan["reference_prompt_lens"])
    tokens = np.random.default_rng([seed, 7]).integers(
        0, config["vocab_size"],
        (len(lens), int(lens.max()) + plan["reference_decode_steps"]),
        dtype=np.int32)
    return small, params, lens, tokens


def reference_logits(config, params, tokens, reference):
    """The plain reference's logits of every position of `tokens`."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return jax.jit(functools.partial(
            reference.forward, hp=reference.hyper(config)))(
                params, jnp.asarray(tokens))


def distances(config, small, params, lens, tokens, model, reference,
              served_by_name, want=None):
    """`check_against_reference` of the serving runner for each of
    `served_by_name`, the reference computed once (`want`, where a
    caller that comes again has kept `reference_logits`): {name: the
    errors of its positions, each the largest logit error over the
    largest |reference| logit, as their largest, median, 99th and 99.9th
    percentile and the share over `logit_tolerance`}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    plan = config["serve"]
    rows, n_dec = len(lens), plan["reference_decode_steps"]
    n_pre = tokens.shape[1] - n_dec
    if want is None:
        want = reference_logits(config, params, tokens, reference)
    top = float(jnp.abs(want).max())
    error = jax.jit(lambda got, want: jnp.abs(
        got.astype(jnp.float32) - want).max(-1))
    out = {}
    at = np.arange(rows)
    for name, served in served_by_name.items():
        step = jax.jit(functools.partial(served, cfg=small))
        cache = model.init_cache(small, rows, plan["max_seq_len"])
        logits, cache = step(params, jnp.asarray(tokens[:, :n_pre]),
                             cache=cache,
                             start_pos=jnp.zeros(rows, jnp.int32))
        errors = [np.asarray(error(logits, want[:, :n_pre])).ravel()]
        for i in range(n_dec):
            logits, cache = step(
                params, jnp.asarray(tokens[at, lens + i][:, None]),
                cache=cache, start_pos=jnp.asarray(lens + i, jnp.int32))
            errors.append(np.asarray(error(logits[:, 0],
                                           want[at, lens + i])))
        e = np.concatenate(errors) / top
        out[name] = {"max": float(e.max()),
                     **{f"q{q}": float(np.quantile(e, float(q) / 100))
                        for q in ("50", "99", "99.9")},
                     "over": float((e > plan["logit_tolerance"]).mean())}
        del cache, logits
    return out


def hit_against_miss(config, small, params, tokens, model):
    """What a prefix hit changes in the logits: a prompt's last row
    from a prefill of the whole prompt (a miss), and from a prefill of
    what follows its last whole block over the rows the miss wrote (a
    hit copies those rows in bit for bit and prefills the rest), for a
    prompt past `index_topk` and a short one. {length: largest logit
    difference over the largest |logit|}."""
    import jax
    import jax.numpy as jnp

    from ray_tpu._private.config import ray_config

    block = ray_config.llm_kv_block_tokens
    step = jax.jit(functools.partial(model.cached_forward, cfg=small))
    out = {}
    for n in (tokens.shape[1], min(3 * block + 8, tokens.shape[1] - 3)):
        prompt = jnp.asarray(tokens[:1, :n])
        cache = model.init_cache(small, 1, config["serve"]["max_seq_len"])
        miss, cache = step(params, prompt, cache=cache,
                           start_pos=jnp.zeros(1, jnp.int32))
        matched = (n - 1) // block * block
        hit, cache = step(params, prompt[:, matched:], cache=cache,
                          start_pos=jnp.full(1, matched, jnp.int32))
        out[n] = float(jnp.abs(hit[0, -1] - miss[0, -1]).max()
                       / jnp.abs(miss[0, -1]).max())
        del cache
    return out


def engine_hit(config, small, params, tokens, reference, n_prompt, n_new):
    """The same prompt asked of an `LLMEngine` twice: the second time
    the prefix cache matches all its whole blocks, so the answer goes
    through the payloads the first one's read-back stored and the
    copy-in of every leaf of the cache. The hit's tokens are held
    against the reference as the runner holds served tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.serve.llm import LLMEngine, SamplingParams

    matched = []

    class Engine(LLMEngine):
        def _prefix_copy_in(self, req, slot, prompt):
            m_tok, chain = super()._prefix_copy_in(req, slot, prompt)
            matched.append(m_tok)
            return m_tok, chain

    prompt = [int(t) for t in tokens[0, :n_prompt]]
    engine = Engine(small, params, max_batch_size=2,
                    max_seq_len=1 << (n_prompt + n_new).bit_length())
    try:
        miss = engine.generate(prompt, SamplingParams(max_tokens=n_new))
        hit = engine.generate(prompt, SamplingParams(max_tokens=n_new))
        totals = engine.metrics()["totals"]
    finally:
        engine.stop()
    with jax.default_matmul_precision("highest"):
        logits, = reference.logits_layer_by_layer(
            params, [jnp.asarray((prompt + hit)[:-1], jnp.int32)],
            reference.hyper(config))
    rows = np.asarray(logits)[n_prompt - 1:]
    short = float((rows.max(-1) - rows[np.arange(n_new), hit]).max()
                  / np.abs(np.asarray(logits)).max())
    same = next((i for i, (a, b) in enumerate(zip(miss, hit)) if a != b),
                n_new)
    return {"matched": matched, "tokens": n_new, "same as the miss": same,
            "worst under the reference": short,
            "blocks read back": totals["kv_blocks_read_back"]}


def main(argv):
    import jax

    from benchmark.harness.manifest import load_json, model_adapter, plugin

    options = {"--weights": "benchmark", "--config": "glm-5.2-serve"}
    for name in options:
        if name in argv:
            options[name] = argv.pop(argv.index(name) + 1)
            argv.remove(name)
    weights = options["--weights"]
    rehearse, hit = "--rehearse" in argv, "--hit" in argv
    argv = [a for a in argv if a not in ("--rehearse", "--hit")]
    seeds = [int(a) for a in argv] or [2147483747]
    config = load_json(ROOT, "benchmark", "configs",
                       options["--config"] + ".json")
    model = model_adapter(config)
    family_faults, unseen, plain_init, rehearsal_lens = \
        FAMILIES[config["family"]]
    if hit and config["family"] != "glm_dsa":
        raise SystemExit("--hit needs a prefix cache: a model with a "
                         "state leaf in its cache is served with none")
    if rehearse:
        config = model.debug(config)
        config["serve"].update(reference_prompt_lens=rehearsal_lens,
                               max_seq_len=64)
    elif jax.default_backend() != "tpu":
        raise SystemExit(f"needs a TPU, found {jax.default_backend()}")
    reference = plugin("references", config["reference"])
    plan = config["serve"]
    tolerance = plan["logit_tolerance"]
    init = {"benchmark": model.init, "plain": plain_init()}[weights]
    served = {"program": model.cached_forward,
              **family_faults(model.cached_forward, model.init_cache)}
    # The benchmark's weights are held as the runner holds them, by the
    # largest error, and by the tool's own limits; the plain weights by
    # the tool's limits alone.
    limits = dict(plan["tool_checks"][weights])
    if weights == "benchmark":
        limits["max"] = tolerance
    ok = True
    for i, seed in enumerate(seeds):
        small, params, lens, tokens = weights_and_tokens(
            config, seed, model, init)
        row = distances(config, small, params, lens, tokens, model,
                        reference, served)
        print(json.dumps({"seed": seed, "weights": weights, **row}),
              flush=True)
        ok = ok and within(row.pop("program"), limits) and not any(
            within(v, limits) for k, v in row.items()
            if k not in unseen[weights])
        if hit and i == 0:
            n_prompt = int(lens.max()) - 12 if rehearse else 2100
            by_length = hit_against_miss(config, small, params, tokens,
                                         model)
            asked = engine_hit(config, small, params, tokens, reference,
                               n_prompt, 8 if rehearse else 24)
            print(json.dumps({"seed": seed, "hit against miss": by_length,
                              "engine hit": asked}), flush=True)
            ok = ok and max(by_length.values()) <= tolerance \
                and asked["matched"][0] == 0 < asked["matched"][1] \
                and asked["worst under the reference"] \
                <= plan["served_token_margin"]
    print(json.dumps({"weights": weights, "tolerance": tolerance,
                      "ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
