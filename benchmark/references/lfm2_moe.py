"""Plain reference of LFM2's sparse decoder (`model_type` `lfm2_moe`,
LFM2-8B-A1B), written from the catalog's `config` beside the
model-configs guide and the family's published description. RMSNorm
everywhere (eps `norm_eps`), no bias anywhere (`conv_bias` false). A
layer, pre-norm:

    x' = x  + mixer(RMSNorm(x; w_a))
    x''= x' + ffn(RMSNorm(x'; w_m))

mixer `conv`, the gated short convolution (d the hidden size, K =
`conv_L_cache`):
    [B | C | u] = h W_in                         W_in d x 3d, the thirds in that order
    z_t = B_t * u_t                              elementwise
    c_t = sum_{j<K} w[:, j] * z_{t-K+1+j}        depthwise, causal, zeros before position 0,
                                                 no bias and no activation
    mixer = (C_t * c_t) W_out                    W_out d x d

mixer `full_attention`: q = h Wq, k = h Wk, v = h Wv, `num_attention_heads`
    query and `num_key_value_heads` key heads of hidden / heads channels;
    RMSNorm over each head's channels of q and of k (one weight vector of a
    head's size each, shared by the heads); the rotary turn of the whole
    head (theta `rope_theta`, channel i with i + half); causal softmax at
    head_size^-1/2, query head i on key head i // (heads / kv heads); Wo.

ffn of the first `num_dense_layers` layers: W2 (silu(W1 h) * W3 h).
ffn of the rest: s = sigmoid(h W_g) over all `num_experts`; T = the
    `num_experts_per_tok` experts with the largest s + b (`use_expert_bias`:
    b chooses and does not weigh); g = s[T]; g <- g / (sum g + 1e-6)
    (`norm_topk_prob`); g <- g * `routed_scaling_factor`;
    out = sum_{e in T} g_e W2_e (silu(W1_e h) * W3_e h). No shared expert.

logits = RMSNorm(x_L; w_f) Embed^T               tied

Float32 `jax.numpy`, no cache, no carry, no kernels: the whole sequence
at once, the convolution the written sum over a zero-padded sequence,
one sequence after the other, one head after the other, one expert
after the other (a matrix is cast to float32 when its turn comes). It
reads the program's parameter tree and nothing else of the program:
`runs`, a list of stacked runs of like layers; a run with `w_in` is
`conv`, one with `w1` has the dense ffn. Call it under
`jax.default_matmul_precision("highest")`. The norm, the rotary turn
and the SwiGLU are the sibling references' (`dense_decoder.rms_norm`,
`sdar_moe.rotate_halves`: channel i with i + half, `cohere2_moe.swiglu`).

Departures from the published model: weights are random, drawn by the
program's initialiser from the seed. What the catalog's `config` leaves
open and how it was read is the configuration's `assumed`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.cohere2_moe import swiglu
from benchmark.references.dense_decoder import _f32, rms_norm
from benchmark.references.sdar_moe import rotate_halves


def hyper(config):
    assert not config["conv_bias"] and config["use_expert_bias"]
    return {
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["norm_eps"]),
        "kernel": config["conv_L_cache"],
        "top_k": config["num_experts_per_tok"],
        "norm_topk": bool(config["norm_topk_prob"]),
        "gate_scale": float(config["routed_scaling_factor"]),
    }


def short_conv(h, lp, hp):
    """The gated short convolution on normed activations h [S, d]."""
    s, k = h.shape[0], hp["kernel"]
    b_gate, c_gate, u = jnp.split(h @ _f32(lp["w_in"]), 3, -1)
    z = jnp.concatenate([jnp.zeros((k - 1, h.shape[1])), b_gate * u])
    w = _f32(lp["conv_w"])                                          # [d, K]
    c = sum(w[:, j] * z[j:j + s] for j in range(k))
    return (c_gate * c) @ _f32(lp["wo"]).reshape(h.shape[1], -1)


def attention(h, lp, hp):
    """Causal grouped-query attention with a norm a head on q and k, on
    normed activations h [S, d], one head after the other."""
    q = jnp.einsum("sd,dhk->shk", h, _f32(lp["wq"]))
    k = jnp.einsum("sd,dhk->shk", h, _f32(lp["wk"]))
    v = jnp.einsum("sd,dhk->shk", h, _f32(lp["wv"]))
    q = rms_norm(q, _f32(lp["q_norm"]), hp["norm_eps"])
    k = rms_norm(k, _f32(lp["k_norm"]), hp["norm_eps"])
    at = jnp.arange(h.shape[0])
    q = rotate_halves(q, at, hp["rope_theta"])
    k = rotate_halves(k, at, hp["rope_theta"])
    group = hp["n_heads"] // hp["n_kv_heads"]
    s = h.shape[0]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scale = q.shape[-1] ** -0.5

    def one_head(xs):
        q_h, head = xs                                             # [S, D]
        scores = q_h @ k[:, head // group].T * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return probs @ v[:, head // group]

    out = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                                 jnp.arange(hp["n_heads"])))   # [H, S, D]
    return jnp.einsum("hsk,hkd->sd", out, _f32(lp["wo"]))


def experts(h, run, i, hp):
    """Layer i's expert half on normed activations h [S, d]; the
    matrices are picked out of the run's stacks one at a time."""
    s = jax.nn.sigmoid(h @ _f32(run["router"][i]))
    _, chosen = jax.lax.top_k(s + _f32(run["router_bias"][i]), hp["top_k"])
    gates = jnp.take_along_axis(s, chosen, -1)
    if hp["norm_topk"]:
        # The published 1e-6; the program divides by the sum alone
        # (`moe._route`), 5e-7 of a gate apart at sums of about 2.
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-6)
    gates = gates * hp["gate_scale"]

    def routed(out, e):
        weight = jnp.where(chosen == e, gates, 0.0).sum(-1)          # [S]
        return out + weight[:, None] * swiglu(
            h, run["we1"][i, e], run["we3"][i, e], run["we2"][i, e]), None

    out, _ = jax.lax.scan(routed, jnp.zeros_like(h),
                          jnp.arange(run["we1"].shape[1]))
    return out


def block(x, run, i, hp):
    """Layer `i` of the stacked `run`. x: [S, d]."""
    lp = {name: run[name][i] for name in run
          if name not in ("router", "router_bias", "we1", "we2", "we3")}
    h = rms_norm(x, _f32(lp["attn_norm"]), hp["norm_eps"])
    x = x + (short_conv(h, lp, hp) if "w_in" in lp else attention(h, lp, hp))
    h = rms_norm(x, _f32(lp["mlp_norm"]), hp["norm_eps"])
    if "w1" in lp:
        return x + swiglu(h, lp["w1"], lp["w3"], lp["w2"])
    return x + experts(h, run, i, hp)


def layers_of(params):
    """(run, index in it) of every layer, bottom to top."""
    for run in params["runs"]:
        for i in range(run["attn_norm"].shape[0]):
            yield run, i


def head(params, x, hp):
    x = rms_norm(x, _f32(params["final_norm"]), hp["norm_eps"])
    return x @ _f32(params["embed"]).T


def sequence_logits(params, tokens, hp):
    """One sequence: tokens [S] -> logits [S, vocab], float32."""
    x = _f32(params["embed"])[tokens]
    for run, i in layers_of(params):
        x = block(x, run, i, hp)
    return head(params, x, hp)


def forward(params, tokens, hp):
    """tokens [B, S] -> logits [B, S, vocab], one sequence at a time."""
    return jax.lax.map(lambda t: sequence_logits(params, t, hp), tokens)


def logits_layer_by_layer(params, sequences, hp):
    """`sequence_logits` of each of `sequences`, as one jitted call a
    layer and sequence, which is handed the run's stacks where they lie
    and the layer's index: beside a model that fills the chip only one
    matrix's float32 copy is alive at a time. Returns a list of
    [S, vocab] arrays on the host, each fetched before the next is
    made."""
    one_block = jax.jit(functools.partial(block, hp=hp))
    xs = [jax.jit(lambda e, t: _f32(e[t]))(params["embed"], t)
          for t in sequences]
    for run, i in layers_of(params):
        xs = [one_block(x, run, jnp.int32(i)) for x in xs]
    top = {k: v for k, v in params.items() if k != "runs"}
    to_logits = jax.jit(functools.partial(head, hp=hp))
    return [np.asarray(to_logits(top, x)) for x in xs]
