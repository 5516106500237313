"""Plain reference of OLMoE-1B-7B (`modeling_olmoe.py` of
`transformers`, as far as its `config.json` reaches): pre-norm blocks of
causal multi-head attention whose projected q and k are RMS-normalised
over the whole projected vector (all heads together, a learned weight
each, before the split into heads and before the rotary positions;
`clip_qkv` is null, nothing is clipped), and a feed-forward of
`num_experts` SwiGLU experts of width `intermediate_size`, of which a
token uses the `num_experts_per_tok` its router scores highest:

    p  = softmax_float32(y Wr)                 # over all experts, no bias
    S  = the top_k largest entries of p        # a tie goes to the lower index
    x' = h + sum_{e in S} g_e * Wd_e(silu(Wg_e y) * (Wu_e y))

with g_e = p_e as it is where `norm_topk_prob` is false (OLMoE), and
p_e over the sum of the chosen p where it is true. Dropless: every
choice is computed. Float32 `jax.numpy`, one sequence after the other
and one expert after the other, an expert's output counted only for the
tokens that chose it (masked; no dispatch, no capacity). It reads the
program's parameter tree (`dense_decoder.py` says how; the two norm
weights are `q_norm` and `k_norm`, and a tree without them is a model
without that norm) and nothing else of the program. Call it under
`jax.default_matmul_precision("highest")`.

The training loss adds `router_aux_loss_coef` times the Switch
load-balancing loss, num_experts * sum_e (share of the tokens that
chose e, over all top_k slots) * (mean router probability of e).

Departures from the published model: the load-balancing loss is
computed per layer and averaged, where `transformers` computes it over
the concatenated router logits of all layers (`moe_top2.py` says why
the two agree); no router z-loss, which OLMoE's paper trains with and
its published `config.json` does not carry; weights are random, drawn
by the program's initialiser from the seed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.references import dense_decoder as dense
from benchmark.references.moe_top2 import load_balancing


def hyper(config):
    return {**dense.hyper(config),
            "n_experts": config["num_experts"],
            "top_k": config["num_experts_per_tok"],
            "norm_topk": bool(config["norm_topk_prob"]),
            "expert_width": config["intermediate_size"],
            "aux_coef": float(config["router_aux_loss_coef"])}


def attention(h, lp, hp):
    """Causal attention of one block, q and k normalised. h: [S, d]."""
    s = h.shape[0]
    q = jnp.einsum("sd,dhk->shk", h, dense._f32(lp["wq"]))
    k = jnp.einsum("sd,dhk->shk", h, dense._f32(lp["wk"]))
    v = jnp.einsum("sd,dhk->shk", h, dense._f32(lp["wv"]))
    if "q_norm" in lp:
        q = dense.rms_norm(q.reshape(s, -1), dense._f32(lp["q_norm"]),
                           hp["norm_eps"]).reshape(q.shape)
        k = dense.rms_norm(k.reshape(s, -1), dense._f32(lp["k_norm"]),
                           hp["norm_eps"]).reshape(k.shape)
    q = dense.rotate(q, hp["rope_theta"])
    k = dense.rotate(k, hp["rope_theta"])
    group = hp["n_heads"] // hp["n_kv_heads"]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    scores = jnp.einsum("qhk,thk->hqt", q, k) * hp["head_dim"] ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    out = jnp.einsum("hqt,thk->qhk", probs, v)
    return jnp.einsum("qhk,hkd->qd", out, dense._f32(lp["wo"]))


def experts(h, lp, hp):
    """h: [S, d] -> (the chosen experts' weighted output [S, d], how
    many tokens chose each expert [E], the router's summed probability
    of each expert [E])."""
    assert lp["we1"].shape == (hp["n_experts"], h.shape[1],
                               hp["expert_width"])
    probs = jax.nn.softmax(h @ dense._f32(lp["router"]), -1)  # [S, E]
    top_p, top_i = jax.lax.top_k(probs, hp["top_k"])
    if hp["norm_topk"]:
        top_p = top_p / top_p.sum(-1, keepdims=True)

    def one(out, expert):
        e, w1, w3, w2 = expert
        weight = jnp.where(top_i == e, top_p, 0.0).sum(-1)    # [S]
        ffn = dense.feed_forward(h, {"w1": w1, "w3": w3, "w2": w2})
        return out + weight[:, None] * ffn, (top_i == e).any(-1).sum()

    out, chose = jax.lax.scan(
        one, jnp.zeros_like(h),
        (jnp.arange(hp["n_experts"]), lp["we1"], lp["we3"], lp["we2"]))
    return out, chose.astype(jnp.float32), probs.sum(0)


def sequence_logits(params, tokens, hp):
    """One sequence: tokens [S] -> (logits [S, vocab], per layer the
    tokens that chose each expert [L, E] and the summed router
    probabilities [L, E])."""
    x = dense._f32(params["embed"])[tokens]
    chose, prob = [], []
    for i in range(params["layers"]["wq"].shape[0]):
        lp = dense.layer_params(params, i)
        x = x + attention(dense.rms_norm(
            x, dense._f32(lp["attn_norm"]), hp["norm_eps"]), lp, hp)
        ffn, c, p = experts(dense.rms_norm(
            x, dense._f32(lp["mlp_norm"]), hp["norm_eps"]), lp, hp)
        x = x + ffn
        chose.append(c)
        prob.append(p)
    return dense.head(params, x, hp), jnp.stack(chose), jnp.stack(prob)


def forward(params, tokens, hp):
    """tokens [B, S] -> (logits [B, S, vocab] float32, the mean of the
    layers' load-balancing losses)."""
    logits, chose, prob = jax.lax.map(
        lambda t: sequence_logits(params, t, hp), tokens)
    return logits, load_balancing(chose, prob, tokens.size, hp)


def loss(params, tokens, targets, hp):
    def one(tt):
        logits, chose, prob = sequence_logits(params, tt[0], hp)
        return dense.cross_entropy(logits, tt[1]), chose, prob

    ce, chose, prob = jax.lax.map(one, (tokens, targets))
    return ce.sum() / tokens.size + hp["aux_coef"] * load_balancing(
        chose, prob, tokens.size, hp)
