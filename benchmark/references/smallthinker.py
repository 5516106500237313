"""Plain reference of SmallThinker-21BA3B-Instruct (PowerInfer; the
catalog's copy of its `config.json`, `modeling_smallthinker.py` as far
as it is remembered, arXiv:2507.20984). One layer, x [S, d] being the
stream as the layer receives it:

    l  = x Wr                                   # float32, 64 wide: the
                                                # router reads x itself,
                                                # before attention and
                                                # before any norm
    S  = the top_k largest entries of l         # a tie: the lower index
    g  = softmax over the chosen l              # norm_topk_prob
    h  = rms_norm(x; w1)
    q, k, v = h Wq, h Wk, h Wv                  # 28 and 4 heads of 128,
                                                # no bias, no q/k norm
    a layer whose entry of `rope_layout` is 1 turns q and k by rotary
        positions (the halves of a head paired, base `rope_theta`); one
        whose entry of `sliding_window_layout` is 1 lets a row see the
        `sliding_window_size` keys that end with itself; a layer with
        0 turns nothing and sees every key before it
    y  = x + softmax(q k^T / sqrt(128), masked) v Wo
    u  = rms_norm(y; w2)
    x' = y + sum_{e in S, e held} g_e * Wd_e(relu(Wg_e u) * (Wu_e u))

Float32 `jax.numpy`, no kernel, no dispatch: one sequence after the
other, attention a block of `Q_BLOCK` queries at a time against every
key under the mask (a head's 16,384 x 16,384 scores are 1 GB), one
held expert after the other over every token, its output counted for
the tokens that chose it (masked). It reads the program's parameter
tree (`{"embed", "runs": [stacked leaves a run of like layers],
"final_norm", "out"}`; `wq` as [d, heads, head size]; `we1`, `we3`,
`we2` the gate, up and down matrices of the experts held) and nothing
else of the program. Call it under
`jax.default_matmul_precision("highest")`.

The training loss is the mean cross-entropy over the vocabulary the
file holds plus `router_aux_loss_coef` times the mean over layers of
the Switch load-balancing loss over all 64 experts, 64 * sum_e (share
of the tokens that chose e, over all top_k slots) * (mean softmax
probability of e over all 64).

Departures from the published model. A share: the file holds
`moe_num_primary_experts` of the published experts (those from
`experts_held_first` on) and a slice of the vocabulary; the router
stays 64 wide and chooses among all, what an absent expert would have
added to a token is left out, as in the program (the chip that holds it
adds it, in the deployment the file states), and that partial stream is
what goes on to the next layer; logits and loss are over the slice. The
load-balancing loss and its coefficient are assumed (the published
`config.json` has none; the file's `assumed` says so). "Secondary
experts", which the family's paper describes, have no key in this
model's config and are not built. Weights are random, drawn by the
program's initialiser from the seed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.references import dense_decoder as dense
from benchmark.references.moe_top2 import load_balancing

# Queries a block of attention: 28 heads x 512 x 16,384 float32 scores
# are 0.94 GB.
Q_BLOCK = 512


def hyper(config):
    depth = config["num_hidden_layers"]
    published = config.get("published", {})
    return {
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "tied": bool(config["tie_word_embeddings"]),
        "window": config["sliding_window_size"],
        # By layer held, bottom to top: (turned by rotary, windowed).
        "layers": tuple(zip(map(bool, config["rope_layout"][:depth]),
                            map(bool,
                                config["sliding_window_layout"][:depth]))),
        # The router's width is the published count whatever is held.
        "n_experts": published.get("moe_num_primary_experts",
                                   config["moe_num_primary_experts"]),
        "held": (config.get("experts_held_first", 0),
                 config["moe_num_primary_experts"]),
        "top_k": config["moe_num_active_primary_experts"],
        "expert_width": config["moe_ffn_hidden_size"],
        "aux_coef": float(config["router_aux_loss_coef"]),
    }


def attention(h, lp, hp, turned, windowed):
    """Grouped-query attention of one block, a block of queries at a
    time. h: [S, d]."""
    s = h.shape[0]
    q = jnp.einsum("sd,dhk->shk", h, dense._f32(lp["wq"]))
    k = jnp.einsum("sd,dhk->shk", h, dense._f32(lp["wk"]))
    v = jnp.einsum("sd,dhk->shk", h, dense._f32(lp["wv"]))
    if turned:
        q, k = dense.rotate(q, hp["rope_theta"]), \
            dense.rotate(k, hp["rope_theta"])
    group = hp["n_heads"] // hp["n_kv_heads"]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    block = min(Q_BLOCK, s)
    n = -(-s // block)
    rows = jnp.arange(n * block).reshape(n, block)
    q = jnp.pad(q, ((0, n * block - s), (0, 0), (0, 0)))

    def one(at):
        back = at[:, None] - jnp.arange(s)[None, :]
        seen = back >= 0
        if windowed:
            seen &= back < hp["window"]
        scores = jnp.einsum("qhk,thk->hqt", q[at], k) \
            * hp["head_dim"] ** -0.5
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqt,thk->qhk", probs, v)

    # (Rematerialised a block at a time where a gradient is taken, so
    # that no block's scores wait for the backward pass: no number of
    # the forward pass or of the gradient changes.)
    out = jax.lax.map(jax.checkpoint(one), rows).reshape(
        n * block, *q.shape[1:])[:s]
    return jnp.einsum("qhk,hkd->qd", out, dense._f32(lp["wo"]))


def route(x, lp, hp):
    """x: [S, d], the stream as the layer received it -> (the chosen
    experts' gates [S, k], which they are [S, k], the softmax over all
    experts [S, E])."""
    logits = x @ dense._f32(lp["router"])                     # [S, E]
    top_l, top_i = jax.lax.top_k(logits, hp["top_k"])
    return jax.nn.softmax(top_l, -1), top_i, jax.nn.softmax(logits, -1)


def reglu(u, w_gate, w_up, w_down):
    return (jax.nn.relu(u @ dense._f32(w_gate)) * (u @ dense._f32(w_up))) \
        @ dense._f32(w_down)


def experts(u, gates, top_i, lp, hp):
    """u: [S, d] -> the held experts' weighted output [S, d]: each over
    every token, counted for those that chose it."""
    first, count = hp["held"]
    assert lp["we1"].shape == (count, u.shape[1], hp["expert_width"])

    def one(out, expert):
        e, w_gate, w_up, w_down = expert
        weight = jnp.where(top_i == e, gates, 0.0).sum(-1)    # [S]
        return out + weight[:, None] * reglu(u, w_gate, w_up, w_down), None

    return jax.lax.scan(
        one, jnp.zeros_like(u),
        (first + jnp.arange(count), lp["we1"], lp["we3"], lp["we2"]))[0]


def block(x, lp, hp, turned, windowed):
    """One layer. x: [S, d] -> (x', how many tokens chose each expert
    [E], the router's summed probability of each expert [E])."""
    gates, top_i, probs = route(x, lp, hp)
    y = x + attention(dense.rms_norm(
        x, dense._f32(lp["attn_norm"]), hp["norm_eps"]), lp, hp, turned,
        windowed)
    u = dense.rms_norm(y, dense._f32(lp["mlp_norm"]), hp["norm_eps"])
    chose = (top_i[..., None] == jnp.arange(hp["n_experts"])).any(1).sum(0)
    return (y + experts(u, gates, top_i, lp, hp),
            chose.astype(jnp.float32), probs.sum(0))


def layers_of(params):
    """One layer's leaves after the other, bottom to top."""
    for run in params["runs"]:
        for i in range(run["wq"].shape[0]):
            yield jax.tree.map(lambda leaf: leaf[i], run)


def sequence_logits(params, tokens, hp):
    """One sequence: tokens [S] -> (logits [S, vocab], per layer the
    tokens that chose each expert [L, E] and the summed router
    probabilities [L, E])."""
    x = dense._f32(params["embed"])[tokens]
    chose, prob = [], []
    for lp, (turned, windowed) in zip(layers_of(params), hp["layers"],
                                      strict=True):
        x, c, p = block(x, lp, hp, turned, windowed)
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
