"""Plain reference of the sparse-expert decoder (Mixtral-8x7B): the
dense decoder's blocks with the feed-forward replaced by `n_experts`
SwiGLU experts, of which each token uses the `top_k` the router scores
highest, weighted by the router's softmax renormalised over those
(softmax over all experts, then top-k, then divide by their sum, as
`MixtralSparseMoeBlock` does). Float32 `jax.numpy`, one sequence after
the other and one expert after the other, an expert's output counted
only for the tokens that chose it (masked; no dispatch, no capacity).
Call it under `jax.default_matmul_precision("highest")`.

The training loss adds `router_aux_loss_coef` times the load-balancing
loss, n_experts * sum_e (share of the batch's tokens that chose e) *
(mean router probability of e over the batch), averaged over the
layers. Both factors are means over tokens, so they are gathered as
sums while the sequences go through.

Departure from the published model: `transformers` computes the
load-balancing loss over the concatenated router logits of all layers;
here, as in the program, it is computed per layer and averaged. Both
are the Switch-Transformer form and agree when layers see the same
number of tokens, which they do.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.references import dense_decoder as dense


def hyper(config):
    return {**dense.hyper(config),
            "n_experts": config["num_local_experts"],
            "top_k": config["num_experts_per_tok"],
            "aux_coef": float(config["router_aux_loss_coef"])}


def experts(h, lp, hp):
    """h: [S, d] -> (the chosen experts' weighted output [S, d], how
    many tokens chose each expert [E], the router's summed probability
    of each expert [E])."""
    probs = jax.nn.softmax(h @ dense._f32(lp["router"]), -1)  # [S, E]
    top_p, top_i = jax.lax.top_k(probs, hp["top_k"])
    top_p = top_p / top_p.sum(-1, keepdims=True)
    out = jnp.zeros_like(h)
    chose = []
    for e in range(hp["n_experts"]):
        weight = jnp.where(top_i == e, top_p, 0.0).sum(-1)    # [S]
        ffn = dense.feed_forward(h, {"w1": lp["we1"][e], "w3": lp["we3"][e],
                                     "w2": lp["we2"][e]})
        out = out + weight[:, None] * ffn
        chose.append((top_i == e).any(-1).sum())
    return out, jnp.stack(chose).astype(jnp.float32), probs.sum(0)


def sequence_logits(params, tokens, hp):
    """One sequence: tokens [S] -> (logits [S, vocab], per layer the
    tokens that chose each expert [L, E] and the summed router
    probabilities [L, E])."""
    x = dense._f32(params["embed"])[tokens]
    chose, prob = [], []
    for i in range(params["layers"]["wq"].shape[0]):
        lp = dense.layer_params(params, i)
        x = x + dense.attention(dense.rms_norm(
            x, dense._f32(lp["attn_norm"]), hp["norm_eps"]), lp, hp)
        ffn, c, p = experts(dense.rms_norm(
            x, dense._f32(lp["mlp_norm"]), hp["norm_eps"]), lp, hp)
        x = x + ffn
        chose.append(c)
        prob.append(p)
    return dense.head(params, x, hp), jnp.stack(chose), jnp.stack(prob)


def load_balancing(chose, prob, n_tokens, hp):
    """chose, prob: [B, L, E] sums per sequence -> the mean over layers
    of n_experts * sum_e share_e * mean_prob_e."""
    share = chose.sum(0) / n_tokens
    mean_prob = prob.sum(0) / n_tokens
    return (hp["n_experts"] * (share * mean_prob).sum(-1)).mean()


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
