"""Plain reference of Olmo Hybrid's decoder (`model_type`
`olmo_hybrid`; the mixer's equations follow the Gated DeltaNet paper's
recurrence and the `fla` layer of that name, the block OLMo 2's, written
from memory of them). Every layer is

    x' = x  + RMSNorm(mixer(x); w_a, eps)            the halves read the stream as it is,
    x''= x' + RMSNorm(W_down(silu(W_gate x') * W_up x'); w_m, eps)     their outputs are normed

and the mixer is one of two, three `linear` to one `full`.

linear, the gated delta rule (H heads of dk key and dv value channels,
a convolution of width K; x the layer's input):
    q~ = x W_q    k~ = x W_k    v~ = x W_v    z = x W_g    al = x W_a    b = x W_b      no bias
    y_t   <- silu(sum_{j<K} c_y[:, j] y~_{t-K+1+j})      y in q, k, v; zeros before the start, no bias
    q_t[h] = q_t[h] / sqrt(|q_t[h]|^2 + 1e-6) / sqrt(dk)      k_t[h] = k_t[h] / sqrt(|k_t[h]|^2 + 1e-6)
    gamma_t[h] = exp(-exp(A_log[h]) softplus(al_t[h] + dt_bias[h]))
    beta_t[h]  = 2 sigmoid(b_t[h])                         linear_allow_neg_eigval
    S_t[h] = gamma_t S_{t-1} + k_t (outer) beta_t (v_t - (gamma_t S_{t-1})^T k_t)      S_{-1} = 0, S [dk, dv]
    o_t[h] = S_t^T q_t
    mixer  = (RMSNorm_dv(o_t[h]; w_o, eps) * silu(z_t[h])) W_o           the norm first, then the gate

full, attention: q, k, v of `n_heads` heads each, RMSNorm over the
    whole projected q and over the whole k, causal softmax at
    head_dim^-1/2, W_o; no positional encoding.

Float32 `jax.numpy`, no cache, no chunks, no kernels: the recurrence is
a `lax.scan` over single positions, the convolution the written sum,
one sequence after the other. It reads the program's parameter tree
and nothing else of the program: `runs`, a list of stacked runs of like
layers; a run with `A_log` is `linear`. Call it under
`jax.default_matmul_precision("highest")`.

Departures from the published model: weights are random, drawn by the
program's initialiser from the seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.references.dense_decoder import (_f32, feed_forward, head,
                                                rms_norm)


def hyper(config):
    return {
        "n_heads": config["num_attention_heads"],
        "norm_eps": float(config["rms_norm_eps"]),
        "neg_eigval": bool(config["linear_allow_neg_eigval"]),
        "tied": bool(config["tie_word_embeddings"]),
    }


def convolve(x, w):
    """Causal depthwise convolution and silu: x [S, C], w [C, K]."""
    s, k = x.shape[0], w.shape[1]
    before = jnp.concatenate([jnp.zeros((k - 1, x.shape[1])), x])
    return jax.nn.silu(sum(w[:, j] * before[j:j + s] for j in range(k)))


def unit(x):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def delta(x, lp, hp):
    """One gated delta-rule mixer on a layer's input x [S, d] ->
    [S, d]."""
    s = x.shape[0]
    heads, dk = lp["wq"].shape[1:]
    dv = lp["wv"].shape[2]
    q, k, v = (
        convolve(jnp.einsum("sd,dhk->shk", x, _f32(lp[w])).reshape(s, -1),
                 _f32(lp[c])).reshape(s, heads, -1)
        for w, c in (("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v")))
    q, k = unit(q) * dk ** -0.5, unit(k)
    z = jnp.einsum("sd,dhv->shv", x, _f32(lp["wg"]))
    gamma = jnp.exp(-jnp.exp(_f32(lp["A_log"])) * jax.nn.softplus(
        x @ _f32(lp["wa"]) + _f32(lp["dt_bias"])))              # [S, H]
    beta = jax.nn.sigmoid(x @ _f32(lp["wb"])) \
        * (2.0 if hp["neg_eigval"] else 1.0)

    def position(state, now):
        q_t, k_t, v_t, gamma_t, beta_t = now
        state = gamma_t[:, None, None] * state
        written = beta_t[:, None] * (
            v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + jnp.einsum("hk,hv->hkv", k_t, written)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(position, jnp.zeros((heads, dk, dv)),
                        (q, k, v, gamma, beta))
    o = rms_norm(o, _f32(lp["o_norm"]), hp["norm_eps"]) * jax.nn.silu(z)
    return jnp.einsum("shv,hvd->sd", o, _f32(lp["wo"]))


def attention(x, lp, hp):
    """Causal attention with a norm over all of q and all of k and no
    positional encoding, one head after the other. x [S, d] -> [S, d]."""
    s, heads = x.shape[0], hp["n_heads"]
    q = rms_norm(x @ _f32(lp["wq"]).reshape(x.shape[1], -1),
                 _f32(lp["q_norm"]), hp["norm_eps"])
    k = rms_norm(x @ _f32(lp["wk"]).reshape(x.shape[1], -1),
                 _f32(lp["k_norm"]), hp["norm_eps"])
    v = jnp.einsum("sd,dhk->hsk", x, _f32(lp["wv"]))
    q, k = (m.reshape(s, heads, -1).transpose(1, 0, 2) for m in (q, k))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def one_head(xs):
        q_h, k_h, v_h = xs
        scores = q_h @ k_h.T * q_h.shape[-1] ** -0.5
        return jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1) @ v_h

    out = jax.lax.map(one_head, (q, k, v))
    return jnp.einsum("hsk,hkd->sd", out, _f32(lp["wo"]))


def mixer_half(x, lp, hp):
    mixed = delta(x, lp, hp) if "A_log" in lp else attention(x, lp, hp)
    return x + rms_norm(mixed, _f32(lp["attn_norm"]), hp["norm_eps"])


def ffn_half(x, lp, hp):
    return x + rms_norm(feed_forward(x, lp), _f32(lp["mlp_norm"]),
                        hp["norm_eps"])


def block(x, lp, hp):
    """One layer. x: [S, d]."""
    return ffn_half(mixer_half(x, lp, hp), lp, hp)


def blocks_of(params):
    """Every layer's parameters, bottom to top, out of the runs."""
    for run in params["runs"]:
        for i in range(jax.tree.leaves(run)[0].shape[0]):
            yield jax.tree.map(lambda x: x[i], run)


def sequence_logits(params, tokens, hp):
    """One sequence: tokens [S] -> logits [S, vocab], float32."""
    x = _f32(params["embed"][tokens])
    for lp in blocks_of(params):
        x = block(x, lp, hp)
    return head(params, x, hp)


def forward(params, tokens, hp):
    """tokens [B, S] -> logits [B, S, vocab], one sequence at a time."""
    return jax.lax.map(lambda t: sequence_logits(params, t, hp), tokens)


def logits_layer_by_layer(params, sequences, hp):
    """`sequence_logits` of each of `sequences`, as jitted calls a half
    layer and sequence: beside a model that fills the chip only one
    mixer's or one SwiGLU's float32 copy is alive at a time. Returns a
    list of [S, vocab] arrays."""
    halves = [jax.jit(functools.partial(half, hp=hp))
              for half in (mixer_half, ffn_half)]
    xs = [jax.jit(lambda e, t: _f32(e[t]))(params["embed"], t)
          for t in sequences]
    for lp in blocks_of(params):
        for half in halves:
            xs = [half(x, lp) for x in xs]
    top = {k: v for k, v in params.items() if k != "runs"}
    to_logits = jax.jit(functools.partial(head, hp=hp))
    return [to_logits(top, x) for x in xs]
