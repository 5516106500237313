"""Plain reference of Kimi Linear's decoder (`model_type` `kimi_linear`,
Kimi-Linear-48B-A3B; the equations follow the catalog row's `config`,
the Kimi Linear report's recurrence and the family's published
modelling code, written from memory of them). Pre-norm blocks,
RMSNorm with eps `rms_norm_eps`, no bias in any projection:

    x' = x  + mixer(RMSNorm(x; w_a))
    x''= x' + ffn(RMSNorm(x'; w_m))

and after the last layer one RMSNorm and an untied head. The mixer is
one of two, three `kda` to one `mla`; a is the normed input.

kda, Kimi Delta Attention (H heads of dk key and dv value channels, a
convolution of width K):
    q~ = a W_q    k~ = a W_k    v~ = a W_v    b = a w_b
    y_t   <- silu(sum_{j<K} c_y[:, j] y~_{t-K+1+j})      y in q, k, v; zeros before the start
    q_t[h] = q_t[h] / sqrt(|q_t[h]|^2 + 1e-6) / sqrt(dk)      k_t[h] = k_t[h] / sqrt(|k_t[h]|^2 + 1e-6)
    g_t[h] = -exp(A_log[h]) softplus(((a W_fa) W_fb)[h] + dt_bias[h])     in R^dk; alpha_t[h] = exp(g_t[h])
    beta_t[h] = sigmoid(b_t[h])
    S_t[h] = Diag(alpha_t) S_{t-1} + k_t (outer) beta_t (v_t - (Diag(alpha_t) S_{t-1})^T k_t)      S_{-1} = 0, S [dk, dv]
    o_t[h] = S_t^T q_t
    mixer  = (RMSNorm_dv(o_t[h]; w_o) * sigmoid(((a W_ga) W_gb)[h])) W_o          the norm first, then the gate

mla, latent attention with no positional encoding (`mla_use_nope`):
    q_h = (a W_q)_h = [q_n | q_r]         no query bottleneck (`q_lora_rank` null)
    [c~ | k_r] = a W_kva ;  c = RMSNorm(c~; kv_norm)          one k_r for all heads, never turned
    [k_n[h] | v[h]] = (c W_kvb)_h
    score[h](t, s) = (q_n[h]_t . k_n[h]_s + q_r[h]_t . k_r_s) / sqrt(nope + rope)      s <= t
    mixer = concat_h(softmax_s(score[h]) v[h]) W_o

ffn of the first layer (`first_k_dense_replace` 1): W_2 (silu(W_1 y) *
W_3 y). Of the others: sig = sigmoid(y W_r) in float32; the
`num_experts_per_token` experts of largest sig + bias; g_e = sig_e /
sum over the chosen (`moe_renormalize`), times `routed_scaling_factor`;
out = SwiGLU_shared(y) + sum_{e chosen, e held} g_e SwiGLU_e(y).

Float32 `jax.numpy`, no cache, no chunks, no kernels, nothing absorbed:
the recurrence is a `lax.scan` over single positions, the convolution
the written sum, keys and values are expanded from the latent a head
at a time, one sequence after the other, one expert after the other.
It reads the program's parameter tree and nothing else of the program:
`runs`, a list of stacked runs of like layers; a run with `A_log` is
`kda`, one with `we1` is sparse. The experts a tree holds are a
contiguous share of those the router chooses among, `first_expert` on
(`hyper`): the pairs routed elsewhere are another chip's to add, here
as in the program. Call it under
`jax.default_matmul_precision("highest")`.

Departures from the published model: weights are random, drawn by the
program's initialiser from the seed; `num_expert_group` and
`topk_group` are 1, so the published grouped top-k is the plain one
written here; the router's selection bias is the DeepSeek-V3 family's
(`experts` below is `references/glm_dsa.py`'s, the same router to the
letter), which the row's config does not name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.references.dense_decoder import (_f32, feed_forward, head,
                                                rms_norm)
from benchmark.references.glm_dsa import experts
from benchmark.references.olmo_hybrid import convolve, unit


def hyper(config):
    assert config["mla_use_nope"] and config["q_lora_rank"] is None
    assert config["num_expert_group"] == config["topk_group"] == 1
    return {
        "nope": config["qk_nope_head_dim"],
        "kv_lora_rank": config["kv_lora_rank"],
        "norm_eps": float(config["rms_norm_eps"]),
        "top_k": config["num_experts_per_token"],
        "norm_topk": bool(config["moe_renormalize"]),
        "gate_scale": float(config["routed_scaling_factor"]),
        "scoring": config["moe_router_activation_func"],
        "first_expert": config["deployment"]["experts_held"][0],
        "tied": bool(config["tie_word_embeddings"]),
    }


def kda(a, lp, hp):
    """One Kimi Delta Attention mixer on normed activations a [S, d] ->
    [S, d]."""
    s = a.shape[0]
    heads, dk = lp["wq"].shape[1:]
    dv = lp["wv"].shape[2]
    q, k, v = (
        convolve(jnp.einsum("sd,dhk->shk", a, _f32(lp[w])).reshape(s, -1),
                 _f32(lp[c])).reshape(s, heads, -1)
        for w, c in (("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v")))
    q, k = unit(q) * dk ** -0.5, unit(k)
    g = -jnp.exp(_f32(lp["A_log"]))[:, None] * jax.nn.softplus(
        jnp.einsum("sr,rhk->shk", a @ _f32(lp["w_fa"]), _f32(lp["w_fb"]))
        + _f32(lp["dt_bias"]))                                # [S, H, dk]
    beta = jax.nn.sigmoid(a @ _f32(lp["wb"]))                   # [S, H]

    def position(state, now):
        q_t, k_t, v_t, alpha_t, beta_t = now
        state = alpha_t[:, :, None] * state
        written = beta_t[:, None] * (
            v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + jnp.einsum("hk,hv->hkv", k_t, written)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(position, jnp.zeros((heads, dk, dv)),
                        (q, k, v, jnp.exp(g), beta))
    gate = jax.nn.sigmoid(jnp.einsum(
        "sr,rhv->shv", a @ _f32(lp["w_ga"]), _f32(lp["w_gb"])))
    o = rms_norm(o, _f32(lp["o_norm"]), hp["norm_eps"]) * gate
    return jnp.einsum("shv,hvd->sd", o, _f32(lp["wo"]))


def latent_attention(a, lp, hp):
    """Causal latent attention on normed activations a [S, d] -> [S, d],
    every head's keys and values expanded from the latent, one head
    after the other; nothing is turned by position."""
    s, c, nope = a.shape[0], hp["kv_lora_rank"], hp["nope"]
    q = jnp.einsum("sd,dhk->hsk", a, _f32(lp["wq"]))           # [H, S, 192]
    kva = a @ _f32(lp["wkva"])
    latent = rms_norm(kva[:, :c], _f32(lp["kv_norm"]), hp["norm_eps"])
    k_shared = kva[:, c:]                                       # [S, R]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def one_head(xs):
        q_h, w_kvb = xs                        # [S, nope + R], [c, nope + v]
        kv = latent @ _f32(w_kvb)
        scores = (q_h[:, :nope] @ kv[:, :nope].T
                  + q_h[:, nope:] @ k_shared.T) * q_h.shape[-1] ** -0.5
        return jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1) \
            @ kv[:, nope:]

    out = jax.lax.map(one_head, (q, lp["wkvb"].transpose(1, 0, 2)))
    return jnp.einsum("hsv,hvd->sd", out, _f32(lp["wo"]))


def mixer_half(x, lp, hp):
    a = rms_norm(x, _f32(lp["attn_norm"]), hp["norm_eps"])
    return x + (kda(a, lp, hp) if "A_log" in lp
                else latent_attention(a, lp, hp))


def ffn_half(x, lp, hp):
    y = rms_norm(x, _f32(lp["mlp_norm"]), hp["norm_eps"])
    return x + (experts(y, lp, hp) if "we1" in lp else feed_forward(y, lp))


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
    mixer's or one FFN's float32 temporaries are alive at a time.
    Returns a list of [S, vocab] arrays."""
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
