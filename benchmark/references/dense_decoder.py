"""Plain reference of the dense decoder (Mistral-7B, the Llama family):
pre-norm blocks of grouped-query causal attention with rotary positions
(split-half, as `transformers` rotates) and a SwiGLU feed-forward,
RMSNorm, untied or tied output head. Straightforward `jax.numpy` in
float32: no kernel, no cache, no fused loss, and no batching: one
sequence goes through the whole model after the other, so that the
[heads, 4096, 4096] scores of a training batch fit beside the model. It
reads the program's parameter tree (stacked layers; `wq` as [d, heads,
head_dim]) and nothing else of the program. Call it under
`jax.default_matmul_precision("highest")`: on a TPU a float32 matmul is
otherwise done in bfloat16 passes.

Departure from the published model: none in the equations; weights are
random, drawn by the program's initialiser from the seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def hyper(config):
    """What the equations need, from the configuration file's
    `config.json` keys."""
    return {
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "tied": bool(config["tie_word_embeddings"]),
    }


def _f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotate(x, theta):
    """x: [S, H, D]; position p turns the pair (x[i], x[i + D/2]) by
    p * theta^(-2i/D)."""
    d = x.shape[-1]
    pos = jnp.arange(x.shape[0], dtype=jnp.float32)
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(h, lp, hp):
    """Causal grouped-query attention of one block. h: [S, d]."""
    q = jnp.einsum("sd,dhk->shk", h, _f32(lp["wq"]))
    k = jnp.einsum("sd,dhk->shk", h, _f32(lp["wk"]))
    v = jnp.einsum("sd,dhk->shk", h, _f32(lp["wv"]))
    q, k = rotate(q, hp["rope_theta"]), rotate(k, hp["rope_theta"])
    group = hp["n_heads"] // hp["n_kv_heads"]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    s = q.shape[0]
    scores = jnp.einsum("qhk,thk->hqt", q, k) * hp["head_dim"] ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    out = jnp.einsum("hqt,thk->qhk", probs, v)
    return jnp.einsum("qhk,hkd->qd", out, _f32(lp["wo"]))


def feed_forward(h, lp):
    gate = jax.nn.silu(h @ _f32(lp["w1"]))
    return (gate * (h @ _f32(lp["w3"]))) @ _f32(lp["w2"])


def layer_params(params, i):
    return jax.tree.map(lambda x: x[i], params["layers"])


def head(params, x, hp):
    x = rms_norm(x, _f32(params["final_norm"]), hp["norm_eps"])
    out = params["embed"].T if hp["tied"] else params["out"]
    return x @ _f32(out)


def block(x, lp, hp):
    """One pre-norm block. x: [S, d]."""
    x = x + attention(
        rms_norm(x, _f32(lp["attn_norm"]), hp["norm_eps"]), lp, hp)
    return x + feed_forward(
        rms_norm(x, _f32(lp["mlp_norm"]), hp["norm_eps"]), lp)


def sequence_logits(params, tokens, hp):
    """One sequence: tokens [S] -> logits [S, vocab], float32."""
    x = _f32(params["embed"])[tokens]
    for i in range(params["layers"]["wq"].shape[0]):
        x = block(x, layer_params(params, i), hp)
    return head(params, x, hp)


def logits_layer_by_layer(params, sequences, hp):
    """`sequence_logits` of each of `sequences` (equal lengths), as one
    jitted call a layer and sequence: beside a model that fills the chip
    only one layer's float32 copy is alive at a time. Returns a list of
    [S, vocab] arrays."""
    one_block = jax.jit(functools.partial(block, hp=hp))
    xs = [jax.jit(lambda e, t: _f32(e[t]))(params["embed"], t)
          for t in sequences]
    for i in range(params["layers"]["wq"].shape[0]):
        lp = layer_params(params, i)
        xs = [one_block(x, lp) for x in xs]
    top = {k: v for k, v in params.items() if k != "layers"}
    to_logits = jax.jit(functools.partial(head, hp=hp))
    return [to_logits(top, x) for x in xs]


def cross_entropy(logits, targets):
    """Summed over the sequence. logits [S, vocab], targets [S]."""
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, targets[:, None], -1).sum()


def forward(params, tokens, hp):
    """tokens [B, S] -> logits [B, S, vocab], one sequence at a time."""
    return jax.lax.map(lambda t: sequence_logits(params, t, hp), tokens)


def loss(params, tokens, targets, hp):
    """Mean next-token cross-entropy, as the train step reports it."""
    sums = jax.lax.map(
        lambda tt: cross_entropy(sequence_logits(params, tt[0], hp), tt[1]),
        (tokens, targets))
    return sums.sum() / tokens.size
