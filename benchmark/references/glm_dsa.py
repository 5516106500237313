"""Plain reference of GLM-5.2's decoder (`model_type` `glm_moe_dsa`; the
equations follow DeepSeek-V3's modelling code for latent attention and
the router, and DeepSeek-V3.2's for the sparse-attention indexer).
Pre-norm blocks, a = RMSNorm(x; attn_norm):

    c_q        = RMSNorm(a Wqa; q_norm)                              [q_lora_rank]
    q_h        = (c_q Wqb)_h = [q_nope_h | q_rope_h]                 h = 1..heads
    [c_kv|k_r] = a Wkva ;  c_kv = RMSNorm(c_kv; kv_norm)             one rotary key for all heads
    q_rope_h, k_r <- rotary positions on interleaved pairs (2i, 2i+1)
    [k_nope_h | v_h] = (c_kv Wkvb)_h
    score[t,s,h] = (q_nope[t,h].k_nope[s,h] + q_rope[t,h].k_r[s]) / sqrt(nope + rope)

    a `full` layer's indexer:
    qI[t,j] = (c_q Wiq)_j ,  kI[s] = LayerNorm(a[s] Wik)              rotary on the first `rope` dims of each
    w[t]    = a[t] Wiw * heads_I^-1/2 * dim_I^-1/2
    I[t,s]  = sum_j w[t,j] relu(qI[t,j] . kI[s]) ,  s <= t
    S[t]    = the index_topk positions s <= t of largest I[t,s]      (`lax.top_k`; all while t < index_topk)
    a `shared` layer uses the S of the nearest `full` layer below it

    o[t,h]  = sum_{s in S[t]} softmax_{s in S[t]}(score[t,s,h]) v[s,h]
    h'      = x + concat_h(o[t,h]) Wo ;   y = RMSNorm(h'; mlp_norm)
    dense:  x' = h' + W2(silu(W1 y) * (W3 y))
    sparse: sig = sigmoid(y Wr) ;  S = top_k of (sig + bias) ;  g_e = scale * sig_e / sum_{e' in S} sig_e'
            x' = h' + SwiGLU_shared(y) + sum_{e in S, e held} g_e SwiGLU_e(y)

Float32 `jax.numpy`, no cache, no kernel, nothing absorbed: keys and
values are expanded a head at a time, one sequence after the other, one
head after the other, one expert after the other (an expert's weights
are cast to float32 when its turn comes, so that a layer's float32
copy never stands whole beside a deployment). It reads the program's
parameter tree and nothing else of the program: `runs`, a list of
stacked runs of like layers; a run with `wiq` is `full`, one with `we1`
is sparse. The experts a tree holds are a contiguous share of those
the router chooses among, `first_expert` on (`hyper`): the pairs routed
elsewhere are another chip's to add, here as in the program. Call it
under `jax.default_matmul_precision("highest")`.

Departures from the published model: no multi-token-prediction layer;
the indexer in the weights' precision without the Hadamard rotation and
fp8 of DeepSeek's kernel (orthogonal, the scores are the same); weights
are random, drawn by the program's initialiser from the seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.references.dense_decoder import (_f32, feed_forward, head,
                                                rms_norm)

INDEX_KEY_EPS = 1e-6


def hyper(config):
    return {
        "n_heads": config["num_attention_heads"],
        "kv_lora_rank": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"],
        "rope_theta": float(config["rope_parameters"]["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "index_topk": config["index_topk"],
        "top_k": config["num_experts_per_tok"],
        "norm_topk": bool(config["norm_topk_prob"]),
        "gate_scale": float(config["routed_scaling_factor"]),
        "scoring": config["scoring_func"],
        "first_expert": config["deployment"]["experts_held"][0],
        "tied": bool(config["tie_word_embeddings"]),
    }


def rotate_pairs(x, theta, n=None):
    """x: [S, ..., D]; position p turns the pair (x[2i], x[2i + 1]) of
    the first `n` (all) of the last axis by p * theta^(-2i/n)."""
    n = n or x.shape[-1]
    pos = jnp.arange(x.shape[0], dtype=jnp.float32)
    ang = pos[:, None] * theta ** (-jnp.arange(0, n, 2,
                                               dtype=jnp.float32) / n)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (n // 2,))
    a, b = x[..., 0:n:2], x[..., 1:n:2]
    turned = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                        a * jnp.sin(ang) + b * jnp.cos(ang)], -1)
    return jnp.concatenate(
        [turned.reshape(x.shape[:-1] + (n,)), x[..., n:]], -1)


def layer_norm(x, w, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def select(a, c_q, lp, hp):
    """The indexer of a `full` layer: mask [S, S], row t true at the
    keys t attends."""
    s = a.shape[0]
    qi = rotate_pairs(jnp.einsum("sr,rjd->sjd", c_q, _f32(lp["wiq"])),
                      hp["rope_theta"], hp["rope"])
    ki = rotate_pairs(layer_norm(a @ _f32(lp["wik"]), _f32(lp["ik_norm"]),
                                 _f32(lp["ik_bias"]), INDEX_KEY_EPS),
                      hp["rope_theta"], hp["rope"])
    n_heads, dim = qi.shape[1:]
    w = a @ _f32(lp["wiw"]) * n_heads ** -0.5 * dim ** -0.5       # [S, J]

    def one_head(total, head):
        q_j, w_j = head
        return total + w_j[:, None] * jax.nn.relu(q_j @ ki.T), None

    scores, _ = jax.lax.scan(one_head, jnp.zeros((s, s)),
                             (qi.transpose(1, 0, 2), w.T))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    if hp["index_topk"] >= s:
        return causal
    chosen = jax.lax.top_k(scores, hp["index_topk"])[1]
    mask = jnp.zeros((s, s), bool).at[jnp.arange(s)[:, None], chosen].set(
        True)
    return mask & causal


def attention(a, lp, hp, selected):
    """One block's attention on normed activations a [S, d]. Returns
    (its output [S, d], the selection it used)."""
    c, nope = hp["kv_lora_rank"], hp["nope"]
    c_q = rms_norm(a @ _f32(lp["wqa"]), _f32(lp["q_norm"]), hp["norm_eps"])
    q = jnp.einsum("sr,rhk->shk", c_q, _f32(lp["wqb"]))
    q_rope = rotate_pairs(q[..., nope:], hp["rope_theta"])
    kva = a @ _f32(lp["wkva"])
    c_kv = rms_norm(kva[:, :c], _f32(lp["kv_norm"]), hp["norm_eps"])
    k_rope = rotate_pairs(kva[:, c:], hp["rope_theta"])             # [S, R]
    if "wiq" in lp:
        selected = select(a, c_q, lp, hp)
    scale = (nope + hp["rope"]) ** -0.5

    def one_head(xs):
        q_n, q_r, w_kvb = xs                  # [S, nope], [S, R], [c, nope+v]
        kv = c_kv @ _f32(w_kvb)
        scores = (q_n @ kv[:, :nope].T + q_r @ k_rope.T) * scale
        probs = jax.nn.softmax(jnp.where(selected, scores, -jnp.inf), -1)
        return probs @ kv[:, nope:]

    out = jax.lax.map(one_head, (
        q[..., :nope].transpose(1, 0, 2), q_rope.transpose(1, 0, 2),
        lp["wkvb"].transpose(1, 0, 2)))                              # [H, S, v]
    return jnp.einsum("hsv,hvd->sd", out, _f32(lp["wo"])), selected


def experts(y, lp, hp):
    """The sparse layer's feed-forward on y [S, d]: the shared expert
    and, of each token's chosen experts, those this tree holds."""
    logits = y @ _f32(lp["router"])
    sig = jax.nn.sigmoid(logits) if hp["scoring"] == "sigmoid" \
        else jax.nn.softmax(logits, -1)
    chosen = jax.lax.top_k(sig + _f32(lp["router_bias"]), hp["top_k"])[1]
    gates = jnp.take_along_axis(sig, chosen, -1)
    if hp["norm_topk"]:
        gates = gates / gates.sum(-1, keepdims=True)
    gates = gates * hp["gate_scale"]

    def one(out, expert):
        e, w1, w3, w2 = expert
        weight = jnp.where(chosen == e, gates, 0.0).sum(-1)          # [S]
        ffn = feed_forward(y, {"w1": w1, "w3": w3, "w2": w2})
        return out + weight[:, None] * ffn, None

    shared = feed_forward(y, {"w1": lp["ws1"], "w3": lp["ws3"],
                              "w2": lp["ws2"]})
    held = hp["first_expert"] + jnp.arange(lp["we1"].shape[0])
    out, _ = jax.lax.scan(one, shared,
                          (held, lp["we1"], lp["we3"], lp["we2"]))
    return out


def block(x, selected, lp, hp):
    """One pre-norm block. x: [S, d] -> (x, the selection it used)."""
    attn, selected = attention(
        rms_norm(x, _f32(lp["attn_norm"]), hp["norm_eps"]), lp, hp, selected)
    x = x + attn
    y = rms_norm(x, _f32(lp["mlp_norm"]), hp["norm_eps"])
    return x + (experts(y, lp, hp) if "we1" in lp
                else feed_forward(y, lp)), selected


def layers_of(params):
    """Every layer's parameters, bottom to top, out of the runs."""
    for run in params["runs"]:
        for i in range(run["wqa"].shape[0]):
            yield jax.tree.map(lambda x: x[i], run)


def sequence_logits(params, tokens, hp):
    """One sequence: tokens [S] -> logits [S, vocab], float32."""
    x = _f32(params["embed"])[tokens]
    selected = None
    for lp in layers_of(params):
        x, selected = block(x, selected, lp, hp)
    return head(params, x, hp)


def forward(params, tokens, hp):
    """tokens [B, S] -> logits [B, S, vocab], one sequence at a time."""
    return jax.lax.map(lambda t: sequence_logits(params, t, hp), tokens)


def logits_layer_by_layer(params, sequences, hp):
    """`sequence_logits` of each of `sequences`, as one jitted call a
    layer and sequence: beside a model that fills the chip only one
    layer's float32 temporaries are alive at a time. Returns a list of
    [S, vocab] arrays."""
    one_block = jax.jit(functools.partial(block, hp=hp))
    xs = [(jax.jit(lambda e, t: _f32(e[t]))(params["embed"], t),
           jnp.zeros((len(t), len(t)), bool)) for t in sequences]
    for lp in layers_of(params):
        xs = [one_block(x, selected, lp) for x, selected in xs]
    top = {k: v for k, v in params.items() if k != "runs"}
    to_logits = jax.jit(functools.partial(head, hp=hp))
    return [to_logits(top, x) for x, _ in xs]
