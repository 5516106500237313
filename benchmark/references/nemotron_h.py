"""Plain reference of Nemotron 3 Super's decoder (`model_type`
`nemotron_h`; the equations follow the Mamba-2 paper's recurrence, the
`nemotron_h` modelling code's layer and DeepSeek-V3's noaux_tc router,
written from memory of them). Every published layer is one part alone,

    x' = x + part(RMSNorm(x; w, eps))

and the part is one of three. With a = the normed input:

M, Mamba-2 (H heads of P channels, G groups of N states, g(h) = h // (H/G)):
    [z | xBC | dt] = a [W_z | W_xbc | W_dt]                       no bias
    xBC_t  <- silu(sum_{j<K} w_conv[:, j] xBC_{t-K+1+j} + b_conv)   zeros before the start
    [xs (H x P) | B (G x N) | C (G x N)] = xBC_t
    dt_t   = softplus(dt_t + dt_bias) ;  A = -exp(A_log)
    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] xs_t[h] (outer) B_t[g(h)]      S_{-1} = 0
    y_t[h] = S_t[h] C_t[g(h)] + D[h] xs_t[h]
    part   = GroupRMSNorm(y_t * silu(z_t); w_n, G groups) W_o        the gate first, then the norm

*, attention: q of `n_heads`, k and v of `n_kv_heads` heads, causal
    softmax at head_dim^-1/2, W_o; no positional encoding.

E, LatentMoE:
    sig  = sigmoid(a W_r)                         the router reads the full width
    S    = top_k of (sig + bias)                  the bias chooses, it does not weigh
    g_e  = scale * sig_e / sum_{e' in S} sig_e'
    u    = a W_dn                                 shared by all experts
    part = (sum_{e in S, e held} g_e W2_e relu(W1_e u)^2) W_up + Ws2 relu(Ws1 a)^2

Float32 `jax.numpy`, no cache, no chunks, no kernels: the recurrence is
a `lax.scan` over single positions, the convolution the written sum,
the experts a loop over the held range (an expert's weights are cast to
float32 when its turn comes, so that a layer's float32 copy never stands
whole beside a deployment), one sequence after the other. It reads the
program's parameter tree and nothing else of the program: `runs`, a
list of stacked runs of like blocks; a run with `A_log` has a Mamba-2
mixer, one with `wq` attention, one with `we1` an expert layer after
its mixer (or alone). The experts a tree holds are a contiguous share
of those the router chooses among, `first_expert` on (`hyper`): the
pairs routed elsewhere are another chip's to add, here as in the
program. Call it under `jax.default_matmul_precision("highest")`.

Departures from the published model: no multi-token-prediction layer;
weights are random, drawn by the program's initialiser from the seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.references.dense_decoder import _f32, head, rms_norm


def hyper(config):
    return {
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "ssm_groups": config["n_groups"],
        "norm_eps": float(config["layer_norm_epsilon"]),
        "top_k": config["num_experts_per_tok"],
        "norm_topk": bool(config["norm_topk_prob"]),
        "gate_scale": float(config["routed_scaling_factor"]),
        "first_expert": config["deployment"]["experts_held"][0],
        "tied": bool(config["tie_word_embeddings"]),
    }


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def mamba2(a, lp, hp):
    """One Mamba-2 mixer on normed activations a [S, d] -> [S, d]."""
    s = a.shape[0]
    heads, width = lp["w_z"].shape[1:]
    groups = hp["ssm_groups"]
    z = jnp.einsum("sd,dhp->shp", a, _f32(lp["w_z"]))
    xbc = a @ _f32(lp["w_xbc"])
    dt = jax.nn.softplus(a @ _f32(lp["w_dt"]) + _f32(lp["dt_bias"]))
    w, k = _f32(lp["conv_w"]), lp["conv_w"].shape[1]
    before = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(sum(w[:, j] * before[j:j + s] for j in range(k))
                      + _f32(lp["conv_b"]))
    xs = xbc[:, :heads * width].reshape(s, heads, width)
    b_mat, c_mat = (m.reshape(s, groups, -1) for m in jnp.split(
        xbc[:, heads * width:], 2, -1))
    group_of = jnp.arange(heads) // (heads // groups)
    decay = jnp.exp(dt * -jnp.exp(_f32(lp["A_log"])))            # [S, H]

    def position(state, now):
        x_t, b_t, c_t, dt_t, decay_t = now
        state = decay_t[:, None, None] * state + jnp.einsum(
            "h,hp,hn->hpn", dt_t, x_t, b_t[group_of])
        return state, jnp.einsum("hpn,hn->hp", state, c_t[group_of])

    _, y = jax.lax.scan(
        position, jnp.zeros((heads, width, b_mat.shape[-1])),
        (xs, b_mat, c_mat, dt, decay))
    y = (y + _f32(lp["D"])[:, None] * xs) * jax.nn.silu(z)
    y = y.reshape(s, groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + hp["norm_eps"])
    return jnp.einsum("shp,hpd->sd",
                      (y.reshape(s, -1) * _f32(lp["ssm_norm"])).reshape(
                          s, heads, width), _f32(lp["wo"]))


def attention(a, lp, hp):
    """Causal grouped-query attention with no positional encoding, one
    head after the other. a [S, d] -> [S, d]."""
    s = a.shape[0]
    rep = hp["n_heads"] // hp["n_kv_heads"]
    q = jnp.einsum("sd,dhk->hsk", a, _f32(lp["wq"]))
    k = jnp.einsum("sd,dhk->hsk", a, _f32(lp["wk"]))
    v = jnp.einsum("sd,dhk->hsk", a, _f32(lp["wv"]))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def one_head(xs):
        q_h, i = xs
        scores = q_h @ k[i // rep].T * q_h.shape[-1] ** -0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return probs @ v[i // rep]

    out = jax.lax.map(one_head, (q, jnp.arange(hp["n_heads"])))
    return jnp.einsum("hsk,hkd->sd", out, _f32(lp["wo"]))


def route(a, lp, hp):
    """The router on normed activations a [S, d]: (the experts each
    token chose [S, k], their gates [S, k])."""
    sig = jax.nn.sigmoid(a @ _f32(lp["router"]))
    chosen = jax.lax.top_k(sig + _f32(lp["router_bias"]), hp["top_k"])[1]
    gates = jnp.take_along_axis(sig, chosen, -1)
    if hp["norm_topk"]:
        gates = gates / gates.sum(-1, keepdims=True)
    return chosen, gates * hp["gate_scale"]


def routed(u, chosen, gates, first, we1, we2):
    """The gate-weighted sum, in the latent, of the experts `first` on
    whose matrices are `we1`, `we2` [n, ...], one after the other, over
    the tokens that chose them. u [S, latent] -> [S, latent]."""
    def one(out, expert):
        # The barrier keeps the casts to float32 inside the loop: moved
        # out of it, the whole stack would stand in float32 at once.
        e, w1, w2 = jax.lax.optimization_barrier(expert)
        weight = jnp.where(chosen == e, gates, 0.0).sum(-1)          # [S]
        return out + weight[:, None] * (relu2(u @ _f32(w1)) @ _f32(w2)), \
            None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (first + jnp.arange(we1.shape[0]), we1, we2))
    return out


def up_and_shared(a, latent, lp):
    """The routed experts' sum projected up, and the shared expert."""
    return latent @ _f32(lp["w_up"]) \
        + relu2(a @ _f32(lp["ws1"])) @ _f32(lp["ws2"])


def experts(a, lp, hp):
    """The expert layer on normed activations a [S, d]: the shared
    expert and, of each token's chosen experts, those this tree holds,
    in the latent."""
    chosen, gates = route(a, lp, hp)
    latent = routed(a @ _f32(lp["w_dn"]), chosen, gates,
                    hp["first_expert"], lp["we1"], lp["we2"])
    return up_and_shared(a, latent, lp)


def mixer_half(x, lp, hp):
    """A block's mixer, where it has one. x: [S, d]."""
    if "attn_norm" not in lp:
        return x
    a = rms_norm(x, _f32(lp["attn_norm"]), hp["norm_eps"])
    return x + (mamba2(a, lp, hp) if "A_log" in lp else attention(a, lp, hp))


def block(x, lp, hp):
    """The published layers one block of the tree holds: a mixer, an
    expert layer, or the one after the other. x: [S, d]."""
    x = mixer_half(x, lp, hp)
    if "mlp_norm" in lp:
        x = x + experts(rms_norm(x, _f32(lp["mlp_norm"]), hp["norm_eps"]),
                        lp, hp)
    return x


def blocks_of(params):
    """Every block's parameters, bottom to top, out of the runs."""
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


# `logits_layer_by_layer` hands the routed experts over this many at a
# time, so that no more of them stand in float32 at once.
EXPERT_BLOCK = 16


def logits_layer_by_layer(params, sequences, hp):
    """`sequence_logits` of each of `sequences`, as jitted calls a
    block and sequence, an expert layer's routed experts `EXPERT_BLOCK`
    a call: beside a model that fills the chip only one mixer's, or a
    few experts', float32 temporaries are alive at a time. Returns a
    list of [S, vocab] arrays."""
    mix = jax.jit(functools.partial(mixer_half, hp=hp))
    some = jax.jit(routed)

    @jax.jit
    def enter(x, lp):
        a = rms_norm(x, _f32(lp["mlp_norm"]), hp["norm_eps"])
        return a, a @ _f32(lp["w_dn"]), route(a, lp, hp)

    leave = jax.jit(lambda x, a, latent, lp: x + up_and_shared(a, latent,
                                                               lp))

    def expert_layer(x, lp):
        held = {k: lp.pop(k) for k in ("we1", "we2")}
        a, u, (chosen, gates) = enter(x, lp)
        latent = jnp.zeros_like(u)
        for i in range(0, held["we1"].shape[0], EXPERT_BLOCK):
            latent = latent + some(
                u, chosen, gates, hp["first_expert"] + i,
                *(w[i:i + EXPERT_BLOCK] for w in held.values()))
        return leave(x, a, latent, lp)

    xs = [jax.jit(lambda e, t: _f32(e[t]))(params["embed"], t)
          for t in sequences]
    for lp in blocks_of(params):
        xs = [mix(x, lp) for x in xs]
        if "mlp_norm" in lp:
            xs = [expert_layer(x, dict(lp)) for x in xs]
    top = {k: v for k, v in params.items() if k != "runs"}
    to_logits = jax.jit(functools.partial(head, hp=hp))
    return [to_logits(top, x) for x in xs]
