"""Plain reference of Command A+'s decoder (`model_type` `cohere2_moe`,
command-a-plus-05-2026), written from the catalog's `config` beside the
model-configs guide. Parallel blocks, h = LayerNorm(x) (the mean taken
off, a weight, no bias, eps `layer_norm_eps`):

    x' = x + Attn(h) + MoE(h)                          one norm, both halves read h
    Attn: q = h Wq [heads x 128], k = h Wk, v = h Wv [kv heads x 128]
          out = softmax(q k^T / sqrt(128) + mask) v Wo     query head i reads kv head i // (heads / kv heads)
      sliding layer: q, k turned by rotary positions (theta, interleaved pairs (2i, 2i+1), all 128 dims);
                     key j seen from i iff j <= i and i - j < sliding_window
      full layer:    no positional encoding at all; key j seen iff j <= i
    MoE:  s = sigmoid(h Wr) in R^experts ;  T = the num_experts_per_tok largest of s ;  g_e = s_e / sum_T s
          routed = sum_{e in T, e held} g_e E_e(h) ,  E(h) = W2 (silu(W1 h) * W3 h)
          shared = (1 / n) sum_{j < n} S_j(h) ,  the same form, n = num_shared_experts
          MoE(h) = routed + shared
    logits = logit_scale * LayerNorm_f(x_L) Embed^T     tied

Float32 `jax.numpy`, no cache, no ring, no blocks of keys: the whole
sequence at once under a dense [S, S] mask a kind of layer, one
sequence after the other, one head after the other, one expert after
the other, each of the shared experts computed apart (a matrix is cast
to float32 when its turn comes, so that a layer's float32 copy never
stands whole beside a deployment). It reads the program's parameter
tree and nothing else of the program: `runs`, a list of stacked runs of
like layers, whose kinds are `layer_types` of the layers held (`hyper`:
the tree's own layers, or as many of the share's top ones as it has);
the program keeps the shared experts side by side in `ws1`, `ws3`
(columns) and `ws2` (rows), and the reference cuts them apart again.
The experts a tree holds are a contiguous share of those the router
chooses among, `first_expert` on: the pairs routed elsewhere are another
chip's to add, here as in the program. Call it under
`jax.default_matmul_precision("highest")`.

Departures from the published model: no vision tower; weights are
random, drawn by the program's initialiser from the seed. What the
catalog's `config` leaves open and how it was read is the
configuration's `assumed`.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.dense_decoder import _f32
from benchmark.references.glm_dsa import rotate_pairs


def hyper(config):
    kinds = [config["layer_types"][i].split("_")[0]
             for i in config["deployment"]["layers_held"]]
    return {
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["layer_norm_eps"]),
        "window": config["sliding_window"],
        "kinds": tuple(kinds),
        "top_k": config["num_experts_per_tok"],
        "norm_topk": bool(config["norm_topk_prob"]),
        "first_expert": config["deployment"]["experts_held"][0],
        "n_shared": config["num_shared_experts"],
        "average": config["shared_expert_combination_strategy"] == "average",
        "logit_scale": float(config["logit_scale"]),
    }


def layer_norm(x, w, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w


def mask(s, kind, hp):
    """[S, S]: row i true at the keys j it sees."""
    back = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    return (back >= 0) & (back < hp["window"]) if kind == "sliding" \
        else back >= 0


def attention(h, lp, kind, hp):
    """One layer's attention on normed activations h [S, d]."""
    q = jnp.einsum("sd,dhk->shk", h, _f32(lp["wq"]))
    k = jnp.einsum("sd,dhk->shk", h, _f32(lp["wk"]))
    v = jnp.einsum("sd,dhk->shk", h, _f32(lp["wv"]))
    if kind == "sliding":
        q = rotate_pairs(q, hp["rope_theta"])
        k = rotate_pairs(k, hp["rope_theta"])
    group = hp["n_heads"] // hp["n_kv_heads"]
    seen = mask(h.shape[0], kind, hp)
    scale = q.shape[-1] ** -0.5

    def one_head(xs):
        q_h, head = xs                                         # [S, D]
        scores = q_h @ k[:, head // group].T * scale
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return probs @ v[:, head // group]

    out = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                                 jnp.arange(hp["n_heads"])))  # [H, S, D]
    return jnp.einsum("hsk,hkd->sd", out, _f32(lp["wo"]))


def swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ _f32(w1)) * (h @ _f32(w3))) @ _f32(w2)


def experts(h, run, i, hp):
    """Layer i's expert half on h [S, d]: of each token's chosen
    experts those this tree holds, and the shared experts, each apart.
    The matrices are picked out of the run's stacks one at a time."""
    s = jax.nn.sigmoid(h @ _f32(run["router"][i]))
    gates, chosen = jax.lax.top_k(s, hp["top_k"])
    if hp["norm_topk"]:
        gates = gates / gates.sum(-1, keepdims=True)

    def routed(out, e):
        weight = jnp.where(chosen == hp["first_expert"] + e, gates,
                           0.0).sum(-1)                              # [S]
        return out + weight[:, None] * swiglu(
            h, run["we1"][i, e], run["we3"][i, e], run["we2"][i, e]), None

    out, _ = jax.lax.scan(routed, jnp.zeros_like(h),
                          jnp.arange(run["we1"].shape[1]))
    n = hp["n_shared"]
    width = run["ws1"].shape[-1] // n
    shared = sum(swiglu(h, run["ws1"][i][:, j * width:(j + 1) * width],
                        run["ws3"][i][:, j * width:(j + 1) * width],
                        run["ws2"][i][j * width:(j + 1) * width])
                 for j in range(n))
    return out + (shared / n if hp["average"] else shared)


def block(x, run, i, kind, hp):
    """Layer `i` of the stacked `run`, of `kind`. x: [S, d]."""
    lp = {name: run[name][i] for name in ("attn_norm", "wq", "wk", "wv",
                                          "wo")}
    h = layer_norm(x, _f32(lp["attn_norm"]), hp["norm_eps"])
    return x + attention(h, lp, kind, hp) + experts(h, run, i, hp)


def layers_of(params, hp):
    """(run, index in it, kind) of every layer, bottom to top. The runs
    are alike in their leaves, so their kinds come from `hyper`: a tree
    of fewer layers than the share holds the share's top ones (the
    runner's shallow copy)."""
    depth = sum(run["wq"].shape[0] for run in params["runs"])
    runs = [(kind, len(list(group))) for kind, group in
            itertools.groupby(hp["kinds"][len(hp["kinds"]) - depth:])]
    assert [n for _, n in runs] == [run["wq"].shape[0]
                                    for run in params["runs"]], runs
    for (kind, n), run in zip(runs, params["runs"]):
        for i in range(n):
            yield run, i, kind


def head(params, x, hp):
    x = layer_norm(x, _f32(params["final_norm"]), hp["norm_eps"])
    return hp["logit_scale"] * (x @ _f32(params["embed"]).T)


def sequence_logits(params, tokens, hp):
    """One sequence: tokens [S] -> logits [S, vocab], float32."""
    x = _f32(params["embed"])[tokens]
    for run, i, kind in layers_of(params, hp):
        x = block(x, run, i, kind, hp)
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
    made (four of 4,351 positions are 2.3 GB on the device together)."""
    one_block = jax.jit(functools.partial(block, hp=hp),
                        static_argnames=("kind",))
    xs = [jax.jit(lambda e, t: _f32(e[t]))(params["embed"], t)
          for t in sequences]
    for run, i, kind in layers_of(params, hp):
        xs = [one_block(x, run, jnp.int32(i), kind=kind) for x in xs]
    top = {k: v for k, v in params.items() if k != "runs"}
    to_logits = jax.jit(functools.partial(head, hp=hp))
    return [np.asarray(to_logits(top, x)) for x in xs]
