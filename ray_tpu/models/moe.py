"""Mixture-of-Experts decoder: the Llama block with its feed-forward
replaced by `n_experts` SwiGLU experts, of which every token uses the
`n_experts_per_token` its router scores highest. Two published models
run through it: Mixtral-8x7B (8 experts, 2 a token, gates renormalised
over the chosen) and OLMoE-1B-7B (64 experts, 8 a token, gates as the
softmax gives them, RMSNorm on the projected q and k); `MoEConfig`
holds what they differ in.

The expert layer is dropless sparse dispatch (`_moe_ffn`): float32
softmax over the router's logits, `lax.top_k` (exactly k experts a
token, ties to the lower index), the Switch-Transformer load-balancing
loss, then the (token, expert) pairs sorted by expert, the tokens
gathered into that order, the three SwiGLU products as grouped matmuls
(`lax.ragged_dot`, which the TPU compiler turns into a grouped-matmul
kernel of its own) whose group sizes are known only on the device, and
a gate-weighted sum back in token order. (Megablox's Pallas `gmm` ran
the products a third faster on the v5e, but tracing its group metadata
for every call adds 2.4 to 4 s to a warm process's first step: PERF.md,
PR 27.) Every pair is computed whatever the load of its expert: no
capacity, nothing dropped, static shapes ([tokens * k, d] rows in all).
Both permutations are row gathers in the forward and in the backward
pass (`_spread` and `_collect` are each other's transpose), never a
scatter.

On a mesh tokens stay on the chip that holds them: dispatch, the
grouped matmuls and the combine run under `shard_map` over the batch
and sequence axes, each shard on its own tokens with every expert's
weights gathered at the edge (the bytes FSDP moves anyway). A mesh with
an `expert` axis gives the right result the same way, every chip
computing all experts for its tokens; making that fast needs an
all-to-all of tokens and is not here.

No reference equivalent (the reference has no model code); this exists
so sparse experts are a first-class, exercised layer (SURVEY.md §2
parallelism inventory calls EP "absent entirely" upstream).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import (
    LlamaConfig,
    _attention,
    _embed_lookup,
    _init_layer,
    _vocab_sharded,
)
from ray_tpu.ops.cross_entropy import (fused_linear_cross_entropy,
                                       softmax_cross_entropy)
from ray_tpu.ops.norms import rms_norm_reference
from ray_tpu.ops.rope import (apply_rope, rope_frequencies,
                              rope_from_positions)
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES,
    logical_to_mesh_axes,
    tree_shardings,
    with_logical_constraint,
)


@dataclasses.dataclass(frozen=True)
class MoEConfig(LlamaConfig):
    n_experts: int = 8
    n_experts_per_token: int = 2
    aux_loss_coeff: float = 0.01
    # Divide a token's k gates by their sum (Mixtral). OLMoE weighs its
    # experts by the softmax over all of them as it is.
    norm_topk_prob: bool = True
    # RMSNorm with a learned weight over the whole projected q and k
    # vectors (all heads together), before rope (OLMoE).
    qk_norm: bool = False

    @staticmethod
    def debug_moe() -> "MoEConfig":
        return MoEConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                         n_kv_heads=2, hidden_dim=128, max_seq_len=128,
                         dtype=jnp.float32, remat=False, n_experts=4,
                         n_experts_per_token=2)

    @staticmethod
    def mixtral_8x7b() -> "MoEConfig":
        return MoEConfig(vocab_size=32000, dim=4096, n_layers=32,
                         n_heads=32, n_kv_heads=8, hidden_dim=14336,
                         rope_theta=1e6, n_experts=8,
                         n_experts_per_token=2)

    @staticmethod
    def olmoe_1b_7b() -> "MoEConfig":
        return MoEConfig(vocab_size=50304, dim=2048, n_layers=16,
                         n_heads=16, n_kv_heads=16, hidden_dim=1024,
                         max_seq_len=4096, rope_theta=1e4, norm_eps=1e-5,
                         n_experts=64, n_experts_per_token=8,
                         norm_topk_prob=False, qk_norm=True)


def _init_moe_layer(cfg: MoEConfig, key) -> Dict[str, Any]:
    base = _init_layer(cfg, key)
    k_router, k1, k2, k3 = jax.random.split(jax.random.fold_in(key, 99), 4)
    init = jax.nn.initializers.normal(stddev=0.02)
    e, d, h = cfg.n_experts, cfg.dim, cfg.hidden_dim
    # Replace the dense FFN with per-expert weights + a router.
    for dead in ("w1", "w2", "w3"):
        del base[dead]
    base["router"] = init(k_router, (d, e), cfg.dtype)
    base["we1"] = init(k1, (e, d, h), cfg.dtype)
    base["we3"] = init(k2, (e, d, h), cfg.dtype)
    base["we2"] = init(k3, (e, h, d), cfg.dtype) * (h ** -0.5)
    if cfg.qk_norm:
        base["q_norm"] = jnp.ones(cfg.n_heads * cfg.head_dim, cfg.dtype)
        base["k_norm"] = jnp.ones(cfg.n_kv_heads * cfg.head_dim, cfg.dtype)
    return base


def init_moe_params(cfg: MoEConfig, rng) -> Dict[str, Any]:
    k_embed, k_out, k_layers = jax.random.split(rng, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    layers = jax.vmap(functools.partial(_init_moe_layer, cfg))(layer_keys)
    params = {
        "embed": jax.nn.initializers.normal(0.02)(
            k_embed, (cfg.vocab_size, cfg.dim), cfg.dtype),
        "layers": layers,
        "final_norm": jnp.ones(cfg.dim, cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["out"] = jax.nn.initializers.normal(0.02)(
            k_out, (cfg.dim, cfg.vocab_size), cfg.dtype)
    return params


# Logical axes of the three expert matrices without the layer axis: how
# they are sharded where they enter the expert layer's shard_map.
_EXPERT_AXES = {
    "we1": ("expert", "embed", "mlp"),
    "we3": ("expert", "embed", "mlp"),
    "we2": ("expert", "mlp", "embed"),
}


def moe_param_logical_axes(cfg: MoEConfig) -> Dict[str, Any]:
    layer = {
        "attn_norm": (None, "norm"),
        "wq": (None, "embed", "heads", "head_dim"),
        "wk": (None, "embed", "kv_heads", "head_dim"),
        "wv": (None, "embed", "kv_heads", "head_dim"),
        "wo": (None, "heads", "head_dim", "embed"),
        "mlp_norm": (None, "norm"),
        "router": (None, "embed", None),
        **{name: (None, *axes) for name, axes in _EXPERT_AXES.items()},
    }
    if cfg.qk_norm:
        layer["q_norm"] = (None, "norm")
        layer["k_norm"] = (None, "norm")
    axes = {
        "embed": ("vocab", "embed"),
        "layers": layer,
        "final_norm": ("norm",),
    }
    if not cfg.tie_embeddings:
        axes["out"] = ("embed", "vocab")
    return axes


def init_moe_params_sharded(cfg: MoEConfig, mesh, rng,
                            rules=DEFAULT_RULES):
    shardings = tree_shardings(mesh, moe_param_logical_axes(cfg), rules)
    return jax.jit(functools.partial(init_moe_params, cfg),
                   out_shardings=shardings)(rng)


# -- the expert layer ---------------------------------------------------------
#
# A token's k choices are the pairs t*k .. t*k + k-1. `order` lists the
# pairs sorted by expert (stable, so an expert's tokens stay in token
# order) and `inv` is its inverse: pair p sits in row inv[p].


def _rows(x, idx):
    return x.at[idx].get(mode="promise_in_bounds")


@jax.custom_vjp
def _spread(x, order, inv):
    """x [T, D] -> [T*k, D]: each pair's token, in expert order."""
    return _rows(x, order // (order.size // x.shape[0]))


@jax.custom_vjp
def _collect(y, gates, order, inv):
    """y [T*k, D] in expert order, gates [T, k] float32 -> [T, D]: every
    token's k rows, weighted by its gates, summed in float32."""
    t, k = gates.shape
    return jnp.einsum("tkd,tk->td", _rows(y, inv).reshape(t, k, -1), gates,
                      preferred_element_type=jnp.float32).astype(y.dtype)


# Each is the other's transpose, so the backward pass is row gathers too
# (autodiff would transpose a gather into a scatter-add).
def _spread_bwd(res, g):
    order, inv, t = res
    summed = _rows(g, inv).reshape(t, order.size // t, -1).sum(
        1, dtype=jnp.float32)
    return summed.astype(g.dtype), None, None


def _collect_bwd(res, g):
    y, gates, order, inv = res
    g_rows = _rows(g, order // gates.shape[1])  # each pair's token's
    d_gates = _rows(jnp.einsum("pd,pd->p", g_rows, y,
                               preferred_element_type=jnp.float32), inv)
    d_y = g_rows * _rows(gates.reshape(-1), order)[:, None]
    return (d_y.astype(y.dtype), d_gates.reshape(gates.shape), None, None)


_spread.defvjp(lambda x, order, inv: (_spread(x, order, inv),
                                      (order, inv, x.shape[0])),
               _spread_bwd)
_collect.defvjp(lambda y, gates, order, inv: (_collect(y, gates, order, inv),
                                              (y, gates, order, inv)),
                _collect_bwd)


def _expert_counts(top_i, n_experts):
    """How many of the pairs chose each expert: [E] int32."""
    hits = top_i[..., None] == jnp.arange(n_experts, dtype=top_i.dtype)
    return hits.sum(tuple(range(top_i.ndim)), dtype=jnp.int32)


def _sparse_experts(x, gates, top_i, we1, we3, we2):
    """The chosen experts of the tokens at hand. x [T, D], gates and
    top_i [T, k], weights [E, ...] -> [T, D]."""
    with jax.named_scope("moe_dispatch"):
        order = jnp.argsort(top_i.reshape(-1), stable=True)
        inv = jnp.argsort(order)
        group_sizes = _expert_counts(top_i, we1.shape[0])
        xs = _spread(x, order, inv)                        # [T*k, D]
    with jax.named_scope("expert_matmul"):
        hidden = jax.nn.silu(lax.ragged_dot(xs, we1, group_sizes)) \
            * lax.ragged_dot(xs, we3, group_sizes)         # [T*k, F]
        ys = lax.ragged_dot(hidden, we2, group_sizes)      # [T*k, D]
    with jax.named_scope("moe_combine"):
        return _collect(ys, gates, order, inv)


def _moe_ffn(cfg: MoEConfig, lp, x, mesh, rules):
    """x: [B, S, D] -> ([B, S, D], aux loss scalar, pairs routed to each
    expert [E] int32)."""
    b, s, d = x.shape
    k = cfg.n_experts_per_token
    with jax.named_scope("router"):
        logits = jnp.einsum("bsd,de->bse", x,
                            lp["router"]).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)            # [B, S, E]
        gates, top_i = lax.top_k(probs, k)                 # [B, S, k]
        if cfg.norm_topk_prob:
            gates = gates / gates.sum(-1, keepdims=True)
        counts = _expert_counts(top_i, cfg.n_experts)
        # Load-balance aux loss: E * sum_e (share of the tokens that
        # chose e) * (mean router probability of e).
        aux = cfg.n_experts * jnp.sum(
            counts / (b * s) * probs.mean(axis=(0, 1)))

    def experts(x, gates, top_i, *ws):
        """On the tokens at hand: the whole batch, or one shard's."""
        t = x.shape[0] * x.shape[1]
        out = _sparse_experts(x.reshape(t, d), gates.reshape(t, k),
                              top_i.reshape(t, k), *ws)
        return out.reshape(x.shape)

    weights = [lp[name] for name in _EXPERT_AXES]
    if mesh is None:
        return experts(x, gates, top_i, *weights), aux, counts

    # Each shard of the batch (and of the sequence) dispatches its own
    # tokens; the expert matrices come in as they are sharded and are
    # gathered whole inside, so that their gradients leave through the
    # matching reduce-scatter.
    w_specs = [logical_to_mesh_axes(axes, rules)
               for axes in _EXPERT_AXES.values()]
    tok = logical_to_mesh_axes(("batch", "seq", None), rules)
    out = jax.shard_map(
        lambda x, gates, top_i, *ws: experts(
            x, gates, top_i,
            *[_gather_whole(w, spec) for w, spec in zip(ws, w_specs)]),
        mesh=mesh, in_specs=(tok, tok, tok, *w_specs), out_specs=tok,
        check_vma=False)(x, gates, top_i, *weights)
    return out, aux, counts


def _gather_whole(w, spec):
    """Inside shard_map: the whole of an array that came in split as
    `spec` says."""
    for dim, axes in enumerate(spec):
        if axes is not None:
            w = lax.all_gather(w, axes, axis=dim, tiled=True)
    return w


def moe_forward_hidden(params, tokens, cfg: MoEConfig, *, mesh=None,
                       rules=DEFAULT_RULES, positions=None):
    """tokens [B, S] -> (final-norm hidden states [B, S, D], the layers'
    mean aux loss, pairs routed per layer and expert [L, E] int32)."""
    # Same SPMD hygiene as llama.forward: explicit positions → elementwise
    # cos/sin sharded with the activations (no table gather), and the
    # embed table size-gated replicated/sharded before the token gather
    # (_embed_lookup) so the partitioner doesn't fully rematerialize the
    # gathered activations.
    if positions is not None:
        cos, sin = rope_from_positions(positions, cfg.head_dim,
                                       cfg.rope_theta)
        cos = with_logical_constraint(cos, "batch", "seq", None,
                                      mesh=mesh, rules=rules)
        sin = with_logical_constraint(sin, "batch", "seq", None,
                                      mesh=mesh, rules=rules)
        positions = None
    else:
        cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                    cfg.rope_theta)
    x = _embed_lookup(params["embed"], tokens, mesh, rules).astype(cfg.dtype)
    x = with_logical_constraint(x, "batch", "seq", "act_embed",
                                mesh=mesh, rules=rules)

    def layer(carry, lp):
        x, aux_acc = carry
        with jax.named_scope("attn"):
            h = rms_norm_reference(x, lp["attn_norm"], cfg.norm_eps)
            q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
            k_ = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
            v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
            if cfg.qk_norm:
                q = _norm_all_heads(q, lp["q_norm"], cfg.norm_eps)
                k_ = _norm_all_heads(k_, lp["k_norm"], cfg.norm_eps)
            q = apply_rope(q, cos, sin, positions)
            k_ = apply_rope(k_, cos, sin, positions)
            attn = _attention(cfg, q, k_, v, mesh, rules)
            x = x + jnp.einsum("bshk,hkd->bsd", attn.astype(cfg.dtype),
                               lp["wo"])
        with jax.named_scope("mlp"):
            h2 = rms_norm_reference(x, lp["mlp_norm"], cfg.norm_eps)
            ffn_out, aux, counts = _moe_ffn(cfg, lp, h2, mesh, rules)
            x = x + ffn_out
        x = with_logical_constraint(x, "batch", "seq", "act_embed",
                                    mesh=mesh, rules=rules)
        return (x, aux_acc + aux), counts

    body = layer
    if cfg.remat:
        body = jax.checkpoint(
            layer, policy=jax.checkpoint_policies.nothing_saveable)
    (x, aux_total), expert_tokens = lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), params["layers"])
    x = rms_norm_reference(x, params["final_norm"], cfg.norm_eps)
    return x, aux_total / cfg.n_layers, expert_tokens


def _norm_all_heads(x, weight, eps):
    """RMSNorm of [B, S, H, K] over heads and head size together."""
    b, s, h, k = x.shape
    return rms_norm_reference(x.reshape(b, s, h * k), weight,
                              eps).reshape(b, s, h, k)


def moe_forward(params, tokens, cfg: MoEConfig, *, mesh=None,
                rules=DEFAULT_RULES, positions=None):
    """Returns (logits [B,S,V], total aux loss)."""
    x, aux, _ = moe_forward_hidden(params, tokens, cfg, mesh=mesh,
                                   rules=rules, positions=positions)
    out_w = params["embed"].T if cfg.tie_embeddings else params["out"]
    logits = jnp.einsum("bsd,dv->bsv", x, out_w.astype(cfg.dtype))
    return logits, aux


def moe_loss_fn(params, batch, cfg: MoEConfig, *, mesh=None,
                rules=DEFAULT_RULES):
    """Mean cross-entropy plus `aux_loss_coeff` times the load-balancing
    loss. The metrics carry the step's routing: `expert_tokens` [L, E],
    the pairs sent to each expert of each layer, and under `span_attrs`
    (what `make_train_step` puts on its dispatch span) the busiest
    expert's count and the mean."""
    x, aux, expert_tokens = moe_forward_hidden(
        params, batch["tokens"], cfg, mesh=mesh, rules=rules,
        positions=batch.get("positions"))
    b, s, d = x.shape
    out_w = (params["embed"].T if cfg.tie_embeddings
             else params["out"]).astype(cfg.dtype)
    targets = batch["targets"].reshape(b * s)
    with jax.named_scope("loss"):
        if cfg.fused_ce and not _vocab_sharded(mesh, rules):
            # As llama.loss_fn: the [tokens, vocab] logits never exist.
            losses = fused_linear_cross_entropy(
                x.reshape(b * s, d), out_w, targets)
        else:
            logits = jnp.einsum("bsd,dv->bsv", x, out_w)
            losses = softmax_cross_entropy(
                logits.reshape(b * s, cfg.vocab_size), targets)
        ce = losses.mean()
        loss = ce + cfg.aux_loss_coeff * aux
    return loss, {"loss": loss, "ce_loss": ce, "aux_loss": aux,
                  "expert_tokens": expert_tokens,
                  "span_attrs": {
                      "expert_tokens_max": expert_tokens.max(),
                      "expert_tokens_mean": expert_tokens.sum()
                      // expert_tokens.size}}
