"""Mixture-of-Experts Llama variant: expert parallelism over the
``expert`` mesh axis.

No reference equivalent (the reference has no model code); this exists so
EP is a first-class, exercised parallelism axis (SURVEY.md §2 parallelism
inventory calls EP "absent entirely" upstream — our charter adds it).

Routing: top-k softmax gating with a load-balancing auxiliary loss
(Switch-Transformer style). Dispatch is the dense-masked formulation:
every expert runs over all tokens with gates zeroing non-selected
contributions — compute-redundant by factor E/k but perfectly shardable
by GSPMD over the expert axis (each device computes only its local
experts; token activations stay put; one psum combines). The
capacity-based sparse dispatch (all-to-all) is the planned optimization
once the EP axis spans real slices.
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
)
from ray_tpu.ops.cross_entropy import softmax_cross_entropy
from ray_tpu.ops.norms import rms_norm_reference
from ray_tpu.ops.rope import (apply_rope, rope_frequencies,
                              rope_from_positions)
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES,
    tree_shardings,
    with_logical_constraint,
)


@dataclasses.dataclass(frozen=True)
class MoEConfig(LlamaConfig):
    n_experts: int = 8
    n_experts_per_token: int = 2
    aux_loss_coeff: float = 0.01

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


def moe_param_logical_axes(cfg: MoEConfig) -> Dict[str, Any]:
    layer = {
        "attn_norm": (None, "norm"),
        "wq": (None, "embed", "heads", "head_dim"),
        "wk": (None, "embed", "kv_heads", "head_dim"),
        "wv": (None, "embed", "kv_heads", "head_dim"),
        "wo": (None, "heads", "head_dim", "embed"),
        "mlp_norm": (None, "norm"),
        "router": (None, "embed", None),
        "we1": (None, "expert", "embed", "mlp"),
        "we3": (None, "expert", "embed", "mlp"),
        "we2": (None, "expert", "mlp", "embed"),
    }
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


def _moe_ffn(cfg: MoEConfig, lp, x, mesh, rules):
    """x: [B, S, D] → ([B, S, D], aux_loss scalar)."""
    b, s, d = x.shape
    logits = jnp.einsum("bsd,de->bse", x, lp["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)            # [B, S, E]
    k = cfg.n_experts_per_token
    topk_vals, _ = lax.top_k(probs, k)
    threshold = topk_vals[..., -1:]
    gates = jnp.where(probs >= threshold, probs, 0.0)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    gates = gates.astype(cfg.dtype)                    # [B, S, E]

    # Load-balance aux loss: E * Σ_e fraction_tokens_e · mean_prob_e.
    token_frac = (gates > 0).astype(jnp.float32).mean(axis=(0, 1))
    prob_frac = probs.mean(axis=(0, 1))
    aux = cfg.n_experts * jnp.sum(token_frac * prob_frac)

    # Dense-masked expert computation, sharded over the expert axis.
    gate_x = jnp.einsum("bsd,edf->ebsf", x, lp["we1"])
    up_x = jnp.einsum("bsd,edf->ebsf", x, lp["we3"])
    hidden = jax.nn.silu(gate_x) * up_x                # [E, B, S, F]
    hidden = with_logical_constraint(hidden, "expert", "batch", "seq",
                                     "mlp", mesh=mesh, rules=rules)
    per_expert = jnp.einsum("ebsf,efd->ebsd", hidden, lp["we2"])
    out = jnp.einsum("ebsd,bse->bsd", per_expert,
                     gates.transpose(0, 1, 2))
    return out, aux


def moe_forward(params, tokens, cfg: MoEConfig, *, mesh=None,
                rules=DEFAULT_RULES, positions=None):
    """Returns (logits [B,S,V], total aux loss)."""
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
            q = apply_rope(q, cos, sin, positions)
            k_ = apply_rope(k_, cos, sin, positions)
            attn = _attention(cfg, q, k_, v, mesh, rules)
            x = x + jnp.einsum("bshk,hkd->bsd", attn.astype(cfg.dtype),
                               lp["wo"])
        with jax.named_scope("mlp"):
            h2 = rms_norm_reference(x, lp["mlp_norm"], cfg.norm_eps)
            ffn_out, aux = _moe_ffn(cfg, lp, h2, mesh, rules)
            x = x + ffn_out
        x = with_logical_constraint(x, "batch", "seq", "act_embed",
                                    mesh=mesh, rules=rules)
        return (x, aux_acc + aux), None

    body = layer
    if cfg.remat:
        body = jax.checkpoint(
            layer, policy=jax.checkpoint_policies.nothing_saveable)
    (x, aux_total), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                 params["layers"])
    x = rms_norm_reference(x, params["final_norm"], cfg.norm_eps)
    out_w = params["embed"].T if cfg.tie_embeddings else params["out"]
    logits = jnp.einsum("bsd,dv->bsv", x, out_w.astype(cfg.dtype))
    return logits, aux_total / cfg.n_layers


def moe_loss_fn(params, batch, cfg: MoEConfig, *, mesh=None,
                rules=DEFAULT_RULES):
    logits, aux = moe_forward(params, batch["tokens"], cfg, mesh=mesh,
                              rules=rules,
                              positions=batch.get("positions"))
    b, s, v = logits.shape
    with jax.named_scope("loss"):
        losses = softmax_cross_entropy(
            logits.reshape(b * s, v), batch["targets"].reshape(b * s))
        ce = losses.mean()
        loss = ce + cfg.aux_loss_coeff * aux
    return loss, {"loss": loss, "ce_loss": ce, "aux_loss": aux}
