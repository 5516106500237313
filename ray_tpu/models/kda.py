"""Kimi Delta Attention (Kimi Linear, 2025: the gated delta rule with a
decay a key channel) for `decoder.block`, served through the slot
cache. It is `gated_delta`'s mixer, whose header has the recurrence,
the chunked form and the rules of the state leaves, and differs from
Gated DeltaNet in what it makes of a layer's input `a` beside q, k and
v:

    g_t[h]     = -exp(A_log[h]) softplus(((a w_fa) w_fb)[h] + dt_bias[h])     in R^dk, alpha_t = exp(g_t)
    beta_t[h]  = sigmoid(b_t[h])                       no factor 2 (`allow_neg_eigval` False)
    S_t[h]     = Diag(alpha_t) S_{t-1} + k_t (outer) beta_t (v_t - (Diag(alpha_t) S_{t-1})^T k_t)
    out_t[h]   = RMSNorm_dv(o_t[h]; o_norm) * sigmoid(((a w_ga) w_gb)[h])

The decay is one number a key channel, made by a projection through
`cfg.gate_rank` channels (`w_fa` [D, rank], `w_fb` [rank, H, dk]) with
a bias a channel (`dt_bias` [H, dk]) and a rate a head (`A_log` [H]);
the output gate is a sigmoid of a projection through as many (`w_ga`,
`w_gb` [rank, H, dv]), where Gated DeltaNet's is the silu of a full
one. No projection has a bias. The two projections are scoped
`delta_gate` inside the mixer's `delta`.

Everything else is `gated_delta`'s and is called, not copied: the three
convolutions, the norms of q and k, the state kernel
(`ops/delta_update.py`, which takes the decay as a column a head) and
the chunked scan, both by the shape of the decay handed down; the four
state leaves (`gated_delta.LEAVES`).
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models import gated_delta
from ray_tpu.models.serving import normal


def init(cfg, key) -> Dict[str, Any]:
    """One layer's leaves but the block's norms: `gated_delta.init`'s
    without its gate (`wg`), its decay's projection (`wa`) and its bias
    a head, and with the four projections through `cfg.gate_rank` and a
    `dt_bias` a key channel, drawn as `gated_delta.init` draws its own
    (dt log-uniform in [0.001, 0.1], softplus(dt_bias) = dt)."""
    d, h, r = cfg.dim, cfg.delta_heads, cfg.gate_rank
    dk, dv = cfg.delta_key_dim, cfg.delta_value_dim
    shared = {name: leaf for name, leaf in gated_delta.init(cfg, key).items()
              if name not in ("wg", "wa", "dt_bias")}
    ks = jax.random.split(jax.random.fold_in(key, 1), 5)
    dt = jnp.exp(jax.random.uniform(ks[4], (h, dk), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return {**shared,
            "w_fa": normal(ks[0], (d, r), cfg.dtype),
            "w_fb": normal(ks[1], (r, h, dk), cfg.dtype),
            "w_ga": normal(ks[2], (d, r), cfg.dtype),
            "w_gb": normal(ks[3], (r, h, dv), cfg.dtype),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt))}


def _gates(cfg, a, lp, stacks=None):
    """`gated_delta._gates` for this mixer: (the log decay a key
    channel [B, T, H, dk] float32; z [B, T, H, dv], of which the output
    gate is the sigmoid). Its four projections are small and read from
    `lp` whatever the mixer was handed whole."""
    del stacks
    with jax.named_scope("delta_gate"):
        f = jnp.einsum("btr,rhk->bthk",
                       jnp.einsum("btd,dr->btr", a, lp["w_fa"]), lp["w_fb"])
        z = jnp.einsum("btr,rhv->bthv",
                       jnp.einsum("btd,dr->btr", a, lp["w_ga"]), lp["w_gb"])
    log_gamma = -jnp.exp(lp["A_log"].astype(jnp.float32))[:, None] \
        * jax.nn.softplus(f.astype(jnp.float32) + lp["dt_bias"])
    return log_gamma, z


def _gated_norm(cfg, o, z, weight):
    """RMSNorm over each head's dv channels, then the sigmoid gate: o,
    z [B, T, H, dv] -> [B, T, H, dv] float32."""
    return gated_delta._normed(cfg, o, weight) \
        * jax.nn.sigmoid(z.astype(jnp.float32))


def mixer(cfg, start_pos, at, *, in_place=False):
    """The mixer of a run of KDA layers: `gated_delta.mixer` with this
    module's decay and gate (`in_place` as its)."""
    return gated_delta.mixer(cfg, start_pos, at, gates=_gates,
                             gated_norm=_gated_norm, in_place=in_place)
