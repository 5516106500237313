"""The gated delta-rule sequence mixer (Gated DeltaNet, Yang, Kautz &
Hatamizadeh 2024: linear attention whose state is corrected, not only
added to) for `decoder.block`, served through the slot cache: the six
projections, three causal depthwise convolutions, the recurrence on a
matrix state a head and the gated norm of its output; the block applies
the output projection (`wo`, [heads, value size, D]).

On a layer's input a, H heads of dk key and dv value channels, K the
convolution's width, float32 wherever the state is touched:

    q~ = a wq   k~ = a wk   v~ = a wv   z = a wg   al = a wa   b = a wb
    x_t <- silu(sum_j conv_x[:, j] x~_{t-K+1+j})   x in q, k, v; zeros before the start, no bias
    q_t[h] = q_t[h] / |q_t[h]|_2 / sqrt(dk)        k_t[h] = k_t[h] / |k_t[h]|_2
    gamma_t[h] = exp(-exp(A_log[h]) softplus(al_t[h] + dt_bias[h]))           in (0, 1)
    beta_t[h]  = 2 sigmoid(b_t[h])                 (`allow_neg_eigval`; else sigmoid)
    S_t[h] = gamma_t S_{t-1} + k_t (outer) beta_t (v_t - (gamma_t S_{t-1})^T k_t)     S [dk, dv]
    o_t[h] = S_t^T q_t
    out_t  = RMSNorm_dv(o_t[h]; o_norm) * silu(z_t[h])

The transition gamma (I - beta k k^T) is no diagonal (Mamba-2's is a
scalar a head, `mamba2._scan`), and with beta up to 2 it may have a
negative eigenvalue. The norm comes before the gate here and after it
in `mamba2._gated_norm`: two functions, not one with a switch.
`mamba2._conv` serves the three convolutions (they have no bias, which
it is told; `lfm2_moe` tells it to leave out the activation too, and
keeps a convolution's carry as its mixer's only state).

What a sequence leaves behind is S [H, dk, dv] (`cfg.state_dtype`) and
the last K - 1 un-convolved rows of q~, k~ and v~: four leaves of the
slot cache with no sequence axis, [layers, slots, ...]. A call writes
its layer of each whole, but for a decode step's states, which are
updated where they lie. `mamba2`'s three rules hold of them: a row
that starts at position 0 starts from zeros whatever its slot held; the
state left is that after position `at` of the call's tokens and no
later (padding past `at` takes beta = 0 and gamma = 1, which neither
writes to the state nor decays it, and the carries are cut at `at`); a
row that starts past 0 continues from its leaves.

A call of one token is the recurrence as written, elementwise in
float32 (scope `delta_update`; `ops/delta_update.py`: on a TPU one
kernel that reads a head's state out of the run's stack once and
writes it back there). A longer one is the chunked form (scope
`delta_scan`). With g_i the log decays summed from the chunk's start
through i, G_i = exp(g_i), and u_j = beta_j (v_j - (gamma_j S_{j-1})^T
k_j) the value position j really writes, the recurrence unrolls to
S_i = G_i S_0 + sum_{j<=i} (G_i / G_j) k_j u_j^T; put into u_i's own
definition it gives, a chunk of C rows at a time,

    (I + A) U = beta V - (beta K * G) S_0,   A_ij = beta_i (k_i . k_j) G_i / G_j  (j < i)

so with T = (I + A)^-1 (`_unit_lower_inverse`: forward substitution,
which stays exact where keys repeat and beta is 2, as a Neumann series
does not), W = T (beta K * G) and U' = T (beta V), all a chunk's own,
the scan across chunks carries S in float32:

    U = U' - W S       O = (Q * G) S + lower(Q K^T * G_i / G_j) U
    S <- G_C S + (K * G_C / G_j)^T U

Every ratio of decays is the exponential of a difference that is not
positive, so a gamma near 0 underflows to the right limit. Products
take their inputs in `cfg.dtype` and accumulate in float32. Forward
only: no backward pass is written for the chunked scan.

A decay a key channel (Kimi Delta Attention, `kda`: gamma_t[h] in
(0, 1)^dk, S_t = Diag(gamma_t) S_{t-1} + ...) goes through the same
kernel, which takes the decay as a column a head either way, and the
same chunked form, by the shape of what the mixer hands down
(`log_gamma` [B, T, H, dk] where a scalar decay is [B, T, H]). What
the scan across chunks carries takes a vector where it took a scalar
and stays as exact: W = T (beta K * G), Q * G, K * G_C / G_j and
S <- Diag(G_C) S + ..., every G now [C, dk]. The two [C, C] matrices
inside a chunk do not factor any more: A_ij = beta_i sum_c k_ic k_jc
exp(g_ic - g_jc), and Q K^T likewise. Written as (k_i * exp(g_i)) .
(k_j * exp(-g_j)) the second factor overflows where a channel forgets
fast; [C, C, dk] whole is 2 MB a head and chunk. `_decayed_products`
builds them by sub-blocks of 16 rows: inside a sub-block on the
diagonal the [16, 16, dk] differences themselves, below it each side
decayed to the first row of the upper sub-block, so the rule above
holds of every exponent.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import mamba2
from ray_tpu.models.serving import normal
# `_update`: the recurrence as written, where `delta_update` has it for
# backends without the kernel; what the tests hold the chunked form to,
# token by token.
from ray_tpu.ops.delta_update import (  # noqa: F401
    delta_update, reference as _update)
from ray_tpu.ops.stacked_product import leaf_product

# Rows below which `_unit_lower_inverse` substitutes row by row.
_INVERSE_BASE = 16
# Rows of a sub-block of `_decayed_products`.
_SUB_BLOCK = 16
_L2_EPS = 1e-6

CONVS = ("conv_q", "conv_k", "conv_v")


def init(cfg, key) -> Dict[str, Any]:
    """One layer's leaves but the block's norms: the six projections,
    the three convolutions, dt's bias, A, the output norm and `wo`.
    `dt_bias`, `A_log` and the convolutions are drawn as `mamba2.init`
    draws them (dt log-uniform in [0.001, 0.1], A uniform in [1, 16],
    the convolutions uniform in +-K^-1/2)."""
    d, h = cfg.dim, cfg.delta_heads
    dk, dv, k = cfg.delta_key_dim, cfg.delta_value_dim, cfg.conv_kernel
    ks = jax.random.split(key, 12)
    dt = jnp.exp(jax.random.uniform(ks[6], (h,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    bound = k ** -0.5

    def conv(key, width):
        return jax.random.uniform(key, (width, k), jnp.float32, -bound,
                                  bound).astype(cfg.dtype)

    return {
        "wq": normal(ks[0], (d, h, dk), cfg.dtype),
        "wk": normal(ks[1], (d, h, dk), cfg.dtype),
        "wv": normal(ks[2], (d, h, dv), cfg.dtype),
        "wg": normal(ks[3], (d, h, dv), cfg.dtype),
        "wa": normal(ks[4], (d, h), cfg.dtype),
        "wb": normal(ks[5], (d, h), cfg.dtype),
        # softplus(dt_bias) = dt
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(ks[7], (h,), jnp.float32, 1.0,
                                            16.0)),
        "conv_q": conv(ks[8], h * dk),
        "conv_k": conv(ks[9], h * dk),
        "conv_v": conv(ks[10], h * dv),
        "o_norm": jnp.ones(dv, cfg.dtype),
        "wo": normal(ks[11], (h, dv, d), cfg.dtype) * d ** -0.5,
    }


def state_shapes(cfg) -> Dict[str, Any]:
    """The four state leaves of a delta layer, as its mixer takes them
    (`LEAVES`): (shape a layer and slot, dtype)."""
    h, dk, dv = cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim
    rows = cfg.conv_kernel - 1
    return {"state": ((h, dk, dv), cfg.state_dtype),
            "conv_q": ((rows, h * dk), cfg.dtype),
            "conv_k": ((rows, h * dk), cfg.dtype),
            "conv_v": ((rows, h * dv), cfg.dtype)}


LEAVES = ("state",) + CONVS


def _l2_normalise(x):
    """x [..., H, dk] over its last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt((x * x).sum(-1, keepdims=True) + _L2_EPS)


def _queries(q):
    """Unit length a head, then 1 / sqrt(dk): q [..., H, dk] float32."""
    return _l2_normalise(q) * q.shape[-1] ** -0.5


def _keys(k):
    """Unit length a head: k [..., H, dk] float32."""
    return _l2_normalise(k)


def _by_rows(a):
    """(I + A)^-1 of a strictly lower triangular A [..., C, C] by
    forward substitution: row i is e_i - sum_{j<i} A_ij row_j."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)

    def row(i, t):
        a_i = lax.dynamic_index_in_dim(a, i, -2, keepdims=False)
        new = eye[i] - (a_i[..., :, None] * t).sum(-2)
        return lax.dynamic_update_index_in_dim(t, new, i, -2)

    return lax.fori_loop(0, c, row, jnp.zeros_like(a))


def _unit_lower_inverse(a):
    """(I + A)^-1 of a strictly lower triangular A [..., C, C], float32:
    by halves down to `_INVERSE_BASE` rows, [[T1, 0], [-T2 A21 T1,
    T2]], the two products at the highest precision."""
    c = a.shape[-1]
    if c <= _INVERSE_BASE or c % 2:
        return _by_rows(a)
    half = c // 2
    t1 = _unit_lower_inverse(a[..., :half, :half])
    t2 = _unit_lower_inverse(a[..., half:, half:])
    below = -jnp.matmul(
        jnp.matmul(t2, a[..., half:, :half], precision="highest"), t1,
        precision="highest")
    return jnp.concatenate([
        jnp.concatenate([t1, jnp.zeros_like(below).swapaxes(-1, -2)], -1),
        jnp.concatenate([below, t2], -1)], -2)


def _decayed_products(xs, k, g):
    """sum_c x_ic k_jc exp(g_ic - g_jc) for j <= i, of each x of `xs`:
    xs and k [..., C, dk], g [..., C, dk] the log decays summed through
    each row (so no row's is above the row's before) -> [..., C, C]
    float32 each, whatever stands above the diagonal to be masked by
    the caller. By sub-blocks of `_SUB_BLOCK` rows, so that every
    exponent is a difference that is not positive: inside a sub-block
    on the diagonal the differences themselves, [rows, rows, dk]; below
    it, with r the first row of i's sub-block, (x_i exp(g_i - g_r)) .
    (k_j exp(g_r - g_j)), one product a sub-block of rows against all
    the keys before it."""
    c, dk = k.shape[-2:]
    n = math.gcd(c, _SUB_BLOCK)
    f32 = jnp.float32

    def rows(x):  # [..., C, dk] -> [..., C / n, n, dk]
        return x.reshape(x.shape[:-2] + (c // n, n, dk))

    g_rows = rows(g)
    first = g_rows[..., :1, :]                       # [..., C / n, 1, dk]
    # Keys before each sub-block, decayed to its first row; a key at or
    # past that row is no key of this product and takes exp(0).
    before = jnp.exp(jnp.minimum(first - g[..., None, :, :], 0.0))
    k_to_first = (k[..., None, :, :].astype(f32) * before).astype(k.dtype)
    own = jnp.tril(jnp.ones((n, n), bool))[..., None]
    within = jnp.exp(jnp.where(
        own, g_rows[..., :, None, :] - g_rows[..., None, :, :], -jnp.inf))
    k_rows = rows(k).astype(f32)
    below = (jnp.arange(c) // n)[:, None] > (jnp.arange(c) // n)[None, :]
    outs = []
    for x in xs:
        x_rows = rows(x).astype(f32)
        from_first = (x_rows * jnp.exp(g_rows - first)).astype(x.dtype)
        off = jnp.einsum("...sik,...sjk->...sij", from_first, k_to_first,
                         preferred_element_type=f32)  # [..., C / n, n, C]
        on = (x_rows[..., :, None, :] * k_rows[..., None, :, :]
              * within).sum(-1)                       # [..., C / n, n, n]
        on = jnp.einsum("...sij,st->...sitj", on,
                        jnp.eye(c // n, dtype=f32))   # onto the diagonal
        outs.append(jnp.where(
            below, off.reshape(off.shape[:-3] + (c, c)),
            on.reshape(on.shape[:-4] + (c, c))))
    return outs


def _scan(cfg, s0, q, k, v, log_gamma, beta):
    """The chunked form over T tokens from the carried state: s0
    [B, H, dk, dv] float32, q and k [B, T, H, dk] (normalised, q
    scaled), v [B, T, H, dv], log_gamma [B, T, H] (a decay a head) or
    [B, T, H, dk] (a decay a key channel) and beta [B, T, H] float32
    (both 0 at a position that is not to count) -> (o [B, T, H, dv]
    float32, S after the last position [B, H, dk, dv] float32)."""
    bsz, t, h, _ = q.shape
    by_channel = log_gamma.ndim == 4
    if not by_channel:  # one column that every key channel reads
        log_gamma = log_gamma[..., None]
    c = min(cfg.chunk_size, t)
    pad = -t % c
    if pad:  # beta = 0, gamma = 1: the tail neither writes nor decays
        q, k, v, log_gamma, beta = (jnp.pad(
            x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, log_gamma, beta))
    nc = (t + pad) // c
    dtype = v.dtype
    f32 = jnp.float32

    def chunks(x):  # [B, T, H, ...] -> [B, nc, H, C, ...]
        return jnp.moveaxis(x.reshape((bsz, nc, c) + x.shape[2:]), 2, 3)

    q, k, v, beta = chunks(q), chunks(k), chunks(v), chunks(beta)
    g = jnp.cumsum(chunks(log_gamma), -2)         # [B, nc, H, C, dk or 1]
    seen = jnp.tril(jnp.ones((c, c), bool))
    if by_channel:
        kk, qk = _decayed_products((k, q), k, g)
    else:
        ratio = jnp.exp(jnp.where(seen, g[..., :, None, 0] - g[..., None, :, 0],
                                  -jnp.inf))                  # G_i / G_j
        kk, qk = (jnp.einsum("bnhik,bnhjk->bnhij", x, k,
                             preferred_element_type=f32) * ratio
                  for x in (k, q))
    a = jnp.where(jnp.tril(seen, -1), beta[..., None] * kk, 0.0)
    written = (_unit_lower_inverse(a) * beta[..., None, :]).astype(dtype)
    w = jnp.einsum("bnhij,bnhjk->bnhik", written,
                   (k.astype(f32) * jnp.exp(g)).astype(dtype),
                   preferred_element_type=f32).astype(dtype)
    u = jnp.einsum("bnhij,bnhjv->bnhiv", written, v,
                   preferred_element_type=f32)
    qk = jnp.where(seen, qk, 0.0).astype(dtype)
    q_in = (q.astype(f32) * jnp.exp(g)).astype(dtype)
    k_out = (k.astype(f32) * jnp.exp(g[..., -1:, :] - g)).astype(dtype)
    whole = jnp.exp(g[..., -1, :])                    # [B, nc, H, dk or 1]

    def chunk(s, xs):
        w, u, qk, q_in, k_out, whole = xs
        entering = s.astype(dtype)
        u = u - jnp.einsum("bhik,bhkv->bhiv", w, entering,
                           preferred_element_type=f32)
        written = u.astype(dtype)
        o = jnp.einsum("bhik,bhkv->bhiv", q_in, entering,
                       preferred_element_type=f32) \
            + jnp.einsum("bhij,bhjv->bhiv", qk, written,
                         preferred_element_type=f32)
        s = s * whole[..., None] + jnp.einsum(
            "bhik,bhiv->bhkv", k_out, written, preferred_element_type=f32)
        return s, o

    last, o = lax.scan(chunk, s0, tuple(
        x.swapaxes(0, 1) for x in (w, u, qk, q_in, k_out, whole)))
    o = jnp.moveaxis(o, 0, 1)                                 # [B, nc, H, C, dv]
    return (jnp.moveaxis(o, 2, 3).reshape(bsz, nc * c, h, -1)[:, :t], last)


def _normed(cfg, o, weight):
    """RMSNorm over each head's dv channels: o [B, T, H, dv] -> the
    same, float32."""
    o = o.astype(jnp.float32)
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps)
    return o * weight.astype(jnp.float32)


def _gated_norm(cfg, o, z, weight):
    """RMSNorm over each head's dv channels, then the gate: o, z
    [B, T, H, dv] -> [B, T, H, dv] float32."""
    return _normed(cfg, o, weight) * jax.nn.silu(z.astype(jnp.float32))


def _gates(cfg, a, lp, stacks=None):
    """What Gated DeltaNet makes of a layer's input beside q, k, v and
    beta: (the log decay, one number a head, [B, T, H] float32; z
    [B, T, H, dv], of which the output gate is the silu). `stacks`: the
    mixer's, where it was handed leaves whole."""
    z = leaf_product("btd,dhv->bthv", a, "wg", lp, stacks)
    log_gamma = -jnp.exp(lp["A_log"].astype(jnp.float32)) \
        * jax.nn.softplus(jnp.einsum("btd,dh->bth", a, lp["wa"])
                          .astype(jnp.float32) + lp["dt_bias"])
    return log_gamma, z


def counts(tokens, start_pos, at):
    """What a call of a stack with delta layers counts, int32 scalars:
    the rows that started from zeros, and the real tokens a prefill
    carried through the chunked scan (its padding past `at` left out;
    none of a call of one token)."""
    at = jnp.broadcast_to(jnp.asarray(at, jnp.int32), start_pos.shape)
    return {"delta_state_resets": (start_pos == 0).sum(dtype=jnp.int32),
            "delta_scan_tokens": (at + 1).sum(dtype=jnp.int32)
            if tokens.shape[1] > 1 else jnp.zeros((), jnp.int32)}


# The projections a decode step reads where they lie in the run's
# stack (`mixer`'s `in_place`): the three the convolutions follow, whose
# slices the scan copied (the compiler wants them laid out for a
# product it then reshapes), and the gate's.
WHOLE = ("wq", "wk", "wv", "wg")


def mixer(cfg, start_pos, at, gates=None, gated_norm=None, *,
          in_place=False):
    """The mixer of a run of delta layers. Its state is the run's four
    stacks (`LEAVES`: S [layers, B, H, dk, dv] and the three carries
    [layers, B, K - 1, channels]), which `decoder.layers` carries
    through the scan; it reads its layer of each and writes it back
    whole, but in a call of one token, which updates its layer of S in
    place in the stack and never slices it out. `start_pos` [B]: a row
    at 0 starts from zeros; `at`: the position of the call's tokens
    after which the state is left, an int or an int32 scalar for all
    rows or int32 [B], one a row. `gates(cfg, a, lp)` and
    `gated_norm(cfg, o, z, weight)` are this module's `_gates` and
    `_gated_norm` unless given (`kda` gives its own): the log decay is
    [B, T, H], one number a head, or [B, T, H, dk], one a key channel,
    and the recurrence and the chunked form follow its shape.
    `in_place`: a decode step's (`stacked_product.engages`), which
    names `WHOLE` as the leaves `decoder.layers` leaves whole and reads
    its layer's in their stacks (`gates` is then handed `stacks=` as
    the mixer was)."""
    h, dk, dv = cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim
    at = jnp.broadcast_to(jnp.asarray(at, jnp.int32), start_pos.shape)
    fresh = start_pos == 0

    def mix(a, lp, rope, state, handed, stacks=None):
        (s_stack, *carries), layer = state
        bsz, t = a.shape[:2]
        convolved = []
        with jax.named_scope("delta_conv"):
            for name, stack, w in zip(CONVS, carries, ("wq", "wk", "wv")):
                x = leaf_product("btd,dhk->bthk", a, w, lp,
                                 stacks).reshape(bsz, t, -1)
                carry = jnp.where(
                    fresh[:, None, None], 0,
                    lax.dynamic_index_in_dim(stack, layer, 0, False))
                convolved.append(mamba2._conv(
                    cfg, {"conv_w": lp[name]}, carry, x, at, bias=False))
        (q, k, v), carries_out = zip(*convolved)
        q = _queries(q.reshape(bsz, t, h, dk))
        k = _keys(k.reshape(bsz, t, h, dk))
        v = v.reshape(bsz, t, h, dv)
        log_gamma, z = (gates or _gates)(
            cfg, a, lp, **({} if stacks is None else {"stacks": stacks}))
        real = (jnp.arange(t) <= at[:, None])[..., None]
        log_gamma = jnp.where(
            real.reshape(real.shape + (1,) * (log_gamma.ndim - 3)),
            log_gamma, 0.0)
        beta = jax.nn.sigmoid(jnp.einsum("btd,dh->bth", a, lp["wb"])
                              .astype(jnp.float32))
        beta = jnp.where(real, 2.0 * beta if cfg.allow_neg_eigval else beta,
                         0.0)
        if t == 1:
            # The stack's one reader and writer in the scan's body: a
            # second one would have the compiler copy the stack to keep
            # the kernel's alias honest.
            with jax.named_scope("delta_update"):
                o, s_stack = delta_update(
                    s_stack, layer, fresh, q[:, 0], k[:, 0],
                    v[:, 0].astype(jnp.float32), jnp.exp(log_gamma[:, 0]),
                    beta[:, 0])
                o = o[:, None]
        else:
            s0 = jnp.where(fresh[:, None, None, None], 0.0,
                           lax.dynamic_index_in_dim(s_stack, layer, 0, False)
                           .astype(jnp.float32))
            with jax.named_scope("delta_scan"):
                o, s = _scan(cfg, s0, q.astype(a.dtype), k.astype(a.dtype),
                             v, log_gamma, beta)
            s_stack = lax.dynamic_update_index_in_dim(
                s_stack, s.astype(s_stack.dtype), layer, 0)
        with jax.named_scope("delta_norm"):
            out = (gated_norm or _gated_norm)(cfg, o, z, lp["o_norm"])
        state = (s_stack,) + tuple(
            lax.dynamic_update_index_in_dim(
                stack, carry.astype(stack.dtype), layer, 0)
            for stack, carry in zip(carries, carries_out))
        return out.astype(a.dtype), state, handed

    mix.scope = "delta"
    if in_place:
        mix.whole = WHOLE
    return mix
