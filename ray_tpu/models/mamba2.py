"""The Mamba-2 sequence mixer (state-space duality, Dao & Gu 2024) for
`decoder.block`, served through the slot cache: the projection, the
causal depthwise convolution, the selective state-space recurrence and
the gated group norm; the block applies the output projection (`wo`,
[heads, head size, D]).

On normed activations a, H heads of P channels, G groups of B and C
with N states, g(h) = h // (H / G):

    [z | xBC | dt] = a [w_z | w_xbc | w_dt]          the published in_proj, cut in three
    xBC_t  <- silu(sum_j conv_w[:, j] xBC_{t-K+1+j} + conv_b)      zeros before the start
    [xs (H x P) | B (G x N) | C (G x N)] = xBC
    dt_t   = softplus(dt_t + dt_bias) ;  A = -exp(A_log)            float32
    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] xs_t[h] (outer) B_t[g(h)]
    y_t[h] = S_t[h] C_t[g(h)] + D[h] xs_t[h]
    out_t  = GroupRMSNorm(y_t * silu(z_t); norm_w, G groups)

What a sequence leaves behind is S [H, P, N] and the last K - 1 rows of
the un-convolved xBC: two leaves of the slot cache with *no sequence
axis*, [layers, slots, H, P, N] (`cfg.state_dtype`) and [layers, slots,
K - 1, C], the same size at any length. A prefill rewrites its layer of
both whole; a decode step rewrites its layer of the convolution's rows
and updates its layer of S where it lies in the stack. Three things
follow from a state that cannot be masked afterwards, as attention
masks keys by length:

- a row that starts at position 0 starts from zeros, whatever its slot
  held (a retired slot keeps stepping until it is admitted again);
- the state left is that after position `at` of the call's tokens and
  no later (one position for all rows, or one a row): a prefill's
  bucket padding past `at` is given dt = 0, which neither decays the
  state nor adds to it, and the convolution's carry is cut at `at`;
- a row that starts past 0 continues from its leaf.

A call of one token (a decode step) is the recurrence as written, in
float32 on the state (scope `ssm_update`; `ops/ssm_update.py`: on a TPU
one kernel that reads a head's state once out of the run's stack and
writes it back there, which is the stack's one reader and writer in the
scan's body; elsewhere `_update` on the sliced layer). A longer one (a
prefill) is the chunked form (scope `ssm_scan`) from the sliced layer,
which it puts back: inside a chunk of `cfg.chunk_size`
the outputs are a masked [Q, Q] product of C, B and the decays, across
chunks a scan carries S in float32 from the row's carried state; the
products take their inputs in `cfg.dtype` and accumulate in float32.
Forward only: no backward pass is written for the chunked scan, so
nothing here is trained.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.serving import normal
# `_update`: the recurrence as written, where `ssm_update` has it for
# the backends with no kernel.
from ray_tpu.ops.ssm_update import (  # noqa: F401
    ssm_update, reference as _update)


def conv_width(cfg) -> int:
    """Channels the convolution runs over: xs, B and C."""
    return cfg.ssm_heads * cfg.ssm_head_dim \
        + 2 * cfg.ssm_groups * cfg.ssm_state


def init(cfg, key) -> Dict[str, Any]:
    """One layer's leaves but the block's norm: the three parts of the
    input projection, the convolution, dt's bias, A, D, the gated norm
    and `wo`. dt_bias, A_log, D and the convolution are drawn as the
    Mamba-2 reference initialises them (dt log-uniform in [0.001, 0.1],
    A uniform in [1, 16], D ones, the convolution uniform in
    +-K^-1/2), so that every term weighs in the output."""
    d, h, p = cfg.dim, cfg.ssm_heads, cfg.ssm_head_dim
    c, k = conv_width(cfg), cfg.conv_kernel
    ks = jax.random.split(key, 8)
    dt = jnp.exp(jax.random.uniform(ks[3], (h,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    bound = k ** -0.5
    return {
        "w_z": normal(ks[0], (d, h, p), cfg.dtype),
        "w_xbc": normal(ks[1], (d, c), cfg.dtype),
        "w_dt": normal(ks[2], (d, h), cfg.dtype),
        # softplus(dt_bias) = dt
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(ks[4], (h,), jnp.float32, 1.0,
                                            16.0)),
        "D": jnp.ones(h, jnp.float32),
        "conv_w": jax.random.uniform(ks[5], (c, k), jnp.float32, -bound,
                                     bound).astype(cfg.dtype),
        "conv_b": jax.random.uniform(ks[6], (c,), jnp.float32, -bound,
                                     bound).astype(cfg.dtype),
        "ssm_norm": jnp.ones(h * p, cfg.dtype),
        "wo": normal(ks[7], (h, p, d), cfg.dtype) * d ** -0.5,
    }


def state_shapes(cfg) -> Dict[str, Any]:
    """The two state leaves of a Mamba-2 layer, as its mixer takes
    them: (shape a layer and slot, dtype)."""
    return {"ssm": ((cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                    cfg.state_dtype),
            "conv": ((cfg.conv_kernel - 1, conv_width(cfg)), cfg.dtype)}


def _conv(cfg, lp, carry, xbc, at, bias=True, activation=jax.nn.silu):
    """The causal depthwise convolution of xbc [B, T, C] behind the
    carried rows [B, K - 1, C] -> (`activation` of it [B, T, C], the
    rows to carry on: the K - 1 un-convolved rows that end at position
    `at[row]`). `bias` False: the convolution has none (`gated_delta`'s
    three). `activation` None: the sum as it is (`lfm2_moe`'s gated
    short convolution, which gates before and after instead, and whose
    only state this carry is: a mixer need keep nothing else)."""
    k, t = cfg.conv_kernel, xbc.shape[1]
    window = jnp.concatenate([carry.astype(xbc.dtype), xbc], 1)
    out = sum(window[:, j:j + t].astype(jnp.float32)
              * lp["conv_w"][:, j].astype(jnp.float32) for j in range(k))
    if bias:
        out = out + lp["conv_b"].astype(jnp.float32)
    if activation is not None:
        out = activation(out)
    return (out.astype(xbc.dtype), jax.vmap(
        lambda rows, last: lax.dynamic_slice_in_dim(rows, last + 1, k - 1)
    )(window, at))


def _scan(cfg, s0, xs, b_mat, c_mat, dt, a):
    """The chunked form over T tokens from the carried state: s0
    [B, H, P, N] float32, xs [B, T, H, P], b and c [B, T, G, N], dt
    [B, T, H] float32 (0 at a position that is not to count), a [H] ->
    (y [B, T, H, P] float32, S after the last position [B, H, P, N]
    float32)."""
    bsz, t, h, p = xs.shape
    g, n = b_mat.shape[2:]
    r = h // g
    q = min(cfg.chunk_size, t)
    pad = -t % q
    if pad:  # dt = 0: the padding neither decays the state nor adds
        xs, b_mat, c_mat, dt = (jnp.pad(
            x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (xs, b_mat, c_mat, dt))
    nc = (t + pad) // q
    dtype = xs.dtype
    # Log decays, summed inside each chunk: [B, nc, Q, H].
    cum = jnp.cumsum((dt * a).reshape(bsz, nc, q, h), 2)
    x_dt = (xs.astype(jnp.float32) * dt[..., None]).reshape(
        bsz, nc, q, g, r, p)
    b_mat = b_mat.reshape(bsz, nc, q, g, n)
    c_mat = c_mat.reshape(bsz, nc, q, g, n)
    # Inside a chunk: y[t] += sum_{s <= t} (C_t . B_s) e^(cum_t - cum_s)
    # dt_s x_s.
    by_head = cum.transpose(0, 1, 3, 2)                     # [B, nc, H, Q]
    seen = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(
        seen, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
    cb = jnp.einsum("bcqgn,bcsgn->bcgqs", c_mat, b_mat,
                    preferred_element_type=jnp.float32)
    weights = cb[:, :, :, None] * decay.reshape(bsz, nc, g, r, q, q)
    y = jnp.einsum("bcgrqs,bcsgrp->bcqgrp", weights.astype(dtype),
                   x_dt.astype(dtype), preferred_element_type=jnp.float32)
    # What each chunk adds to the state by its end, and its whole decay.
    to_end = jnp.exp(cum[:, :, -1:] - cum).reshape(bsz, nc, q, g, r)
    added = jnp.einsum("bcsgn,bcsgrp->bcgrpn", b_mat,
                       (x_dt * to_end[..., None]).astype(dtype),
                       preferred_element_type=jnp.float32)
    whole = jnp.exp(cum[:, :, -1]).reshape(bsz, nc, g, r)

    def chunk(s, xs):
        """The state a chunk starts from, and the next one's."""
        added, whole = xs
        return s * whole[..., None, None] + added, s

    last, entering = lax.scan(
        chunk, s0.reshape(bsz, g, r, p, n),
        (added.swapaxes(0, 1), whole.swapaxes(0, 1)))
    # Across chunks: y[t] += e^(cum_t) C_t . (the state entering).
    carried = jnp.einsum("bcqgn,cbgrpn->bcqgrp", c_mat,
                         entering.astype(dtype),
                         preferred_element_type=jnp.float32)
    y = y + carried * jnp.exp(cum).reshape(bsz, nc, q, g, r)[..., None]
    return (y.reshape(bsz, nc * q, h, p)[:, :t],
            last.reshape(bsz, h, p, n))


def _gated_norm(cfg, y, z, weight):
    """GroupRMSNorm(y * silu(z)): the gate first, then the norm over
    each of `ssm_groups` groups of channels. y, z: [B, T, H, P]."""
    b, t, h, p = y.shape
    gated = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32)))
    groups = gated.reshape(b, t, cfg.ssm_groups, -1)
    groups = groups * lax.rsqrt(
        jnp.mean(groups * groups, -1, keepdims=True) + cfg.norm_eps)
    return groups.reshape(b, t, h * p) * weight.astype(jnp.float32)


def mixer(cfg, start_pos, at, *, in_place=False):
    """The mixer of a run of Mamba-2 layers. Its state is the run's two
    stacks, (S [layers, B, H, P, N], conv rows [layers, B, K - 1, C]),
    which `decoder.layers` carries through the scan; it reads its layer
    of each and writes it back whole, but in a call of one token, which
    updates its layer of S in place in the stack and never slices it
    out. `start_pos` [B]: a row at 0 starts from zeros; `at`: the
    position of the call's tokens after which the state is left, an int
    or an int32 scalar for all rows or int32 [B], one a row.
    `in_place`: a decode step's (`stacked_product.engages`), which
    names the output projection as the leaf `decoder.layers` leaves
    whole, for the block to read its layer's in the stack (the scan
    copied the slice; `w_xbc` the compiler reads where it lies itself,
    and `w_z`, which the TPU keeps [D, P, heads] with the contracted
    axis outermost, no kernel of `ops.stacked_product` can: its slice
    stays)."""
    h, p = cfg.ssm_heads, cfg.ssm_head_dim
    g, n = cfg.ssm_groups, cfg.ssm_state
    at = jnp.broadcast_to(jnp.asarray(at, jnp.int32), start_pos.shape)

    def mix(a, lp, rope, state, handed, stacks=None):
        del stacks  # `wo` is the block's to read
        (ssm_stack, conv_stack), layer = state
        t = a.shape[1]
        fresh = start_pos == 0
        carry = lax.dynamic_index_in_dim(conv_stack, layer, 0, False)
        carry = jnp.where(fresh[:, None, None], 0, carry)
        z = jnp.einsum("btd,dhp->bthp", a, lp["w_z"])
        xbc = jnp.einsum("btd,dc->btc", a, lp["w_xbc"])
        dt = jnp.einsum("btd,dh->bth", a, lp["w_dt"])
        with jax.named_scope("ssm_conv"):
            xbc, carry = _conv(cfg, lp, carry, xbc, at)
        xs = xbc[..., :h * p].reshape(xbc.shape[:2] + (h, p))
        b_mat, c_mat = (m.reshape(m.shape[:2] + (g, n)) for m in jnp.split(
            xbc[..., h * p:], 2, -1))
        dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
        dt = jnp.where((jnp.arange(t) <= at[:, None])[..., None], dt, 0.0)
        a_neg = -jnp.exp(lp["A_log"].astype(jnp.float32))
        if t == 1:
            # The stack's one reader and writer in the scan's body: a
            # second one would have the compiler copy the stack to keep
            # the kernel's alias honest.
            with jax.named_scope("ssm_update"):
                y, ssm_stack = ssm_update(
                    ssm_stack, layer, fresh, xs[:, 0].astype(jnp.float32),
                    b_mat[:, 0].astype(jnp.float32),
                    c_mat[:, 0].astype(jnp.float32), dt[:, 0], a_neg)
                y = y[:, None]
        else:
            s0 = jnp.where(fresh[:, None, None, None], 0.0,
                           lax.dynamic_index_in_dim(ssm_stack, layer, 0, False)
                           .astype(jnp.float32))
            with jax.named_scope("ssm_scan"):
                y, s = _scan(cfg, s0, xs, b_mat, c_mat, dt, a_neg)
            ssm_stack = lax.dynamic_update_index_in_dim(
                ssm_stack, s.astype(ssm_stack.dtype), layer, 0)
        y = y + lp["D"].astype(jnp.float32)[:, None] \
            * xs.astype(jnp.float32)
        out = _gated_norm(cfg, y, z, lp["ssm_norm"])
        state = (ssm_stack, lax.dynamic_update_index_in_dim(
            conv_stack, carry.astype(conv_stack.dtype), layer, 0))
        return out.reshape(y.shape).astype(a.dtype), state, handed

    mix.scope = "ssm"
    if in_place:
        mix.whole = ("wo",)
    return mix
