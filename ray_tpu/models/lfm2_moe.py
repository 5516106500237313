"""LFM2's sparse decoder (`model_type` `lfm2_moe`, LFM2-8B-A1B), served
through the slot cache: `decoder`'s sequential block over layers of two
mixers and two FFNs (`benchmark/references/lfm2_moe.py` has the
equations in full):

- a `conv` layer mixes by a *gated short convolution*: one projection
  of the normed stream into three parts, [B | C | u]; a causal
  depthwise convolution of width `conv_kernel` over B * u, no bias and
  no activation (`mamba2._conv`, told so); the result gated by C; the
  block applies the output projection (`wo`). What a sequence leaves
  behind is the last `conv_kernel - 1` rows of B * u and nothing else:
  one state leaf, [layers, slots, K - 1, D], 8 KB a slot and layer at
  the published widths, under `mamba2`'s three rules (a row that starts
  at position 0 starts from zeros, the rows left are those that end at
  `at`, a row that starts later continues from its leaf);
- a `full` layer is grouped-query attention with an RMSNorm over the
  channels of each head of q and of k (one weight of a head's size
  each) before the rotary turn (`ops/rope.py`'s split halves), through
  cached keys and values. A row of keys is one axis of kv heads x head
  size channels (512 = 8 x 64), not [8, 64]: `olmo_hybrid.py` has what
  the other layout costs. A decode step on a TPU hands the run's stacks
  whole to `ops.attention.decode_attention`; a prefill from position 0
  at a bucket the flash kernel tiles goes through
  `ops.attention.flash_attention_forward` over the call's own keys
  (`serving.own_keys` chooses on the device); anything else through
  `llama._cached_attention` a block of queries at a time;
- the `n_dense_layers` leading layers have a SwiGLU of
  `dense_hidden_dim`, the rest `moe`'s expert layer: a sigmoid router
  in float32 whose selection bias chooses and does not weigh, the
  chosen gates renormalised, no shared expert. A layer holds all its
  experts and reads them where they lie in the run's stack
  (`moe.served_ffn` of the share that is the whole, as `sdar_moe`);
- the head is the embedding (`Family.tied`).

Like layers in a row are one run of `decoder.hidden_runs`, a kind of
layer being (FFN, mixer) as `glm_dsa`'s is. The cache is {"runs": [a
dict a run]}: `conv` of a run of conv layers, `k` and `v` of a run of
full ones.

Not here: an uncached forward pass and a loss (the model is served, not
trained).
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from ray_tpu.models import decoder, llama, mamba2, moe
from ray_tpu.models.serving import (Family, attention_init, by_query_blocks,
                                    keys_read_by_blocks, normal, own_keys)
from ray_tpu.ops import attention, block_rows
from ray_tpu.ops.norms import rms_norm_reference
from ray_tpu.ops.rope import apply_rope

PUBLISHED_LAYER_TYPES = (
    "conv", "conv", "full", "conv", "conv", "conv", "full", "conv", "conv",
    "conv", "full", "conv", "conv", "conv", "full", "conv", "conv", "conv",
    "full", "conv", "conv", "full", "conv", "conv")


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig(moe.MoEConfig):
    """Defaults are LFM2-8B-A1B's. `hidden_dim` is one expert's width,
    `dense_hidden_dim` the leading dense layers'. `layer_types` names
    the layers held, bottom to top, "conv" or "full", `n_layers` of
    them, the first `n_dense_layers` of which have the dense FFN."""
    vocab_size: int = 65536
    dim: int = 2048
    n_layers: int = 24
    n_heads: int = 32
    n_kv_heads: int = 8
    hidden_dim: int = 1792
    dense_hidden_dim: int = 7168
    max_seq_len: int = 128000
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    tie_embeddings: bool = True
    logit_scale: float = 1.0
    # Here the norm is over each head's channels (`_attention`).
    qk_norm: bool = True
    n_experts: int = 32
    n_experts_per_token: int = 4
    scoring: str = "sigmoid"
    selection_bias: bool = True
    norm_topk_prob: bool = True
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    n_dense_layers: int = 2
    conv_kernel: int = 3

    @property
    def kinds(self):
        """(FFN, mixer) of each layer held."""
        assert len(self.layer_types) == self.n_layers \
            and set(self.layer_types) <= {"conv", "full"}, self.layer_types
        return tuple(("dense" if i < self.n_dense_layers else "sparse", mixer)
                     for i, mixer in enumerate(self.layer_types))

    def runs(self):
        """[((FFN, mixer), layers)]: the stack as runs of like layers."""
        return [(kind, len(list(group)))
                for kind, group in itertools.groupby(self.kinds)]

    @staticmethod
    def debug_lfm2() -> "Lfm2MoeConfig":
        """Both leading dense layers, then a period and a half; four
        query heads a key head, as published."""
        return Lfm2MoeConfig(
            vocab_size=512, dim=64, n_layers=8, n_heads=8, n_kv_heads=2,
            hidden_dim=32, dense_hidden_dim=96, max_seq_len=256,
            dtype=jnp.float32, n_experts=8,
            n_experts_per_token=3, layer_types=PUBLISHED_LAYER_TYPES[:8])


# ---------------------------------------------------------------------------
# Parameters and cache
# ---------------------------------------------------------------------------


def _init_layer(cfg: Lfm2MoeConfig, kind, key) -> Dict[str, Any]:
    """One layer of `kind`. The convolution is drawn as the Mamba-2
    reference draws its own, uniform in +-K^-1/2."""
    ffn, mixer = kind
    d = cfg.dim
    k_mixer, k_conv, k_ffn = jax.random.split(key, 3)
    lp = {"attn_norm": jnp.ones(d, cfg.dtype),
          "mlp_norm": jnp.ones(d, cfg.dtype)}
    if mixer == "conv":
        k_in, k_out = jax.random.split(k_mixer)
        bound = cfg.conv_kernel ** -0.5
        lp.update(
            w_in=normal(k_in, (d, 3 * d), cfg.dtype),
            conv_w=jax.random.uniform(
                k_conv, (d, cfg.conv_kernel), jnp.float32, -bound,
                bound).astype(cfg.dtype),
            wo=normal(k_out, (1, d, d), cfg.dtype) * d ** -0.5)
    else:
        lp.update(attention_init(cfg, normal, jax.random.split(k_mixer, 4)))
        if cfg.qk_norm:
            lp.update(q_norm=jnp.ones(cfg.head_dim, cfg.dtype),
                      k_norm=jnp.ones(cfg.head_dim, cfg.dtype))
    if ffn == "dense":
        f = cfg.dense_hidden_dim
        k1, k2, k3 = jax.random.split(k_ffn, 3)
        lp.update(w1=normal(k1, (d, f), cfg.dtype),
                  w3=normal(k2, (d, f), cfg.dtype),
                  w2=normal(k3, (f, d), cfg.dtype) * f ** -0.5)
    else:
        lp.update(moe.expert_init(cfg, jax.random.split(k_ffn, 4), normal))
    return lp


def _leaves(cfg: Lfm2MoeConfig, kind):
    """A run's cache leaves: the convolution's carried rows (state) or
    the keys and values (rows)."""
    if kind[1] == "conv":
        return {"conv": ((cfg.conv_kernel - 1, cfg.dim), cfg.dtype)}
    row = ((cfg.n_kv_heads * cfg.head_dim,), cfg.dtype)
    return {"k": row, "v": row}


# ---------------------------------------------------------------------------
# The conv layer
# ---------------------------------------------------------------------------


def _short_conv(cfg: Lfm2MoeConfig, lp, carry, bcu, fresh, at):
    """[B | C | u] [B, T, 3 D] behind the carried rows [B, K - 1, D] ->
    (C * conv(B * u) [B, T, D], the rows to carry on). A row that is
    `fresh` starts from zeros whatever its carry held."""
    carry = jnp.where(fresh[:, None, None], 0, carry)
    b_gate, c_gate, u = jnp.split(bcu, 3, -1)
    conv, carry = mamba2._conv(cfg, lp, carry, b_gate * u, at, bias=False,
                               activation=None)
    return c_gate * conv, carry


def _conv_mixer(cfg: Lfm2MoeConfig, start_pos, at):
    """The mixer of a run of conv layers. Its state is the run's one
    stack, [layers, B, K - 1, D], which `decoder.layers` carries
    through the scan; it reads its layer of it and writes it back
    whole. `start_pos` and `at` as `mamba2.mixer`'s."""
    at = jnp.broadcast_to(jnp.asarray(at, jnp.int32), start_pos.shape)

    def mix(h, lp, rope, state, handed):
        (stack,), layer = state
        with jax.named_scope("conv_in"):
            bcu = jnp.einsum("btd,dc->btc", h, lp["w_in"])
        y, carry = _short_conv(
            cfg, lp, lax.dynamic_index_in_dim(stack, layer, 0, False), bcu,
            start_pos == 0, at)
        stack = lax.dynamic_update_index_in_dim(
            stack, carry.astype(stack.dtype), layer, 0)
        return y[:, :, None], (stack,), handed

    mix.scope = "conv"
    return mix


# ---------------------------------------------------------------------------
# The full layer
# ---------------------------------------------------------------------------

# A prefill of a multiple of so many rows goes through the flash kernel
# where it can (`serving.own_keys`): every bucket from here up is one
# (`serve.llm.prefill_bucket`: 384, 768 and 1,536 among them), and the
# kernel's tile is the largest such multiple, up to 1,024, that divides
# the call's rows (`attention.forward_tile`).
_FLASH_ROWS = 128


def _attention(cfg: Lfm2MoeConfig, start_pos, positions):
    """The mixer of a run of `full` layers; its state is the run's
    (K, V) stacks, each [layers, B, S, kv heads x head size]."""
    b, t = positions.shape
    g, d = cfg.n_kv_heads, cfg.head_dim

    def mixer(h, lp, rope, state, handed):
        (k_stack, v_stack), layer = state
        cached = k_stack.dtype
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"]).astype(cached)
        if cfg.qk_norm:
            q = rms_norm_reference(q, lp["q_norm"], cfg.norm_eps)
            k = rms_norm_reference(k, lp["k_norm"], cfg.norm_eps)
        q = apply_rope(q, *rope).astype(cached)
        k = apply_rope(k, *rope).astype(cached)
        k_stack, v_stack = block_rows.write_tokens(
            (k_stack, v_stack), layer,
            (k.reshape(b, t, g * d), v.reshape(b, t, g * d)), start_pos)
        if t == 1 and attention.on_tpu():
            out = attention.decode_attention(
                q[:, 0], k_stack, v_stack, layer, positions[:, 0] + 1)[:, None]
            return out, (k_stack, v_stack), handed

        def plain():
            # The layer's rows pinned to the order the leaf lies in. A
            # view [rows, kv heads, 64] has half a row of lanes a head,
            # so the compiler would rather keep it with the rows in the
            # lanes, and, the view being the carried stack's own bytes,
            # keep the stack so: compiled for the v5e without the pin,
            # a prefill copied every key and value leaf whole into that
            # order and back (6 x 134 MB a call at 64 slots).
            rows = k_stack.shape[2]
            keys, values = (
                with_layout_constraint(
                    decoder.layer_rows(x, layer, 0, rows),
                    Layout(major_to_minor=(0, 1, 2))).reshape(b, rows, g, d)
                for x in (k_stack, v_stack))
            return by_query_blocks(
                lambda q, pos: (llama._cached_attention(
                    cfg, q, keys, values, pos),), t, q, positions)[0]

        out = own_keys(
            not t % _FLASH_ROWS, start_pos,
            lambda: attention.flash_attention_forward(q, k, v), plain)
        return out, (k_stack, v_stack), handed

    return mixer


# ---------------------------------------------------------------------------
# Through the slot cache (`models.serving`)
# ---------------------------------------------------------------------------


def _halves(cfg: Lfm2MoeConfig, start_pos, positions, at):
    # The share that is the whole: the grouped products pick (layer,
    # expert) out of the run's stack as they fetch a matrix.
    whole = dataclasses.replace(cfg, experts_held=(0, cfg.n_experts))
    ffns = {"dense": llama.swiglu(), "sparse": moe.served_ffn(whole)}
    mixers = {"conv": _conv_mixer(cfg, start_pos, at),
              "full": _attention(cfg, start_pos, positions)}
    return {kind: (mixers[kind[1]], ffns[kind[0]])
            for kind in set(cfg.kinds)}


def _counts(cfg, tokens, cache, start_pos, at):
    """What a call counts, int32 scalars: the rows whose convolutions
    started from zeros, and the real tokens a prefill carried through
    them (its padding past `at` left out; none of a call of one
    token)."""
    at = jnp.broadcast_to(jnp.asarray(at, jnp.int32), start_pos.shape)
    return {"conv_state_resets": (start_pos == 0).sum(dtype=jnp.int32),
            "conv_prefill_tokens": (at + 1).sum(dtype=jnp.int32)
            if tokens.shape[1] > 1 else jnp.zeros((), jnp.int32)}


FAMILY = Family(
    init_layer=_init_layer, draw=normal, leaves=_leaves, halves=_halves,
    state=frozenset({"conv"}), tied=True, counts=_counts,
    keys_read=keys_read_by_blocks)
init_params, init_cache = FAMILY.init_params, FAMILY.init_cache
state_leaves = FAMILY.state_leaves
forward, forward_with_cache = FAMILY.forward, FAMILY.forward_with_cache
