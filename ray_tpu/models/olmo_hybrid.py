"""Olmo Hybrid's decoder (`model_type` `olmo_hybrid`), served through
the slot cache: `decoder` over two kinds of layer, three `linear` to
one `full` (`benchmark/references/olmo_hybrid.py` has the equations in
full):

- a `linear` layer mixes by the gated delta rule (`gated_delta`): its
  state is four cache leaves with no sequence axis, a float32 matrix a
  head and the carried rows of three convolutions, rewritten whole at
  every call;
- a `full` layer is attention through cached keys and values, as many
  key-value heads as query heads, an RMSNorm over the whole projected q
  and k, and no positional encoding (the linear layers carry the
  order);
- every layer has a SwiGLU, and the block is OLMo 2's
  (`norm_placement` "output"): a half reads the stream as it is and its
  output is normed before the residual takes it.

Like layers in a row are one run of `decoder.hidden_runs`, parameters
and cache stacked by run. The cache is {"runs": [a dict a run]}:
`gated_delta.LEAVES` of a `linear` run (state leaves, [layers, slots,
...]), `k` and `v` of a `full` run (row leaves, [layers, slots,
max_seq, heads x head size]). `state_leaves` says which is which, for
the engine.

A row of keys is one axis of 3,840 channels, not [30, 128]. An array
whose last two axes are [30, 128] the TPU keeps with its rows before
its heads ([slots, heads, max_seq, head size] in memory, to spare the
padding of 30 to 32), and a program that writes a row at a position
and reads a layer's rows wants it the other way: compiled for the v5e
with the leaf as [..., max_seq, 30, 128], a decode step copied every
key and value leaf into the other order at entry and back at exit (12
copies of 503 MB at 32 slots of 2,048, 5.9 GB of temporaries on top of
10.4 GB of arguments: it did not fit), and with the heads merged but
attention through `llama._cached_attention` on a [max_seq, 30, 128]
view it copied each layer's keys and values once a step. A decode step
therefore attends on the merged axis itself: on a TPU
`ops.attention.decode_attention` is handed the run's stacks whole and
reads out of them the blocks of rows each slot holds, q spread over a
block diagonal so that the scores of all heads are one product with a
block where it lies. A prefill, one slot's rows, pays the view
(15.7 MB a leaf), and off the TPU so does a decode step.

Not here: an uncached forward pass and a loss (the chunked delta scan
has no backward pass: the model is served, not trained).
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import decoder, gated_delta, llama
from ray_tpu.models.serving import (Family, attention_init, by_query_blocks,
                                    keys_read_by_blocks, normal)
from ray_tpu.ops import attention, block_rows, stacked_product
from ray_tpu.ops.stacked_product import leaf_product

PUBLISHED_LAYER_TYPES = ("linear", "linear", "linear", "full") * 8


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig(llama.LlamaConfig):
    """Defaults are Olmo-Hybrid-7B's. `layer_types` names the layers
    held, bottom to top, "linear" or "full", `n_layers` of them."""
    vocab_size: int = 100352
    dim: int = 3840
    n_layers: int = 32
    n_heads: int = 30
    n_kv_heads: int = 30
    hidden_dim: int = 11008
    max_seq_len: int = 65536
    norm_eps: float = 1e-6
    qk_norm: bool = True
    norm_placement: str = "output"
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    delta_heads: int = 30
    delta_key_dim: int = 96
    delta_value_dim: int = 192
    conv_kernel: int = 4
    # beta in (0, 2): an eigenvalue of the transition may be negative.
    allow_neg_eigval: bool = True
    chunk_size: int = 64
    # The delta state's dtype in the cache; the recurrence itself runs
    # in float32 whatever this is.
    state_dtype: Any = jnp.float32

    def runs(self):
        """[(kind, layers)]: the stack as runs of like layers."""
        assert len(self.layer_types) == self.n_layers \
            and set(self.layer_types) <= {"linear", "full"}, self.layer_types
        return [(kind, len(list(group)))
                for kind, group in itertools.groupby(self.layer_types)]

    @staticmethod
    def debug_olmo_hybrid() -> "OlmoHybridConfig":
        """Two periods; dk != dv, a head count that is no power of two,
        a chunk shorter than the CPU tests' prompts."""
        return OlmoHybridConfig(
            vocab_size=512, dim=60, n_layers=8, n_heads=3, n_kv_heads=3,
            hidden_dim=96, max_seq_len=256, dtype=jnp.float32,
            layer_types=("linear", "linear", "linear", "full") * 2,
            delta_heads=3, delta_key_dim=8, delta_value_dim=16,
            chunk_size=8)


# ---------------------------------------------------------------------------
# Parameters and cache
# ---------------------------------------------------------------------------


def _init_layer(cfg: OlmoHybridConfig, kind, key) -> Dict[str, Any]:
    d, hd, f = cfg.dim, cfg.head_dim, cfg.hidden_dim
    k_mixer, k1, k2, k3 = jax.random.split(key, 4)
    lp = {"attn_norm": jnp.ones(d, cfg.dtype),
          "mlp_norm": jnp.ones(d, cfg.dtype),
          "w1": normal(k1, (d, f), cfg.dtype),
          "w3": normal(k2, (d, f), cfg.dtype),
          "w2": normal(k3, (f, d), cfg.dtype) * f ** -0.5}
    if kind == "linear":
        lp.update(gated_delta.init(cfg, k_mixer))
    else:
        lp.update(attention_init(cfg, normal, jax.random.split(k_mixer, 4)),
                  q_norm=jnp.ones(cfg.n_heads * hd, cfg.dtype),
                  k_norm=jnp.ones(cfg.n_kv_heads * hd, cfg.dtype))
    return lp


def _leaves(cfg: OlmoHybridConfig, kind):
    """A run's cache leaves, as the module's docstring lists them."""
    if kind == "linear":
        return gated_delta.state_shapes(cfg)
    keys = ((cfg.n_kv_heads * cfg.head_dim,), cfg.dtype)
    return {"k": keys, "v": keys}


# ---------------------------------------------------------------------------
# The full layer
# ---------------------------------------------------------------------------


def _attention(cfg: OlmoHybridConfig, start_pos, positions):
    """The mixer of a run of `full` layers: attention through the slot
    cache with a norm over all of q's and all of k's heads and no
    rotary turn. A prefill goes through `llama._cached_attention` a
    block of queries at a time, so that its scores are never [T,
    max_seq] a head, and so does any call off the TPU; on a TPU a
    decode step hands the stacks whole to
    `attention.decode_attention`."""
    def mixer(h, lp, rope, state, handed, stacks=None):
        (k_stack, v_stack), layer = state
        b, t = h.shape[:2]
        q = leaf_product("bsd,dhk->bshk", h, "wq", lp, stacks)
        k = leaf_product("bsd,dhk->bshk", h, "wk", lp, stacks)
        if cfg.qk_norm:
            q = llama.norm_all_heads(q, lp["q_norm"], cfg.norm_eps)
            k = llama.norm_all_heads(k, lp["k_norm"], cfg.norm_eps)
        v = leaf_product("bsd,dhk->bshk", h, "wv", lp, stacks)
        k_stack, v_stack = block_rows.write_tokens(
            (k_stack, v_stack), layer,
            (k.reshape(b, t, -1), v.reshape(b, t, -1)), start_pos)
        q = q.astype(k_stack.dtype)
        if t == 1 and attention.on_tpu():
            out = attention.decode_attention(
                q[:, 0], k_stack, v_stack, layer, positions[:, 0] + 1)[:, None]
        else:
            max_seq = k_stack.shape[2]
            keys = decoder.layer_rows(k_stack, layer, 0, max_seq)
            values = decoder.layer_rows(v_stack, layer, 0, max_seq)
            by_head = (b, max_seq, cfg.n_kv_heads, cfg.head_dim)
            out, = by_query_blocks(
                lambda q, pos: (llama._cached_attention(
                    cfg, q, keys.reshape(by_head), values.reshape(by_head),
                    pos),), t, q, positions)
        return out, (k_stack, v_stack), handed

    # A decode step reads the three projections where they lie in the
    # run's stack (`ops.stacked_product`), a matrix a head.
    if stacked_product.engages(positions.shape[1]):
        mixer.whole = ("wq", "wk", "wv")
    return mixer


# ---------------------------------------------------------------------------
# Through the slot cache (`models.serving`)
# ---------------------------------------------------------------------------


def _halves(cfg: OlmoHybridConfig, start_pos, positions, at):
    ffn = llama.swiglu()
    linear = gated_delta.mixer(
        cfg, start_pos, at,
        in_place=stacked_product.engages(positions.shape[1]))
    return {"linear": (linear, ffn),
            "full": (_attention(cfg, start_pos, positions), ffn)}


def _counts(cfg, tokens, cache, start_pos, at):
    return gated_delta.counts(tokens, start_pos, at)


FAMILY = Family(
    init_layer=_init_layer, draw=normal, leaves=_leaves, halves=_halves,
    state=frozenset(gated_delta.LEAVES), counts=_counts,
    keys_read=keys_read_by_blocks)
init_params, init_cache = FAMILY.init_params, FAMILY.init_cache
state_leaves = FAMILY.state_leaves
forward, forward_with_cache = FAMILY.forward, FAMILY.forward_with_cache
