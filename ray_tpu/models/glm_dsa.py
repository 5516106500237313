"""GLM-5.2's decoder (`model_type` `glm_moe_dsa`, the DeepSeek-V3.2
family), served through the slot cache: `decoder` with latent attention
whose keys a learned indexer selects, a dense SwiGLU in the leading
layers and `moe`'s expert layer (sigmoid router, a shared expert, a
held share of the experts) in the rest.

A layer, on normed activations `a` (`benchmark/references/glm_dsa.py`
has the equations in full):

- *Latent attention.* Queries go through a `q_lora_rank` bottleneck
  with an RMSNorm; keys and values are one `kv_lora_rank` latent a token
  (RMS-normed) and one rotary key of `qk_rope_head_dim` shared by all
  heads. The slot cache holds just those two, 576 numbers a token and
  layer. `wkvb` would expand a latent into each head's key and value;
  here its key half is absorbed into the query and its value half is
  applied to the attention output, so scores and the weighted sum run
  against the cached latent itself, for prefill and decode alike.
- *The indexer*, on a `full` layer: `index_n_heads` small queries from
  the query bottleneck, one key a token (LayerNorm, cached beside the
  latent), I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s]) in float32 over
  the keys s <= t, and the `index_topk` largest I of a row are the keys
  that row attends (all of them while there are no more). A `shared`
  layer has no indexer and attends the keys the nearest `full` layer
  below it chose (IndexShare): the selection is the value `decoder`
  hands from layer to layer, a mask [B, T, S].
- *Selection* is by the k-th largest score, found by bisection on the
  scores' bit patterns (32 compare-and-count passes, exact): a mask of
  the keys above it and the first of those tied with it, which are the
  keys `lax.top_k` returns; never a sort and never a gather of keys.
- *Attention* over the selected keys is masked dense
  (`serving.latent_attention`, which `kimi_linear` calls too): by
  blocks of queries and, inside, blocks of keys up to the last one the
  block's positions can see, with a running softmax in float32, so that
  no array of [T, S] a head exists and a row pays for the keys before
  it, not for the slot's whole region. Rows stand at their own positions
  (`positions`), as `llama._cached_attention` guarantees.

Layers differ (dense or sparse FFN, `full` or `shared` indexer), so the
stack is `decoder.hidden_runs` over runs of like layers, parameters and
cache stacked by run. Not here: the multi-token-prediction layer (a
draft head, ROADMAP M9), fp8 and the Hadamard rotation of the indexer
(orthogonal: the scores are the same), an uncached forward pass and a
loss (the model is served, not trained).
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models import decoder, moe
from ray_tpu.models.llama import swiglu
from ray_tpu.models.serving import (
    KEY_BLOCK as _KEY_BLOCK, Family, by_query_blocks as _by_query_blocks,
    key_blocks as _key_blocks, latent_attention as _attend,
    rotate_pairs as _rotate_pairs)
from ray_tpu.ops import block_rows
from ray_tpu.ops.norms import layer_norm, rms_norm_reference

_INDEX_KEY_EPS = 1e-6

def published_kinds(n_layers: int, first_dense: int = 3, freq: int = 4,
                    offset: int = 3):
    """(FFN, indexer) of each layer as GLM-5.2's `config.json` lists
    them: `first_dense` dense layers, sparse after; the first three
    indexers `full`, then every `freq`-th."""
    return tuple(
        ("dense" if i < first_dense else "sparse",
         "full" if i < offset or (i - offset) % freq == freq - 1
         else "shared") for i in range(n_layers))


@dataclasses.dataclass(frozen=True)
class GlmDsaConfig(moe.MoEConfig):
    """Defaults are GLM-5.2's. `hidden_dim` is an expert's width and
    `dense_hidden_dim` the leading dense layers'."""
    vocab_size: int = 154880
    dim: int = 6144
    n_layers: int = 78
    n_heads: int = 64
    n_kv_heads: int = 64
    hidden_dim: int = 2048
    max_seq_len: int = 1048576
    rope_theta: float = 8e6
    norm_eps: float = 1e-5
    n_experts: int = 256
    n_experts_per_token: int = 8
    scoring: str = "sigmoid"
    selection_bias: bool = True
    gate_scale: float = 2.5
    shared_hidden_dim: int = 2048
    dense_hidden_dim: int = 12288
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    # (FFN, indexer) of each layer; () is the published pattern.
    layer_kinds: Tuple[Tuple[str, str], ...] = ()

    @property
    def head_dim(self) -> int:
        """What rotates: `decoder.rope_tables` makes (cos, sin) of this
        many dimensions, for the rotary part of a head and of the
        indexer's queries and key alike."""
        return self.qk_rope_head_dim

    @property
    def kinds(self):
        kinds = self.layer_kinds or published_kinds(self.n_layers)
        assert len(kinds) == self.n_layers and kinds[0][1] == "full", kinds
        return kinds

    def runs(self):
        """[((FFN, indexer), layers)]: the stack as runs of like layers."""
        return [(kind, len(list(group)))
                for kind, group in itertools.groupby(self.kinds)]

    @staticmethod
    def debug_glm() -> "GlmDsaConfig":
        return GlmDsaConfig(
            vocab_size=512, dim=64, n_layers=3, n_heads=4, n_kv_heads=4,
            hidden_dim=32, dense_hidden_dim=128, max_seq_len=128,
            dtype=jnp.float32, n_experts=8, n_experts_per_token=2,
            shared_hidden_dim=32, q_lora_rank=48, kv_lora_rank=32,
            qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16,
            index_n_heads=4, index_head_dim=16, index_topk=8,
            layer_kinds=(("dense", "full"), ("sparse", "shared"),
                         ("sparse", "full")))


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _init_layer(cfg: GlmDsaConfig, kind, key) -> Dict[str, Any]:
    ffn_kind, indexer = kind
    d, h, r = cfg.dim, cfg.n_heads, cfg.q_lora_rank
    c, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    ks = jax.random.split(key, 12)
    init = jax.nn.initializers.normal(stddev=0.02)
    lp = {
        "attn_norm": jnp.ones(d, cfg.dtype),
        "wqa": init(ks[0], (d, r), cfg.dtype),
        "q_norm": jnp.ones(r, cfg.dtype),
        "wqb": init(ks[1], (r, h, cfg.qk_nope_head_dim + rope), cfg.dtype),
        "wkva": init(ks[2], (d, c + rope), cfg.dtype),
        "kv_norm": jnp.ones(c, cfg.dtype),
        "wkvb": init(ks[3], (c, h, cfg.qk_nope_head_dim + cfg.v_head_dim),
                     cfg.dtype),
        "wo": init(ks[4], (h, cfg.v_head_dim, d), cfg.dtype) * d ** -0.5,
        "mlp_norm": jnp.ones(d, cfg.dtype),
    }
    if indexer == "full":
        lp.update(
            wiq=init(ks[5], (r, cfg.index_n_heads, cfg.index_head_dim),
                     cfg.dtype),
            wik=init(ks[6], (d, cfg.index_head_dim), cfg.dtype),
            ik_norm=jnp.ones(cfg.index_head_dim, cfg.dtype),
            ik_bias=jnp.zeros(cfg.index_head_dim, cfg.dtype),
            wiw=init(ks[7], (d, cfg.index_n_heads), cfg.dtype))
    if ffn_kind == "dense":
        f = cfg.dense_hidden_dim
        lp.update(w1=init(ks[8], (d, f), cfg.dtype),
                  w3=init(ks[9], (d, f), cfg.dtype),
                  w2=init(ks[10], (f, d), cfg.dtype) * f ** -0.5)
    else:
        lp.update(moe.expert_init(cfg, jax.random.split(ks[11], 4)))
    return lp


def _leaves(cfg: GlmDsaConfig, kind):
    """A run's cache leaves, all rows: the latent and the rotary key of
    every layer, the indexer's key of the `full` ones."""
    leaves = {"latent": ((cfg.kv_lora_rank,), cfg.dtype),
              "rope": ((cfg.qk_rope_head_dim,), cfg.dtype)}
    if kind[1] == "full":
        leaves["index"] = ((cfg.index_head_dim,), cfg.dtype)
    return leaves


# ---------------------------------------------------------------------------
# The mixer
# ---------------------------------------------------------------------------


def _rotate_head(x, cos, sin, n):
    """`_rotate_pairs` on the first `n` of the last axis."""
    return jnp.concatenate(
        [_rotate_pairs(x[..., :n], cos, sin), x[..., n:]], -1)


def _index_scores(qi, w, index_cache, positions):
    """I[b, t, s] = sum_j w[b, t, j] relu(qi[b, t, j] . k[b, s]) in
    float32 for s <= positions[b, t], -inf past it. qi [B, T, J, D], w
    [B, T, J] float32, index_cache (the stack [layers, B, S, D], the
    layer), read a block of keys at a time -> [B, T, S]."""
    b, t = positions.shape
    s = index_cache[0].shape[2]
    tk = math.gcd(s, _KEY_BLOCK)

    def body(j, out):
        keys = decoder.layer_rows(*index_cache, j * tk, tk)
        dots = jnp.einsum("btjd,bsd->btjs", qi, keys,
                          preferred_element_type=jnp.float32)
        block = (jax.nn.relu(dots) * w[..., None]).sum(2)
        return lax.dynamic_update_slice_in_dim(out, block, j * tk, 2)

    out = lax.fori_loop(0, _key_blocks(positions, s, tk), body,
                        jnp.full((b, t, s), -jnp.inf, jnp.float32))
    seen = jnp.arange(s)[None, None, :] <= positions[:, :, None]
    return jnp.where(seen, out, -jnp.inf)


def _top_k_mask(scores, k):
    """Mask of the k largest entries of each row (last axis), ties to
    the lower index, as `lax.top_k` chooses: the k-th largest value by
    bisection on the float32 bit patterns, made to order as the numbers
    do, then the entries above it and the first of those equal to it.
    All of a row shorter than k."""
    if k >= scores.shape[-1]:
        return jnp.ones(scores.shape, bool)
    bits = lax.bitcast_convert_type(scores, jnp.uint32)
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def body(i, reached):
        higher = reached | (jnp.uint32(1 << 31) >> jnp.uint32(i))
        enough = (keys >= higher).sum(-1, keepdims=True) >= k
        return jnp.where(enough, higher, reached)

    kth = lax.fori_loop(0, 32, body,
                        jnp.zeros(scores.shape[:-1] + (1,), jnp.uint32))
    above = keys > kth
    tied = keys == kth
    room = k - above.sum(-1, keepdims=True)
    return above | (tied & (jnp.cumsum(tied, -1, dtype=jnp.int32) <= room))


def _select(cfg, scores, positions):
    """The keys each row attends: the `index_topk` of largest score
    among those it can see."""
    seen = jnp.arange(scores.shape[-1])[None, None, :] \
        <= positions[:, :, None]
    return _top_k_mask(scores, cfg.index_topk) & seen


def _mixer(cfg: GlmDsaConfig, indexer, start_pos, positions):
    """The mixer of a run of `full` or of `shared` layers. Its state is
    the run's stacks of the slot cache, (latent, rotary key) and for
    `full` layers the indexer's key, each [layers, B, S, width], which
    `decoder.layers` carries through the scan: the layer's B x T new
    rows go into them at (layer, row, `start_pos[row]`), all of the
    layer's leaves in one `block_rows.write_tokens` call, and the
    indexer and attention read the layer's keys out of them by blocks.
    It is handed and hands on the selection [B, T, S]."""
    nope, rot = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    scale = (nope + rot) ** -0.5
    index_scale = (cfg.index_n_heads * cfg.index_head_dim) ** -0.5

    def mixer(h, lp, rope, state, selected):
        stacks, layer = state
        cos, sin = rope
        with jax.named_scope("mla_proj"):
            c_q = rms_norm_reference(
                jnp.einsum("btd,dr->btr", h, lp["wqa"]), lp["q_norm"],
                cfg.norm_eps)
            q = jnp.einsum("btr,rhk->bthk", c_q, lp["wqb"])
            kva = jnp.einsum("btd,dc->btc", h, lp["wkva"])
            c_kv = rms_norm_reference(kva[..., :cfg.kv_lora_rank],
                                      lp["kv_norm"], cfg.norm_eps)
            new = (c_kv, _rotate_pairs(kva[..., cfg.kv_lora_rank:], cos, sin))
            cached = stacks[0].dtype
            q_nope = q[..., :nope].astype(cached)
            q_rope = _rotate_pairs(q[..., nope:], cos, sin).astype(cached)
        if indexer == "full":
            with jax.named_scope("indexer"):
                qi = _rotate_head(
                    jnp.einsum("btr,rjd->btjd", c_q, lp["wiq"]), cos, sin,
                    rot)
                ki = _rotate_head(layer_norm(
                    jnp.einsum("btd,de->bte", h, lp["wik"]), lp["ik_norm"],
                    lp["ik_bias"], _INDEX_KEY_EPS), cos, sin, rot)
                qi = qi.astype(stacks[2].dtype)
                w = jnp.einsum("btd,dj->btj", h, lp["wiw"]).astype(
                    jnp.float32) * index_scale
            new += (ki,)
        # Each a stack with this layer's new rows in it, and the layer:
        # what the indexer and attention read blocks from.
        new_state = block_rows.write_tokens(stacks, layer, new, start_pos)
        caches = [(stack, layer) for stack in new_state]
        latent, rope_keys = caches[:2]

        def attend(q_nope, q_rope, pos, *chosen):
            """One block of queries. `chosen`: the indexer's queries
            and weights on a `full` layer, the selection handed up on a
            `shared` one."""
            if indexer == "full":
                with jax.named_scope("indexer"):
                    scores = _index_scores(*chosen, caches[2], pos)
                with jax.named_scope("index_select"):
                    mask = _select(cfg, scores, pos)
            else:
                mask, = chosen
            with jax.named_scope("sparse_attn"):
                # `wkvb`'s key half goes into the query and its value
                # half onto the output: scores against the latent.
                q_lat = jnp.einsum("bthk,chk->bthc", q_nope,
                                   lp["wkvb"][..., :nope])
                out = _attend(q_lat, q_rope, latent, rope_keys, mask, pos,
                              scale)
                out = jnp.einsum("bthc,chv->bthv", out.astype(cached),
                                 lp["wkvb"][..., nope:])
            return out, mask

        chosen = (qi, w) if indexer == "full" else (selected,)
        out, selected = _by_query_blocks(
            attend, h.shape[1], q_nope, q_rope, positions, *chosen)
        return out, new_state, selected

    return mixer


# ---------------------------------------------------------------------------
# Through the slot cache (`models.serving`)
# ---------------------------------------------------------------------------


def _halves(cfg: GlmDsaConfig, start_pos, positions, at):
    return {kind: (_mixer(cfg, kind[1], start_pos, positions),
                   swiglu() if kind[0] == "dense" else moe.served_ffn(cfg))
            for kind in set(cfg.kinds)}


def _no_selection(tokens, cache):
    """What the first layer, a `full` one, is handed: a mask [B, T, S]
    as every layer hands up."""
    return jnp.zeros(tokens.shape + (cache["runs"][0]["latent"].shape[2],),
                     bool)


def keys_attended(cfg: GlmDsaConfig, lengths):
    """Of `lengths` cached keys a row (host integers), how many the
    row's next token attends."""
    return np.minimum(lengths, cfg.index_topk)


# Every matrix is drawn in the config's dtype (ROADMAP D12).
FAMILY = Family(
    init_layer=_init_layer, draw=jax.nn.initializers.normal(0.02),
    leaves=_leaves, halves=_halves, handed=_no_selection,
    keys_attended=keys_attended)
init_params, init_cache = FAMILY.init_params, FAMILY.init_cache
forward, forward_with_cache = FAMILY.forward, FAMILY.forward_with_cache
