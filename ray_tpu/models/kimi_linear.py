"""Kimi Linear's decoder (`model_type` `kimi_linear`,
Kimi-Linear-48B-A3B), served through the slot cache: `decoder`'s
sequential pre-norm block over layers of two mixers and two FFNs
(`benchmark/references/kimi_linear.py` has the equations in full):

- a `kda` layer mixes by Kimi Delta Attention (`kda`: the gated delta
  rule with a decay a key channel). Its state is `gated_delta`'s four
  cache leaves with no sequence axis, a float32 matrix [dk, dv] a head
  and the carried rows of three convolutions;
- an `mla` layer is latent attention over every key before the row,
  with no positional encoding at all (`mla_use_nope`: the KDA layers
  carry the order): queries of `qk_nope_head_dim + qk_rope_head_dim`
  channels a head straight from the stream (no query bottleneck), keys
  and values one `kv_lora_rank` latent a token (RMS-normed) and one key
  of `qk_rope_head_dim` channels shared by all heads, which the name
  notwithstanding is never turned. The slot cache holds those two,
  `glm_dsa`'s leaves `latent` and `rope`, 576 numbers a token and
  layer, the second in rows of 128 lanes with zeros behind its
  channels (`_shared_row`), and the attention is `serving.latent_attention`, which GLM-5.2
  calls too: the key half of `wkvb`
  absorbed into the query, its value half applied to the output, scores
  against the cached latent by blocks of keys, for prefill and decode
  alike. No indexer: the mask is "every key up to the row's position"
  and no array;
- the leading `dense` layer has a SwiGLU of `dense_hidden_dim`, the
  rest `moe`'s expert layer: a sigmoid router in float32 whose
  selection bias chooses and does not weigh, the chosen gates
  renormalised and scaled, one shared expert, a held share of the
  experts (`cfg.experts_held`). The published grouped top-k has one
  group, so it is a plain one.

Like layers in a row are one run of `decoder.hidden_runs`, a kind of
layer being (FFN, mixer). The cache is {"runs": [a dict a run]}:
`gated_delta.LEAVES` of a run of `kda` layers (state), `latent` and
`rope` of a run of `mla` ones (rows). With state leaves the model is
served with no prefix cache.

Not here: an uncached forward pass and a loss (the chunked delta scan
has no backward pass: the model is served, not trained).
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import gated_delta, kda, llama, moe
from ray_tpu.models.serving import (KEY_BLOCK, Family, by_query_blocks,
                                    key_blocks, latent_attention, normal)
from ray_tpu.ops import block_rows, stacked_product
from ray_tpu.ops.norms import rms_norm_reference


def published_kinds(n_layers: int = 27, first_dense: int = 1):
    """(FFN, mixer) of each layer as Kimi-Linear-48B-A3B's `config.json`
    lists them: `first_dense` dense layers, sparse after; latent
    attention in every fourth layer and the last, KDA in the rest."""
    return tuple(
        ("dense" if i < first_dense else "sparse",
         "mla" if i % 4 == 3 or i == 26 else "kda") for i in range(n_layers))


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig(moe.MoEConfig):
    """Defaults are Kimi-Linear-48B-A3B's. `hidden_dim` is an expert's
    width and `dense_hidden_dim` the leading dense layer's."""
    vocab_size: int = 163840
    dim: int = 2304
    n_layers: int = 27
    n_heads: int = 32
    n_kv_heads: int = 32
    hidden_dim: int = 1024
    dense_hidden_dim: int = 9216
    max_seq_len: int = 1048576
    norm_eps: float = 1e-5
    n_experts: int = 256
    n_experts_per_token: int = 8
    scoring: str = "sigmoid"
    selection_bias: bool = True
    norm_topk_prob: bool = True
    gate_scale: float = 2.446
    shared_hidden_dim: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    delta_heads: int = 32
    delta_key_dim: int = 128
    delta_value_dim: int = 128
    conv_kernel: int = 4
    # The channels the decay's and the output gate's projections pass
    # through.
    gate_rank: int = 128
    # beta in (0, 1): `gated_delta.mixer` doubles it where this is set.
    allow_neg_eigval: bool = False
    chunk_size: int = 64
    # The delta state's dtype in the cache; the recurrence itself runs
    # in float32 whatever this is.
    state_dtype: Any = jnp.float32
    # (FFN, mixer) of each layer held; () is the published pattern.
    layer_kinds: Tuple[Tuple[str, str], ...] = ()

    @property
    def head_dim(self) -> int:
        """What `decoder.rope_tables` makes its tables for; no layer of
        this model reads them."""
        return self.qk_rope_head_dim

    @property
    def kinds(self):
        kinds = self.layer_kinds or published_kinds(self.n_layers)
        assert len(kinds) == self.n_layers and all(
            ffn in ("dense", "sparse") and mixer in ("kda", "mla")
            for ffn, mixer in kinds), kinds
        return kinds

    def runs(self):
        """[((FFN, mixer), layers)]: the stack as runs of like layers."""
        return [(kind, len(list(group)))
                for kind, group in itertools.groupby(self.kinds)]

    @staticmethod
    def debug_kimi_linear() -> "KimiLinearConfig":
        """Two whole periods, the leading dense layer in the first;
        dk != dv, a head count that is no power of two, a chunk of two
        sub-blocks shorter than the CPU tests' prompts, a quarter of
        the router's experts held."""
        return KimiLinearConfig(
            vocab_size=512, dim=48, n_layers=8, n_heads=3, n_kv_heads=3,
            hidden_dim=32, dense_hidden_dim=96, max_seq_len=256,
            dtype=jnp.float32, n_experts=16, n_experts_per_token=2,
            shared_hidden_dim=32, experts_held=(4, 4), kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            delta_heads=3, delta_key_dim=8, delta_value_dim=16,
            gate_rank=8, chunk_size=32, layer_kinds=published_kinds(8))


# ---------------------------------------------------------------------------
# Parameters and cache
# ---------------------------------------------------------------------------


def _init_layer(cfg: KimiLinearConfig, kind, key) -> Dict[str, Any]:
    ffn, mixer = kind
    d, h = cfg.dim, cfg.n_heads
    k_mixer, k_ffn = jax.random.split(key)
    lp = {"attn_norm": jnp.ones(d, cfg.dtype),
          "mlp_norm": jnp.ones(d, cfg.dtype)}
    if mixer == "kda":
        lp.update(kda.init(cfg, k_mixer))
    else:
        c, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
        kq, ka, kb, ko = jax.random.split(k_mixer, 4)
        lp.update(
            wq=normal(kq, (d, h, nope + cfg.qk_rope_head_dim), cfg.dtype),
            wkva=normal(ka, (d, c + cfg.qk_rope_head_dim), cfg.dtype),
            kv_norm=jnp.ones(c, cfg.dtype),
            wkvb=normal(kb, (c, h, nope + cfg.v_head_dim), cfg.dtype),
            wo=normal(ko, (h, cfg.v_head_dim, d), cfg.dtype) * d ** -0.5)
    if ffn == "dense":
        f = cfg.dense_hidden_dim
        k1, k2, k3 = jax.random.split(k_ffn, 3)
        lp.update(w1=normal(k1, (d, f), cfg.dtype),
                  w3=normal(k2, (d, f), cfg.dtype),
                  w2=normal(k3, (f, d), cfg.dtype) * f ** -0.5)
    else:
        lp.update(moe.expert_init(cfg, jax.random.split(k_ffn, 4), normal))
    return lp


_LANES = 128
# Keys of a block of a decode step's latent attention. Every slot reads
# whole blocks up to the longest slot's position, so at `KEY_BLOCK` the
# step a slot pays moves by a thousand keys a latent layer when one
# request of 64 passes a multiple of 1,024 (PERF.md section 6, PR 57).
_DECODE_KEY_BLOCK = 256


def _shared_row(x):
    """The shared key channels, or a query's share of them, as the
    cache keeps them: zeros behind them up to a whole row of lanes. A
    leaf whose rows are 64 channels the TPU keeps with its positions in
    the lanes, and a program that writes a row at a position and reads
    blocks of rows wants it the other way: compiled for the v5e with
    the leaf 64 wide, every step copied each such leaf whole into the
    other order at entry and back at exit (4 x 34 MB a latent layer and
    decode step at 64 slots of 4,096, where the step reads 9 MB of it).
    A zero adds nothing to a score."""
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, -x.shape[-1] % _LANES),))


def _leaves(cfg: KimiLinearConfig, kind):
    """A run's cache leaves: the delta state and the three carries
    (state), or the latent and the shared key channels (rows)."""
    if kind[1] == "kda":
        return gated_delta.state_shapes(cfg)
    shared = cfg.qk_rope_head_dim + -cfg.qk_rope_head_dim % _LANES
    return {"latent": ((cfg.kv_lora_rank,), cfg.dtype),
            "rope": ((shared,), cfg.dtype)}


# ---------------------------------------------------------------------------
# The latent layer
# ---------------------------------------------------------------------------


def _latent_mixer(cfg: KimiLinearConfig, start_pos, positions):
    """The mixer of a run of `mla` layers. Its state is the run's two
    stacks of the slot cache, the latent and the shared key channels,
    each [layers, B, S, width]: the layer's B x T new rows go into them
    at (layer, row, `start_pos[row]`) in one `block_rows.write_tokens`
    call, and attention reads the layer's keys out of them by
    blocks."""
    nope, c = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    scale = (nope + cfg.qk_rope_head_dim) ** -0.5

    def mixer(h, lp, rope, state, handed):
        stacks, layer = state
        cached = stacks[0].dtype
        with jax.named_scope("mla_proj"):
            q = jnp.einsum("btd,dhk->bthk", h, lp["wq"]).astype(cached)
            kva = jnp.einsum("btd,dc->btc", h, lp["wkva"])
            # `mla_use_nope`: neither the shared key channels nor the
            # queries' share of them are turned by position.
            new = (rms_norm_reference(kva[..., :c], lp["kv_norm"],
                                      cfg.norm_eps),
                   _shared_row(kva[..., c:]))
        new_state = block_rows.write_tokens(stacks, layer, new, start_pos)
        latent, shared_keys = ((stack, layer) for stack in new_state)

        def attend(q, pos):
            with jax.named_scope("latent_attn"):
                # `wkvb`'s key half goes into the query and its value
                # half onto the output: scores against the latent.
                q_lat = jnp.einsum("bthk,chk->bthc", q[..., :nope],
                                   lp["wkvb"][..., :nope])
                out = latent_attention(
                    q_lat, _shared_row(q[..., nope:]), latent, shared_keys,
                    None, pos, scale,
                    _DECODE_KEY_BLOCK if q.shape[1] == 1 else KEY_BLOCK)
                return jnp.einsum("bthc,chv->bthv", out.astype(cached),
                                  lp["wkvb"][..., nope:]),

        out, = by_query_blocks(attend, h.shape[1], q, positions)
        return out, new_state, handed

    return mixer


# ---------------------------------------------------------------------------
# Through the slot cache (`models.serving`)
# ---------------------------------------------------------------------------


def _halves(cfg: KimiLinearConfig, start_pos, positions, at):
    ffns = {"dense": llama.swiglu(), "sparse": moe.served_ffn(cfg)}
    mixers = {"kda": kda.mixer(cfg, start_pos, at,
                               in_place=stacked_product.engages(
                                   positions.shape[1])),
              "mla": _latent_mixer(cfg, start_pos, positions)}
    return {kind: (mixers[kind[1]], ffns[kind[0]])
            for kind in set(cfg.kinds)}


def _counts(cfg, tokens, cache, start_pos, at):
    """What a call counts beside its expert layers' pairs, int32
    scalars: `gated_delta.counts` of the KDA layers, and
    `latent_keys_read`, the cached keys a decode step's latent layers
    fetched: `latent_attention` visits whole blocks of keys up to the
    block of the longest row's position, for every row alike (none of
    a prefill, whose rows a query block's own positions bound)."""
    read = jnp.zeros((), jnp.int32)
    if tokens.shape[1] == 1:
        rows = [run["latent"] for run in cache["runs"] if "latent" in run]
        slots, max_seq = rows[0].shape[1:3]
        block = math.gcd(max_seq, _DECODE_KEY_BLOCK)
        read = (key_blocks(start_pos, max_seq, block) * block * slots
                * sum(x.shape[0] for x in rows)).astype(jnp.int32)
    return {**gated_delta.counts(tokens, start_pos, at),
            "latent_keys_read": read}


FAMILY = Family(
    init_layer=_init_layer, draw=normal, leaves=_leaves, halves=_halves,
    state=frozenset(gated_delta.LEAVES), counts=_counts)
init_params, init_cache = FAMILY.init_params, FAMILY.init_cache
state_leaves = FAMILY.state_leaves
forward, forward_with_cache = FAMILY.forward, FAMILY.forward_with_cache
