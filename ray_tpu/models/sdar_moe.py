"""SDAR's decoder (`model_type` `sdar_moe`), served through the slot
cache: a model that generates by diffusion over blocks of
`block_length` positions (`benchmark/references/sdar_moe.py` has the
equations in full). `decoder`'s sequential block over layers that are
all alike:

- grouped-query attention with an RMSNorm over the channels of *each
  head* of q and of k (one weight of a head's size each: the Qwen3
  family's norm, not `llama.norm_all_heads`), rotary positions on
  split halves at absolute positions, and a mask that is causal
  between blocks and goes both ways inside one: the query at position
  i sees key j iff `j // block_length <= i // block_length`;
- `moe`'s expert layer on every layer: a softmax router over all the
  experts, the chosen gates renormalised, no shared expert. A layer
  holds all its experts and reads them where they lie in the run's
  stack, as a held share does (`moe.served_ffn` of the share that is
  the whole: the slice of a layer out of the scan's stack would be a
  copy of 1.2 GB a layer at the published widths);
- an untied head, and no shift: the logits at position i score the
  token *at* i, so a position that holds the mask token predicts
  itself.

`forward` and `forward_with_cache` take `tokens` [B, T] at `start_pos`
[B] as every family's do, with T and `start_pos` multiples of
`block_length`: the call's rows are written first, and a row then sees
its slot's rows up to the end of its own block. `start_pos` [B, T /
`block_length`] gives every block of the call a start of its own
(`models/serving.py`). The cache is two row
leaves a run, `k` and `v`, [layers, slots, max_seq, kv heads x head
size]: a row of keys is one axis of 512 channels and not [4, 128] (an
array whose last two axes are [4, 128] the TPU pads to whole tiles of
rows, and a view of it as rows x heads is then a copy;
`olmo_hybrid.py` has the measurement). Rows past a slot's length are
scratch: a denoising pass writes its block's rows from mask tokens,
and the pass that commits the block writes them again from the final
ones.

Three shapes of call: block steps (T = `block_length`, or a start a
block: every query of a block sees the same keys, so the block's
queries x heads ride `ops.attention.decode_attention` as further query
heads of their key head, the blocks of a call as groups of them with a
length each, and the slot's keys are read once; the engine's step is
two blocks a slot, the one it commits and the one it denoises, their
rows written by `ops.block_rows.write_blocks`, and a block that the
next is written over goes through no expert and reads one key); a
prefill from
position 0 at a bucket the flash kernel tiles, on a TPU
(`ops.attention.flash_attention_forward` with the block mask, over the
call's own keys; `serving.own_keys` chooses on the device, since a tail
behind a prefix-cache hit starts later); and anything else through
`llama._cached_attention` a block of queries at a time, each query
handed the last position of its block.

Not here: an uncached forward pass and a loss (block-diffusion training
runs a noised and a clean copy of the sequence under one mask, which
nothing of the trained path has yet), and the confidence-threshold
remasking schedule (`low_confidence_dynamic`): the engine fixes a
static count a step.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models import decoder, llama, moe
from ray_tpu.models.serving import (Family, attention_init, by_query_blocks,
                                    keys_read_by_blocks, normal, own_keys)
from ray_tpu.ops import attention, block_rows
from ray_tpu.ops.norms import rms_norm_reference
from ray_tpu.ops.rope import apply_rope


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig(moe.MoEConfig):
    """Defaults are SDAR-30B-A3B-Chat's. `hidden_dim` is one expert's
    width. `block_length` is the length the family's chat checkpoints
    were released with and `denoising_steps` what the benchmark's
    configuration serves them at (the published config names neither);
    `mask_token_id` is the mask token of its published vocabulary."""
    vocab_size: int = 151936
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_size: int = 128
    hidden_dim: int = 768
    max_seq_len: int = 32768
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    n_experts: int = 128
    n_experts_per_token: int = 8
    scoring: str = "softmax"
    norm_topk_prob: bool = True
    # A block's positions are generated together; a denoising step
    # fixes `block_length // denoising_steps` of them.
    block_length: int = 4
    denoising_steps: int = 2
    mask_token_id: int = 151669

    @property
    def head_dim(self) -> int:
        """Published beside the hidden size, not its quotient by the
        heads: 32 heads of 128 over a stream of 2048."""
        return self.head_size

    def runs(self):
        """[(kind, layers)]: every layer is alike."""
        return [("full", self.n_layers)]

    @staticmethod
    def debug() -> "SdarMoeConfig":
        return SdarMoeConfig(
            vocab_size=512, dim=64, n_layers=2, n_heads=8, n_kv_heads=2,
            head_size=16, hidden_dim=32, max_seq_len=128,
            dtype=jnp.float32, remat=False, n_experts=8,
            n_experts_per_token=3, mask_token_id=511)


# ---------------------------------------------------------------------------
# Parameters and cache
# ---------------------------------------------------------------------------


def _init_layer(cfg: SdarMoeConfig, key) -> Dict[str, Any]:
    *k_attention, k_ffn = jax.random.split(key, 5)
    return {"attn_norm": jnp.ones(cfg.dim, cfg.dtype),
            "mlp_norm": jnp.ones(cfg.dim, cfg.dtype),
            "q_norm": jnp.ones(cfg.head_dim, cfg.dtype),
            "k_norm": jnp.ones(cfg.head_dim, cfg.dtype),
            **attention_init(cfg, normal, k_attention),
            **moe.expert_init(cfg, jax.random.split(k_ffn, 4), normal)}


def _leaves(cfg: SdarMoeConfig, kind):
    row = ((cfg.n_kv_heads * cfg.head_dim,), cfg.dtype)
    return {"k": row, "v": row}


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

# A prefill of a multiple of so many rows goes through the flash kernel
# where it can (`serving.own_keys`): every bucket from here up is one
# (`serve.llm.prefill_bucket`: 384, 768 and 1,536 among them), and the
# kernel's tile is the largest such multiple, up to 1,024, that divides
# the call's rows (`attention.forward_tile`).
_FLASH_ROWS = 128


def norm_each_head(x, weight, eps):
    """RMSNorm of [B, S, H, K] over the K channels of each head, one
    weight [K] for all of them."""
    return rms_norm_reference(x, weight, eps)


def block_ends(positions, block):
    """The last position of each position's block."""
    return positions // block * block + block - 1


def _mixer(cfg: SdarMoeConfig, start_pos, positions):
    """The mixer of every layer; its state is the run's (K, V) stacks.
    `start_pos` [B] is where the call's rows begin, or [B, T / block],
    a start a block of the call (a step of the engine)."""
    block = cfg.block_length
    b, t = positions.shape
    assert t % block == 0, (t, block)
    g, d = cfg.n_kv_heads, cfg.head_dim
    rep = cfg.n_heads // g

    def block_step(q, k_stack, v_stack, layer, ends):
        # A block's queries see the same keys, so they stand beside
        # each other as query heads of their key head, [key head,
        # position, query head of it]; the blocks of a call with a
        # start each as groups of them, `ends` [B, blocks] an end each.
        heads = q.reshape(b, t, g, rep, d).transpose(0, 2, 1, 3, 4)
        out = attention.decode_attention(
            heads.reshape(b, g * t * rep, d), k_stack, v_stack, layer, ends)
        out = out.reshape(b, g, t, rep, d).transpose(0, 2, 1, 3, 4)
        return out.reshape(b, t, g * rep, d)

    def mixer(h, lp, rope, state, handed):
        (k_stack, v_stack), layer = state
        cached = k_stack.dtype
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"]).astype(cached)
        q = norm_each_head(q, lp["q_norm"], cfg.norm_eps)
        k = norm_each_head(k, lp["k_norm"], cfg.norm_eps)
        q = apply_rope(q, *rope).astype(cached)
        k = apply_rope(k, *rope).astype(cached)
        k_rows, v_rows = k.reshape(b, t, g * d), v.reshape(b, t, g * d)
        if start_pos.ndim == 2:
            # Blocks in the call's order, each written where it starts
            # (of two at one start the later one's rows stay) and then
            # reading up to its own end, the slot's keys once for all.
            k_stack, v_stack = block_rows.write_blocks(
                (k_stack, v_stack), layer, (k_rows, v_rows), start_pos)
            # (A block that is written over reads one key, not its
            # slot's: what comes of it is nobody's.)
            ends = jnp.where(_written_over(start_pos), 1, start_pos + block)
            out = block_step(q, k_stack, v_stack, layer, ends)
            return out, (k_stack, v_stack), handed
        k_stack = decoder.write_rows(k_stack, layer, k_rows, start_pos)
        v_stack = decoder.write_rows(v_stack, layer, v_rows, start_pos)
        if t == block:
            out = block_step(q, k_stack, v_stack, layer, start_pos + t)
            return out, (k_stack, v_stack), handed

        def plain():
            rows = k_stack.shape[2]
            keys, values = (
                decoder.layer_rows(x, layer, 0, rows).reshape(b, rows, g, d)
                for x in (k_stack, v_stack))
            return by_query_blocks(
                lambda q, ends: (llama._cached_attention(
                    cfg, q, keys, values, ends),),
                t, q, block_ends(positions, block))[0]

        out = own_keys(
            not t % _FLASH_ROWS, start_pos,
            lambda: attention.flash_attention_forward(q, k, v, block=block),
            plain)
        return out, (k_stack, v_stack), handed

    return mixer


# ---------------------------------------------------------------------------
# Through the slot cache (`models.serving`)
# ---------------------------------------------------------------------------


def _written_over(start_pos):
    """[B, blocks] bool, of a call with a start a block: the block
    stands where the one behind it does, so its rows are written over
    and nobody reads what comes of them."""
    return jnp.pad(start_pos[:, :-1] == start_pos[:, 1:], ((0, 0), (0, 1)))


def _halves(cfg: SdarMoeConfig, start_pos, positions, at):
    # The share that is the whole: the grouped products pick (layer,
    # expert) out of the run's stack as they fetch a matrix.
    whole = dataclasses.replace(cfg, experts_held=(0, cfg.n_experts))
    live = None
    if start_pos.ndim == 2:
        # A block that is written over goes through no expert.
        live = ~jnp.repeat(_written_over(start_pos), cfg.block_length, -1)
    return {"full": (_mixer(cfg, start_pos, positions),
                     moe.served_ffn(whole, live))}


FAMILY = Family(
    init_layer=lambda cfg, kind, key: _init_layer(cfg, key), draw=normal,
    leaves=_leaves, halves=_halves, keys_read=keys_read_by_blocks,
    block_length=lambda cfg: cfg.block_length)
init_params, init_cache = FAMILY.init_params, FAMILY.init_cache
forward, forward_with_cache = FAMILY.forward, FAMILY.forward_with_cache
