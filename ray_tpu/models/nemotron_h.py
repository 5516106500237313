"""Nemotron 3 Super's decoder (`model_type` `nemotron_h`), served through
the slot cache: `decoder` over blocks whose halves are optional, with
three kinds of part, one a published layer
(`benchmark/references/nemotron_h.py` has the equations in full):

- `M`, a Mamba-2 mixer (`mamba2`): its state is two cache leaves with
  no sequence axis, a layer's rewritten whole by a prefill; a decode
  step updates a layer's S where it lies in the run's stack
  (`ops/ssm_update.py`) and rewrites its convolution's rows;
- `*`, grouped-query attention through cached keys and values, with no
  positional encoding (the state-space layers carry the order);
- `E`, a LatentMoE (`moe`'s expert layer): a sigmoid router with a
  selection bias over the full width, relu^2 experts with two matrices
  in a latent of `latent_dim`, a shared expert at the full width, and a
  held share of the experts.

Every published layer is one part alone: norm, part, residual. A mixer
followed by an expert layer is one `decoder.block`; a mixer followed by
a mixer is a block with no FFN, an expert layer that follows no mixer a
block with no mixer. Like blocks in a row are one run of
`decoder.hidden_runs`, parameters and cache stacked by run.

The cache is {"runs": [a dict a run]}: `ssm` and `conv` of a Mamba-2
run (state leaves, [layers, slots, ...] with no sequence axis), `k` and
`v` of an attention run (row leaves, [layers, slots, max_seq, kv heads,
head size]), nothing of a run with no mixer. `state_leaves` says which
is which, for the engine.

Not here: the multi-token-prediction layer (a draft head; rolling a
recurrent state back on a rejected draft is ROADMAP M9), an uncached
forward pass and a loss (the chunked scan has no backward pass: the
model is served, not trained).
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import decoder, llama, mamba2, moe
from ray_tpu.models.serving import (Family, attention_init, by_query_blocks,
                                    normal)
from ray_tpu.ops import block_rows, stacked_product

PUBLISHED_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
_MIXERS = {"M": "ssm", "*": "attn"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(moe.MoEConfig):
    """Defaults are Nemotron 3 Super's. `hidden_dim` is a routed
    expert's width; `n_layers` counts published layers, `pattern`'s
    letters (`M` Mamba-2, `*` attention, `E` experts)."""
    vocab_size: int = 131072
    dim: int = 4096
    n_layers: int = 88
    n_heads: int = 32
    n_kv_heads: int = 2
    hidden_dim: int = 2688
    max_seq_len: int = 262144
    rope_theta: float = 1e4  # published and unused: no rotary turn
    norm_eps: float = 1e-5
    n_experts: int = 512
    n_experts_per_token: int = 22
    scoring: str = "sigmoid"
    selection_bias: bool = True
    gate_scale: float = 5.0
    shared_hidden_dim: int = 5376
    expert_kind: str = "relu2"
    latent_dim: int = 1024
    pattern: str = PUBLISHED_PATTERN
    ssm_heads: int = 128
    ssm_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    # The recurrent state's dtype in the cache; the recurrence itself
    # runs in float32 whatever this is.
    state_dtype: Any = jnp.float32

    @property
    def blocks(self) -> Tuple[Tuple[Optional[str], Optional[str]], ...]:
        """(mixer, FFN) of each block, bottom to top: "ssm", "attn" or
        None, and "moe" or None."""
        assert len(self.pattern) == self.n_layers \
            and set(self.pattern) <= set("M*E"), self.pattern
        out, at = [], 0
        while at < len(self.pattern):
            mixer = _MIXERS.get(self.pattern[at])
            at += mixer is not None
            ffn = at < len(self.pattern) and self.pattern[at] == "E"
            at += ffn
            out.append((mixer, "moe" if ffn else None))
        return tuple(out)

    def runs(self):
        """[((mixer, FFN), blocks)]: the stack as runs of like blocks."""
        return [(kind, len(list(group)))
                for kind, group in itertools.groupby(self.blocks)]

    @staticmethod
    def debug_nemotron() -> "NemotronHConfig":
        """Every kind of block, a quarter of the router's experts held,
        a latent narrower than the hidden size."""
        return NemotronHConfig(
            vocab_size=512, dim=64, n_layers=7, n_heads=4, n_kv_heads=2,
            hidden_dim=48, max_seq_len=128, dtype=jnp.float32,
            n_experts=16, n_experts_per_token=3, shared_hidden_dim=96,
            latent_dim=32, experts_held=(4, 4), pattern="MEMEM*E",
            ssm_heads=8, ssm_head_dim=16, ssm_groups=2, ssm_state=16,
            chunk_size=8)


# ---------------------------------------------------------------------------
# Parameters and cache
# ---------------------------------------------------------------------------


def _init_block(cfg: NemotronHConfig, kind, key) -> Dict[str, Any]:
    mixer, ffn = kind
    k_mixer, k_ffn = jax.random.split(key)
    lp = {}
    if mixer is not None:
        lp["attn_norm"] = jnp.ones(cfg.dim, cfg.dtype)
    if mixer == "ssm":
        lp.update(mamba2.init(cfg, k_mixer))
    elif mixer == "attn":
        lp.update(attention_init(cfg, normal, jax.random.split(k_mixer, 4)))
    if ffn is not None:
        lp["mlp_norm"] = jnp.ones(cfg.dim, cfg.dtype)
        lp.update(moe.expert_init(cfg, jax.random.split(k_ffn, 4), normal))
    return lp


def _leaves(cfg: NemotronHConfig, kind):
    """A run's cache leaves, as the module's docstring lists them."""
    if kind[0] == "ssm":
        return mamba2.state_shapes(cfg)
    keys = ((cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
    return {"k": keys, "v": keys} if kind[0] == "attn" else {}


# ---------------------------------------------------------------------------
# The parts
# ---------------------------------------------------------------------------


def _attention(cfg: NemotronHConfig, start_pos, positions):
    """The mixer of a run of attention layers: `llama`'s attention
    through the slot cache with no rotary turn, a block of queries at a
    time so that a prefill's scores are never [T, max_seq] a head."""
    def mixer(h, lp, rope, state, handed):
        (k_stack, v_stack), layer = state
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
        k_stack, v_stack = block_rows.write_tokens(
            (k_stack, v_stack), layer,
            (jnp.einsum("bsd,dhk->bshk", h, lp["wk"]),
             jnp.einsum("bsd,dhk->bshk", h, lp["wv"])), start_pos)
        max_seq = k_stack.shape[2]
        keys = decoder.layer_rows(k_stack, layer, 0, max_seq)
        values = decoder.layer_rows(v_stack, layer, 0, max_seq)
        out, = by_query_blocks(
            lambda q, pos: (llama._cached_attention(cfg, q, keys, values,
                                                    pos),),
            h.shape[1], q.astype(k_stack.dtype), positions)
        return out, (k_stack, v_stack), handed

    return mixer


# ---------------------------------------------------------------------------
# Through the slot cache (`models.serving`)
# ---------------------------------------------------------------------------


def _halves(cfg: NemotronHConfig, start_pos, positions, at):
    mixers = {"ssm": mamba2.mixer(cfg, start_pos, at,
                                  in_place=stacked_product.engages(
                                      positions.shape[1])),
              "attn": _attention(cfg, start_pos, positions), None: None}
    return {kind: (mixers[kind[0]], moe.served_ffn(cfg) if kind[1] else None)
            for kind in set(cfg.blocks)}


FAMILY = Family(
    init_layer=_init_block, draw=normal, leaves=_leaves, halves=_halves,
    state=frozenset(("ssm", "conv")))
init_params, init_cache = FAMILY.init_params, FAMILY.init_cache
state_leaves = FAMILY.state_leaves
forward, forward_with_cache = FAMILY.forward, FAMILY.forward_with_cache
