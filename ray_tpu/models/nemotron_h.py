"""Nemotron 3 Super's decoder (`model_type` `nemotron_h`), served through
the slot cache: `decoder` over blocks whose halves are optional, with
three kinds of part, one a published layer
(`benchmark/references/nemotron_h.py` has the equations in full):

- `M`, a Mamba-2 mixer (`mamba2`): its state is two cache leaves with
  no sequence axis, rewritten whole at every call;
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
import functools
import itertools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import decoder, llama, mamba2, moe
from ray_tpu.models.glm_dsa import _by_query_blocks

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


# Every matrix is drawn in float32 and cast: `mamba2.normal` says why.
_init = mamba2.normal(0.02)


def _init_block(cfg: NemotronHConfig, kind, key) -> Dict[str, Any]:
    mixer, ffn = kind
    k_mixer, k_ffn = jax.random.split(key)
    lp = {}
    if mixer is not None:
        lp["attn_norm"] = jnp.ones(cfg.dim, cfg.dtype)
    if mixer == "ssm":
        lp.update(mamba2.init(cfg, k_mixer))
    elif mixer == "attn":
        d, hd = cfg.dim, cfg.head_dim
        kq, kk, kv, ko = jax.random.split(k_mixer, 4)
        lp.update(wq=_init(kq, (d, cfg.n_heads, hd), cfg.dtype),
                  wk=_init(kk, (d, cfg.n_kv_heads, hd), cfg.dtype),
                  wv=_init(kv, (d, cfg.n_kv_heads, hd), cfg.dtype),
                  wo=_init(ko, (cfg.n_heads, hd, d), cfg.dtype) * d ** -0.5)
    if ffn is not None:
        lp["mlp_norm"] = jnp.ones(cfg.dim, cfg.dtype)
        lp.update(moe.expert_init(cfg, jax.random.split(k_ffn, 4), _init))
    return lp


def init_params(cfg: NemotronHConfig, rng) -> Dict[str, Any]:
    """embed, `runs` (a list, one dict of stacked leaves a run of like
    blocks: which leaves a run has says what its blocks are), final
    norm, `out`."""
    k_embed, k_out, k_blocks = jax.random.split(rng, 3)
    keys = jax.random.split(k_blocks, len(cfg.blocks))
    runs, at = [], 0
    for kind, n in cfg.runs():
        runs.append(jax.vmap(functools.partial(_init_block, cfg, kind))(
            keys[at:at + n]))
        at += n
    return {"embed": _init(k_embed, (cfg.vocab_size, cfg.dim), cfg.dtype),
            "runs": runs,
            "final_norm": jnp.ones(cfg.dim, cfg.dtype),
            "out": _init(k_out, (cfg.dim, cfg.vocab_size), cfg.dtype)}



# The cache leaves of a run, by its mixer: a Mamba-2 run's are state,
# an attention run's rows.
_LEAVES = {"ssm": ("ssm", "conv"), "attn": ("k", "v")}


def init_cache(cfg: NemotronHConfig, n_slots: int,
               max_seq: int) -> Dict[str, Any]:
    """The slot cache, a run at a time: the recurrent state and the
    convolution's rows of a Mamba-2 run ([layers, slots, ...], no
    sequence axis), keys and values of an attention run ([layers,
    slots, max_seq, kv heads, head size]), {} of a run with no mixer."""
    runs = []
    for (mixer, _), n in cfg.runs():
        if mixer == "ssm":
            runs.append(mamba2.init_state(cfg, n, n_slots))
        elif mixer == "attn":
            shape = (n, n_slots, max_seq, cfg.n_kv_heads, cfg.head_dim)
            runs.append({"k": jnp.zeros(shape, cfg.dtype),
                         "v": jnp.zeros(shape, cfg.dtype)})
        else:
            runs.append({})
    return {"runs": runs}


def state_leaves(cache):
    """`cache`'s structure with True at a leaf that is state (no
    sequence axis, rewritten whole) and False at one of rows."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: path[-1].key in _LEAVES["ssm"], cache)


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
        k_stack = decoder.write_rows(
            k_stack, layer, jnp.einsum("bsd,dhk->bshk", h, lp["wk"]),
            start_pos)
        v_stack = decoder.write_rows(
            v_stack, layer, jnp.einsum("bsd,dhk->bshk", h, lp["wv"]),
            start_pos)
        max_seq = k_stack.shape[2]
        keys = decoder.layer_rows(k_stack, layer, 0, max_seq)
        values = decoder.layer_rows(v_stack, layer, 0, max_seq)
        out, = _by_query_blocks(
            lambda q, pos: (llama._cached_attention(cfg, q, keys, values,
                                                    pos),),
            h.shape[1], q.astype(k_stack.dtype), positions)
        return out, (k_stack, v_stack), handed

    return mixer


# ---------------------------------------------------------------------------
# Forward through the slot cache
# ---------------------------------------------------------------------------



def _hidden(params, tokens, cfg: NemotronHConfig, cache, start_pos, at):
    """The stack through the slot cache: (final-norm hidden states
    [B, T, D], new cache, the expert layers' counts)."""
    positions = start_pos[:, None] + jnp.arange(tokens.shape[1])[None, :]
    mixers = {"ssm": mamba2.mixer(cfg, start_pos, at),
              "attn": _attention(cfg, start_pos, positions), None: None}
    runs = [(mixers[mixer], moe.served_ffn(cfg) if ffn else None, stacked,
             tuple(run[k] for k in _LEAVES[mixer]) if mixer else None)
            for ((mixer, ffn), _), stacked, run in zip(
                cfg.runs(), params["runs"], cache["runs"])]
    x, states, extras = decoder.hidden_runs(params, tokens, cfg, runs,
                                            positions=positions)
    new_cache = {"runs": [
        dict(zip(_LEAVES[mixer], state)) if mixer else {}
        for ((mixer, _), _), state in zip(cfg.runs(), states)]}
    counted = [e for e in extras if e is not None]
    counts = jax.tree.map(lambda *xs: sum(x.sum() for x in xs),
                          *counted) if counted else {}
    return x, new_cache, counts


def _logits(params, x, cfg):
    """The head in float32, as `glm_dsa`'s: the logits feed an argmax."""
    return jnp.einsum("...d,dv->...v", x, params["out"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def forward(params, tokens, cfg: NemotronHConfig, cache, start_pos, at):
    """What the engine serves through (`models.serving`): `tokens`
    [B, T] from per-row absolute offsets `start_pos` [B], prefill (T =
    the prompt's bucket) and decode (T = 1) alike. Returns (the logits
    of position `at` of `tokens`, [B, vocab] float32; the new cache,
    whose state leaves are those after position `at` and no later; what
    the expert layers counted over the call, int32 scalars summed over
    them: `pairs_held`, `pairs_routed`, `pair_overflows`,
    `experts_touched`, `experts_held_steps`)."""
    x, cache, counts = _hidden(params, tokens, cfg, cache, start_pos, at)
    x = lax.dynamic_index_in_dim(x, at, 1, keepdims=False)
    return _logits(params, x, cfg), cache, counts


def forward_with_cache(params, tokens, cfg: NemotronHConfig, cache,
                       start_pos, at=None):
    """`forward` with the logits of every position, [B, T, vocab]
    float32, and no counts: what a comparison with a reference steps
    through. The state left is that after position `at` (an int for
    all rows, or int32 [B], one a row), the last of `tokens` unless
    given."""
    at = tokens.shape[1] - 1 if at is None else at
    x, cache, _ = _hidden(params, tokens, cfg, cache, start_pos, at)
    return _logits(params, x, cfg), cache
