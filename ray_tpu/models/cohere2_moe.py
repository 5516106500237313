"""Command A+'s decoder (`model_type` `cohere2_moe`), served through the
slot cache: `decoder`'s parallel block (one LayerNorm without bias,
attention and the expert layer both reading its output, one residual)
over two kinds of layer, three `sliding` to one `full`
(`benchmark/references/cohere2_moe.py` has the equations in full):

- a `sliding` layer's queries and keys are turned by rotary positions
  (interleaved pairs, all of a head) and a row sees the `sliding_window`
  keys that end with itself;
- a `full` layer has no positional encoding at all and a row sees every
  key before it;
- both are grouped-query attention, and beside both stands `moe`'s
  expert layer: a sigmoid router whose chosen gates are renormalised,
  a held share of the experts, and `n_shared_experts` shared experts
  whose mean is added (fused into one, `moe._add_shared_expert`).

The cache is {"runs": [a dict a run of like layers]}. A `full` run's
leaves `k` and `v` are rows, [layers, slots, max_seq, kv heads, head
size], written through `block_rows.write_tokens` (`decoder.write_rows`,
or for a decode step on a TPU the kernel that does the same) and read
through `layer_rows`. A `sliding` run's `ring_k` and `ring_v` are *rings*,
[layers, slots, `sliding_window`, kv heads, head size] whatever
`max_seq` is: the key of position p lies in row p mod
`sliding_window`, turned before it was written, and which position a
row holds follows from the slot's length alone (the newest below it
that is congruent to the row), so a slot shorter than the ring masks
the rows it has not written and a slot that was used before needs no
clearing. `state_leaves` calls the rings state: the engine only slices
their slots (`models/serving.py` says what that costs).

Attention (`_attend`) goes by blocks of queries and, inside, blocks of
keys with a running softmax in float32, and visits only the blocks of
keys that some row of the block of queries can see: up to its last
position on a `full` layer, from `sliding_window` before its first one
on a `sliding` layer. No array of [T, S] a head exists. What a call's
rows attend on a `sliding` layer is the ring as the call found it (the
positions before `start_pos`: all of a decode step's keys but its own,
nothing of a prefill from position 0) and the call's own keys, which
never pass through the ring: a prefill longer than the ring attends
keys the ring will not keep. On a TPU a prefill from position 0 of
1,024 rows or a multiple, whose rows see its own keys and no others,
takes `ops.attention.flash_attention_forward` instead, on both kinds of
layer (the trained path's forward kernel, with a window on a `sliding`
layer: no score leaves the core); `serving.own_keys` chooses on the
device.
Only then is the ring written, with the call's last `sliding_window`
real rows: a decode step's one row in place, a prefill's rows up to
`at` and none of its bucket's padding (row p + `sliding_window` lands
on row p's place, and p is a real key inside the window).

A decode step (one row a slot, a shape the code sees) has a seam that
computes nothing: a `lax.optimization_barrier` between the three
projections and the rotary turn. Without it the TPU's compiler gives
the *weight* the layout that yields q already in pairs: on a `sliding`
layer the step had `copy` -> `bf16[1,4096,128,128]{1,2,3,0}`, the
layer's `wq` read and written anew, 134 MB each way, and 8.4 MB each of
`wk` and `wv` after it, to spare q's 0.5 MB a turn of its own (1.4 ms
of a 16.1 ms step; PERF.md, PR 40). With it the products read the
weights as they lie and the step schedules no such `copy`
(`tests/models/test_moe_compile_tpu.py` holds that). A prefill has no
barrier: there q is the large array and the compiler's trade costs
nothing that shows.

Not here: the vision tower (not in the language model's config), an
uncached forward pass and a loss (the model is served, not trained:
the trained path has had a window in its backward kernels and a loss
over runs of unlike layers since `smallthinker.py`, and this family
would stand on both).
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models import decoder, moe
from ray_tpu.models.serving import (
    KEY_BLOCK as _KEY_BLOCK, Family, attention_init, by_query_blocks, normal,
    own_keys, rotate_pairs)
from ray_tpu.ops import attention, block_rows, stacked_product
from ray_tpu.ops.stacked_product import leaf_product

PUBLISHED_LAYER_TYPES = ("sliding", "sliding", "sliding", "full") * 8


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig(moe.MoEConfig):
    """Defaults are Command A+'s (command-a-plus-05-2026). `hidden_dim`
    is one routed expert's width, `shared_hidden_dim` the four shared
    experts' together; `layer_types` names the layers held, bottom to
    top, "sliding" or "full", `n_layers` of them."""
    vocab_size: int = 262144
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 128
    n_kv_heads: int = 8
    head_size: int = 128
    hidden_dim: int = 4096
    max_seq_len: int = 200000
    rope_theta: float = 5e4
    norm_eps: float = 1e-5
    tie_embeddings: bool = True
    norm_kind: str = "layer"
    parallel_block: bool = True
    n_experts: int = 128
    n_experts_per_token: int = 8
    scoring: str = "sigmoid"
    norm_topk_prob: bool = True
    shared_hidden_dim: int = 16384
    n_shared_experts: int = 4
    shared_combination: str = "average"
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    # A row of a `sliding` layer sees this many keys, itself among them.
    sliding_window: int = 4096
    logit_scale: float = 1.0

    @property
    def head_dim(self) -> int:
        """Published beside the hidden size, not its quotient by the
        heads: 128 heads of 128 over a stream of 4096."""
        return self.head_size

    def runs(self):
        """[(kind, layers)]: the stack as runs of like layers."""
        assert len(self.layer_types) == self.n_layers \
            and set(self.layer_types) <= {"sliding", "full"}, self.layer_types
        return [(kind, len(list(group)))
                for kind, group in itertools.groupby(self.layer_types)]


# ---------------------------------------------------------------------------
# Parameters and cache
# ---------------------------------------------------------------------------


def _init_layer(cfg: Cohere2MoeConfig, key) -> Dict[str, Any]:
    *k_attention, k_ffn = jax.random.split(key, 5)
    lp = {"attn_norm": jnp.ones(cfg.dim, cfg.dtype),
          **attention_init(cfg, normal, k_attention),
          **moe.expert_init(cfg, jax.random.split(k_ffn, 4), normal)}
    # `expert_init` scales the fused down-projection by the root of its
    # whole width; each of the shared experts in it is an expert of a
    # `n_shared_experts`-th of that.
    lp["ws2"] = lp["ws2"] * cfg.n_shared_experts ** 0.5
    return lp


def _leaves(cfg: Cohere2MoeConfig, kind):
    """A run's cache leaves, as the module's docstring lists them."""
    keys = (cfg.n_kv_heads, cfg.head_dim)
    if kind == "sliding":
        ring = ((cfg.sliding_window,) + keys, cfg.dtype)
        return {"ring_k": ring, "ring_v": ring}
    return {"k": (keys, cfg.dtype), "v": (keys, cfg.dtype)}


def keys_attended(cfg: Cohere2MoeConfig, lengths):
    """Of the keys a full-attention stack of the held depth would read
    for a row of `lengths` cached keys (host integers), `lengths` a
    layer, how many this one reads, as a mean over its layers: the
    window's worth on a `sliding` layer."""
    sliding = sum(kind == "sliding" for kind in cfg.layer_types)
    full = cfg.n_layers - sliding
    return (sliding * np.minimum(lengths, cfg.sliding_window)
            + full * lengths) // cfg.n_layers


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _attend(q, positions, sources, g, window=None):
    """Grouped-query attention of q [B, T, H, D] at `positions` [B, T]
    over keys of `g` heads that come a block at a time, with a running
    softmax in float32: [B, T, H, D] in q's dtype. `sources` is a
    sequence of (fetch, first, last): `fetch(j)` gives block j's keys
    and values [B, S, G, D] and their positions [B, S] (below zero: no
    key), for the blocks first <= j < last (int32 scalars: the loop
    visits no other, so a block no row can see costs nothing). A row
    sees the keys at or before its position and, with `window`, no
    further back than that many, itself counted. Keys and values enter both products in the
    dtype they are stored in."""
    b, t, h, d = q.shape
    scale = d ** -0.5

    def body(fetch, j, carry):
        top, total, acc = carry
        keys, values, key_pos = fetch(j)
        back = positions[:, :, None] - key_pos[:, None, :]   # [B, T, S]
        seen = (back >= 0) & (key_pos[:, None, :] >= 0)
        if window is not None:
            seen &= back < window
        seen = seen[:, None, None]                     # [B, 1, 1, T, S]
        scores = jnp.einsum(
            "btgrd,bsgd->bgrts", q.reshape(b, t, g, h // g, d), keys,
            preferred_element_type=jnp.float32) * scale
        new_top = jnp.maximum(top, jnp.where(seen, scores, -1e30).max(-1))
        probs = jnp.where(seen, jnp.exp(scores - new_top[..., None]), 0.0)
        shrink = jnp.exp(top - new_top)
        total = total * shrink + probs.sum(-1)
        acc = acc * shrink[..., None] + jnp.einsum(
            "bgrts,bsgd->bgrtd", probs.astype(values.dtype), values,
            preferred_element_type=jnp.float32)
        return new_top, total, acc

    carry = (jnp.full((b, g, h // g, t), -1e30, jnp.float32),
             jnp.zeros((b, g, h // g, t), jnp.float32),
             jnp.zeros((b, g, h // g, t, d), jnp.float32))
    for fetch, first, last in sources:
        carry = lax.fori_loop(first, last, functools.partial(body, fetch),
                              carry)
    _, total, acc = carry
    # A row that saw no key (a bucket's padding on a short ring) has
    # total 0; its output is never read.
    out = acc / jnp.maximum(total, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, h, d).astype(q.dtype)


def _blocks_seen(positions, origin, block, n_blocks, window=None):
    """(first, last): the blocks of `block` keys, of `n_blocks` whose
    first key stands at position `origin` [B], that hold a key some row
    of `positions` [B, T] can see."""
    ahead = positions - origin[:, None]
    last = jnp.clip(ahead.max() // block + 1, 0, n_blocks)
    if window is None:
        return 0, last
    return jnp.clip((ahead.min() - window + 1) // block, 0, last), last


def _ring_positions(start_pos, window):
    """The position whose key each row of a ring holds, [B, window],
    for slots that hold `start_pos` [B] keys: the newest position below
    `start_pos` congruent to the row, below zero where the slot has not
    written the row yet."""
    newest = start_pos[:, None] - 1
    return newest - (newest - jnp.arange(window)[None, :]) % window


def _write_rings(stacks, layer, news, start_pos, at):
    """The call's rows (`news`, one [B, T, ...] a ring), at positions
    `start_pos` [B] on and real up to index `at` ([B] or a scalar), into
    the rings `stacks` (each [layers, B, window, ...]): row r takes the
    newest real position congruent to r, if the call has one, and keeps
    what it held otherwise. One token a row is written where it lies,
    as any layer's new row is; more rewrite the layer's rings, [B,
    window, ...]."""
    window = stacks[0].shape[2]
    if news[0].shape[1] == 1:
        return block_rows.write_tokens(stacks, layer, news,
                                       start_pos % window)
    at = jnp.broadcast_to(at, start_pos.shape)[:, None]
    rows = jnp.arange(window)[None, :]
    index = at - (start_pos[:, None] + at - rows) % window    # [B, window]

    def rewritten(stack, new):
        tail = (1,) * (new.ndim - 2)
        picked = jnp.take_along_axis(
            new, jnp.maximum(index, 0).reshape(index.shape + tail), 1)
        old = decoder.layer_rows(stack, layer, 0, window)
        ring = jnp.where((index >= 0).reshape(index.shape + tail),
                         picked.astype(stack.dtype), old)
        return lax.dynamic_update_slice(
            stack, ring[None], (layer,) + (0,) * (stack.ndim - 1))

    return tuple(rewritten(stack, new) for stack, new in zip(stacks, news))


# The kinds of layer whose queries and keys are turned by rotary
# positions.
_ROTATED = ("sliding",)

# A call of so many rows or a multiple of it (every prefill bucket from
# here up) goes through the flash kernel where it can:
# `serving.own_keys`.
_FLASH_ROWS = 1024


def _mixer(cfg: Cohere2MoeConfig, kind, start_pos, positions, at):
    """The mixer of a run of `sliding` or of `full` layers; its state is
    the run's (K, V) stacks, rows or rings. A ring's length is its
    leaf's (`init_cache` made it `sliding_window` long); what a row sees
    is `cfg.sliding_window`'s to say."""
    def mixer(h, lp, rope, state, handed, stacks=None):
        (k_stack, v_stack), layer = state
        b, t = positions.shape
        cached = k_stack.dtype
        q = leaf_product("bsd,dhk->bshk", h, "wq", lp, stacks)
        k = leaf_product("bsd,dhk->bshk", h, "wk", lp, stacks)
        v = leaf_product("bsd,dhk->bshk", h, "wv", lp, stacks).astype(cached)
        if kind in _ROTATED:
            if t == 1:
                # The seam the module's docstring describes: the turn
                # is q's and k's to pay for, not the weights'.
                q, k, v = lax.optimization_barrier((q, k, v))
            q, k = rotate_pairs(q, *rope), rotate_pairs(k, *rope)
        q, k = q.astype(cached), k.astype(cached)
        rows = k_stack.shape[2]
        tr = math.gcd(rows, _KEY_BLOCK)
        if kind == "full":
            k_stack, v_stack = block_rows.write_tokens(
                (k_stack, v_stack), layer, (k, v), start_pos)
            origin = jnp.zeros_like(start_pos)

            def fetch(j):
                return (decoder.layer_rows(k_stack, layer, j * tr, tr),
                        decoder.layer_rows(v_stack, layer, j * tr, tr),
                        jnp.broadcast_to(j * tr + jnp.arange(tr), (b, tr)))

            def attend(q, pos):
                return _attend(q, pos, [
                    (fetch, *_blocks_seen(pos, origin, tr, rows // tr))],
                    cfg.n_kv_heads),

            out = own_keys(
                not t % _FLASH_ROWS, start_pos,
                lambda: attention.flash_attention_forward(q, k, v),
                lambda: by_query_blocks(attend, t, q, positions)[0])
            return out, (k_stack, v_stack), handed

        with jax.named_scope("window"):
            # The ring as the call found it, then the call's own keys.
            window = cfg.sliding_window
            ring_pos = _ring_positions(start_pos, rows)
            tk = math.gcd(t, _KEY_BLOCK)
            reach = jnp.minimum(start_pos.max(), rows)

            def ring(j):
                return (decoder.layer_rows(k_stack, layer, j * tr, tr),
                        decoder.layer_rows(v_stack, layer, j * tr, tr),
                        lax.dynamic_slice_in_dim(ring_pos, j * tr, tr, 1))

            def own(j):
                return (lax.dynamic_slice_in_dim(k, j * tk, tk, 1),
                        lax.dynamic_slice_in_dim(v, j * tk, tk, 1),
                        start_pos[:, None] + j * tk + jnp.arange(tk))

            def attend(q, pos):
                return _attend(q, pos, [
                    (ring, 0, (reach + tr - 1) // tr),
                    (own, *_blocks_seen(pos, start_pos, tk, t // tk,
                                        window))],
                    cfg.n_kv_heads, window),

            out = own_keys(
                not t % _FLASH_ROWS, start_pos,
                lambda: attention.flash_attention_forward(
                    q, k, v, window=window),
                lambda: by_query_blocks(attend, t, q, positions)[0])
            k_stack, v_stack = _write_rings(
                (k_stack, v_stack), layer, (k, v), start_pos, at)
        return out, (k_stack, v_stack), handed

    # A decode step reads `wq` where it lies in the run's stack
    # (`ops.stacked_product`; `decoder.layers` keeps the leaves a half
    # names out of its scan). Not `wk` and `wv`: a stack of theirs is
    # 25 MB, which the compiler then fetches whole into fast memory
    # ahead of the kernel, once a layer, three times what a layer reads.
    if stacked_product.engages(positions.shape[1]):
        mixer.whole = ("wq",)
    return mixer


# ---------------------------------------------------------------------------
# Through the slot cache (`models.serving`)
# ---------------------------------------------------------------------------


def _halves(cfg: Cohere2MoeConfig, start_pos, positions, at):
    return {kind: (_mixer(cfg, kind, start_pos, positions, at),
                   moe.served_ffn(cfg)) for kind in ("sliding", "full")}


FAMILY = Family(
    init_layer=lambda cfg, kind, key: _init_layer(cfg, key), draw=normal,
    leaves=_leaves, halves=_halves, state=frozenset(("ring_k", "ring_v")),
    tied=True, keys_attended=keys_attended)
init_params, init_cache = FAMILY.init_params, FAMILY.init_cache
state_leaves = FAMILY.state_leaves
forward, forward_with_cache = FAMILY.forward, FAMILY.forward_with_cache
