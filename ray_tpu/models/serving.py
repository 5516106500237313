"""What `ray_tpu.serve.llm` runs a model through, found by the type of
its config: the one place that says which architectures have a path
through the slot cache. The engine knows no model; it takes from here

- ``forward(params, tokens, cfg, cache, start_pos, at) -> (logits
  [B, vocab], cache, counts)``: `tokens` [B, T] from per-row offsets
  `start_pos` [B], prefill and decode alike; the logits are those of
  position `at` of `tokens` (an int or an int32 scalar: a prefill wants
  its last real position, a decode step its only one); `counts` is a
  dict of int32 scalars the model counted over the call, which a decode
  block sums and hands to the host with its tokens, {} of a model that
  counts nothing;
- ``init_cache(cfg, n_slots, max_seq) -> cache``: any pytree of
  leaves of two kinds, each [layers_i, slots, ...]. A *row* leaf is
  [layers_i, slots, max_seq, ...], one row a position (keys and values,
  latents): `forward` returns it with the call's [B, T] new rows a
  layer written in and nothing else moved; the engine slices slots,
  reads and writes blocks of rows and sizes its prefix cache by these
  leaves. A *state* leaf has no sequence axis (a recurrent layer's
  state, a vector a channel as Mamba-2's or a matrix a head as the
  delta rule's, and a convolution's last rows, of which a layer may
  carry several, `gated_delta` has three, and which may be all a mixer
  keeps: `lfm2_moe`'s gated short convolution has no other state):
  `forward` rewrites it whole, a row
  that starts at position 0 starts from zeros whatever the leaf held,
  and what is left is the state after position `at` and no later (a
  prefill's bucket padding must not enter it); the engine only slices
  its slots. A *ring* is a state leaf too, though it has an axis of
  rows: [layers_i, slots, window, ...], the keys and values of a
  windowed layer, position p in row p mod window whatever `max_seq`
  is (`cohere2_moe`). Which position a row holds follows from the
  slot's length, which the model is handed as `start_pos`, so the
  engine need not clear a ring between two requests; but a row is no
  position the engine could name, so it may not read, write or copy
  blocks of a ring's rows, nor size anything by them, and what holds
  for a recurrent state holds for a ring: `forward` leaves in it the
  rows up to `at` and none of the padding after. All leaves ride the
  model's layer scan as its carry (`decoder.layers`), so a cache that
  is donated to the program is updated where it lies;
- ``state_leaves(cache) -> the same structure of bools``: True at a
  state leaf; all rows unless the model says otherwise. A model with a
  state leaf is served with no prefix cache: the prefix cache holds
  blocks of rows, and neither a recurrent layer nor a ring can resume
  from a block of rows, only from a snapshot of its state at the
  block's boundary (for a ring: the window's worth of rows that end
  there), which nothing takes yet;
- ``keys_attended(cfg, lengths) -> per row``: of `lengths` cached keys
  (host integers) how many the next token attends: all of them, unless
  the model selects keys;
- ``keys_read(cfg, lengths) -> per row``, or None: the rows a layer's
  attention fetches for a row of `lengths` keys, where a decode step
  reads by the slot's length (`ops.attention.decode_attention`: the
  length rounded up to the kernel's block of rows; the engine holds it
  to the slot's region). None where a step reads the region whole or
  reads something that is no region (a ring, selected keys).

A family whose stack is `decoder.block`s over `cfg.runs()`, runs of
like layers, declares a `Family` and takes all of the above from it,
one body for all (`_llama` apart: the dense decoder's cached forward
pass is `llama`'s own). Its cache is {"runs": [a dict a run]}, its
parameters {"embed", "runs": [a dict of stacked leaves a run],
"final_norm", "out" unless tied}. The declaration's fields:

- ``init_layer(cfg, kind, key) -> lp``: one layer's parameters, which
  `init_params` stacks a run at a time, and ``draw(key, shape, dtype)``
  for `embed` and `out`;
- ``leaves(cfg, kind) -> {name: (shape, dtype)}``: a run's cache
  leaves in the order its mixer takes and returns them, {} of a run
  with no mixer; `shape` is what follows [layers, slots, max_seq] of a
  row leaf and [layers, slots] of a state leaf, and ``state`` names the
  leaves that are state;
- ``halves(cfg, start_pos, positions, at) -> {kind: (mixer, ffn)}``:
  what `decoder.hidden_runs` is handed for a run of each kind (None:
  the block has no such half), made once a call: a recurrent mixer
  broadcasts `at` when it is made, and a decode step's halves
  (`ops.stacked_product.engages(positions.shape[1])`: one token a
  slot, on a TPU) may name in `whole` the big leaves they read where
  they lie in the run's stack, which a prefill's never do;
- what only some have: ``tied`` (the head is the embedding, times
  `cfg.logit_scale`), ``handed(tokens, cache)`` (what enters the first
  layer as `handed`), ``counts(cfg, tokens, cache, start_pos, at)``
  (counts of the family's own, beside what its FFNs count; `cache` the
  one the call was handed), ``keys_attended`` and
  ``keys_read``.

A model that generates by blocks (``ServedModel.block_length`` B, None
of the others; diffusion over blocks, `sdar_moe`) keeps all of the
above and differs in what a step is:

- ``forward`` sees `tokens` [slots, T] with T and `start_pos` multiples
  of B. A row's query at position i sees the cache's rows up to the
  end of i's block, this call's rows written first: causal between
  blocks, both ways inside one. There is no shift: the logits at
  position i score the token at i. With `at` None it returns the
  logits of every position, [slots, T, vocab]; a prefill's logits are
  never read. `start_pos` may be [slots, T / B], a start a block of
  the call in place of one for its rows: the blocks are written in
  the call's order, each where it starts, and then each sees the rows
  up to its own end as before; two blocks of a row may stand at one
  start: the later one's rows are those that stay, and what comes of
  the earlier one is nobody's (the model may spare its work). Of such
  a call `at` None means the logits of the last block, [slots, B,
  vocab];
- its config names `mask_token_id`, what an open position is fed as,
  and `denoising_steps`, in how many steps a block is fixed unless a
  request says (`SamplingParams.denoising_steps`);
- a step of the engine feeds every slot two blocks (T = 2 B, a start
  each) and *denoises* the second: the most confident of its open
  positions take their tokens. Where the slot's block before is
  whole and *awaits its commit*, it is the first, at the slot's
  length, with the block being denoised behind it: its rows,
  computed from the final tokens, are written before anything of
  the next block attends them, they stay, and the length grows by B
  in the forward that fixes the next block's first positions. Where
  nothing awaits a commit (a request's first block; a block's
  second step) both are the block being denoised, at the slot's
  length: no row below the length is written again, and none past
  the block. It hands the host a row of B tokens a slot, the step of
  the block at which each was fixed, whether the block is whole now
  and whether the one before was committed: 0 or B tokens a step,
  not one. The cache holds rows only, so the prefix cache works as
  before: a block's keys depend on nothing after the block, as long
  as its blocks of rows are whole blocks of the model
  (`llm_kv_block_tokens` a multiple of B).

Below the stack stands what the families' layers share and no one of
them owns: the initialiser, the attention projections, the rotary turn
of interleaved pairs, the choice between a prefill's flash kernel and
the plain path, the loop over blocks of queries, and absorbed latent
attention against the cached latent by blocks of keys
(`latent_attention`: GLM-5.2 hands it the mask its indexer chose, Kimi
Linear none, every key up to the row's position).
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import decoder
from ray_tpu.ops import attention
from ray_tpu.ops.attention import decode_block_rows


def _every_key(cfg, lengths):
    return lengths


def keys_read_by_blocks(cfg, lengths):
    """`lengths` rounded up to `decode_attention`'s block of rows for
    `cfg`'s key heads."""
    rows = decode_block_rows(cfg.n_kv_heads, cfg.head_dim, cfg.dtype)
    return -(-lengths // rows) * rows


def _all_rows(cache):
    return jax.tree.map(lambda _: False, cache)


@dataclasses.dataclass(frozen=True)
class ServedModel:
    forward: Callable
    init_cache: Callable
    keys_attended: Callable = _every_key
    state_leaves: Callable = _all_rows
    keys_read: Optional[Callable] = None
    # Positions in a block, of a model that generates by blocks; None
    # of one that yields a token a step.
    block_length: Optional[int] = None


@dataclasses.dataclass(frozen=True, eq=False)
class Family:
    """A served family's declaration (the module's docstring has the
    fields) and, as its methods, the stack through the slot cache."""
    init_layer: Callable
    draw: Callable
    leaves: Callable
    halves: Callable
    state: frozenset = frozenset()
    tied: bool = False
    handed: Optional[Callable] = None
    counts: Optional[Callable] = None
    keys_attended: Callable = _every_key
    keys_read: Optional[Callable] = None
    block_length: Optional[Callable] = None

    def __post_init__(self):
        # One function object a name: what a family's module binds is
        # what `served_model` hands the engine, and a jit of either is
        # a jit of both.
        for name in ("init_params", "init_cache", "state_leaves", "forward",
                     "forward_with_cache"):
            object.__setattr__(self, name, getattr(self, name))

    def init_params(self, cfg, rng):
        """Which leaves a run has says what its layers are."""
        k_embed, *k_out, k_layers = jax.random.split(rng, 3 - self.tied)
        keys = jax.random.split(k_layers, sum(n for _, n in cfg.runs()))
        runs, at = [], 0
        for kind, n in cfg.runs():
            runs.append(jax.vmap(functools.partial(
                self.init_layer, cfg, kind))(keys[at:at + n]))
            at += n
        return {"embed": self.draw(k_embed, (cfg.vocab_size, cfg.dim),
                                   cfg.dtype),
                "runs": runs, "final_norm": jnp.ones(cfg.dim, cfg.dtype),
                **{"out": self.draw(key, (cfg.dim, cfg.vocab_size),
                                    cfg.dtype) for key in k_out}}

    def init_cache(self, cfg, n_slots: int, max_seq: int):
        rows = {True: (n_slots,), False: (n_slots, max_seq)}
        return {"runs": [
            {name: jnp.zeros((n,) + rows[name in self.state] + shape, dtype)
             for name, (shape, dtype) in self.leaves(cfg, kind).items()}
            for kind, n in cfg.runs()]}

    def state_leaves(self, cache):
        """`cache`'s structure with True at a leaf that is state and
        False at one of rows."""
        return jax.tree_util.tree_map_with_path(
            lambda path, _: path[-1].key in self.state, cache)

    def _stack(self, params, tokens, cfg, cache, start_pos, at):
        """(final-norm hidden states [B, T, D], new cache, what the
        FFNs counted, summed over the layers; a run whose FFN reports
        nothing adds nothing)."""
        if start_pos.ndim == 2:  # a start a block of the call
            blocks = start_pos.shape[1]
            positions = (start_pos[:, :, None] + jnp.arange(
                tokens.shape[1] // blocks)).reshape(tokens.shape)
        else:
            positions = start_pos[:, None] \
                + jnp.arange(tokens.shape[1])[None, :]
        halves = self.halves(cfg, start_pos, positions, at)
        names = [tuple(self.leaves(cfg, kind)) for kind, _ in cfg.runs()]
        runs = [(*halves[kind], stacked,
                 tuple(run[name] for name in leaves) if leaves else None)
                for (kind, _), leaves, stacked, run in zip(
                    cfg.runs(), names, params["runs"], cache["runs"])]
        x, states, extras = decoder.hidden_runs(
            params, tokens, cfg, runs, positions=positions,
            handed=self.handed and self.handed(tokens, cache))
        new_cache = {"runs": [dict(zip(leaves, state or ()))
                              for leaves, state in zip(names, states)]}
        counted = [e for e in extras if e is not None]
        counts = jax.tree.map(lambda *xs: sum(x.sum() for x in xs),
                              *counted) if counted else {}
        return x, new_cache, counts

    def _logits(self, params, x, cfg):
        """The head in float32: the served logits feed an argmax, and
        two near-equal logits rounded to bfloat16 are a tie."""
        product, weight = ("...d,vd->...v", "embed") if self.tied \
            else ("...d,dv->...v", "out")
        out = jnp.einsum(product, x, params[weight].astype(cfg.dtype),
                         preferred_element_type=jnp.float32)
        scale = cfg.logit_scale if self.tied else 1.0
        return out if scale == 1.0 else out * scale

    def forward(self, params, tokens, cfg, cache, start_pos, at):
        """The module's `forward`, prefill (T = the prompt's bucket)
        and decode (T = 1) alike: the logits of position `at` without
        the [T, vocab] product of the rest (where `at` is None, a block
        model's step: of every position, [B, T, vocab], or, of a call
        with a start a block, of its last block, [B, T / blocks,
        vocab]), and as counts the FFNs' and the family's own."""
        x, new_cache, counts = self._stack(params, tokens, cfg, cache,
                                           start_pos, at)
        if at is not None:
            x = lax.dynamic_index_in_dim(x, at, 1, keepdims=False)
        elif start_pos.ndim == 2:
            x = x[:, -(tokens.shape[1] // start_pos.shape[1]):]
        logits = self._logits(params, x, cfg)
        if self.counts is not None:
            counts = {**counts, **self.counts(cfg, tokens, cache, start_pos,
                                              at)}
        return logits, new_cache, counts

    def forward_with_cache(self, params, tokens, cfg, cache, start_pos,
                           at=None, keep=None):
        """`forward` with the logits of every position (of the first
        `keep`, where given: a padded prefill's real ones), [B, T,
        vocab] float32, and no counts: what a comparison with a
        reference steps through. The state left is that after position
        `at` (an int for all rows, or int32 [B], one a row), the last
        of `tokens` unless given."""
        at = tokens.shape[1] - 1 if at is None else at
        x, cache, _ = self._stack(params, tokens, cfg, cache, start_pos, at)
        return self._logits(params, x if keep is None else x[:, :keep],
                            cfg), cache

    def served(self, cfg) -> ServedModel:
        return ServedModel(
            self.forward, self.init_cache, self.keys_attended,
            self.state_leaves if self.state else _all_rows, self.keys_read,
            self.block_length and self.block_length(cfg))


# ---------------------------------------------------------------------------
# What the families' layers share
# ---------------------------------------------------------------------------

# Queries and keys go through attention in blocks of at most this many.
QUERY_BLOCK = 256
KEY_BLOCK = 1024


def normal(key, shape, dtype):
    """`jax.nn.initializers.normal(0.02)` drawn in float32 and cast.
    Drawn in bfloat16 itself the samples' mean is -0.012 sigma (jax
    0.9.0: -2.4e-4 for sigma 0.02 over 22 M samples, 56 standard
    errors), so every matrix carries a rank-one part along the all-ones
    direction that is the same in every layer; a relu^2 amplifies what
    it adds to the stream, and a few blocks up every token's hidden
    state points the same way (PERF.md section 6, PR 34)."""
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


def attention_init(cfg, draw, keys):
    """The attention projections from four keys: `wq`, `wk`, `wv`
    [D, heads, head size] and `wo` [heads, head size, D], the last
    scaled by D^-1/2."""
    d, hd = cfg.dim, cfg.head_dim
    kq, kk, kv, ko = keys
    return {"wq": draw(kq, (d, cfg.n_heads, hd), cfg.dtype),
            "wk": draw(kk, (d, cfg.n_kv_heads, hd), cfg.dtype),
            "wv": draw(kv, (d, cfg.n_kv_heads, hd), cfg.dtype),
            "wo": draw(ko, (cfg.n_heads, hd, d), cfg.dtype) * d ** -0.5}


def rotate_pairs(x, cos, sin):
    """Rotary positions on interleaved pairs (2i, 2i + 1) of the last
    axis. x: [B, T, ..., D]; cos, sin: [B, T, D / 2]."""
    extra = x.ndim - 3
    cos = cos.reshape(cos.shape[:2] + (1,) * extra + cos.shape[2:])
    sin = sin.reshape(cos.shape)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], -1)
    return out.reshape(x.shape).astype(x.dtype)


def own_keys(tiled, start_pos, flash, plain):
    """A prefill's attention: `flash()` where the call's own keys are
    all its rows can see (every row starts at position 0: a prompt's one
    prefill, the engine's case) and the flash kernel has its tiles
    (`tiled`, the family's to say of the call's rows; on a TPU alone),
    else `plain()`, which also reads what the cache held. `start_pos` is
    the device's to know, so a program that may use the kernel holds
    both."""
    if not tiled or not attention.on_tpu():
        return plain()
    return lax.cond(start_pos.max() == 0, flash, plain)


def by_query_blocks(fn, t, *arrays):
    """`fn` over blocks of the query axis (axis 1 of every array), its
    results (a tuple of arrays) joined along it again."""
    tq = math.gcd(t, QUERY_BLOCK)
    if tq == t:
        return fn(*arrays)
    n = t // tq

    def split(x):
        x = x.reshape((x.shape[0], n, tq) + x.shape[2:])
        return jnp.moveaxis(x, 1, 0)

    outs = lax.map(lambda xs: fn(*xs), tuple(split(x) for x in arrays))
    return tuple(jnp.moveaxis(o, 0, 1).reshape(
        (o.shape[1], t) + o.shape[3:]) for o in outs)


def key_blocks(positions, max_seq, block):
    """How many blocks of `block` keys hold every key the rows at
    `positions` can see."""
    return jnp.minimum(positions.max() // block + 1, max_seq // block)


def latent_attention(q_lat, q_rope, latent, rope_keys, mask, positions, scale,
                     block=KEY_BLOCK):
    """Absorbed latent attention (`glm_dsa`, `kimi_linear`: the key
    half of `wkvb` already in the query, its value half still to come
    onto the output) of q over the cached keys `mask` allows, against
    the latent: q_lat [B, T, H, C], q_rope [B, T, H, R], mask [B, T, S], or
    None for every key up to the row's position (`kimi_linear`, whose
    latent layers select nothing and so need no array of [T, S]) ->
    [B, T, H, C] float32. `latent` and `rope_keys` are each (the stack
    [layers, B, S, width], the layer), read a block of keys at a time.
    Scores, softmax and both accumulations are float32; the caches
    enter both products in the dtype they are stored in. `block`: the
    keys of a block, at most (every row reads whole blocks up to the
    one that holds the furthest position any row can see)."""
    b, t, h, c = q_lat.shape
    s = latent[0].shape[2]
    tk = math.gcd(s, block)

    def body(j, carry):
        top, total, acc = carry
        lat = decoder.layer_rows(*latent, j * tk, tk)
        rot = decoder.layer_rows(*rope_keys, j * tk, tk)
        if mask is None:
            allowed = (j * tk + jnp.arange(tk) <= positions[..., None])[:, None]
        else:
            allowed = lax.dynamic_slice_in_dim(mask, j * tk, tk, 2)[:, None]
        scores = (jnp.einsum("bthc,bsc->bhts", q_lat, lat,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bthr,bsr->bhts", q_rope, rot,
                               preferred_element_type=jnp.float32)) * scale
        new_top = jnp.maximum(
            top, jnp.where(allowed, scores, -1e30).max(-1))
        probs = jnp.where(allowed, jnp.exp(scores - new_top[..., None]), 0.0)
        shrink = jnp.exp(top - new_top)
        total = total * shrink + probs.sum(-1)
        acc = acc * shrink[..., None] + jnp.einsum(
            "bhts,bsc->bhtc", probs.astype(lat.dtype), lat,
            preferred_element_type=jnp.float32)
        return new_top, total, acc

    _, total, acc = lax.fori_loop(
        0, key_blocks(positions, s, tk), body,
        (jnp.full((b, h, t), -1e30, jnp.float32),
         jnp.zeros((b, h, t), jnp.float32),
         jnp.zeros((b, h, t, c), jnp.float32)))
    return (acc / total[..., None]).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# The registry
# ---------------------------------------------------------------------------


def _llama(cfg):
    from ray_tpu.models import llama

    def forward(params, tokens, cfg, cache, start_pos, at):
        logits, cache = llama.forward_with_cache(params, tokens, cfg, cache,
                                                 start_pos)
        return logits[:, at], cache, {}

    return ServedModel(forward, llama.init_kv_cache,
                       keys_read=keys_read_by_blocks)


def _family(module):
    """The `FAMILY` that `ray_tpu.models.<module>` declares, imported
    when a config of its type is first served."""
    return lambda cfg: importlib.import_module(
        f"ray_tpu.models.{module}").FAMILY.served(cfg)


# By the config's own type, not its bases: `MoEConfig` is a
# `LlamaConfig` and has no cached forward pass.
_SERVED = {"LlamaConfig": _llama, "GlmDsaConfig": _family("glm_dsa"),
           "NemotronHConfig": _family("nemotron_h"),
           "Cohere2MoeConfig": _family("cohere2_moe"),
           "OlmoHybridConfig": _family("olmo_hybrid"),
           "SdarMoeConfig": _family("sdar_moe"),
           "Lfm2MoeConfig": _family("lfm2_moe"),
           "KimiLinearConfig": _family("kimi_linear")}


def served_model(cfg) -> ServedModel:
    find = _SERVED.get(type(cfg).__name__)
    if find is None:
        raise TypeError(
            f"no served path for {type(cfg).__name__}: ray_tpu/models/"
            f"serving.py names a cached forward pass and a cache "
            f"initialiser for {sorted(_SERVED)}")
    return find(cfg)
