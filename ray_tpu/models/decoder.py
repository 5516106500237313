"""The decoder stack that every architecture under `models/` composes.

A block has one of three forms (`block`, the only place that opens the
mixer's scope, `attn` unless the mixer names its kind, and `mlp`).
Sequential, the default: norm, *sequence mixer*, residual, norm, *FFN*,
residual, either half optional. Parallel (`cfg.parallel_block`): one
norm, whose output both halves read, and one residual that takes the
sum of the two. Sequential with `cfg.norm_placement` "output" (OLMo 2's
arrangement): each half reads the stream as it is and its *output* is
normed before the residual takes it. The norm is RMSNorm unless
`cfg.norm_kind` is "layer" (the mean taken off, a weight, no bias), the
final norm with it. What differs between architectures beyond that is
handed in as two functions:

- ``mixer(h, lp, rope, state, handed) -> (attn [B, S, H, K], state,
  handed)``: normed activations and the layer's parameters to the
  attention output before the `wo` projection. `rope` is the stack's
  `(cos, sin)`. `state` is None, or what the mixer keeps from call to
  call as `(stacks, layer)`: the run's stacked leaves (a slot cache's,
  each [layers, slots, max_seq, ...]) and the index of this layer in
  them. Only the mixer writes them, its new rows at its own layer
  (`ops.block_rows.write_tokens`: all of the layer's leaves in one
  call, by `write_rows`' scatter or, for a decode step on a TPU, by a
  kernel that does what `write_rows` defines), and it reads its layer
  back out of them (`layer_rows`); it returns the stacks. `handed` is
  what a layer hands up to the layer above beside `x` (a
  sparse-attention layer's selection), None in a stack that hands
  nothing on. A mixer that is no attention carries its kind as the
  attribute `scope` (`"ssm"`), the name of the scope the block opens
  around it.
- ``ffn(h, lp) -> (out [B, S, D], extras)``: `extras` is a pytree the
  layer reports (an expert layer's aux loss and counts), or None. An
  FFN that reads the stream as the layer received it, before the mixer
  and before any norm (SmallThinker's router), carries the attribute
  `stream` and is called ``ffn(h, lp, stream=x)``.

A half, mixer or FFN, that reads some of its parameters in place names
them in its attribute `whole`: `layers` keeps those leaves of the run's
stack out of the scan and the block calls the half with
``stacks=(leaves, layer)`` beside what it is called with above, `lp`
holding the rest. A mixer may name `wo`, which the block then reads in
place for it.

Around it: the parameter skeleton, the stack (`hidden`: one run of
like layers; `hidden_runs`: several, each with its own mixer, FFN,
parameters and state), the output head (`logits`) and the loss tail
(`loss`, over one run or several). No architecture is known here:
`llama.py` and `moe.py` compose this with their mixers and FFNs. `cfg`
is any config with `LlamaConfig`'s fields.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.cross_entropy import (fused_linear_cross_entropy,
                                       softmax_cross_entropy)
from ray_tpu.ops.norms import layer_norm, rms_norm_reference
from ray_tpu.ops import stacked_product
from ray_tpu.ops.rope import rope_frequencies, rope_from_positions
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES,
    tree_shardings,
    with_logical_constraint,
)


def init_params(cfg, rng, init_layer) -> Dict[str, Any]:
    """embed, `cfg.n_layers` of `init_layer(key)` stacked along a leading
    axis, final norm, and `out` unless the embeddings are tied."""
    k_embed, k_out, k_layers = jax.random.split(rng, 3)
    init = jax.nn.initializers.normal(0.02)
    params = {
        "embed": init(k_embed, (cfg.vocab_size, cfg.dim), cfg.dtype),
        "layers": jax.vmap(init_layer)(
            jax.random.split(k_layers, cfg.n_layers)),
        "final_norm": jnp.ones(cfg.dim, cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["out"] = init(k_out, (cfg.dim, cfg.vocab_size), cfg.dtype)
    return params


def param_logical_axes(cfg, layer_axes) -> Dict[str, Any]:
    """Same structure as `init_params` output, with logical-axis tuples as
    leaves. `layer_axes` names one layer's; the leading `None` added
    here is the scanned layer axis."""
    axes = {
        "embed": ("vocab", "embed"),
        "layers": {name: (None, *a) for name, a in layer_axes.items()},
        "final_norm": ("norm",),
    }
    if not cfg.tie_embeddings:
        axes["out"] = ("embed", "vocab")
    return axes


def init_params_sharded(init, axes, mesh, rng, rules=DEFAULT_RULES):
    """`init(rng)` directly into sharded device buffers (no host staging
    — required for models bigger than host/chip memory)."""
    return jax.jit(init, out_shardings=tree_shardings(mesh, axes, rules))(
        rng)


def norm(cfg, x, weight):
    """The stack's norm, by `cfg.norm_kind`: "rms", or "layer", a
    LayerNorm with a weight and no bias."""
    if cfg.norm_kind == "layer":
        return layer_norm(x, weight, None, cfg.norm_eps)
    assert cfg.norm_kind == "rms", cfg.norm_kind
    return rms_norm_reference(x, weight, cfg.norm_eps)


def block(mixer, ffn, cfg, rope, x, lp, state=None, handed=None, *,
          stacks=None, mesh=None, rules=DEFAULT_RULES):
    """One block. x: [B, S, D] -> (x, state, extras, handed). Either
    half may be absent (`mixer` or `ffn` None: a stack whose layers are
    a mixer or an FFN alone); the block is then the other half, norm,
    part, residual. With `cfg.parallel_block` both halves are there and
    read the one norm's output (`attn_norm`), and x takes their sum.
    With `cfg.norm_placement` "output" a half reads x itself and its
    output is normed (by the same leaves, `attn_norm` and `mlp_norm`)
    before it is added. The
    two halves are scoped so that a device trace can tell their ops
    apart: the FFN `mlp`, the mixer by its kind, which is `attn` unless
    the mixer says otherwise (its attribute `scope`: a state-space mixer
    is no attention). `state` goes to the mixer as it is and comes back
    as the mixer returns it; `handed` is what the layer below handed up
    beside x, None in most stacks. An FFN with the attribute `stream`
    is also handed x as the block received it (`stream=`). `stacks` is
    `layers`': (the leaves the halves named in `whole`, the layer), or
    None; a half that named any is handed it (`stacks=`), and the
    output projection reads `wo` there if the mixer named it."""
    extras = None
    received = {"stream": x} if getattr(ffn, "stream", False) else {}

    def named(half):
        return {"stacks": stacks} if stacks is not None \
            and getattr(half, "whole", ()) else {}
    parallel = cfg.parallel_block
    assert cfg.norm_placement in ("input", "output"), cfg.norm_placement
    after = cfg.norm_placement == "output"
    assert not parallel or (mixer is not None and ffn is not None
                            and not after)
    if mixer is not None:
        with jax.named_scope(getattr(mixer, "scope", "attn")):
            h = x if after else norm(cfg, x, lp["attn_norm"])
            attn, state, handed = mixer(h, lp, rope, state, handed,
                                        **named(mixer))
            mixed = stacked_product.leaf_product(
                "bshk,hkd->bsd", attn.astype(cfg.dtype), "wo", lp, stacks)
            if after:
                mixed = norm(cfg, mixed, lp["attn_norm"])
            if not parallel:
                x = x + mixed
    if ffn is not None:
        with jax.named_scope("mlp"):
            if after:
                h = x
            elif not parallel:
                h = norm(cfg, x, lp["mlp_norm"])
            out, extras = ffn(h, lp, **received, **named(ffn))
            if after:
                out = norm(cfg, out, lp["mlp_norm"])
            x = x + mixed + out if parallel else x + out
    x = with_logical_constraint(x, "batch", "seq", "act_embed",
                                mesh=mesh, rules=rules)
    return x, state, extras, handed


def layers(mixer, ffn, cfg, rope, x, stacked, state=None, handed=None, *,
           save: Optional[Sequence[str]] = None, mesh=None,
           rules=DEFAULT_RULES):
    """x through a run of like layers (`stacked`: their parameters along
    a leading axis) by `lax.scan`. Returns (x, state, extras, handed):
    `extras` stacked by layer, `handed` as the last layer left it.

    `state` is what the mixer keeps from call to call, stacked by layer
    like the parameters (a slot cache's leaves, [layers, slots, max_seq,
    ...]), or None. It is the scan's carry, never a scanned input or
    output: a scanned leaf that the body changes is copied out of its
    stack and into a new one, layer by layer, whole. The mixer is handed
    `(state, layer)`, the stacks and its layer's index in them, writes
    its new rows there (`block_rows.write_tokens`, which is
    `write_rows` or its kernel), reads its layer through `layer_rows`,
    and returns the stacks. With no state it is handed
    None and the scan carries x and `handed` alone.

    Parameters ride whole beside the state where a half asks for them:
    the leaves of `stacked` that the mixer or the FFN names in its
    attribute `whole` are no scanned input either, and that half is
    called with `stacks=(those leaves, layer)` (`block`), `lp` holding
    the rest.
    A scanned leaf reaches the body as a slice of its stack, which
    fuses into a matmul that reads it and is a copy of the slice,
    whole, ahead of a kernel that takes no fused operand (the grouped
    products of a held share of the experts, `moe.served_ffn`: the copy
    was a third of a decode step) and ahead of a product the compiler
    wants laid out otherwise (a decode step's projections,
    `ops.stacked_product`). Forward only: the gradient of a stack
    handed whole would be the size of the stack at every layer, so a
    trained half names nothing and keeps its slices, and a run whose
    halves name nothing is scanned as it always was.

    `save` is the remat policy: None keeps every activation; a list
    rematerialises each layer in the backward pass but for the
    `checkpoint_name`s in it (an empty list saves nothing)."""
    whole = {name: stacked[name] for half in (mixer, ffn)
             for name in getattr(half, "whole", ()) if name in stacked}
    if whole:
        stacked = {name: leaf for name, leaf in stacked.items()
                   if name not in whole}
    stacked_product.note("sliced", *jax.tree.leaves(stacked))

    def body(carry, scanned):
        x, handed, state = carry
        lp, layer = scanned
        x, state, extras, handed = block(
            mixer, ffn, cfg, rope, x, lp,
            None if state is None else (state, layer), handed,
            stacks=(whole, layer) if whole else None, mesh=mesh, rules=rules)
        return (x, handed, state), extras

    if save is not None:
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(*save))
    index = None if state is None and not whole else jnp.arange(
        jax.tree.leaves(stacked)[0].shape[0])
    (x, handed, state), extras = lax.scan(body, (x, handed, state),
                                          (stacked, index))
    return x, state, extras, handed


def write_rows(stack, layer, new, start_pos):
    """`new` [B, T, ...] into `stack` [layers, B, S, ...] at (layer,
    row, start_pos[row]), cast to the stack's dtype: B x T rows are
    written and no other byte of the stack is read or written. A row
    that would pass S is moved back to end there, as
    `lax.dynamic_update_slice` does.

    A scatter of one window a slot. A prefill's one window is a plain
    `dynamic-update-slice`; a decode step's B windows the TPU runs one
    at a time, 2.5 to 4 us each with the select and the bounds check
    that ride beside them, whatever they hold, which is why a mixer
    writes through `ops.block_rows.write_tokens`: that is this function
    a leaf at a time, but for a step of one token a slot on a TPU,
    where one kernel call rewrites the tiles the rows lie in. This
    stays the definition the kernel is tested against."""
    rows = jnp.arange(new.shape[0], dtype=start_pos.dtype)
    at = jnp.stack([jnp.full_like(rows, layer), rows, start_pos], -1)
    return lax.scatter(
        stack, at, new.astype(stack.dtype),
        lax.ScatterDimensionNumbers(
            update_window_dims=tuple(range(1, new.ndim)),
            inserted_window_dims=(0, 1),
            scatter_dims_to_operand_dims=(0, 1, 2)),
        indices_are_sorted=True, unique_indices=True, mode="clip")


def layer_rows(stack, layer, start, rows):
    """Rows [start, start + rows) of every slot of one layer of `stack`
    [layers, B, S, ...], to be read: [B, rows, ...], one slice of the
    stack itself, so that a loop over blocks of rows never holds the
    layer whole."""
    tail = stack.shape[3:]
    return lax.dynamic_slice(
        stack, (layer, 0, start) + (0,) * len(tail),
        (1, stack.shape[1], rows) + tail)[0]


def rope_tables(cfg, positions=None, *, mesh=None, rules=DEFAULT_RULES):
    """(cos, sin) for `apply_rope`: the [max_seq, D/2] tables, or, from
    explicit `positions` [B, S], the pre-selected [B, S, D/2]."""
    # With context parallelism each shard sees a sequence chunk; RoPE
    # must use global positions, which the caller passes in. Default is
    # the unsharded arange. For explicit positions, cos/sin come from an
    # elementwise compute (no table gather) hoisted out of the layer
    # loop and constrained to the activation sharding — the gather form
    # makes the SPMD partitioner replicate-and-repartition the looked-up
    # values every step ("involuntary full rematerialization").
    if positions is None:
        return rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                cfg.rope_theta)
    return tuple(
        with_logical_constraint(t, "batch", "seq", None, mesh=mesh,
                                rules=rules)
        for t in rope_from_positions(positions, cfg.head_dim,
                                     cfg.rope_theta))


# Tables up to this size are replicated before the token gather: with the
# table left vocab-sharded the SPMD partitioner partitions the gather on
# the vocab dim and then "involuntarily rematerializes" (fully replicates)
# the gathered activations to reach the activation sharding, so one table
# transition is strictly cheaper. Past the threshold (large-vocab TP
# configs) replication would cost vocab*embed bytes of HBM per device, so
# the table keeps its embed-dim shard instead — the gather then moves only
# the looked-up rows, at the price of an all-gather over the activations.
_EMBED_REPLICATE_MAX_BYTES = 1 << 27  # 128 MiB


def _embed_lookup(embed, tokens, mesh, rules):
    small = embed.size * embed.dtype.itemsize <= _EMBED_REPLICATE_MAX_BYTES
    axes = (None, None) if small else (None, "embed")
    embed = with_logical_constraint(embed, *axes, mesh=mesh, rules=rules)
    return embed[tokens]


def hidden_runs(params, tokens, cfg, runs, *, handed=None, mesh=None,
                rules=DEFAULT_RULES, positions=None, save=None):
    """tokens: [B, S] int32 → (final-norm hidden states [B, S, D] in
    cfg.dtype, each run's mixer state, each run's FFN extras) — the
    stack without the output projection. `runs` is a sequence of
    (mixer, ffn, stacked parameters, state), one `layers` scan each, for
    a stack whose layers are not all alike: `handed` enters the first
    layer and every layer's goes to the one above, across runs too."""
    rope = rope_tables(cfg, positions, mesh=mesh, rules=rules)
    x = _embed_lookup(params["embed"], tokens, mesh, rules).astype(cfg.dtype)
    x = with_logical_constraint(x, "batch", "seq", "act_embed",
                                mesh=mesh, rules=rules)
    states, extras = [], []
    for mixer, ffn, stacked, state in runs:
        x, state, run_extras, handed = layers(
            mixer, ffn, cfg, rope, x, stacked, state, handed, save=save,
            mesh=mesh, rules=rules)
        states.append(state)
        extras.append(run_extras)
    return norm(cfg, x, params["final_norm"]), states, extras


def hidden(params, tokens, cfg, mixer, ffn, *, mesh=None,
           rules=DEFAULT_RULES, positions=None, state=None, save=None):
    """`hidden_runs` of a stack that is one run of like layers,
    `params["layers"]`: (hidden states, the mixer's state, the FFN's
    extras, both stacked by layer), so the loss can fuse projection+CE
    (`fused_linear_cross_entropy`)."""
    x, (state,), (extras,) = hidden_runs(
        params, tokens, cfg, [(mixer, ffn, params["layers"], state)],
        mesh=mesh, rules=rules, positions=positions, save=save)
    return x, state, extras


def _head_weight(params, cfg):
    out_w = params["embed"].T if cfg.tie_embeddings else params["out"]
    return out_w.astype(cfg.dtype)


def logits(params, x, cfg, *, mesh=None, rules=DEFAULT_RULES):
    """Final-norm hidden states [B, S, D] → logits [B, S, vocab]."""
    out = jnp.einsum("bsd,dv->bsv", x, _head_weight(params, cfg))
    return with_logical_constraint(out, "batch", "seq", "vocab",
                                   mesh=mesh, rules=rules)


def _vocab_sharded(mesh, rules) -> bool:
    if mesh is None:
        return False
    axis = dict(rules).get("vocab")
    if axis is None:
        return False
    axes = axis if isinstance(axis, tuple) else (axis,)
    size = 1
    for a in axes:
        size *= mesh.shape.get(a, 1)
    return size > 1


def loss(params, batch, cfg, mixer=None, ffn=None, *, runs=None, mesh=None,
         rules=DEFAULT_RULES, save=None):
    """batch: {"tokens": [B,S], "targets": [B,S], optional "mask": [B,S],
    optional "positions": [B,S]}. Returns (mean cross-entropy over the
    unmasked tokens f32, how many those are, the FFN's extras). The
    stack is one run of like layers (`mixer`, `ffn`, over
    `params["layers"]`) or, with `runs`, `hidden_runs`' sequence of
    (mixer, ffn, stacked parameters, state): the extras are then a list,
    a run each, and `save` rematerialises every run's layers alike."""
    x, _, extras = hidden_runs(
        params, batch["tokens"], cfg,
        runs or [(mixer, ffn, params["layers"], None)], mesh=mesh,
        rules=rules, positions=batch.get("positions"), save=save)
    if runs is None:
        extras, = extras
    b, s, d = x.shape
    targets = batch["targets"].reshape(b * s)
    if cfg.fused_ce and not _vocab_sharded(mesh, rules):
        # Fused projection+CE: the [tokens, vocab] logits tensor is never
        # materialized (the largest single activation at 128k vocab).
        with jax.named_scope("loss"):  # holds the output projection too
            losses = fused_linear_cross_entropy(
                x.reshape(b * s, d), _head_weight(params, cfg), targets)
    else:
        out = logits(params, x, cfg, mesh=mesh, rules=rules)
        with jax.named_scope("loss"):
            losses = softmax_cross_entropy(
                out.reshape(b * s, cfg.vocab_size), targets)
    with jax.named_scope("loss"):
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones((b, s), jnp.float32)
        total = jnp.maximum(mask.sum(), 1.0)
        ce = (losses.reshape(b, s) * mask).sum() / total
    return ce, total, extras
