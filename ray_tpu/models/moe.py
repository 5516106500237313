"""Mixture-of-Experts decoder: the Llama block with its feed-forward
replaced by `n_experts` SwiGLU experts, of which every token uses the
`n_experts_per_token` its router scores highest. Two published models
run through it: Mixtral-8x7B (8 experts, 2 a token, gates renormalised
over the chosen) and OLMoE-1B-7B (64 experts, 8 a token, gates as the
softmax gives them, RMSNorm on the projected q and k); `MoEConfig`
holds what they differ in, and what the served families add: a
sigmoid router with a selection bias, a shared expert (or several,
summed or averaged, fused into one), a held share of the experts,
experts that are relu^2 with two matrices or gated by a relu, a latent
the routed experts work in (`expert_kind`, `latent_dim`), and a router
that scores another input than the experts read (SmallThinker's, the
layer's input: `_moe_ffn`'s `routed`).

The expert layer is dropless sparse dispatch (`_moe_ffn`): float32
softmax over the router's logits, `lax.top_k` (exactly k experts a
token, ties to the lower index), the Switch-Transformer load-balancing
loss, then the (token, expert) pairs sorted by expert, the tokens
gathered into that order, the three SwiGLU products as grouped matmuls
whose group sizes are known only on the device, and a gate-weighted sum
back in token order. Every pair is computed whatever the load of its
expert: no capacity, nothing dropped, static shapes ([tokens * k, d]
rows in all).
Both permutations are row gathers in the forward and in the backward
pass (`_spread` and `_collect` are each other's transpose), never a
scatter.

A model that holds a share of a layer's experts (`cfg.experts_held`)
goes another way from the router on: only the pairs that fell on the
share are gathered and computed, a buffer of rows at a time, whatever
the load (no pair on a held expert is dropped), and the pairs on absent
experts are left out of the layer's output. Trained
(`_held_experts_trained`), the buffers' products are `lax.ragged_dot`'s
like the whole layer's, both permutations are row gathers forward and
backward, and the layer has a gradient; a share is trained as it is
served. Served (`_held_experts`, forward only), the expert
matrices are read where they lie in the run's stack, and a token's
rows are added into it by a scatter. `served_ffn` names
them as the leaves `decoder.layers` keeps whole beside the scan, and
the grouped products pick the layer's experts out of the stack: a
layer's matrices sliced out of the stack by the scan are a copy of
every held expert's weights ahead of a kernel that reads a few of them
(a third to two fifths of a decode step, PERF.md, PR 35). The trained
layer keeps its scanned slice: a stack handed whole would make every
layer's weight gradient the size of the stack.

Two kernels run the grouped products, one a path. The trained layer's
are `lax.ragged_dot`, which the TPU compiler turns into a grouped-matmul
kernel of its own: thousands of rows a group, bound by the MXU, and it
has the VJP training needs. (Megablox's Pallas `gmm` ran them a third
faster on the v5e, but tracing its group metadata for every call adds
2.4 to 4 s to a warm process's first step: PERF.md, PR 27.) A served
share's are `ops.grouped_matmul`, a Pallas kernel tiled for what a
served step gives it, one to three rows an expert in a decode step and
tens to hundreds in a prefill, where the product is bound by reading
the touched experts' matrices once: the compiler's kernel reads them at
two fifths of memory speed and less (PERF.md, PR 37). That kernel is
forward only (a trained share's products are `lax.ragged_dot`'s), and
its group metadata is built once a layer for the layer's two or three
products.

On a mesh the layer moves tokens, not expert matrices. The matrices
are split over `fsdp` along their hidden width F alone (`_EXPERT_AXES`),
so a chip owns F/n of every expert, and dispatch, the grouped products
and the combine run under `shard_map`: every chip of the n takes all
their tokens (an all-gather), sorts all the pairs once, runs the same
three grouped products over n times the rows at an n-th of the width
(the same FLOPs a chip, and the chips stay level however the router
collapses: every chip computes every expert), and a reduce-scatter adds
the chips' partial outputs in float32 and hands each its own tokens
(`_sparse_experts`' `over`). The backward pass is the transpose, and a
weight's gradient is born whole on the chip that holds the slice. A
layer, forward and backward, moves about 14 bytes a token and unit of
D over the chips (tokens, output and the two cotangents, the sums in
float32: 0.94 GB for Mixtral's 16,384 tokens of 4,096), where gathering
the matrices twice and scattering their gradients moved 18 bytes a
weight, E x D x F x 18 B (8.5 GB). Moving tokens wins while the `fsdp`
group's tokens a step stay under about 1.3 x E x F: 147 k tokens at
Mixtral's widths, 84 k at OLMoE's, and no configuration here is past
it. One that is should make the layer choose by those two byte counts,
which it can read off its operands' shapes; there is one path until
then, and no knob. Over an `expert` or `tensor` axis the matrices are
gathered whole inside and every chip computes all experts for its own
tokens: the right result, and no cell has such an axis above 1. The
exchange by expert (an all-to-all of tokens to the chips that hold
whole experts) is not here: its busiest chip waits on the busiest
experts' load, three times the mean where the router has collapsed.

No reference equivalent (the reference has no model code); this exists
so sparse experts are a first-class, exercised layer (SURVEY.md §2
parallelism inventory calls EP "absent entirely" upstream).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import decoder
from ray_tpu.models.llama import (
    LlamaConfig,
    attention_axes,
    attention_init,
    norm_all_heads,
    self_attention,
)
from ray_tpu.ops import grouped_matmul
from ray_tpu.ops.norms import rms_norm_reference  # noqa: F401
from ray_tpu.parallel.sharding import DEFAULT_RULES, logical_to_mesh_axes

# The benchmark's fault test of the q/k norm patches this name, and
# builds its fault from `rms_norm_reference` above, both as attributes of
# this module (`tests/benchmark/test_olmoe.py`).
_norm_all_heads = norm_all_heads


@dataclasses.dataclass(frozen=True)
class MoEConfig(LlamaConfig):
    n_experts: int = 8
    n_experts_per_token: int = 2
    aux_loss_coeff: float = 0.01
    # Divide a token's k gates by their sum (Mixtral). OLMoE weighs its
    # experts by the softmax over all of them as it is.
    norm_topk_prob: bool = True
    # What the DeepSeek-V3 family's router differs in. An expert's score
    # is the "softmax" over all experts or its own "sigmoid".
    scoring: str = "softmax"
    # The k experts are chosen by score plus a per-expert bias (the leaf
    # `router_bias`); the gates are the scores themselves.
    selection_bias: bool = False
    # The gates are multiplied by this, after `norm_topk_prob`.
    gate_scale: float = 1.0
    # Width of a SwiGLU expert that every token passes through beside
    # its k (leaves `ws1`, `ws3`, `ws2`); 0: none. Several shared
    # experts are one of their summed width, `n_shared_experts` side by
    # side in the leaves' columns (one pair of matmuls, not one a
    # shared expert): its output is their sum, or with
    # `shared_combination` "average" their mean (Command A+).
    shared_hidden_dim: int = 0
    n_shared_experts: int = 1
    shared_combination: str = "sum"
    # (first, count): the contiguous range of the `n_experts` experts
    # whose weights this program holds, the share of one chip of a
    # deployment that spreads a layer's experts over several. The router
    # stays `n_experts` wide and chooses among all of them; the pairs
    # that fall on absent experts are left out of the layer's output
    # (the chip that holds them adds them). None: all.
    experts_held: Optional[Tuple[int, int]] = None
    # What an expert, routed or shared, computes: "swiglu",
    # w2 (silu(w1 x) * w3 x), three matrices; "reglu", the same with
    # relu in silu's place; "relu2", w2 relu(w1 x)^2, two (the leaves
    # `we3` and `ws3` are then absent).
    expert_kind: str = "swiglu"
    # Width of the latent the routed experts work in (LatentMoE): the
    # layer projects a token down once (`w_dn`, shared by the experts)
    # before dispatch and up again (`w_up`) after the combine; the
    # router and the shared expert read the full width. 0: none.
    latent_dim: int = 0

    @property
    def n_experts_held(self) -> int:
        return self.experts_held[1] if self.experts_held else self.n_experts

    @staticmethod
    def debug_moe() -> "MoEConfig":
        return MoEConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                         n_kv_heads=2, hidden_dim=128, max_seq_len=128,
                         dtype=jnp.float32, remat=False, n_experts=4,
                         n_experts_per_token=2)

    @staticmethod
    def mixtral_8x7b() -> "MoEConfig":
        return MoEConfig(vocab_size=32000, dim=4096, n_layers=32,
                         n_heads=32, n_kv_heads=8, hidden_dim=14336,
                         rope_theta=1e6, n_experts=8,
                         n_experts_per_token=2)

    @staticmethod
    def olmoe_1b_7b() -> "MoEConfig":
        return MoEConfig(vocab_size=50304, dim=2048, n_layers=16,
                         n_heads=16, n_kv_heads=16, hidden_dim=1024,
                         max_seq_len=4096, rope_theta=1e4, norm_eps=1e-5,
                         n_experts=64, n_experts_per_token=8,
                         norm_topk_prob=False, qk_norm=True)


def _init_moe_layer(cfg: MoEConfig, key) -> Dict[str, Any]:
    k_router, k1, k2, k3 = jax.random.split(jax.random.fold_in(key, 99), 4)
    return {**attention_init(cfg, key),
            **expert_init(cfg, (k_router, k1, k2, k3))}


def expert_init(cfg: MoEConfig, keys, init=None) -> Dict[str, Any]:
    """The expert layer's leaves from four keys: the router over all
    `n_experts`, the matrices of the experts held (three, or two of
    `expert_kind` "relu2"; `latent_dim` wide where the config has a
    latent, with the pair `w_dn`, `w_up` around them), and what the
    config's router and shared expert add. `init(key, shape, dtype)`
    draws a matrix; `jax.nn.initializers.normal(0.02)` unless given."""
    k_router, k1, k2, k3 = keys
    init = init or jax.nn.initializers.normal(stddev=0.02)
    e, d, h = cfg.n_experts_held, cfg.dim, cfg.hidden_dim
    gated = _gate_of(cfg) is not None
    w = cfg.latent_dim or d  # what a routed expert reads and writes
    leaves = {
        "router": init(k_router, (d, cfg.n_experts), cfg.dtype),
        "we1": init(k1, (e, w, h), cfg.dtype),
        "we2": init(k3, (e, h, w), cfg.dtype) * (h ** -0.5),
    }
    if gated:
        leaves["we3"] = init(k2, (e, w, h), cfg.dtype)
    if cfg.latent_dim:
        kd, ku = jax.random.split(jax.random.fold_in(k_router, 2))
        leaves.update(w_dn=init(kd, (d, w), cfg.dtype),
                      w_up=init(ku, (w, d), cfg.dtype))
    if cfg.selection_bias:
        # A buffer the published training balances the load with: small
        # beside a sigmoid score, large enough to change who is chosen.
        leaves["router_bias"] = 0.1 * jax.random.normal(
            jax.random.fold_in(k_router, 1), (cfg.n_experts,), jnp.float32)
    if cfg.shared_hidden_dim:
        ks = jax.random.split(jax.random.fold_in(k1, 1), 3)
        f = cfg.shared_hidden_dim
        leaves.update(ws1=init(ks[0], (d, f), cfg.dtype),
                      ws2=init(ks[2], (f, d), cfg.dtype) * (f ** -0.5))
        if gated:
            leaves["ws3"] = init(ks[1], (d, f), cfg.dtype)
    return leaves


def init_moe_params(cfg: MoEConfig, rng) -> Dict[str, Any]:
    return decoder.init_params(cfg, rng,
                               functools.partial(_init_moe_layer, cfg))


# Logical axes of the expert matrices without the layer axis: how they
# are sharded where they enter the expert layer's shard_map. Split
# along the hidden width alone (`expert_mlp`: over `fsdp` and `tensor`,
# `parallel/sharding.py`) and whole along the model width, which the
# other matrices split over `fsdp`: a chip then owns its slice of every
# expert, contracts over all of D at home, and the layer moves tokens
# to the matrices (`_sparse_experts`' `over`), 0.94 GB a Mixtral layer
# and step where gathering the matrices moved 8.5. A layer has `we3`
# only if its experts are gated, and hands the grouped products the
# matrices it has, in this order.
_EXPERT_AXES = {
    "we1": ("expert", None, "expert_mlp"),
    "we3": ("expert", None, "expert_mlp"),
    "we2": ("expert", "expert_mlp", None),
}


def moe_param_logical_axes(cfg: MoEConfig) -> Dict[str, Any]:
    return decoder.param_logical_axes(
        cfg, {**attention_axes(cfg), "router": ("embed", None),
              **_EXPERT_AXES})


def init_moe_params_sharded(cfg: MoEConfig, mesh, rng,
                            rules=DEFAULT_RULES):
    return decoder.init_params_sharded(
        functools.partial(init_moe_params, cfg),
        moe_param_logical_axes(cfg), mesh, rng, rules)


# -- the expert layer ---------------------------------------------------------
#
# A token's k choices are the pairs t*k .. t*k + k-1. `order` lists the
# pairs sorted by expert (stable, so an expert's tokens stay in token
# order) and `inv` is its inverse: pair p sits in row inv[p].


def _rows(x, idx):
    return x.at[idx].get(mode="promise_in_bounds")


@jax.custom_vjp
def _spread(x, order, inv):
    """x [T, D] -> [T*k, D]: each pair's token, in expert order."""
    return _rows(x, order // (order.size // x.shape[0]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _collect(y, gates, order, inv, keep=None):
    """y [T*k, D] in expert order, gates [T, k] float32 -> [T, D]: every
    token's k rows, weighted by its gates, summed in float32. `keep`
    says that y is a chip's partial sums in float32 (`_partial_product`)
    and so is the result, to be added to the other chips'; what the
    backward pass reads of y, for the gates' gradient, is then y
    rounded to `keep`, under the name `expert_out`."""
    t, k = gates.shape
    return jnp.einsum("tkd,tk->td", _rows(y, inv).reshape(t, k, -1), gates,
                      preferred_element_type=jnp.float32).astype(y.dtype)


# Each is the other's transpose, so the backward pass is row gathers too
# (autodiff would transpose a gather into a scatter-add).
def _spread_bwd(res, g):
    order, inv, t = res
    summed = _rows(g, inv).reshape(t, order.size // t, -1).sum(
        1, dtype=jnp.float32)
    return summed.astype(g.dtype), None, None


def _collect_fwd(y, gates, order, inv, keep):
    kept = y if keep is None else checkpoint_name(y.astype(keep),
                                                  "expert_out")
    return _collect(y, gates, order, inv, keep), (kept, gates, order, inv)


def _collect_bwd(keep, res, g):
    y, gates, order, inv = res
    # Each pair's token's. (A partial sum's cotangent crossed the chips
    # in `keep`, `_own_rows_summed`: its rows are gathered as narrow.)
    g_rows = _rows(g.astype(y.dtype), order // gates.shape[1])
    d_gates = _rows(jnp.einsum("pd,pd->p", g_rows, y,
                               preferred_element_type=jnp.float32), inv)
    d_y = (g_rows * _rows(gates.reshape(-1), order)[:, None]).astype(y.dtype)
    if keep is not None:
        # Rounded where it is made, as the plain product's cotangent is;
        # float32 is the type a partial sum's must have, and the
        # compiler drops the way there and back (`_partial_product`).
        d_y = d_y.astype(jnp.float32)
    return d_y, d_gates.reshape(gates.shape), None, None


_spread.defvjp(lambda x, order, inv: (_spread(x, order, inv),
                                      (order, inv, x.shape[0])),
               _spread_bwd)
_collect.defvjp(_collect_fwd, _collect_bwd)


# -- a chip's slice of every expert, and everyone's tokens --------------------
#
# Inside `shard_map`, over mesh axes whose n chips each hold a slice of
# the experts' hidden width and tokens of their own. A chip's last
# product is a partial sum over its slice; what crosses the chips is
# rows of tokens, and every sum of partial sums is made in float32.


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _all_rows(x, axes):
    """x [T, ...], a chip's rows -> [n*T, ...], every chip's. Backward,
    each chip has a cotangent for all of them: their sum, in float32."""
    return lax.all_gather(x, axes, axis=0, tiled=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _own_rows_summed(y, axes, dtype):
    """y [n*T, D] float32, a chip's partial sums for every chip's rows
    -> [T, D] in `dtype`: the chips' sum, added in float32, of this
    chip's rows. Backward the cotangent crosses the chips in `dtype`."""
    return lax.psum_scatter(y, axes, scatter_dimension=0,
                            tiled=True).astype(dtype)


# Each is the other's transpose.
_all_rows.defvjp(
    lambda x, axes: (_all_rows(x, axes), None),
    lambda axes, _, g: (_own_rows_summed(g.astype(jnp.float32), axes,
                                         g.dtype),))
_own_rows_summed.defvjp(
    lambda y, axes, dtype: (_own_rows_summed(y, axes, dtype), None),
    lambda axes, dtype, _, g: (_all_rows(g, axes).astype(jnp.float32),))


@jax.custom_vjp
def _partial_product(rows, w, group_sizes):
    """`lax.ragged_dot` over a slice of the contracted width: the result
    stays in float32, one term of a sum across chips that is rounded
    once, after it. The backward products are the plain product's own,
    on the cotangent rounded to the rows' dtype as the plain product's
    arrives."""
    return lax.ragged_dot(rows, w, group_sizes,
                          preferred_element_type=jnp.float32)


def _partial_product_bwd(res, g):
    rows, w, group_sizes = res
    g = g.astype(rows.dtype)
    d_rows, = jax.linear_transpose(
        lambda r: lax.ragged_dot(r, w, group_sizes), rows)(g)
    d_w, = jax.linear_transpose(
        lambda m: lax.ragged_dot(rows, m, group_sizes), w)(g)
    return d_rows, d_w, None


_partial_product.defvjp(
    lambda rows, w, group_sizes: (_partial_product(rows, w, group_sizes),
                                  (rows, w, group_sizes)),
    _partial_product_bwd)


def _expert_counts(top_i, n_experts):
    """How many of the pairs chose each expert: [E] int32."""
    hits = top_i[..., None] == jnp.arange(n_experts, dtype=top_i.dtype)
    return hits.sum(tuple(range(top_i.ndim)), dtype=jnp.int32)


def _relu2(x):
    """What a "relu2" expert puts between its two matrices."""
    return jnp.square(jax.nn.relu(x))


_GATES = {"swiglu": jax.nn.silu, "reglu": jax.nn.relu, "relu2": None}


def _gate_of(cfg: MoEConfig):
    """What a gated expert puts around its first product before it
    multiplies the second (`expert_kind`); None where experts have two
    matrices and no gate."""
    assert cfg.expert_kind in _GATES, cfg.expert_kind
    return _GATES[cfg.expert_kind]


def _expert_matrices(lp):
    return [lp[name] for name in _EXPERT_AXES if name in lp]


def _grouped_experts(xs, product, we1, *rest, gate=jax.nn.silu, last=None):
    """Rows in expert order through their experts: [R, D] -> [R, D].
    `rest` is (we3, we2) of gated experts, whose first product goes
    through `gate` (`_gate_of`), (we2,) of relu^2 ones;
    `product(rows, w)` is the path's grouped product of rows with their
    experts' matrices among `w`. The products carry the names of
    `_SAVED` for a rematerialised layer's policy to keep; under no
    `jax.checkpoint`, or one whose list lacks them, a name is an
    identity. What lies between them (`gate(h) * up`, `_relu2`) has no
    name: from saved products it is one elementwise pass. `last`, where
    given, is the last product in `product`'s place, a chip's partial
    sums in float32: it gets its name where it is rounded (`_collect`)."""
    *gated, we2 = rest
    with jax.named_scope("expert_matmul"):
        hidden = checkpoint_name(product(xs, we1), "expert_gate")  # [R, F]
        if gated:
            hidden = gate(hidden) * checkpoint_name(
                product(xs, gated[0]), "expert_up")
        else:
            hidden = _relu2(hidden)
        if last is not None:
            return last(hidden, we2)
        return checkpoint_name(product(hidden, we2), "expert_out")  # [R, D]


def _sparse_experts(x, gates, top_i, we1, *rest, gate=jax.nn.silu, over=()):
    """The chosen experts of the tokens at hand. x [T, D], gates and
    top_i [T, k], weights [E, ...] -> [T, D].

    `over`, inside `shard_map`: the mesh axes whose n chips each hold a
    slice of every expert's hidden width, [E, D, F/n] and [E, F/n, D],
    and T tokens of their own. Every chip then takes all n*T tokens
    (`token_exchange`, an all-gather), sorts all their pairs once and
    runs the same three grouped products over n times the rows at a
    n-th of the width: the first two are whole (they contract over D),
    the last is a partial sum over the chip's slice, kept in float32
    through the weighted sum, and a reduce-scatter adds the chips' and
    hands each its own tokens. The backward pass is the transpose: the
    output's cotangent gathered, the tokens' and the gates' summed in
    float32, and a weight's gradient complete on the chip that holds
    the slice. No expert matrix crosses the chips."""
    dtype = x.dtype
    if over:
        with jax.named_scope("token_exchange"):
            x, gates = _all_rows(x, over), _all_rows(gates, over)
            top_i = lax.all_gather(top_i, over, axis=0, tiled=True)
    with jax.named_scope("moe_dispatch"):
        order = jnp.argsort(top_i.reshape(-1), stable=True)
        inv = jnp.argsort(order)
        group_sizes = _expert_counts(top_i, we1.shape[0])
        xs = _spread(x, order, inv)                        # [T*k, D]
    ys = _grouped_experts(
        xs, functools.partial(lax.ragged_dot, group_sizes=group_sizes),
        we1, *rest, gate=gate, last=functools.partial(
            _partial_product, group_sizes=group_sizes) if over else None)
    with jax.named_scope("moe_combine"):
        out = _collect(ys, gates, order, inv, dtype if over else None)
    if over:
        with jax.named_scope("token_exchange"):
            out = _own_rows_summed(out, over, dtype)
    return out


# A share's grouped products run over buffers of this many times the
# pairs it would be dealt were the router uniform, and never of fewer
# rows than this (a decode step's few pairs then always fit in one).
_HELD_ROWS_SLACK = 2
_HELD_ROWS_MIN = 256
# The TPU compiler keeps a buffer's gathered rows in its 16 MiB of fast
# memory when they fit there alone, and then has no room for the
# gather's own 2.9 MiB beside 13.5 MiB of them: a prefill of 1,536
# tokens at D = 2,304 (3,072 rows) was refused, "ran out of memory in
# memory space vmem", where 1,024 tokens compiled and 12,288 rows of
# 6,144 never go there (PERF.md, PR 57). A buffer of so many bytes
# takes the rows that put it past the fast memory instead.
_HELD_BYTES_REFUSED = (13 << 20, 16 << 20)


def _held_experts(cfg: MoEConfig, x, gates, top_i, layer, *stacks):
    """`_sparse_experts` of a share of the experts (`cfg.experts_held`),
    at the cost of the pairs that landed on it. The weights are read
    where they lie: `stacks` are the expert matrices of a run of layers,
    each [layers, experts held, ...], and `layer` (an int32 scalar, as
    `decoder.layers` counts) says whose turn it is. The grouped products
    are `ops.grouped_matmul`'s, not the trained path's `lax.ragged_dot`:
    its kernel picks (layer, expert) out of the stack as it fetches a
    matrix, so nothing slices the layer out of the stack first (that
    slice was a copy of every held expert's weights, a layer), an
    expert no pair fell on is not read, and a touched one is read once,
    at memory speed, for its one to three rows of a decode step (the
    compiler's kernel, tiled for training's groups, took 2.5 times as
    long: PERF.md, PR 37). The groups' metadata is built here, once a
    buffer, for the layer's two or three products.

    The pairs are sorted held experts first, and the held ones go
    through the grouped products a buffer of `rows` rows at a time
    (static: `_HELD_ROWS_SLACK` times the uniform share of the T * k
    pairs), as many buffers as hold them: one, unless the router
    crowded this share. No pair is dropped, none on an absent expert is
    gathered or computed, and each token's rows are added into it (a
    scatter-add over the buffer's rows: forward only, this is a serving
    path). Returns (out [T, D], pairs held int32, buffers beyond the
    first int32, held experts with at least one pair int32)."""
    first, count = cfg.experts_held
    k = top_i.shape[1]
    pairs = top_i.size
    rows = min(pairs, max(
        _HELD_ROWS_MIN,
        -(-_HELD_ROWS_SLACK * pairs * count // cfg.n_experts)))
    row_bytes = x.shape[-1] * x.dtype.itemsize
    low, high = _HELD_BYTES_REFUSED
    if low < rows * row_bytes < high:
        rows = min(pairs, -(-high // (8 * row_bytes)) * 8)
    with jax.named_scope("moe_dispatch"):
        local = top_i.reshape(-1) - first
        local = jnp.where((local >= 0) & (local < count), local, count)
        # The held pairs in expert order, then the absent; padded so
        # that a buffer's slice never runs off the end.
        order = jnp.pad(jnp.argsort(local, stable=True), (0, rows))
        held_counts = _expert_counts(local, count)
        ends = jnp.cumsum(held_counts)
        n_held = ends[-1]
        flat_gates = gates.reshape(-1)

    def buffer(i, out):
        lo = i * rows
        with jax.named_scope("moe_dispatch"):
            pair = lax.dynamic_slice_in_dim(order, lo, rows)
            token = pair // k
            # Each expert's rows inside [lo, lo + rows).
            inside = jnp.clip(ends, lo, lo + rows)
            groups = grouped_matmul.plan(jnp.diff(inside, prepend=lo), rows)
            held = jnp.arange(rows) < n_held - lo
            xs = _rows(x, token)                           # [rows, D]
        ys = _grouped_experts(
            xs, functools.partial(grouped_matmul.grouped_matmul,
                                  layer=layer, groups=groups),
            *stacks, gate=_gate_of(cfg))
        with jax.named_scope("moe_combine"):
            # Rows past the held pairs belong to no group: whatever the
            # grouped product left there counts nothing.
            weight = jnp.where(held, _rows(flat_gates, pair), 0.0)
            ys = jnp.where(held[:, None], ys.astype(jnp.float32), 0.0)
            return out.at[token].add(ys * weight[:, None])

    n_buffers = (n_held + rows - 1) // rows
    out = lax.fori_loop(0, n_buffers, buffer,
                        jnp.zeros(x.shape, jnp.float32))
    return (out.astype(x.dtype), n_held.astype(jnp.int32),
            jnp.maximum(n_buffers - 1, 0).astype(jnp.int32),
            (held_counts > 0).sum(dtype=jnp.int32))


# A trained share's buffers hold this many times the pairs it would be
# dealt were the router uniform (and never fewer rows than
# `_HELD_ROWS_MIN`): what a buffer's rows take in memory is paid
# whatever the load, so less room than a served share's; the rare
# buffer beyond the first is recomputed in the backward pass.
_TRAINED_ROWS_SLACK = 1.5


@jax.custom_vjp
def _spread_some(x, pair, row, live):
    """x [T, D] -> [R, D]: the tokens of the R pairs `pair` (a pair is
    token * k + choice). `row` [T, k] says in which of the R rows each
    pair lies and `live` [T, k] whether it lies in any."""
    return _rows(x, pair // live.shape[1])


def _sum_by_token(y, row, live, weights=None):
    """[T, D] float32: every token's rows among y [R, D], as `row` and
    `live` [T, k] name them, times `weights` [T, k] if given, summed.
    A choice at a time: one gather of all T x k rows would stand whole
    in memory, 1.9 GB of a 16k step's, where this holds [T, D]. A pair
    that is not live counts nothing, whatever the row it names holds."""
    out = jnp.zeros((row.shape[0], y.shape[1]), jnp.float32)
    for j in range(row.shape[1]):
        picked = jnp.where(live[:, j, None], _rows(y, row[:, j]), 0)
        picked = picked.astype(jnp.float32)
        out += picked if weights is None else picked * weights[:, j, None]
    return out


@jax.custom_vjp
def _collect_some(y, gates, pair, row, live):
    """y [R, D], the rows of the pairs `pair`, gates [T, k] float32 ->
    [T, D]: every token's rows among them, weighted by their gates and
    summed in float32."""
    return _sum_by_token(y, row, live, gates).astype(y.dtype)


# Each other's transpose again, so the backward pass of a share is row
# gathers too: `row` is where a gradient's rows are found by token.
def _spread_some_bwd(res, g):
    row, live = res
    return _sum_by_token(g, row, live).astype(g.dtype), None, None, None


def _collect_some_bwd(res, g):
    y, gates, pair, row, live = res
    k = gates.shape[1]
    g_rows = _rows(g, pair // k)                           # [R, D]
    # A row that no live pair names (one past the pairs held, or the
    # padding behind the last pair) has no gate of its own.
    live_row = _rows(live.reshape(-1), pair) \
        & (_rows(row.reshape(-1), pair) == jnp.arange(pair.size))
    d_gates = _rows(jnp.einsum("rd,rd->r", g_rows, y,
                               preferred_element_type=jnp.float32),
                    row.reshape(-1)).reshape(gates.shape)
    d_y = g_rows * jnp.where(live_row, _rows(gates.reshape(-1), pair),
                             0.0)[:, None]
    return (d_y.astype(y.dtype), jnp.where(live, d_gates, 0.0), None, None,
            None)


_spread_some.defvjp(lambda x, pair, row, live: (
    _spread_some(x, pair, row, live), (row, live)), _spread_some_bwd)
_collect_some.defvjp(lambda y, gates, pair, row, live: (
    _collect_some(y, gates, pair, row, live), (y, gates, pair, row, live)),
    _collect_some_bwd)


def _held_buffer(cfg: MoEConfig, rows: int, lo, x, gates, order, at, ends,
                 *ws):
    """The held pairs in rows [lo, lo + rows) of the order through
    their experts and back to their tokens: [T, D]. `order` lists the
    pairs held experts first (padded to whole buffers), `at` [T, k]
    says where in it each pair lies, `ends` [count] where each held
    expert's pairs end; the last of them is the number held."""
    n_held = ends[-1]
    with jax.named_scope("moe_dispatch"):
        pair = lax.dynamic_slice_in_dim(order, lo, rows)
        live = (at >= lo) & (at < jnp.minimum(n_held, lo + rows))
        row = jnp.clip(at - lo, 0, rows - 1)
        sizes = jnp.diff(jnp.clip(ends, lo, lo + rows), prepend=lo)
        xs = _spread_some(x, pair, row, live)              # [rows, D]
    ys = _grouped_experts(
        xs, functools.partial(lax.ragged_dot, group_sizes=sizes), *ws,
        gate=_gate_of(cfg))
    with jax.named_scope("moe_combine"):
        return _collect_some(ys, gates, pair, row, live)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _further_buffers(cfg, rows, out, x, gates, order, at, ends, *ws):
    """`out` plus the buffers beyond the first, as many as the pairs
    held reach: a loop whose count only the device knows, none where
    the first buffer held them all. Its backward pass is the same loop
    again, each buffer recomputed and pulled back (autodiff has no
    transpose for such a loop, and under `lax.cond` in a `lax.scan` a
    buffer that never runs still keeps zeros the size of x and of the
    weights an iteration, from the forward pass on)."""
    return lax.fori_loop(
        1, (ends[-1] + rows - 1) // rows,
        lambda i, out: out + _held_buffer(cfg, rows, i * rows, x, gates,
                                          order, at, ends, *ws), out)


def _further_buffers_bwd(cfg, rows, res, g):
    x, gates, order, at, ends, ws = res

    def pulled(i, acc):
        _, pull = jax.vjp(
            lambda x, gates, *ws: _held_buffer(
                cfg, rows, i * rows, x, gates, order, at, ends, *ws),
            x, gates, *ws)
        return jax.tree.map(jnp.add, acc, pull(g))

    d_x, d_gates, *d_ws = lax.fori_loop(
        1, (ends[-1] + rows - 1) // rows, pulled,
        jax.tree.map(jnp.zeros_like, (x, gates, *ws)))
    return (g, d_x, d_gates, None, None, None, *d_ws)


_further_buffers.defvjp(
    lambda cfg, rows, out, x, gates, order, at, ends, *ws: (
        _further_buffers(cfg, rows, out, x, gates, order, at, ends, *ws),
        (x, gates, order, at, ends, ws)), _further_buffers_bwd)


def _held_experts_trained(cfg: MoEConfig, x, gates, top_i, *ws):
    """`_sparse_experts` of a share of the experts (`cfg.experts_held`),
    with a backward pass, at the cost of the pairs that landed on the
    share: x [T, D], gates and top_i [T, k], this layer's matrices of
    the experts held, [count, ...] -> (out [T, D], pairs held int32,
    buffers beyond the first int32, held experts with a pair int32).

    The pairs are sorted held experts first (stable, so an expert's
    tokens stay in token order) and the held ones go through the
    grouped products, `lax.ragged_dot`'s as the whole layer's are, a
    buffer of `rows` rows at a time (`_held_buffer`): static,
    `_TRAINED_ROWS_SLACK` times the uniform share of the T * k pairs.
    The first buffer is the layer's as autodiff sees it; further ones
    (`_further_buffers`) run as far as the pairs held reach, so none is
    ever dropped, and a buffer no pair reaches costs nothing. A pair on
    an absent expert is neither gathered nor computed, and adds nothing
    to its token. Gathers and the weighted sum back are `_spread_some`
    and `_collect_some`."""
    first, count = cfg.experts_held
    t, k = top_i.shape
    pairs = t * k
    rows = min(pairs, max(_HELD_ROWS_MIN, -(-int(
        _TRAINED_ROWS_SLACK * pairs * count) // cfg.n_experts)))
    n_buffers = -(-pairs // rows)
    with jax.named_scope("moe_dispatch"):
        local = top_i.reshape(-1) - first
        local = jnp.where((local >= 0) & (local < count), local, count)
        # The held pairs in expert order, then the absent; padded so
        # that the last buffer's slice does not run off the end.
        order = jnp.argsort(local, stable=True)
        at = jnp.argsort(order).reshape(t, k)   # where each pair lies
        order = jnp.pad(order, (0, n_buffers * rows - pairs))
        held_counts = _expert_counts(local, count)
        ends = jnp.cumsum(held_counts)
    out = _held_buffer(cfg, rows, jnp.int32(0), x, gates, order, at, ends,
                       *ws)
    if n_buffers > 1:
        out = _further_buffers(cfg, rows, out, x, gates, order, at, ends,
                               *ws)
    n_held = ends[-1]
    return (out, n_held.astype(jnp.int32),
            jnp.maximum((n_held + rows - 1) // rows - 1, 0).astype(jnp.int32),
            (held_counts > 0).sum(dtype=jnp.int32))


def _route(cfg: MoEConfig, lp, x):
    """The router: x [B, S, D] -> (every expert's score [B, S, E]
    float32, the k chosen experts' gates [B, S, k] float32, which they
    are [B, S, k])."""
    k = cfg.n_experts_per_token
    if cfg.scoring == "sigmoid":
        # DeepSeek-V3's gate multiplies in float32: with hundreds of
        # experts the k-th and the next score lie closer than a
        # bfloat16 logit tells apart.
        probs = jax.nn.sigmoid(jnp.einsum(
            "bsd,de->bse", x.astype(jnp.float32),
            lp["router"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
    else:
        logits = jnp.einsum("bsd,de->bse", x,
                            lp["router"]).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)            # [B, S, E]
    if cfg.selection_bias:
        # The bias chooses, it does not weigh.
        _, top_i = lax.top_k(probs + lp["router_bias"], k)
        gates = jnp.take_along_axis(probs, top_i, -1)
    else:
        gates, top_i = lax.top_k(probs, k)                 # [B, S, k]
    if cfg.norm_topk_prob:
        gates = gates / gates.sum(-1, keepdims=True)
    if cfg.gate_scale != 1.0:
        gates = gates * cfg.gate_scale
    return probs, gates, top_i


def _moe_ffn(cfg: MoEConfig, lp, x, mesh, rules, stacks=None, *,
             routed=None, trained=False, live=None):
    """x: [B, S, D] -> ([B, S, D], aux loss scalar, pairs routed to each
    expert [E] int32, and what the layer computed of them as int32
    scalars: `pairs_held`, the pairs that fell on experts held here
    (all of them unless `cfg.experts_held`), `pairs_routed`,
    `pair_overflows`, the buffers beyond the first that a held share's
    pairs took, `experts_touched`, the experts held here that at least
    one pair fell on, whose matrices the grouped products read, and
    `experts_held_steps`, the experts held here). With `cfg.latent_dim`
    the routed experts work on x projected down to the latent (scope
    `latent_down`) and their combined output is projected up again
    (`latent_up`); the router and the shared expert read x itself.
    `stacks` is None, the expert matrices being `lp`'s, or, for a held
    share, (the matrices of the whole run stacked by layer, this
    layer's index in them), `lp` holding the layer's other leaves.
    `routed` [B, S, D] is what the router scores where that is not x
    (SmallThinker scores the layer's input, its experts read the
    normed stream after attention). `trained` says that the layer is
    to have a gradient: a held share then goes through
    `_held_experts_trained` and its own matrices. `live` [B, S] bool
    says which rows are anyone's: a row that is not chooses no expert,
    so a served share computes and counts nothing for it (its pairs are
    absent, as those on an expert not held are) and the routed
    experts add nothing to it."""
    b, s, _ = x.shape
    k = cfg.n_experts_per_token
    with jax.named_scope("router"):
        probs, gates, top_i = _route(cfg, lp, x if routed is None else routed)
        if live is not None:
            assert cfg.experts_held is not None and not trained
            top_i = jnp.where(live[..., None], top_i, cfg.n_experts)
        counts = _expert_counts(top_i, cfg.n_experts)
        # Load-balance aux loss: E * sum_e (share of the tokens that
        # chose e) * (mean router probability of e).
        aux = cfg.n_experts * jnp.sum(
            counts / (b * s) * probs.mean(axis=(0, 1)))

    full = x
    if cfg.latent_dim:
        with jax.named_scope("latent_down"):
            x = jnp.einsum("bsd,dl->bsl", x, lp["w_dn"])
    d = x.shape[-1]

    def experts(x, gates, top_i, *ws, over=()):
        """On the tokens at hand: the whole batch, or one shard's."""
        t = x.shape[0] * x.shape[1]
        out = _sparse_experts(x.reshape(t, d), gates.reshape(t, k),
                              top_i.reshape(t, k), *ws, gate=_gate_of(cfg),
                              over=over)
        return out.reshape(x.shape)

    run, layer = stacks or (lp, None)
    weights = _expert_matrices(run)
    n_held, over = jnp.int32(b * s * k), jnp.int32(0)
    touched = (counts > 0).sum(dtype=jnp.int32)
    if cfg.experts_held is not None:
        assert mesh is None or mesh.size == 1, \
            "a held share of the experts runs on one chip"
        flat = (x.reshape(b * s, d), gates.reshape(b * s, k),
                top_i.reshape(b * s, k))
        if trained:
            assert stacks is None, "a trained layer keeps its scanned slice"
            out, n_held, over, touched = _held_experts_trained(
                cfg, *flat, *weights)
        else:
            if stacks is None:  # a layer's own matrices: a run of one layer
                weights, layer = [w[None] for w in weights], 0
            out, n_held, over, touched = _held_experts(
                cfg, *flat, layer, *weights)
        out = out.reshape(x.shape)
    elif mesh is None:
        out = experts(x, gates, top_i, *weights)
    else:
        # The expert matrices come in as they are sharded. Over the
        # axes that split the tokens too (`_token_exchange`) they stay
        # as they came, a slice of every expert's hidden width, and the
        # shards' tokens go to them; over any other (`expert`,
        # `tensor`) they are gathered whole inside, their gradients
        # leaving through the matching reduce-scatter, and each shard
        # dispatches its own tokens.
        exchange = _token_exchange(mesh, rules)
        w_specs = [logical_to_mesh_axes(axes, rules)
                   for name, axes in _EXPERT_AXES.items() if name in lp]
        tok = logical_to_mesh_axes(("batch", "seq", None), rules)
        out = jax.shard_map(
            lambda x, gates, top_i, *ws: experts(
                x, gates, top_i,
                *[_gather_but(w, spec, mesh, exchange)
                  for w, spec in zip(ws, w_specs)], over=exchange),
            mesh=mesh, in_specs=(tok, tok, tok, *w_specs), out_specs=tok,
            check_vma=False)(x, gates, top_i, *weights)
    if cfg.latent_dim:
        with jax.named_scope("latent_up"):
            out = jnp.einsum("bsl,ld->bsd", out, lp["w_up"])
    return _add_shared_expert(cfg, lp, full, out), aux, counts, {
        "pairs_held": n_held, "pairs_routed": jnp.int32(b * s * k),
        "pair_overflows": over, "experts_touched": touched,
        "experts_held_steps": jnp.int32(cfg.n_experts_held)}


def _add_shared_expert(cfg: MoEConfig, lp, x, out):
    """`out` plus the expert every token passes through at the full
    width, of the config's `expert_kind`, where the config has one:
    `n_shared_experts` of them fused (their hidden units side by side,
    so the down-projection sums them), the sum divided by their number
    where the config averages them."""
    if not cfg.shared_hidden_dim:
        return out
    assert cfg.shared_combination in ("sum", "average"), cfg
    with jax.named_scope("shared_expert"):
        hidden = jnp.einsum("bsd,df->bsf", x, lp["ws1"])
        gate = _gate_of(cfg)
        if gate is not None:
            hidden = gate(hidden) * jnp.einsum("bsd,df->bsf", x, lp["ws3"])
        else:
            hidden = _relu2(hidden)
        shared = jnp.einsum("bsf,fd->bsd", hidden, lp["ws2"])
        if cfg.shared_combination == "average":
            shared = shared / cfg.n_shared_experts
        return out + shared


def _mesh_axes(entry):
    """An entry of a PartitionSpec as a tuple of mesh axes."""
    if entry is None:
        return ()
    return (entry,) if isinstance(entry, str) else tuple(entry)


def _token_axes(rules):
    """The mesh axes that split the tokens, batch or sequence."""
    return [axis
            for entry in logical_to_mesh_axes(("batch", "seq"), rules)
            for axis in _mesh_axes(entry)]


def _token_exchange(mesh, rules):
    """The mesh axes over which the expert layer moves tokens and not
    expert matrices: those of more than one chip that split both the
    experts' hidden width and the tokens. Empty without a mesh or on
    one chip: the layer then issues no collective."""
    if mesh is None:
        return ()
    tokens = _token_axes(rules)
    return tuple(axis for axis in _mesh_axes(dict(rules).get("expert_mlp"))
                 if axis in tokens and mesh.shape[axis] > 1)


def _rows_received(mesh, rules, tokens):
    """The token rows a chip's expert layer takes from other chips, a
    layer, of a batch of `tokens` in all: n - 1 times its own, n the
    chips of `_token_exchange`."""
    if mesh is None:
        return 0
    holders = math.prod(mesh.shape[axis] for axis in _token_axes(rules))
    chips = math.prod(mesh.shape[axis]
                      for axis in _token_exchange(mesh, rules))
    return (chips - 1) * (tokens // holders)


def _gather_but(w, spec, mesh, kept):
    """Inside shard_map: an array that came in split as `spec` says,
    whole but for the mesh axes `kept`, over which it stays split."""
    for dim, entry in enumerate(spec):
        axes = tuple(axis for axis in _mesh_axes(entry)
                     if axis not in kept and mesh.shape[axis] > 1)
        if axes:
            w = lax.all_gather(w, axes, axis=dim, tiled=True)
    return w


def served_ffn(cfg: MoEConfig, live=None):
    """The FFN `decoder` is handed for a served expert layer: the
    layer's output and, as its extras, what it counted (`_moe_ffn`'s
    int32 scalars). Where the config holds a share of the experts, it
    names the expert matrices as the leaves `decoder.layers` leaves
    whole (`whole`) and reads its layer's in their stacks
    (`_held_experts`); a layer of all the experts takes its slice.
    `live` [B, S] is `_moe_ffn`'s: the call's rows that are anyone's,
    all of them unless given."""
    def ffn(h, lp, stacks=None):
        out, _, _, share = _moe_ffn(cfg, lp, h, None, DEFAULT_RULES, stacks,
                                    live=live)
        return out, share

    if cfg.experts_held is not None:
        ffn.whole = tuple(_EXPERT_AXES)
    return ffn


# What a rematerialised layer of this family keeps for its backward
# pass (`decoder.layers`' `save`): the attention kernel's output and
# logsumexp, as the dense family keeps them, with its q, k and v
# (`ops.attention` names all five), and the three grouped products
# (`_grouped_experts`; `expert_up` only where experts are gated,
# `expert_out` being what `_collect_bwd` needs for the gates'
# gradient). The backward pass then runs 6 grouped products a layer and
# no forward attention kernel, where keeping nothing made it 9 and
# one; the output projection, the norms, the router, the row gathers
# and the experts' activation are still recomputed. A kept array is
# written into the scan's stack and sliced out of it again, two copies
# that a kernel's output and operand cannot be fused into: a third of
# what the products' recomputation cost comes back as those (PERF.md,
# PR 48). The price is memory, per layer, per chip and per token, in
# bfloat16 (R pairs, F an expert's width, T tokens, D the hidden size):
#
#                           OLMoE-1B-7B, T 16,384,   Mixtral-8x7B, T 4,096,
#                           R 131,072, F 1,024,      4 chips: R 32,768,
#                           D 2,048                  F 3,584, D 4,096
#   expert_gate, expert_up  268 MB each (R x F)      235 MB each
#   expert_out              537 MB (R x D)           268 MB
#   flash_out + flash_lse   67 + 1 MB (T x D)        34 + 0.5 MB
#   flash_q, _k, _v         67 MB each               34, 8 and 8 MB
#
# (Mixtral's four chips each run the four's pairs at a quarter of the
# width, `_sparse_experts`: `expert_out` is a chip's partial sums for
# all of them, kept rounded to bfloat16, four times what its own
# tokens' were.)
# 1.34 and 0.82 GB a layer: it fits the benchmark's two cells because
# they are cut to 2 layers. At a published depth the hidden products
# stop fitting a v5e long before the others do; a list chosen by bytes
# is PERF.md section 7's open question, and no knob chooses one now.
_SAVED = ("flash_out", "flash_lse", "flash_q", "flash_k", "flash_v",
          "expert_gate", "expert_up", "expert_out")


def _parts(cfg: MoEConfig, mesh, rules):
    """What `decoder` is handed for this architecture: the mixer, the
    FFN, and what a rematerialised layer saves (`_SAVED`, with its
    bytes; `cfg.remat` false saves every activation)."""
    def ffn(h, lp):
        out, aux, counts, _ = _moe_ffn(cfg, lp, h, mesh, rules, trained=True)
        return out, {"aux": aux, "counts": counts}

    # `_norm_all_heads` is read here, when a forward pass is traced, so
    # that a test's patch of it reaches the program.
    return dict(mixer=self_attention(cfg, mesh, rules, _norm_all_heads),
                ffn=ffn, save=_SAVED if cfg.remat else None, mesh=mesh,
                rules=rules)


def moe_forward_hidden(params, tokens, cfg: MoEConfig, *, mesh=None,
                       rules=DEFAULT_RULES, positions=None):
    """tokens [B, S] -> (final-norm hidden states [B, S, D], the layers'
    mean aux loss, pairs routed per layer and expert [L, E] int32)."""
    x, _, extras = decoder.hidden(params, tokens, cfg, positions=positions,
                                  **_parts(cfg, mesh, rules))
    return x, extras["aux"].mean(), extras["counts"]


def moe_forward(params, tokens, cfg: MoEConfig, *, mesh=None,
                rules=DEFAULT_RULES, positions=None):
    """Returns (logits [B,S,V], total aux loss)."""
    x, aux, _ = moe_forward_hidden(params, tokens, cfg, mesh=mesh,
                                   rules=rules, positions=positions)
    return decoder.logits(params, x, cfg, mesh=mesh, rules=rules), aux


def moe_loss_fn(params, batch, cfg: MoEConfig, *, mesh=None,
                rules=DEFAULT_RULES):
    """Mean cross-entropy plus `aux_loss_coeff` times the load-balancing
    loss. The metrics carry the step's routing: `expert_tokens` [L, E],
    the pairs sent to each expert of each layer, and under `span_attrs`
    (what `make_train_step` puts on its dispatch span) the busiest
    expert's count, the mean, and `expert_rows_received`: the token
    rows a chip's expert layer took from other chips, a layer
    (`_token_exchange`; from the shapes, 0 on one chip)."""
    ce, _, extras = decoder.loss(params, batch, cfg,
                                 **_parts(cfg, mesh, rules))
    aux = extras["aux"].mean()
    expert_tokens = extras["counts"]
    loss = ce + cfg.aux_loss_coeff * aux
    return loss, {"loss": loss, "ce_loss": ce, "aux_loss": aux,
                  "expert_tokens": expert_tokens,
                  "span_attrs": {
                      "expert_tokens_max": expert_tokens.max(),
                      "expert_tokens_mean": expert_tokens.sum()
                      // expert_tokens.size,
                      "expert_rows_received": jnp.int32(_rows_received(
                          mesh, rules, batch["tokens"].size))}}
