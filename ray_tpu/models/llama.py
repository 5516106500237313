"""Llama-3-family decoder-only LM, written TPU-first.

Design choices (vs. a torch port):
- Parameters are a plain pytree of arrays with a parallel pytree of
  *logical axis names* (`param_logical_axes`) — sharding is data, not code.
- Layers are stacked along a leading axis and driven by `lax.scan` with
  `jax.checkpoint` on the body: O(1) compile time in depth, per-layer
  rematerialization for HBM.
- Attention is pluggable: Pallas flash kernel (single-device sequence),
  ring attention or Ulysses over the ``seq`` mesh axis (context parallel),
  or the reference einsum (CPU tests).
- bf16 params/activations, f32 for softmax/norm statistics — the MXU path.

Config presets follow the Llama-3 family (rope_theta 500000, GQA,
SwiGLU with the 8/3 expansion).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import decoder
from ray_tpu.ops import attention, block_rows, stacked_product
from ray_tpu.ops.attention import (attention_reference, flash_attention,
                                   on_tpu)
from ray_tpu.ops.norms import rms_norm_reference
from ray_tpu.ops.rope import apply_rope
from ray_tpu.ops.stacked_product import leaf_product
from ray_tpu.parallel.ring_attention import ring_attention
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES,
    logical_to_mesh_axes,
    with_logical_constraint,
)
from ray_tpu.parallel.ulysses import ulysses_attention


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    hidden_dim: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # "auto" | "flash" | "ring" | "ulysses" | "reference"
    attention: str = "auto"
    # False | True (save attn out/lse only) | "gate" (+silu(w1) act) |
    # "mlp" (+both ffn acts). Validated in _saved_names.
    remat: Any = True
    # Fuse the output projection into the CE loss (logits never
    # materialized). Auto-disabled when the vocab dim is sharded.
    fused_ce: bool = True
    # RMSNorm with a learned weight over the whole projected q and k
    # vectors (all heads together), before rope (OLMoE).
    qk_norm: bool = False
    # What `decoder.block` is: "rms" or "layer" (the mean taken off, a
    # weight, no bias) for its norms, and whether mixer and FFN both
    # read one norm's output and are added to x together (Cohere's
    # parallel block) or follow each other, each behind its own norm.
    norm_kind: str = "rms"
    parallel_block: bool = False
    # "input": a half reads the norm of the stream (Llama). "output": it
    # reads the stream and its output is normed before the residual
    # takes it (OLMo 2 and 3, `olmo_hybrid`).
    norm_placement: str = "input"

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def num_params(self) -> int:
        d, h, l, v = self.dim, self.hidden_dim, self.n_layers, self.vocab_size
        per_layer = (
            d * self.n_heads * self.head_dim          # wq
            + 2 * d * self.n_kv_heads * self.head_dim  # wk, wv
            + self.n_heads * self.head_dim * d         # wo
            + 3 * d * h                                # w1, w2, w3
            + 2 * d                                    # norms
            + self.qk_norm * (self.n_heads + self.n_kv_heads) * self.head_dim
        )
        embeds = v * d * (1 if self.tie_embeddings else 2)
        return l * per_layer + embeds + d

    # -- presets ---------------------------------------------------------

    @staticmethod
    def debug() -> "LlamaConfig":
        return LlamaConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                           n_kv_heads=2, hidden_dim=128, max_seq_len=128,
                           dtype=jnp.float32, remat=False)

    @staticmethod
    def llama3_1b() -> "LlamaConfig":
        # Llama-3.2-1B: 1.23B params, tied embeddings.
        return LlamaConfig(vocab_size=128256, dim=2048, n_layers=16,
                           n_heads=32, n_kv_heads=8, hidden_dim=8192,
                           tie_embeddings=True)

    @staticmethod
    def llama3_3b() -> "LlamaConfig":
        return LlamaConfig(vocab_size=128256, dim=3072, n_layers=28,
                           n_heads=24, n_kv_heads=8, hidden_dim=8192,
                           tie_embeddings=True)

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig()  # defaults are 8B

    @staticmethod
    def llama3_70b() -> "LlamaConfig":
        return LlamaConfig(dim=8192, n_layers=80, n_heads=64, n_kv_heads=8,
                           hidden_dim=28672)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# A layer's key is split seven ways: the first four are attention's, the
# last three the FFN's (an FFN with leaves of its own draws them from a
# key folded out of the layer's, as `moe.py` does).


def attention_init(cfg: LlamaConfig, key) -> Dict[str, Any]:
    """One layer's leaves but for the FFN's: the two block norms, the
    four attention projections, and the q/k norm weights if configured."""
    d, hd = cfg.dim, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 7)[:4]
    init = jax.nn.initializers.normal(stddev=0.02)
    leaves = {
        "attn_norm": jnp.ones(d, cfg.dtype),
        "wq": init(k1, (d, cfg.n_heads, hd), cfg.dtype),
        "wk": init(k2, (d, cfg.n_kv_heads, hd), cfg.dtype),
        "wv": init(k3, (d, cfg.n_kv_heads, hd), cfg.dtype),
        "wo": (init(k4, (cfg.n_heads, hd, d), cfg.dtype) * d ** -0.5),
        "mlp_norm": jnp.ones(d, cfg.dtype),
    }
    if cfg.qk_norm:
        leaves["q_norm"] = jnp.ones(cfg.n_heads * hd, cfg.dtype)
        leaves["k_norm"] = jnp.ones(cfg.n_kv_heads * hd, cfg.dtype)
    return leaves


def attention_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    """Logical axes of `attention_init`'s leaves (no layer axis)."""
    axes = {
        "attn_norm": ("norm",),
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
        "mlp_norm": ("norm",),
    }
    if cfg.qk_norm:
        axes.update(q_norm=("norm",), k_norm=("norm",))
    return axes


def _swiglu_init(cfg: LlamaConfig, key) -> Dict[str, Any]:
    k5, k6, k7 = jax.random.split(key, 7)[4:]
    init = jax.nn.initializers.normal(stddev=0.02)
    return {
        "w1": init(k5, (cfg.dim, cfg.hidden_dim), cfg.dtype),
        "w3": init(k6, (cfg.dim, cfg.hidden_dim), cfg.dtype),
        "w2": (init(k7, (cfg.hidden_dim, cfg.dim), cfg.dtype)
               * cfg.hidden_dim ** -0.5),
    }


_SWIGLU_AXES = {
    "w1": ("embed", "mlp"),
    "w3": ("embed", "mlp"),
    "w2": ("mlp", "embed"),
}


def init_params(cfg: LlamaConfig, rng) -> Dict[str, Any]:
    return decoder.init_params(cfg, rng, lambda key: {
        **attention_init(cfg, key), **_swiglu_init(cfg, key)})


def param_logical_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    """Same structure as `init_params` output, with logical-axis tuples as
    leaves. Leading `None` on layer params is the scanned layer axis."""
    return decoder.param_logical_axes(
        cfg, {**attention_axes(cfg), **_SWIGLU_AXES})


def init_params_sharded(cfg: LlamaConfig, mesh, rng,
                        rules=DEFAULT_RULES) -> Dict[str, Any]:
    """Initialize directly into sharded device buffers (no host staging —
    required for models bigger than host/chip memory)."""
    return decoder.init_params_sharded(
        functools.partial(init_params, cfg), param_logical_axes(cfg), mesh,
        rng, rules)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def norm_all_heads(x, weight, eps):
    """RMSNorm of [B, S, H, K] over heads and head size together."""
    b, s, h, k = x.shape
    return rms_norm_reference(x.reshape(b, s, h * k), weight,
                              eps).reshape(b, s, h, k)


def _qkv(cfg: LlamaConfig, h, lp, rope, positions, qk_norm, turned=True,
         stacks=None):
    """The projections of normed activations h [B, S, D], with the q/k
    norm if configured and rope (none where not `turned`: a layer with
    no positional encoding): q [B,S,H,D], k and v [B,S,Hkv,D].
    `stacks`: the mixer's, where `decoder.layers` handed it the three
    leaves whole (a served decode step)."""
    q = leaf_product("bsd,dhk->bshk", h, "wq", lp, stacks)
    k = leaf_product("bsd,dhk->bshk", h, "wk", lp, stacks)
    v = leaf_product("bsd,dhk->bshk", h, "wv", lp, stacks)
    if cfg.qk_norm:
        q = qk_norm(q, lp["q_norm"], cfg.norm_eps)
        k = qk_norm(k, lp["k_norm"], cfg.norm_eps)
    if not turned:
        return q, k, v
    return (apply_rope(q, *rope, positions), apply_rope(k, *rope, positions),
            v)


def _attention(cfg: LlamaConfig, q, k, v, mesh, rules, window=None):
    """q: [B,S,H,D]; k/v: [B,S,Hkv,D] → [B,S,H,D]. With `window` a row
    sees that many keys, itself the last (the flash kernels and the
    reference; the context-parallel paths have none)."""
    impl = cfg.attention
    if impl == "auto":
        seq_parallel = mesh is not None and mesh.shape.get("seq", 1) > 1
        if seq_parallel:
            impl = "ring"
        else:
            impl = "flash" if on_tpu() else "reference"
    if impl == "flash":
        attn = functools.partial(flash_attention, causal=True, window=window)
        if mesh is None:
            return attn(q, k, v)
        # GSPMD cannot partition the kernel's custom call: left bare in
        # a sharded step it would gather the batch and run the whole
        # attention on every chip. Each device runs it on its own batch
        # rows and heads instead (kv_heads % tensor == 0 keeps every
        # query head beside its GQA kv head).
        q_spec = logical_to_mesh_axes(("batch", None, "heads"), rules)
        kv_spec = logical_to_mesh_axes(("batch", None, "kv_heads"), rules)
        return jax.shard_map(
            attn, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
            out_specs=q_spec, check_vma=False)(q, k, v)
    if impl in ("ring", "ulysses"):
        assert window is None, f"no window in {impl} attention"
        # Ring/Ulysses currently take equal head counts; expand GQA KV
        # heads (cheap relative to long-context attention itself).
        rep = cfg.n_heads // cfg.n_kv_heads
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        fn = ring_attention if impl == "ring" else ulysses_attention
        return fn(q, k, v, mesh=mesh, axis_name="seq", causal=True)
    # reference
    rep = cfg.n_heads // cfg.n_kv_heads
    out = attention_reference(
        q.transpose(0, 2, 1, 3),
        jnp.repeat(k, rep, axis=2).transpose(0, 2, 1, 3),
        jnp.repeat(v, rep, axis=2).transpose(0, 2, 1, 3),
        True, cfg.head_dim ** -0.5, window)
    return out.transpose(0, 2, 1, 3)


def self_attention(cfg: LlamaConfig, mesh=None, rules=DEFAULT_RULES,
                   qk_norm=norm_all_heads, *, window=None, turned=True):
    """The mixer of training and of the uncached forward: causal
    attention of the sequence over itself. With `window` a row sees
    that many keys, itself the last, and the layer's attention runs in
    the scope `window` (inside the block's `attn`, as the served
    windowed mixer's does); without `turned` q and k are not turned by
    rotary positions (a layer with no positional encoding)."""
    def mixer(h, lp, rope, state, handed):
        q, k, v = _qkv(cfg, h, lp, rope, None, qk_norm, turned)
        q = with_logical_constraint(q, "batch", "seq", "heads", "head_dim",
                                    mesh=mesh, rules=rules)
        with contextlib.nullcontext() if window is None \
                else jax.named_scope("window"):
            return (_attention(cfg, q, k, v, mesh, rules, window), state,
                    handed)

    return mixer


def swiglu(mesh=None, rules=DEFAULT_RULES):
    """The dense FFN: w2(silu(w1 h) * w3 h), no extras."""
    def ffn(h, lp):
        # Named for selective remat: cfg.remat="mlp" saves these two (the
        # dominant recompute cost) while still rematerializing the rest.
        gate = checkpoint_name(
            jax.nn.silu(jnp.einsum("bsd,df->bsf", h, lp["w1"])),
            "ffn_gate")
        up = checkpoint_name(
            jnp.einsum("bsd,df->bsf", h, lp["w3"]), "ffn_up")
        ff = with_logical_constraint(gate * up, "batch", "seq", "mlp",
                                     mesh=mesh, rules=rules)
        return jnp.einsum("bsf,fd->bsd", ff, lp["w2"]), None

    return ffn


def _saved_names(cfg: LlamaConfig):
    """What `cfg.remat` keeps across the remat boundary, as
    `decoder.layers` takes it."""
    if not cfg.remat:
        return None
    # Save the flash-attention output + logsumexp across the remat
    # boundary: the backward then recomputes only the cheap projections
    # (for the q/k/v residuals) and never re-runs the forward attention
    # kernel. ~37MB/layer at 4x2048 — a large step-time win for a small
    # slice of HBM. remat="mlp" additionally saves the two MLP hidden
    # activations (the dominant recompute FLOPs; ~268MB/layer at
    # 4x2048) — worth it when the fused-CE loss path leaves the HBM
    # headroom.
    if cfg.remat not in (True, "mlp", "gate"):
        raise ValueError(
            f"remat={cfg.remat!r}: expected False, True, 'gate', or "
            "'mlp' (a typo here would silently train with attn-only "
            "checkpointing)")
    names = ["flash_out", "flash_lse"]
    if cfg.remat == "mlp":
        names += ["ffn_gate", "ffn_up"]
    elif cfg.remat == "gate":  # half the HBM of "mlp"
        names += ["ffn_gate"]
    return names


def _parts(cfg: LlamaConfig, mesh, rules):
    """What `decoder` is handed for the dense architecture."""
    return dict(mixer=self_attention(cfg, mesh, rules),
                ffn=swiglu(mesh, rules), save=_saved_names(cfg), mesh=mesh,
                rules=rules)


def forward_hidden(params, tokens, cfg: LlamaConfig, *, mesh=None,
                   rules=DEFAULT_RULES, positions=None):
    """tokens: [B, S] int32 → final-norm hidden states [B, S, D]
    (cfg.dtype) — the stack without the output projection, so the loss
    can fuse projection+CE (`fused_linear_cross_entropy`)."""
    return decoder.hidden(params, tokens, cfg, positions=positions,
                          **_parts(cfg, mesh, rules))[0]


def forward(params, tokens, cfg: LlamaConfig, *, mesh=None,
            rules=DEFAULT_RULES, positions=None):
    """tokens: [B, S] int32 → logits [B, S, vocab] (cfg.dtype)."""
    x = forward_hidden(params, tokens, cfg, mesh=mesh, rules=rules,
                       positions=positions)
    return decoder.logits(params, x, cfg, mesh=mesh, rules=rules)


def loss_fn(params, batch, cfg: LlamaConfig, *, mesh=None,
            rules=DEFAULT_RULES):
    """batch: {"tokens": [B,S], "targets": [B,S], optional "mask": [B,S],
    optional "positions": [B,S]}. Returns (mean loss f32, metrics dict)."""
    loss, total, _ = decoder.loss(params, batch, cfg,
                                  **_parts(cfg, mesh, rules))
    return loss, {"loss": loss, "tokens": total,
                  "perplexity": jnp.exp(loss)}


# ---------------------------------------------------------------------------
# KV-cache inference path (prefill + decode) — used by ray_tpu.serve.llm
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: LlamaConfig, n_slots: int, max_seq: int,
                  dtype=None) -> Dict[str, Any]:
    """Slot-based KV cache: [layers, slots, max_seq, kv_heads, head_dim].
    One slot per in-flight sequence; continuous batching admits/retires
    requests per slot without touching the others (static shapes → one
    compiled decode program)."""
    dtype = dtype or cfg.dtype
    shape = (cfg.n_layers, n_slots, max_seq, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _cached_attention(cfg, q, k_cache, v_cache, q_positions):
    """q: [B, T, H, D]; caches: [B, S, Hkv, D]; q_positions: [B, T]
    absolute positions. Causal over absolute key positions.

    Guarantees: K and V enter both contractions in the dtype and layout
    the cache stores them in (`forward_with_cache` hands q over in that
    dtype), each byte read once; the `rep = H // Hkv` query heads of one
    KV head are contracted together (a group of one for MHA), so no
    array of `rep` x cache size and none of cache size in float32
    exists. Scores, mask, softmax and both accumulations are float32;
    `probs` is cast to the cache's dtype for the P.V product. Right for
    any (B, T), decode (T = 1), prefill (B = 1) and in between, but
    every row reads the slot's whole region S = max_seq, whatever the
    slot holds: on a TPU a call of one token a slot goes through
    `ops.attention.decode_attention` instead, which gives the same
    guarantees and reads the blocks of rows a slot's length reaches;
    off the TPU that function comes back here. The
    scores are one dense [B, H, T, S] float32 array over the
    region: 4 B x H x T x S bytes, 2.1 GB at 32 heads
    of a 1,024-token prefill against 16,384 rows and 69 GB at 128 heads
    of 8,192 tokens, so a chip's 16 GB hold it up to about T x S = 2^24
    at 32 heads. A long-context family goes through it a block of
    queries at a time (`nemotron_h._attention`) or uses
    `cohere2_moe._attend`, which also visits only the blocks of keys a
    row can see.

    The benchmark's tests rely on this function's name and signature
    (`tests/benchmark/test_references.py` patches it), on the cache
    layout [layers, slots, max_seq, kv_heads, head_dim], and on the call
    sitting inside the `attn` scope (`decoder.block` opens it around the
    mixer, which looks this name up in this module when it is traced)."""
    b, t, h, d = q.shape
    s, g = k_cache.shape[1], k_cache.shape[2]
    qg = q.reshape(b, t, g, h // g, d)
    scores = jnp.einsum("btgrd,bsgd->bgrts", qg, k_cache,
                        preferred_element_type=jnp.float32) * (d ** -0.5)
    key_pos = jnp.arange(s)
    mask = key_pos[None, None, :] <= q_positions[:, :, None]  # [B, T, S]
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrts,bsgd->btgrd", probs.astype(v_cache.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    return out.reshape(b, t, h, d).astype(q.dtype)


def _cached_self_attention(cfg: LlamaConfig, start_pos, positions):
    """The mixer of serving. Its state is the slot cache's two stacks,
    (K, V), each [layers, B, S, Hkv, D], which `decoder.layers` carries
    through the scan: the layer's new K and V go into them at (layer,
    row, `start_pos[row]`), B x T rows a stack
    (`block_rows.write_tokens`: a scatter, or on a TPU, for one token a
    slot, one kernel call for both stacks where they lie). On a TPU a
    call of one token a slot (a decode step) then hands the stacks
    whole to `attention.decode_attention`, which reads out of them the
    blocks of rows each slot holds; a call of more tokens (a prefill),
    and any call off the TPU, has `_cached_attention` (looked up in
    this module when the mixer is traced) read the layer's [B, S, Hkv,
    D] out of them."""
    def mixer(h, lp, rope, state, handed, stacks=None):
        (k_stack, v_stack), layer = state
        q, k, v = _qkv(cfg, h, lp, rope, positions, norm_all_heads,
                       stacks=stacks)
        k_stack, v_stack = block_rows.write_tokens(
            (k_stack, v_stack), layer, (k, v), start_pos)
        if q.shape[1] == 1 and attention.on_tpu():
            out = attention.decode_attention(
                q[:, 0], k_stack, v_stack, layer, positions[:, 0] + 1)[:, None]
        else:
            max_seq = k_stack.shape[2]
            out = _cached_attention(
                cfg, q, decoder.layer_rows(k_stack, layer, 0, max_seq),
                decoder.layer_rows(v_stack, layer, 0, max_seq), positions)
        return out, (k_stack, v_stack), handed

    # A decode step reads the three projections where they lie in the
    # stack of layers (`ops.stacked_product`; `decoder.layers` keeps
    # the leaves a half names out of its scan).
    if stacked_product.engages(positions.shape[1]):
        mixer.whole = ("wq", "wk", "wv")
    return mixer


def forward_with_cache(params, tokens, cfg: LlamaConfig, cache,
                       start_pos):
    """Incremental forward: runs `tokens` [B, T] starting at per-sequence
    absolute offsets `start_pos` [B], reading/writing the KV cache.
    Returns (logits [B, T, vocab], new_cache). Works for prefill (T =
    prompt length) and decode (T = 1) with one code path.
    """
    positions = start_pos[:, None] + jnp.arange(tokens.shape[1])[None, :]
    x, (k_new, v_new), _ = decoder.hidden(
        params, tokens, cfg, state=(cache["k"], cache["v"]), ffn=swiglu(),
        mixer=_cached_self_attention(cfg, start_pos, positions))
    return decoder.logits(params, x, cfg), {"k": k_new, "v": v_new}
