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

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.attention import (attention_reference, flash_attention,
                                   on_tpu)
from ray_tpu.ops.cross_entropy import (fused_linear_cross_entropy,
                                       softmax_cross_entropy)
from ray_tpu.ops.norms import rms_norm_reference
from ray_tpu.ops.rope import (apply_rope, rope_frequencies,
                              rope_from_positions)
from ray_tpu.parallel.ring_attention import ring_attention
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES,
    logical_to_mesh_axes,
    tree_shardings,
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
    # "mlp" (+both ffn acts). Validated in forward_hidden.
    remat: Any = True
    # Fuse the output projection into the CE loss (logits never
    # materialized). Auto-disabled when the vocab dim is sharded.
    fused_ce: bool = True

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


def _init_layer(cfg: LlamaConfig, key) -> Dict[str, Any]:
    d, hd = cfg.dim, cfg.head_dim
    k1, k2, k3, k4, k5, k6, k7 = jax.random.split(key, 7)
    scale = d ** -0.5
    hidden_scale = cfg.hidden_dim ** -0.5
    init = jax.nn.initializers.normal(stddev=0.02)
    return {
        "attn_norm": jnp.ones(d, cfg.dtype),
        "wq": init(k1, (d, cfg.n_heads, hd), cfg.dtype),
        "wk": init(k2, (d, cfg.n_kv_heads, hd), cfg.dtype),
        "wv": init(k3, (d, cfg.n_kv_heads, hd), cfg.dtype),
        "wo": (init(k4, (cfg.n_heads, hd, d), cfg.dtype) * scale),
        "mlp_norm": jnp.ones(d, cfg.dtype),
        "w1": init(k5, (d, cfg.hidden_dim), cfg.dtype),
        "w3": init(k6, (d, cfg.hidden_dim), cfg.dtype),
        "w2": (init(k7, (cfg.hidden_dim, d), cfg.dtype) * hidden_scale),
    }


def init_params(cfg: LlamaConfig, rng) -> Dict[str, Any]:
    k_embed, k_out, k_layers = jax.random.split(rng, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    layers = jax.vmap(functools.partial(_init_layer, cfg))(layer_keys)
    params = {
        "embed": jax.nn.initializers.normal(0.02)(
            k_embed, (cfg.vocab_size, cfg.dim), cfg.dtype),
        "layers": layers,
        "final_norm": jnp.ones(cfg.dim, cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["out"] = jax.nn.initializers.normal(0.02)(
            k_out, (cfg.dim, cfg.vocab_size), cfg.dtype)
    return params


def param_logical_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    """Same structure as `init_params` output, with logical-axis tuples as
    leaves. Leading `None` on layer params is the scanned layer axis."""
    layer = {
        "attn_norm": (None, "norm"),
        "wq": (None, "embed", "heads", "head_dim"),
        "wk": (None, "embed", "kv_heads", "head_dim"),
        "wv": (None, "embed", "kv_heads", "head_dim"),
        "wo": (None, "heads", "head_dim", "embed"),
        "mlp_norm": (None, "norm"),
        "w1": (None, "embed", "mlp"),
        "w3": (None, "embed", "mlp"),
        "w2": (None, "mlp", "embed"),
    }
    axes = {
        "embed": ("vocab", "embed"),
        "layers": layer,
        "final_norm": ("norm",),
    }
    if not cfg.tie_embeddings:
        axes["out"] = ("embed", "vocab")
    return axes


def init_params_sharded(cfg: LlamaConfig, mesh, rng,
                        rules=DEFAULT_RULES) -> Dict[str, Any]:
    """Initialize directly into sharded device buffers (no host staging —
    required for models bigger than host/chip memory)."""
    shardings = tree_shardings(mesh, param_logical_axes(cfg), rules)
    fn = jax.jit(functools.partial(init_params, cfg),
                 out_shardings=shardings)
    return fn(rng)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _attention(cfg: LlamaConfig, q, k, v, mesh, rules):
    """q: [B,S,H,D]; k/v: [B,S,Hkv,D] → [B,S,H,D]."""
    impl = cfg.attention
    if impl == "auto":
        seq_parallel = mesh is not None and mesh.shape.get("seq", 1) > 1
        if seq_parallel:
            impl = "ring"
        else:
            impl = "flash" if on_tpu() else "reference"
    if impl == "flash":
        attn = functools.partial(flash_attention, causal=True)
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
        True, cfg.head_dim ** -0.5)
    return out.transpose(0, 2, 1, 3)


def layer_fn(cfg: LlamaConfig, mesh, rules, cos, sin, x, lp, positions):
    """One transformer block. x: [B, S, D]. The two halves are scoped
    (`attn`, `mlp`) so that a device trace can tell their ops apart."""
    with jax.named_scope("attn"):
        h = rms_norm_reference(x, lp["attn_norm"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
        q = with_logical_constraint(q, "batch", "seq", "heads", "head_dim",
                                    mesh=mesh, rules=rules)
        attn = _attention(cfg, q, k, v, mesh, rules)
        x = x + jnp.einsum("bshk,hkd->bsd", attn.astype(cfg.dtype),
                           lp["wo"])
    with jax.named_scope("mlp"):
        h2 = rms_norm_reference(x, lp["mlp_norm"], cfg.norm_eps)
        # Named for selective remat: cfg.remat="mlp" saves these two (the
        # dominant recompute cost) while still rematerializing the rest.
        gate = checkpoint_name(
            jax.nn.silu(jnp.einsum("bsd,df->bsf", h2, lp["w1"])),
            "ffn_gate")
        up = checkpoint_name(
            jnp.einsum("bsd,df->bsf", h2, lp["w3"]), "ffn_up")
        ff = with_logical_constraint(gate * up, "batch", "seq", "mlp",
                                     mesh=mesh, rules=rules)
        x = x + jnp.einsum("bsf,fd->bsd", ff, lp["w2"])
    x = with_logical_constraint(x, "batch", "seq", "act_embed",
                                mesh=mesh, rules=rules)
    return x


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


def forward_hidden(params, tokens, cfg: LlamaConfig, *, mesh=None,
                   rules=DEFAULT_RULES, positions=None):
    """tokens: [B, S] int32 → final-norm hidden states [B, S, D]
    (cfg.dtype) — the stack without the output projection, so the loss
    can fuse projection+CE (`fused_linear_cross_entropy`)."""
    # With context parallelism each shard sees a sequence chunk; RoPE
    # must use global positions, which the caller passes in. Default is
    # the unsharded arange. For explicit positions, cos/sin come from an
    # elementwise compute (no table gather) hoisted out of the layer
    # loop and constrained to the activation sharding — the gather form
    # makes the SPMD partitioner replicate-and-repartition the looked-up
    # values every step ("involuntary full rematerialization").
    if positions is not None:
        cos, sin = rope_from_positions(positions, cfg.head_dim,
                                       cfg.rope_theta)
        cos = with_logical_constraint(cos, "batch", "seq", None,
                                      mesh=mesh, rules=rules)
        sin = with_logical_constraint(sin, "batch", "seq", None,
                                      mesh=mesh, rules=rules)
        positions = None
    else:
        cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                    cfg.rope_theta)
    x = _embed_lookup(params["embed"], tokens, mesh, rules).astype(cfg.dtype)
    x = with_logical_constraint(x, "batch", "seq", "act_embed",
                                mesh=mesh, rules=rules)

    body = functools.partial(layer_fn, cfg, mesh, rules, cos, sin)

    def scan_body(x, lp):
        return body(x, lp, positions), None

    if cfg.remat:
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
        scan_body = jax.checkpoint(
            scan_body,
            policy=jax.checkpoint_policies.save_only_these_names(*names))
    x, _ = lax.scan(scan_body, x, params["layers"])
    return rms_norm_reference(x, params["final_norm"], cfg.norm_eps)


def forward(params, tokens, cfg: LlamaConfig, *, mesh=None,
            rules=DEFAULT_RULES, positions=None):
    """tokens: [B, S] int32 → logits [B, S, vocab] (cfg.dtype)."""
    x = forward_hidden(params, tokens, cfg, mesh=mesh, rules=rules,
                       positions=positions)
    out_w = params["embed"].T if cfg.tie_embeddings else params["out"]
    logits = jnp.einsum("bsd,dv->bsv", x, out_w.astype(cfg.dtype))
    return with_logical_constraint(logits, "batch", "seq", "vocab",
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


def loss_fn(params, batch, cfg: LlamaConfig, *, mesh=None,
            rules=DEFAULT_RULES):
    """batch: {"tokens": [B,S], "targets": [B,S], optional "mask": [B,S],
    optional "positions": [B,S]}. Returns (mean loss f32, metrics dict)."""
    b, s = batch["tokens"].shape
    if cfg.fused_ce and not _vocab_sharded(mesh, rules):
        # Fused projection+CE: the [tokens, vocab] logits tensor is never
        # materialized (the largest single activation at 128k vocab).
        x = forward_hidden(params, batch["tokens"], cfg, mesh=mesh,
                           rules=rules, positions=batch.get("positions"))
        out_w = params["embed"].T if cfg.tie_embeddings else params["out"]
        with jax.named_scope("loss"):  # holds the output projection too
            losses = fused_linear_cross_entropy(
                x.reshape(b * s, cfg.dim), out_w.astype(cfg.dtype),
                batch["targets"].reshape(b * s))
    else:
        logits = forward(params, batch["tokens"], cfg, mesh=mesh,
                         rules=rules, positions=batch.get("positions"))
        with jax.named_scope("loss"):
            losses = softmax_cross_entropy(
                logits.reshape(b * s, cfg.vocab_size),
                batch["targets"].reshape(b * s))
    with jax.named_scope("loss"):
        losses = losses.reshape(b, s)
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones((b, s), jnp.float32)
        total = jnp.maximum(mask.sum(), 1.0)
        loss = (losses * mask).sum() / total
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
    `probs` is cast to the cache's dtype for the P.V product. Holds for
    any (B, T): decode (T = 1), prefill (B = 1) and in between.

    The benchmark's tests rely on this function's name and signature
    (`tests/benchmark/test_references.py` patches it), on the cache
    layout [layers, slots, max_seq, kv_heads, head_dim], and on the call
    sitting inside the `attn` scope of `forward_with_cache`."""
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


def forward_with_cache(params, tokens, cfg: LlamaConfig, cache,
                       start_pos):
    """Incremental forward: runs `tokens` [B, T] starting at per-sequence
    absolute offsets `start_pos` [B], reading/writing the KV cache.
    Returns (logits [B, T, vocab], new_cache). Works for prefill (T =
    prompt length) and decode (T = 1) with one code path.
    """
    b, t = tokens.shape
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                cfg.rope_theta)
    positions = start_pos[:, None] + jnp.arange(t)[None, :]  # [B, T]
    x = params["embed"][tokens].astype(cfg.dtype)

    def write_cache(cache_b, new_b, start_b):
        # cache_b: [S, Hkv, D]; new_b: [T, Hkv, D]
        return lax.dynamic_update_slice(
            cache_b, new_b.astype(cache_b.dtype), (start_b, 0, 0))

    def layer(x, scanned):
        lp, k_cache_l, v_cache_l = scanned
        with jax.named_scope("attn"):
            h = rms_norm_reference(x, lp["attn_norm"], cfg.norm_eps)
            q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
            k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
            v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
            k_cache_l = jax.vmap(write_cache)(k_cache_l, k, start_pos)
            v_cache_l = jax.vmap(write_cache)(v_cache_l, v, start_pos)
            attn = _cached_attention(cfg, q, k_cache_l, v_cache_l,
                                     positions)
            x = x + jnp.einsum("bshk,hkd->bsd", attn.astype(cfg.dtype),
                               lp["wo"])
        with jax.named_scope("mlp"):
            h2 = rms_norm_reference(x, lp["mlp_norm"], cfg.norm_eps)
            gate = jax.nn.silu(jnp.einsum("bsd,df->bsf", h2, lp["w1"]))
            up = jnp.einsum("bsd,df->bsf", h2, lp["w3"])
            x = x + jnp.einsum("bsf,fd->bsd", gate * up, lp["w2"])
        return x, (k_cache_l, v_cache_l)

    x, (k_new, v_new) = lax.scan(
        layer, x, (params["layers"], cache["k"], cache["v"]))
    x = rms_norm_reference(x, params["final_norm"], cfg.norm_eps)
    out_w = params["embed"].T if cfg.tie_embeddings else params["out"]
    logits = jnp.einsum("bsd,dv->bsv", x, out_w.astype(cfg.dtype))
    return logits, {"k": k_new, "v": v_new}
