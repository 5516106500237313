"""Pipeline parallelism over the ``pipe`` mesh axis.

The reference has no in-tree pipeline parallelism (only the Alpa release
test, `release/alpa_tests/train_opt_2_7b_minimum.py:95` — SURVEY.md §2
parallelism inventory). Here PP is a first-class mesh axis: stage
parameters are sharded over ``pipe`` (each device group holds one stage)
and microbatches stream through a `lax.scan` whose carried state rotates
between neighbouring stages via `lax.ppermute` — the standard SPMD
"collective pipeline" formulation, which keeps everything inside one XLA
program (no host round-trips between stages, unlike actor-staged PP).

Two schedules:

- ``pipeline_apply`` — GPipe fill/drain, forward only (inference /
  autodiff-through-the-scan). S+M-1 ticks; bubble (S-1)/(S+M-1).
- ``pipeline_train_1f1b`` — interleaved one-forward-one-backward
  TRAINING schedule (Megatron-style 1F1B, the synchronized-collective
  variant): every tick runs one forward sub-slot and one backward
  sub-slot on every stage, activations ppermute right while gradients
  ppermute left, and the backward of microbatch m starts as soon as its
  loss gradient exists — S-1 ticks after injection, NOT after all M
  forwards. The activation stash per stage is therefore bounded by
  ``min(M, 2(S-1)+1)`` microbatch INPUTS (constant in M; GPipe-through-
  autodiff stashes all M), with the stage forward rematerialized from
  the stashed input during its backward sub-slot. Total ticks
  M + 2(S-1): bubble fraction 2(S-1)/(M + 2(S-1)), the 1F1B bound.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from ray_tpu.parallel.collectives import axis_size as _axis_size
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _pipeline_sharded(stage_params, x_mb, stage_fn: Callable,
                      axis_name: str):
    """Per-shard body. stage_params: this stage's params (local). x_mb:
    [M, mb, ...] microbatched input — only stage 0's copy is consumed.
    Returns [M, mb, ...] outputs (valid on the last stage; replicated back
    by the caller via ppermute)."""
    n_stages = _axis_size(axis_name)
    stage_idx = lax.axis_index(axis_name)
    n_mb = x_mb.shape[0]
    ticks = n_stages + n_mb - 1

    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        state, outputs = carry
        # Which microbatch does stage 0 inject this tick?
        mb_idx = jnp.clip(t, 0, n_mb - 1)
        injected = lax.dynamic_index_in_dim(x_mb, mb_idx, axis=0,
                                            keepdims=False)
        inp = jnp.where(stage_idx == 0, injected, state)
        out = stage_fn(stage_params, inp)
        # Last stage records its result at slot t - (n_stages - 1).
        out_idx = jnp.clip(t - (n_stages - 1), 0, n_mb - 1)
        is_valid = (t >= n_stages - 1) & (stage_idx == n_stages - 1)
        current = lax.dynamic_index_in_dim(outputs, out_idx, 0, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(is_valid, out, current), out_idx, 0
        )
        # Shift activations to the next stage.
        state = lax.ppermute(out, axis_name, fwd_perm)
        return (state, outputs), None

    state0 = jnp.zeros_like(stage_fn(stage_params,
                                     jax.tree.map(lambda a: a[0], x_mb)))
    outputs0 = jnp.zeros((n_mb,) + state0.shape, state0.dtype)
    (_, outputs), _ = lax.scan(tick, (state0, outputs0),
                               jnp.arange(ticks))
    # Broadcast final outputs from the last stage to all stages so the
    # caller sees a replicated result (psum over one-hot contribution).
    contribution = jnp.where(stage_idx == n_stages - 1, outputs,
                             jnp.zeros_like(outputs))
    return lax.psum(contribution, axis_name)


def pipeline_apply(stage_fn: Callable, stage_params, x_microbatches, *,
                   mesh: Optional[Mesh] = None, axis_name: str = "pipe"):
    """Run `stage_fn(params, x)` as a pipeline over `axis_name`.

    - `stage_params`: pytree whose leaves have a leading stage dimension of
      size n_stages, sharded over `axis_name` (each shard sees its own
      stage's slice with the stage dim collapsed).
    - `x_microbatches`: [num_microbatches, microbatch, ...] input,
      replicated over `axis_name`.
    Returns outputs [num_microbatches, microbatch, ...], replicated.
    """
    body = functools.partial(_pipeline_sharded, stage_fn=stage_fn,
                             axis_name=axis_name)
    if mesh is None:
        return body(stage_params, x_microbatches)
    param_spec = jax.tree.map(lambda _: P(axis_name), stage_params)
    fn = jax.shard_map(
        lambda p, x: body(jax.tree.map(lambda a: a[0], p), x),
        mesh=mesh,
        in_specs=(param_spec, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x_microbatches)


def schedule_info(n_stages: int, n_microbatches: int) -> Dict[str, Any]:
    """Static properties of the 1F1B schedule — what the tests and the
    dryrun assert: tick count, per-stage stash bound, bubble fraction."""
    ticks = n_microbatches + 2 * (n_stages - 1)
    return {
        "ticks": ticks,
        "stash_slots": min(n_microbatches, 2 * (n_stages - 1) + 1),
        "bubble_fraction": 2 * (n_stages - 1) / ticks,
    }


def _tree_where(pred, a, b):
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def _tree_acc(acc, delta, valid):
    return jax.tree.map(
        lambda a, d: a + jnp.where(valid, d, jnp.zeros_like(d)),
        acc, delta)


def _1f1b_sharded(stage_params, head_params, x_mb, aux_mb, *,
                  stage_fn: Callable, head_loss_fn: Callable,
                  n_stages: int, axis_name: str):
    """Per-shard 1F1B body. stage_params: THIS stage's slice (no stage
    dim). x_mb: [M, mb, ...] pipeline input activations (replicated).
    aux_mb: [M, ...] per-microbatch head targets. Returns (mean loss,
    d stage_params (local), d head_params, d x_mb) — loss/dhead/dx
    replicated via psum, dstage left per-shard.

    Known compute trade of the homogeneous-SPMD formulation: every
    stage executes both the last-stage path (head fwd+bwd) and the
    interior path (stage vjp) each tick, with `where`-selects keeping
    one. `lax.cond` cannot help — its predicate is device-varying here,
    which lowers to a select executing both branches anyway. Removing
    the waste needs per-stage program heterogeneity (one jit per stage
    + explicit send/recv), a different architecture. The schedule's
    wins (bounded stash, in-program collectives, zero host round-trips)
    hold; budget roughly 2x stage FLOPs + one head fwd+bwd per tick."""
    S = n_stages
    s = lax.axis_index(axis_name)
    M = x_mb.shape[0]
    BUF = min(M, 2 * (S - 1) + 1)
    T = M + 2 * (S - 1)
    fwd_perm = [(i, i + 1) for i in range(S - 1)]
    bwd_perm = [(i + 1, i) for i in range(S - 1)]
    is_last = s == S - 1
    is_first = s == 0

    def fwd_and_loss(hp, sp, x, aux):
        y = stage_fn(sp, x)
        return head_loss_fn(hp, y, aux)

    def tick(carry, t):
        (a_state, g_state, x_buf, dstage, dhead, dx_mb,
         loss_acc) = carry
        # ---- forward sub-slot: stage s forwards microbatch t - s.
        fm = t - s
        f_valid = (fm >= 0) & (fm < M)
        fm_c = jnp.clip(fm, 0, M - 1)
        x_inj = lax.dynamic_index_in_dim(x_mb, fm_c, 0, keepdims=False)
        x_in = jnp.where(is_first, x_inj, a_state)
        y = stage_fn(stage_params, x_in)
        slot_f = jnp.mod(fm_c, BUF)
        prev = lax.dynamic_index_in_dim(x_buf, slot_f, 0,
                                        keepdims=False)
        x_buf = lax.dynamic_update_index_in_dim(
            x_buf, jnp.where(f_valid, x_in, prev), slot_f, 0)
        # ---- backward sub-slot: stage s backwards microbatch
        # t - 2(S-1) + s (for the LAST stage that is the microbatch it
        # just forwarded — its loss gradient is born this tick).
        bm = t - 2 * (S - 1) + s
        b_valid = (bm >= 0) & (bm < M)
        bm_c = jnp.clip(bm, 0, M - 1)
        x_saved = lax.dynamic_index_in_dim(
            x_buf, jnp.mod(bm_c, BUF), 0, keepdims=False)
        aux = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, bm_c, 0,
                                               keepdims=False), aux_mb)
        # Last stage: loss + its gradients seed the backward wave.
        (loss_m, (dh, dsp_last, dx_last)) = jax.value_and_grad(
            fwd_and_loss, argnums=(0, 1, 2))(
            head_params, stage_params, x_saved, aux)
        # Interior stages: VJP against the gradient from the right.
        _, vjp = jax.vjp(stage_fn, stage_params, x_saved)
        dsp_mid, dx_mid = vjp(g_state)
        dsp = _tree_where(is_last, dsp_last, dsp_mid)
        dx = jnp.where(is_last, dx_last, dx_mid)
        dstage = _tree_acc(dstage, dsp, b_valid)
        dhead = _tree_acc(dhead, dh, b_valid & is_last)
        loss_acc = loss_acc + jnp.where(b_valid & is_last,
                                        loss_m, 0.0)
        dx_cur = lax.dynamic_index_in_dim(dx_mb, bm_c, 0,
                                          keepdims=False)
        dx_mb = lax.dynamic_update_index_in_dim(
            dx_mb, jnp.where(b_valid & is_first, dx, dx_cur), bm_c, 0)
        # ---- communicate: activations right, gradients left.
        a_state = lax.ppermute(y, axis_name, fwd_perm)
        g_state = lax.ppermute(dx, axis_name, bwd_perm)
        return (a_state, g_state, x_buf, dstage, dhead, dx_mb,
                loss_acc), None

    mb_shape = x_mb.shape[1:]
    zeros_mb = jnp.zeros(mb_shape, x_mb.dtype)
    carry0 = (
        zeros_mb,                                   # a_state
        zeros_mb,                                   # g_state
        jnp.zeros((BUF,) + mb_shape, x_mb.dtype),   # x_buf
        jax.tree.map(jnp.zeros_like, stage_params),  # dstage
        jax.tree.map(jnp.zeros_like, head_params),   # dhead
        jnp.zeros_like(x_mb),                        # dx_mb
        jnp.float32(0.0),                            # loss_acc
    )
    (_, _, _, dstage, dhead, dx_mb, loss_acc), _ = lax.scan(
        tick, carry0, jnp.arange(T))
    # Loss / head grads / input grads live on one stage each — psum
    # replicates them (contributions elsewhere are zero by masking).
    loss = lax.psum(loss_acc, axis_name) / M
    dhead = jax.tree.map(lambda a: lax.psum(a, axis_name) / M, dhead)
    dx_mb = lax.psum(dx_mb, axis_name) / M
    dstage = jax.tree.map(lambda a: a / M, dstage)
    return loss, dstage, dhead, dx_mb


def pipeline_train_1f1b(stage_fn: Callable, head_loss_fn: Callable,
                        stage_params, head_params, x_mb, aux_mb, *,
                        mesh: Optional[Mesh] = None,
                        axis_name: str = "pipe",
                        n_stages: Optional[int] = None
                        ) -> Tuple[Any, Any, Any, Any]:
    """Interleaved 1F1B TRAINING step over the ``axis_name`` mesh axis.

    - ``stage_fn(stage_slice, x) -> y``: one homogeneous pipeline stage
      (e.g. a stack of transformer layers via an inner scan).
    - ``head_loss_fn(head_params, y, aux) -> scalar``: the loss head
      applied to the LAST stage's output (final norm + projection + CE
      for an LM); its gradient seeds the backward wave.
    - ``stage_params``: pytree with a leading stage dimension of size S,
      sharded over ``axis_name``.
    - ``x_mb``: [M, microbatch, ...] pipeline input activations
      (embeddings computed outside), replicated.
    - ``aux_mb``: [M, ...] per-microbatch targets, replicated.

    Returns ``(mean_loss, d_stage_params (stage-stacked, sharded like
    stage_params), d_head_params, d_x_mb)`` — everything needed to
    apply an optimizer update and to continue the backward into the
    (outside) embedding.
    """
    if mesh is not None and n_stages is None:
        n_stages = mesh.shape[axis_name]
    if n_stages is None:
        raise ValueError("pass mesh or n_stages")
    body = functools.partial(
        _1f1b_sharded, stage_fn=stage_fn, head_loss_fn=head_loss_fn,
        n_stages=n_stages, axis_name=axis_name)
    if mesh is None:
        return body(stage_params, head_params, x_mb, aux_mb)
    param_spec = jax.tree.map(lambda _: P(axis_name), stage_params)
    rep = jax.tree.map(lambda _: P(), head_params)
    def _shard_body(sp, hp, x, aux):
        loss, dstage, dhead, dx = body(
            jax.tree.map(lambda a: a[0], sp), hp, x, aux)
        # Re-add the unit stage axis so the out-spec concatenation over
        # `pipe` rebuilds the stage-stacked layout of stage_params.
        return loss, jax.tree.map(lambda a: a[None], dstage), dhead, dx

    fn = jax.shard_map(
        _shard_body,
        mesh=mesh,
        in_specs=(param_spec, rep, P(), P()),
        out_specs=(P(), jax.tree.map(lambda _: P(axis_name),
                                     stage_params), rep, P()),
        check_vma=False,
    )
    loss, dstage, dhead, dx = fn(stage_params, head_params, x_mb,
                                 aux_mb)
    return loss, dstage, dhead, dx


def llama_pp_parts(cfg, params, *, n_stages: int):
    """Split llama parameters into 1F1B pipeline pieces.

    Returns ``(stage_params, head_params, stage_fn, head_loss_fn,
    embed_fn)``: the transformer blocks become ``n_stages`` homogeneous
    stages (each an inner scan over n_layers/n_stages blocks, stacked on
    a leading stage axis for the ``pipe`` sharding); the final norm +
    output projection + next-token CE form the loss head that seeds the
    backward wave; the embedding runs OUTSIDE the pipeline (replicated),
    with its gradient recoverable from the returned d_x_mb.
    """
    from ray_tpu.models import decoder, llama
    from ray_tpu.ops.norms import rms_norm_reference

    L = cfg.n_layers
    if L % n_stages:
        raise ValueError(f"n_layers={L} not divisible by "
                         f"n_stages={n_stages}")
    per = L // n_stages
    stage_params = jax.tree.map(
        lambda a: a.reshape((n_stages, per) + a.shape[1:]),
        params["layers"])
    head_params = {"final_norm": params["final_norm"]}
    if "out" in params:
        head_params["out"] = params["out"]
    else:  # tied embeddings project through embed.T
        head_params["out_t"] = params["embed"]
    mixer, ffn = llama.self_attention(cfg), llama.swiglu()
    rope = decoder.rope_tables(cfg)

    def stage_fn(layers_slice, x):
        return decoder.layers(mixer, ffn, cfg, rope, x, layers_slice)[0]

    def head_loss_fn(hp, y, tokens):
        h = rms_norm_reference(y, hp["final_norm"], cfg.norm_eps)
        w = hp["out"] if "out" in hp else hp["out_t"].T
        logits = jnp.einsum("btd,dv->btv", h.astype(jnp.float32),
                            w.astype(jnp.float32))
        logp = jax.nn.log_softmax(logits[:, :-1])
        tgt = tokens[:, 1:]
        nll = -jnp.take_along_axis(logp, tgt[..., None], -1)[..., 0]
        return nll.mean()

    def embed_fn(embed, tokens):
        return embed[tokens].astype(cfg.dtype)

    return stage_params, head_params, stage_fn, head_loss_fn, embed_fn
