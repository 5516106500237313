"""Per-chip HBM planning + abstract shape-check for large configs.

Reference role: the capacity planning the reference's release configs
encode implicitly (`release/benchmarks/` cluster templates pick machine
shapes per model size). Here it's a first-class tool: given a
LlamaConfig and a mesh shape, account parameter / optimizer / gradient
/ activation bytes per chip against the HBM budget, and prove the
sharded train step TRACES consistently on a virtual mesh of that shape
via ``jax.eval_shape`` — no weights materialized, no compilation, so an
8B/70B plan runs in seconds on a CPU host.

``plan_llama`` is what `__graft_entry__.dryrun_multichip` runs for the
Llama-3-8B-on-v5e-64 north star (BASELINE.md): the measured config on
this 1-chip host is 1.24B, but the 8B layout is shape-checked every
round.
"""

from __future__ import annotations

from typing import Any, Dict

HBM_PER_CHIP = {
    "v5e": 16.0,       # GiB
    "v5p": 95.0,
    "v4": 32.0,
}


def _gib(n_bytes: float) -> float:
    return n_bytes / (1 << 30)


def plan_llama(cfg, mesh_shape: Dict[str, int], *, batch_per_chip: int,
               seq_len: int, chip: str = "v5e",
               moment_dtype_bytes: int = 4,
               remat: Any = True) -> Dict[str, Any]:
    """Analytic per-chip HBM budget for training `cfg` on a mesh of
    `mesh_shape` (e.g. {"data": 1, "fsdp": 16, "tensor": 4} = 64 chips).

    Accounting (bf16 params/grads, fp32-or-bf16 Adam moments):
    - params:   2 bytes, sharded over fsdp*tensor
    - grads:    2 bytes, same sharding (live during the update)
    - adam:     2 moments * moment_dtype_bytes, same sharding
    - activations: with remat=True the scan saves, per layer, the
      residual-stream carry plus the flash out+lse; the backward's
      working set adds one layer's full activations. "mlp"/"gate"
      additionally save the ffn hiddens.
    - loss: fused CE never materializes [B, S, V] logits; the fp32
      hidden row chunk is negligible.
    """
    n_chips = 1
    for v in mesh_shape.values():
        n_chips *= v
    shard = mesh_shape.get("fsdp", 1) * mesh_shape.get("tensor", 1)
    p = cfg.num_params()
    param_b = 2 * p / shard
    grad_b = 2 * p / shard
    opt_b = 2 * moment_dtype_bytes * p / shard

    b, s, d, h = batch_per_chip, seq_len, cfg.dim, cfg.hidden_dim
    heads, hd = cfg.n_heads, cfg.head_dim
    # per-layer SAVED bytes under the remat policy (bf16 = 2 bytes)
    carry = b * s * d * 2
    flash = b * s * heads * hd * 2 + b * heads * s * 4  # out + lse(fp32)
    saved = carry + flash
    if remat == "gate":
        saved += b * s * h * 2
    elif remat == "mlp":
        saved += 2 * b * s * h * 2
    elif not remat:
        # everything live: q,k,v,attn,out,2 norms,3 ffn ~ rough 12x carry
        saved = carry * 6 + flash + 3 * b * s * h * 2
    act_b = saved * cfg.n_layers
    # backward working set: one layer recomputed in full
    work_b = carry * 6 + flash + 3 * b * s * h * 2
    # embedding table (replicated below the gather threshold, else
    # embed-sharded) + fp32 CE chunk
    embed_bytes = cfg.vocab_size * d * 2
    embed_b = embed_bytes if embed_bytes <= (1 << 27) \
        else embed_bytes / mesh_shape.get("tensor", 1)

    total_b = param_b + grad_b + opt_b + act_b + work_b + embed_b
    hbm = HBM_PER_CHIP[chip] * (1 << 30)
    return {
        "config": f"{p/1e9:.2f}B params",
        "mesh": dict(mesh_shape),
        "chips": n_chips,
        "chip": chip,
        "batch_per_chip": b,
        "seq_len": s,
        "per_chip_gib": {
            "params": round(_gib(param_b), 3),
            "grads": round(_gib(grad_b), 3),
            "optimizer": round(_gib(opt_b), 3),
            "activations_saved": round(_gib(act_b), 3),
            "backward_working_set": round(_gib(work_b), 3),
            "embedding": round(_gib(embed_b), 3),
            "total": round(_gib(total_b), 3),
        },
        "hbm_gib": HBM_PER_CHIP[chip],
        "utilization": round(total_b / hbm, 3),
        "fits": total_b < hbm * 0.92,  # leave XLA scratch headroom
        "global_tokens_per_step": b * s * mesh_shape.get("data", 1)
        * mesh_shape.get("fsdp", 1),
    }


def shape_check_llama(cfg, mesh_shape: Dict[str, int],
                      *, batch_per_chip: int, seq_len: int,
                      moment_dtype=None) -> Dict[str, Any]:
    """Abstract-eval the FULL sharded train step for `cfg` on a virtual
    mesh of `mesh_shape` — params, optimizer state, and one step's
    outputs as ShapeDtypeStructs with their NamedShardings resolved.
    Nothing is allocated; tracing catches every shape/sharding
    inconsistency the real run would hit.

    Requires enough (virtual) devices for the mesh — run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama, training
    from ray_tpu.parallel import MeshConfig, create_mesh

    mesh = create_mesh(MeshConfig(**mesh_shape))
    n_chips = int(np.prod(list(mesh.shape.values())))

    def init_fn(rng):
        return llama.init_params(cfg, rng)

    params_abs = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    tx = training.make_optimizer(3e-4, moment_dtype=moment_dtype)
    state_abs = training.TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32), params=params_abs,
        opt_state=jax.eval_shape(tx.init, params_abs))
    shardings = training.state_shardings(
        llama.param_logical_axes(cfg), mesh, tx, params_abs)

    data_shards = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
    global_batch = batch_per_chip * data_shards
    batch_abs = {
        "tokens": jax.ShapeDtypeStruct((global_batch, seq_len),
                                       jnp.int32),
        "targets": jax.ShapeDtypeStruct((global_batch, seq_len),
                                        jnp.int32),
    }

    def step(state, batch):
        def loss(p, b):
            return llama.loss_fn(p, b, cfg, mesh=mesh)

        grads = jax.grad(lambda p: loss(p, batch),
                         has_aux=True)(state.params)[0]
        updates, opt_state = tx.update(grads, state.opt_state,
                                       state.params)
        import optax

        params = optax.apply_updates(state.params, updates)
        return state._replace(params=params, opt_state=opt_state,
                              step=state.step + 1)

    out_abs = jax.eval_shape(step, state_abs, batch_abs)
    n_leaves = len(jax.tree.leaves(out_abs))
    param_count = sum(int(np.prod(l.shape))
                     for l in jax.tree.leaves(params_abs))
    return {
        "chips": n_chips,
        "mesh": dict(mesh.shape),
        "params": param_count,
        "global_batch": global_batch,
        "seq_len": seq_len,
        "state_leaves": n_leaves,
        "sharding_resolved": len(jax.tree.leaves(shardings)) > 0,
        "ok": True,
    }
