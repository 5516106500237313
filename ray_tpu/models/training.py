"""Sharded train-step factory: TrainState + optimizer + jit wiring.

The reference's training substrate is torch DDP/FSDP wrapped per-process
(`train/torch/train_loop_utils.py:92-101`); the TPU-native equivalent is a
single jit-compiled SPMD program: gradients are averaged by XLA collectives
implied by the batch sharding, optimizer states inherit parameter shardings
(ZeRO-3 falls out of the `embed`→fsdp rule), and the whole step is donated
so params update in place in HBM.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu._private import critical_path
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES,
    logical_to_mesh_axes,
    tree_shardings,
)


class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    opt_state: Any


def make_optimizer(
    learning_rate: float = 3e-4,
    *,
    warmup_steps: int = 100,
    total_steps: Optional[int] = None,
    weight_decay: float = 0.1,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: float = 1.0,
    moment_dtype: Any = None,
) -> optax.GradientTransformation:
    """AdamW with warmup(+cosine when total_steps given) and global-norm
    clipping. `moment_dtype=jnp.bfloat16` halves optimizer HBM — the
    standard single-chip-budget trade."""
    if total_steps is not None:
        schedule = optax.warmup_cosine_decay_schedule(
            0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1))
    elif warmup_steps > 0:
        schedule = optax.linear_schedule(0.0, learning_rate, warmup_steps)
    else:
        schedule = learning_rate
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.scale_by_adam(b1=b1, b2=b2, mu_dtype=moment_dtype),
        optax.add_decayed_weights(
            weight_decay,
            mask=lambda params: jax.tree.map(lambda p: p.ndim > 1, params),
        ),
        optax.scale_by_learning_rate(schedule),
    )


def init_train_state(params, tx: optax.GradientTransformation) -> TrainState:
    """Build a TrainState from already-sharded params; every optimizer
    moment is created in its parameter's sharding, and the counters on
    the same devices. That has to be said: `tx.init` reads only shapes,
    so jit prunes the params from the program, nothing is left to
    follow, and the whole optimizer state would be built on the first
    device — and the train step compiled a second time once its own
    output had replaced it."""
    shardings = optax.tree_map_params(
        tx, lambda _, p: p.sharding, jax.eval_shape(tx.init, params),
        params, transform_non_params=lambda _: None)

    def init(params):
        return jnp.zeros((), jnp.int32), tx.init(params)

    step, opt_state = jax.jit(init, out_shardings=(None, shardings))(params)
    return TrainState(step=step, params=params, opt_state=opt_state)


def make_train_step(
    loss_fn: Callable,
    tx: optax.GradientTransformation,
    *,
    mesh=None,
    rules=DEFAULT_RULES,
    batch_logical: Any = None,
    donate: bool = True,
) -> Callable:
    """Returns jitted `(state, batch) -> (state, metrics)`.

    `loss_fn(params, batch) -> (scalar_loss, metrics_dict)`.
    `batch_logical`: pytree of logical-axis tuples matching `batch` (e.g.
    `{"tokens": ("batch", "seq"), ...}`); defaults to sharding every leaf's
    leading dim over ("data","fsdp"). The step also has
    `.lower(state, batch)`, as a `jax.jit` function does.

    A device trace splits the step by `op_name`: autodiff marks the
    backward pass (`transpose(jvp(...))`), the models scope their loss
    (`loss`), and the update is scoped `optimizer` here. On a mesh the
    host's two halves are `critical_path` spans: `train.place_batch`
    and `train.step_dispatch`. Where the loss's metrics hold a dict
    `span_attrs` of scalars (the expert layer's routing counts, in
    `models/moe.py`), the dispatch span carries as attributes those of
    the last step that has finished.
    """

    def step_fn(state: TrainState, batch):
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        (loss, metrics), grads = grad_fn(state.params, batch)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = optax.apply_updates(state.params, updates)
            metrics = dict(metrics)
            metrics["grad_norm"] = optax.global_norm(grads)
        return TrainState(state.step + 1, params, opt_state), metrics

    if mesh is None:
        return jax.jit(step_fn, donate_argnums=(0,) if donate else ())

    def batch_shardings(batch):
        if batch_logical is not None:
            return tree_shardings(mesh, batch_logical, rules)
        spec = logical_to_mesh_axes(("batch",), rules)
        return jax.tree.map(lambda _: NamedSharding(mesh, spec), batch)

    jitted = jax.jit(step_fn, donate_argnums=(0,) if donate else ())

    def place(batch):
        shardings = batch_shardings(batch)
        return jax.tree.map(
            lambda x, s: x if getattr(x, "sharding", None) == s
            else jax.device_put(x, s),
            batch, shardings)

    pending = {}  # `span_attrs` of the step dispatched last, on the device

    def finished_attrs():
        """The last step's `span_attrs`, if that step has finished:
        nothing here waits for the device."""
        if critical_path.enabled() and pending and all(
                a.is_ready() for a in pending.values()):
            return {k: v.item() for k, v in jax.device_get(pending).items()}
        return {}

    @functools.wraps(step_fn)
    def wrapper(state, batch):
        with critical_path.span("train.place_batch"):
            batch = place(batch)
        with critical_path.span("train.step_dispatch",
                                **finished_attrs()):
            state, metrics = jitted(state, batch)
        pending.clear()
        pending.update(metrics.get("span_attrs", {}))
        return state, metrics

    # Like the mesh-less return value, the step can be lowered without
    # running it (to read the compiled program or its memory analysis).
    wrapper.lower = lambda state, batch: jitted.lower(state, place(batch))
    return wrapper


def make_eval_step(loss_fn: Callable, *, mesh=None,
                   rules=DEFAULT_RULES) -> Callable:
    def eval_fn(params, batch):
        _, metrics = loss_fn(params, batch)
        return metrics

    return jax.jit(eval_fn)


def state_shardings(cfg_logical_axes, mesh, tx, params_abstract,
                    rules=DEFAULT_RULES):
    """Shardings pytree for a full TrainState (params + matching optimizer
    moments + replicated scalars) — used when restoring checkpoints
    directly onto a mesh."""
    param_sh = tree_shardings(mesh, cfg_logical_axes, rules)
    opt_abstract = jax.eval_shape(tx.init, params_abstract)
    replicated = NamedSharding(mesh, P())

    param_leaves = jax.tree.leaves(params_abstract)
    shape_to_sh = {}
    for leaf, sh in zip(param_leaves, jax.tree.leaves(param_sh)):
        shape_to_sh.setdefault(leaf.shape, sh)

    def match(leaf):
        return shape_to_sh.get(leaf.shape, replicated)

    opt_sh = jax.tree.map(match, opt_abstract)
    return TrainState(step=replicated, params=param_sh, opt_state=opt_sh)
