"""Logical-axis sharding rules → concrete `NamedSharding`s.

The reference has no analog (its FSDP support is a passthrough wrapper,
`python/ray/train/torch/train_loop_utils.py:101`); this is the GSPMD-native
replacement: model code names its array dimensions with *logical* axes
("batch", "embed", "heads", ...) and a rules table maps those to mesh axes.
Swapping parallelism strategy = swapping the rules table, with no model
changes — the property that makes TP/FSDP/SP composable.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Each rule: logical axis name -> mesh axis (str), tuple of mesh axes, or None
LogicalRules = Sequence[Tuple[str, Union[str, Tuple[str, ...], None]]]

# The canonical table for transformer LMs. Matches the axis convention in
# parallel.mesh: params shard over (fsdp, tensor); activations over
# (data+fsdp for batch, seq for sequence, tensor for heads/mlp).
DEFAULT_RULES: LogicalRules = (
    ("batch", ("data", "fsdp")),
    ("seq", "seq"),          # activation sequence dim (context parallel)
    ("embed", "fsdp"),       # param embed dim (ZeRO-3 shard)
    ("act_embed", None),     # activation embed dim: replicated — batch
                             # already consumes data+fsdp; tensor-sharding
                             # activations here would force a transpose
                             # before every matmul
    ("mlp", "tensor"),       # param/activation mlp hidden dim
    ("heads", "tensor"),     # attention heads
    ("kv_heads", "tensor"),
    ("head_dim", None),
    ("vocab", "tensor"),
    ("expert", "expert"),
    ("expert_mlp", ("fsdp", "tensor")),  # an expert matrix's hidden
                             # width, the one axis of it that is split:
                             # over `fsdp` a chip owns its slice of every
                             # expert and is sent the tokens
                             # (`models/moe.py`), so no expert matrix is
                             # gathered and no gradient of one scattered
    ("stage", "pipe"),
    ("norm", None),
)


def logical_to_mesh_axes(logical_axes: Sequence[Optional[str]],
                         rules: LogicalRules = DEFAULT_RULES) -> P:
    """Map a tuple of logical axis names (None = replicated) to a
    PartitionSpec under the given rules."""
    table = dict(rules)
    out = []
    for ax in logical_axes:
        if ax is None:
            out.append(None)
        else:
            out.append(table.get(ax))
    # Trailing Nones are dropped for a tidier spec.
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def named_sharding(mesh: Mesh, *logical_axes: Optional[str],
                   rules: LogicalRules = DEFAULT_RULES) -> NamedSharding:
    spec = logical_to_mesh_axes(logical_axes, rules)
    return NamedSharding(mesh, spec)


def tree_shardings(mesh: Mesh, logical_tree,
                   rules: LogicalRules = DEFAULT_RULES):
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings."""
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, logical_to_mesh_axes(axes, rules)),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x
        ),
    )


def shard_pytree(tree, mesh: Mesh, logical_tree,
                 rules: LogicalRules = DEFAULT_RULES):
    """Place a pytree of host arrays onto the mesh with the given logical
    axis annotations (pytree of tuples, same structure)."""
    shardings = tree_shardings(mesh, logical_tree, rules)
    return jax.tree.map(lambda x, s: jax.device_put(x, s), tree, shardings)


def with_logical_constraint(x, *logical_axes: Optional[str],
                            mesh: Optional[Mesh] = None,
                            rules: LogicalRules = DEFAULT_RULES):
    """`lax.with_sharding_constraint` in logical-axis vocabulary.

    Inside jit the mesh comes from the surrounding context when omitted
    (requires the mesh's axis names to be bound, e.g. via
    `jax.sharding.use_mesh` or in/out shardings on the jit).
    """
    spec = logical_to_mesh_axes(logical_axes, rules)
    if mesh is not None:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (RuntimeError, ValueError):
        # No mesh in scope (single-device path), or one without the
        # rules' axes (the body of a pipeline stage's shard_map): the
        # constraint is moot.
        return x
