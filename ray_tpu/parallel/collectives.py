"""In-program collective wrappers.

The reference's `ray.util.collective` (`util/collective/collective.py:258-615`)
offers allreduce/allgather/reducescatter/broadcast/barrier/send/recv between
actors via NCCL/Gloo *at runtime*. The TPU-native equivalents are XLA
collectives *inside compiled programs* — `lax.psum` and friends under
`shard_map`/`pjit` — which XLA schedules onto ICI. These wrappers exist to
give that surface one place (naming parity with the reference, and a couple
of conveniences like axis-group handling), plus host-level helpers for the
rare out-of-program exchange.

An actor-level runtime collective API (process groups over the object plane,
for host-side data) lives in `ray_tpu.util.collective`.
"""

from __future__ import annotations

from typing import Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

AxisName = Union[str, Sequence[str]]


def allreduce(x, axis_name: AxisName, op: str = "sum"):
    """Reference parity: `collective.allreduce` (collective.py:258)."""
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    if op == "mean":
        return lax.pmean(x, axis_name)
    raise ValueError(f"unsupported reduce op: {op}")


def allgather(x, axis_name: AxisName, axis: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reducescatter(x, axis_name: AxisName, axis: int = 0):
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def broadcast(x, axis_name: AxisName, root: int = 0):
    """Every shard gets root's value. XLA has no bcast primitive; select the
    root's contribution then sum (dead data is DCE'd into an efficient
    collective)."""
    idx = lax.axis_index(axis_name)
    contribution = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(contribution, axis_name)


def all_to_all(x, axis_name: AxisName, split_axis: int, concat_axis: int):
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def permute(x, axis_name: AxisName, shift: int = 1):
    """Ring shift by `shift` positions (the ring-attention building block)."""
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def send_recv(x, axis_name: AxisName, pairs: Sequence[tuple]):
    """Point-to-point as a sparse permute: `pairs` is [(src, dst), ...]."""
    return lax.ppermute(x, axis_name, list(pairs))


def axis_index(axis_name: AxisName):
    return lax.axis_index(axis_name)


def axis_size(axis_name: AxisName):
    """Static size of a named mesh axis."""
    return lax.axis_size(axis_name)


def barrier(axis_name: AxisName):
    """Synchronization point; inside XLA programs ordering is handled by the
    compiler, so this is only meaningful as an optimization barrier."""
    token = lax.psum(jnp.zeros((), jnp.float32), axis_name)
    return token


# ---------------------------------------------------------------------------
# Host-level (out-of-program) helpers
# ---------------------------------------------------------------------------


def host_broadcast(tree, mesh, logical_axes=None):
    """Replicate a host pytree onto every device of a mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P())
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)


def host_allgather(x):
    """Gather a fully-addressable sharded array back to the host."""
    return jax.device_get(x)
