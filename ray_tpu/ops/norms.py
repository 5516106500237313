"""Normalization layers (RMSNorm, LayerNorm) in plain JAX, float32
statistics: XLA fuses the norm arithmetic into its neighbours.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm_reference(x, weight, eps: float = 1e-6):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x, weight, bias=None, eps: float = 1e-6):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mean) * jax.lax.rsqrt(var + eps)
    out = out * weight.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(dtype)
