"""What every model of ``models/`` builds its layers from: the weights'
initialisation, RMSNorm and the rotary embedding (halves rotated)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def dense_init(key, shape, dtype, fan_in):
    """normal(0, fan_in^-1/2), drawn in float32 and cast to ``dtype``."""
    scale = fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)).astype(x.dtype) * w


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embeddings. x: [B, T, H, D], positions: [B, T]."""
    d_half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, d_half, dtype=jnp.float32) / d_half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,T,Dh]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)
