"""Positional embeddings: rotary (RoPE) and sinusoidal."""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["rope_freqs", "apply_rope", "apply_rope_interleaved",
           "sinusoidal_embedding"]


def rope_freqs(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 10000.0) -> jnp.ndarray:
    """x: (..., S, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_rope_interleaved(x: jnp.ndarray, positions: jnp.ndarray,
                           theta: float = 10000.0) -> jnp.ndarray:
    """RoPE with DeepSeek's pairing: element ``2i`` rotates with ``2i + 1``
    at frequency ``i``. The result comes out in half-split order (the
    rotated even elements, then the odd ones), as DeepSeek-V3's reference
    code leaves it; q and k share the order, so their products do not
    change."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rope(x, positions, theta)


def sinusoidal_embedding(positions: jnp.ndarray, dim: int,
                         max_period: float = 10000.0) -> jnp.ndarray:
    """positions: (...,) -> (..., dim) sinusoidal embedding."""
    half = dim // 2
    freqs = jnp.exp(-jnp.log(max_period) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
