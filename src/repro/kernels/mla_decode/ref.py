"""jnp oracle of :func:`repro.kernels.mla_decode.mla_decode.mla_decode_attention`
(the path the CPU runs)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["mla_decode_attention_ref"]


def mla_decode_attention_ref(q, ckv, kpe, starts, ends, *, scale: float,
                             compute_dtype=jnp.float32):
    """Same contract as the kernel: (rows, H, R) float32."""
    r = ckv.shape[-1]
    cdt = jnp.dtype(compute_dtype)
    q = q.astype(cdt)
    c = ckv.astype(cdt)
    s = (jnp.einsum("bhr,btr->bht", q[..., :r], c,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhd,btd->bht", q[..., r:], kpe.astype(cdt),
                      preferred_element_type=jnp.float32)) * scale
    t = jnp.arange(ckv.shape[1], dtype=jnp.int32)[None, :]
    live = (t >= jnp.asarray(starts)[:, None]) & (t < jnp.asarray(ends)[:, None])
    s = jnp.where(live[:, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
    l = p.sum(axis=-1, keepdims=True)
    o = jnp.einsum("bht,btr->bhr", p.astype(cdt), c,
                   preferred_element_type=jnp.float32)
    return o / jnp.where(l == 0.0, 1.0, l)
