"""Public wrapper of the absorbed latent-attention decode kernel: Pallas
on TPU (or under ``force_impl("pallas")``), the jnp oracle elsewhere."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.mla_decode.mla_decode import \
    mla_decode_attention as mla_decode_attention_pallas
from repro.kernels.mla_decode.ref import mla_decode_attention_ref
from repro.kernels.quant_matmul.ops import _resolve_impl

__all__ = ["mla_decode_attention"]


def mla_decode_attention(q, ckv, kpe, starts, ends, *, scale: float,
                         compute_dtype=jnp.float32):
    """See :func:`repro.kernels.mla_decode.mla_decode.mla_decode_attention`."""
    if _resolve_impl(None) == "pallas":
        return mla_decode_attention_pallas(
            q, ckv, kpe, starts, ends, scale=scale,
            compute_dtype=jnp.dtype(compute_dtype))
    return mla_decode_attention_ref(q, ckv, kpe, starts, ends, scale=scale,
                                    compute_dtype=compute_dtype)
