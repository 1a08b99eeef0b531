"""Public wrapper: attention output + DyMoE Eq. (1) token importance."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.attn_scores.attn_scores import (
    flash_fwd_pallas,
    key_mass_pallas,
)
from repro.kernels.attn_scores.ref import attention_with_scores_ref

__all__ = ["flash_attention_with_scores"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention_with_scores(q: jnp.ndarray, k: jnp.ndarray,
                                v: jnp.ndarray, *, causal: bool = True,
                                impl: Optional[str] = None,
                                interpret: bool = False,
                                block_q: int = 128, block_k: int = 128):
    """Single-sequence attention with heavy-hitter scores.

    Args:
      q, k, v: (H, S, D) head-major. (GQA callers repeat KV heads first.)
    Returns:
      out: (H, S, D) float32 attention output.
      token_importance: (S,) float32 — per-key attention mass averaged over
        heads; DyMoE Eq. (1).
    """
    if impl is None:
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        out, mass = attention_with_scores_ref(q, k, v, causal=causal)
    elif impl == "pallas":
        out, lse = flash_fwd_pallas(q, k, v, causal=causal, block_q=block_q,
                                    block_k=block_k, interpret=interpret)
        mass = key_mass_pallas(q, k, lse, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret)
    else:
        raise ValueError(f"unknown impl {impl!r}")
    return out, mass.mean(axis=0)
