"""Multi-head latent attention (DeepSeek-V2/V3), no q LoRA.

Per token, ``x W_kv_a`` gives a latent ``c_kv`` (``kv_lora_rank`` wide,
normed by ``kv_norm``) and one rope key ``k_pe`` shared by every head;
``c_kv W_kv_b`` decompresses each head's ``k_nope`` and ``v``. A head's
q·k spans ``qk_nope_head_dim + qk_rope_head_dim`` values and its v
``v_head_dim``; the scale is ``1/sqrt(qk_nope + qk_rope)``. RoPE pairs
elements as DeepSeek does (:func:`apply_rope_interleaved`).

  * ``mla_train`` (training, prefill): decompressed per-head attention
    through the shared q-chunked causal core, which also yields DyMoE's
    received-attention mass (Eq. 1) over the heads.
  * ``mla_decode`` (one step): absorbed attention against the latent
    cache (:func:`repro.models.kv_cache.init_latent_cache`): the key
    up-projection folds into the query, the
    :func:`repro.kernels.mla_decode.mla_decode_attention` kernel scores and
    sums the cache in the latent space, and the value up-projection folds
    into the output.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.kv_cache import KVCache, update_kv_cache
from repro.models.layers.attention import causal_attention
from repro.models.layers.norms import init_rmsnorm, rmsnorm
from repro.models.layers.rotary import apply_rope_interleaved

__all__ = ["init_mla", "mla_train", "mla_decode"]


def init_mla(cfg: ModelConfig, key, dtype) -> dict:
    h, dm, r = cfg.num_heads, cfg.d_model, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": (jax.random.normal(ks[0], (dm, h * (dn + dr))) * dm ** -0.5
               ).astype(dtype),
        "wkv_a": (jax.random.normal(ks[1], (dm, r + dr)) * dm ** -0.5
                  ).astype(dtype),
        "kv_norm": init_rmsnorm(r, dtype),
        "wkv_b": (jax.random.normal(ks[2], (r, h * (dn + dv))) * r ** -0.5
                  ).astype(dtype),
        "wo": (jax.random.normal(ks[3], (h * dv, dm)) * (h * dv) ** -0.5
               ).astype(dtype),
    }


def _project(p, cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray):
    """x (B, S, dm) -> q_nope (B, H, S, Dn), q_pe (B, H, S, Dr) roped,
    c_kv (B, S, R) normed, k_pe (B, S, Dr) roped."""
    b, s, _ = x.shape
    h, r = cfg.num_heads, cfg.kv_lora_rank
    dn = cfg.qk_nope_head_dim
    q = (x @ p["wq"]).reshape(b, s, h, -1).transpose(0, 2, 1, 3)
    q_pe = apply_rope_interleaved(q[..., dn:], positions[:, None, :],
                                  cfg.rope_theta)
    kv = x @ p["wkv_a"]
    c_kv = rmsnorm(p["kv_norm"], kv[..., :r], cfg.norm_eps)
    k_pe = apply_rope_interleaved(kv[..., r:], positions, cfg.rope_theta)
    return q[..., :dn], q_pe, c_kv, k_pe


def mla_train(p, cfg: ModelConfig, x: jnp.ndarray, *,
              positions: Optional[jnp.ndarray] = None,
              kv_valid: Optional[jnp.ndarray] = None,
              want_token_importance: bool = False, chunk: int = 1024
              ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray],
                         Tuple[jnp.ndarray, jnp.ndarray]]:
    """Causal latent attention, decompressed (see
    :func:`repro.models.layers.attention.attention_train` for ``positions``,
    ``kv_valid`` and the mass). Returns (out (B, S, dm), token importance
    (B, S) or None, (c_kv (B, 1, S, R), k_pe (B, 1, S, Dr)) for the latent
    cache)."""
    b, s, _ = x.shape
    h, dv = cfg.num_heads, cfg.v_head_dim
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                     (b, s))
    with jax.named_scope("mla"):
        q_nope, q_pe, c_kv, k_pe = _project(p, cfg, x, positions)
        kv = (c_kv @ p["wkv_b"]).reshape(b, s, h, -1).transpose(0, 2, 1, 3)
        dn = cfg.qk_nope_head_dim
        q = jnp.concatenate([q_nope, q_pe], axis=-1)      # (B, H, S, Dqk)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            k_pe[:, None], (b, h, s, k_pe.shape[-1]))], axis=-1)
        out, mass = causal_attention(cfg, q[:, :, None], k, kv[..., dn:],
                                     cfg.qk_head_dim ** -0.5,
                                     kv_valid=kv_valid,
                                     want_mass=want_token_importance,
                                     chunk=chunk)
        out = out[:, :, 0].transpose(0, 2, 1, 3).reshape(b, s, h * dv)
        out = out.astype(x.dtype) @ p["wo"]
    tok_imp = mass.sum(axis=1) if want_token_importance else None
    return out, tok_imp, (c_kv[:, None], k_pe[:, None])


def mla_decode(p, cfg: ModelConfig, x: jnp.ndarray, cache: KVCache,
               live: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, KVCache]:
    """One-token absorbed decode against a latent cache. x (B, 1, dm);
    ``live`` as in :func:`repro.models.layers.attention.attention_decode`."""
    from repro.kernels.mla_decode import mla_decode_attention

    b = x.shape[0]
    h, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    with jax.named_scope("mla"):
        q_nope, q_pe, c_kv, k_pe = _project(p, cfg, x, cache.length[:, None])
        cache = update_kv_cache(cache, c_kv[:, None], k_pe[:, None],
                                live=live)
        cdt = jnp.dtype(cfg.attn_compute_dtype)
        w_kvb = p["wkv_b"].reshape(r, h, dn + dv).astype(cdt)
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, :, 0].astype(cdt),
                           w_kvb[..., :dn])
        q_all = jnp.concatenate([q_lat, q_pe[:, :, 0].astype(cdt)], axis=-1)
        start = cache.offset
        o_lat = mla_decode_attention(
            q_all, cache.k[:, 0], cache.v[:, 0], start,
            jnp.minimum(start + cache.length, cache.k.shape[2]),
            scale=cfg.qk_head_dim ** -0.5, compute_dtype=cdt)  # (B, H, R)
        out = jnp.einsum("bhr,rhv->bhv", o_lat.astype(cdt), w_kvb[..., dn:])
        out = out.reshape(b, 1, h * dv).astype(x.dtype) @ p["wo"]
    return out, cache
