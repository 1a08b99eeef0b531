"""Grouped-query attention with RoPE / qk-norm / QKV-bias / sliding window.

Entry points:
  * ``attention_train``  — full-sequence causal attention (training and
    prefill), q-chunked so the S×S probability matrix is never materialized
    (memory O(chunk × S)); optionally returns the per-token received-
    attention mass used by DyMoE's prefill importance estimator (Eq. 1).
  * ``attention_decode`` — one-token step against a :class:`KVCache`
    (full or ring-buffer/sliding-window).

GQA is computed in grouped layout (B, H_kv, G, S, D) so KV heads are never
replicated in memory. Shapes are batch-major: x (B, S, d_model).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.kv_cache import KVCache, update_kv_cache
from repro.models.layers.norms import init_rmsnorm, rmsnorm
from repro.models.layers.rotary import apply_rope

__all__ = ["init_attention", "attention_train", "attention_decode",
           "causal_attention"]

_NEG_INF = -1e30


def init_attention(cfg: ModelConfig, key, dtype) -> dict:
    h, hk, d, dm = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    ks = jax.random.split(key, 4)
    scale = dm ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (dm, h * d)) * scale).astype(dtype),
        "wk": (jax.random.normal(ks[1], (dm, hk * d)) * scale).astype(dtype),
        "wv": (jax.random.normal(ks[2], (dm, hk * d)) * scale).astype(dtype),
        "wo": (jax.random.normal(ks[3], (h * d, dm)) * (h * d) ** -0.5
               ).astype(dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * d,), dtype)
        p["bk"] = jnp.zeros((hk * d,), dtype)
        p["bv"] = jnp.zeros((hk * d,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(d, dtype)
        p["k_norm"] = init_rmsnorm(d, dtype)
    return p


def _project_qkv(p, cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray):
    """x: (B, S, dm) -> q (B,Hkv,G,S,D), k/v (B,Hkv,S,D), RoPE applied."""
    b, s, _ = x.shape
    h, hk, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = cfg.kv_groups
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, d).transpose(0, 2, 1, 3)       # (B, H, S, D)
    k = k.reshape(b, s, hk, d).transpose(0, 2, 1, 3)      # (B, Hkv, S, D)
    v = v.reshape(b, s, hk, d).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, positions[:, None, :], cfg.rope_theta)
        k = apply_rope(k, positions[:, None, :], cfg.rope_theta)
    q = q.reshape(b, hk, g, s, d)
    return q, k, v


def _pick_chunk(s: int, target: int = 1024) -> int:
    """Largest divisor of s that is <= target."""
    c = min(s, target)
    while s % c:
        c -= 1
    return c


def causal_attention(cfg: ModelConfig, q: jnp.ndarray, k: jnp.ndarray,
                     v: jnp.ndarray, scale: float, *,
                     kv_valid: Optional[jnp.ndarray] = None,
                     want_mass: bool = False, chunk: int = 1024
                     ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """The q-chunked causal core of :func:`attention_train`, for any q·k
    width and v width: q (B, Hkv, G, S, Dqk), k (B, Hkv, S, Dqk), v
    (B, Hkv, S, Dv). Returns (out (B, Hkv, G, S, Dv) float32, received
    attention mass (B, Hkv, S) — over queries, averaged over heads — or
    None)."""
    b, hk, g, s, _ = q.shape
    cq = _pick_chunk(s, chunk)
    nc = s // cq
    cdt = jnp.dtype(cfg.attn_compute_dtype)
    kf = k.astype(cdt)
    vf = v.astype(cdt)

    mass = (jnp.zeros((b, hk, s), jnp.float32) if want_mass else None)
    outs = []
    for ci in range(nc):
        qc = q[:, :, :, ci * cq:(ci + 1) * cq].astype(cdt)
        lo, hi = 0, s
        if cfg.attn_causal_skip:
            hi = (ci + 1) * cq  # keys beyond the causal frontier: skipped
            if cfg.sliding_window:
                lo = max(0, ci * cq - cfg.sliding_window + 1)
        logits = jnp.einsum("bkgqd,bkpd->bkgqp", qc, kf[:, :, lo:hi]
                            ).astype(jnp.float32) * scale
        qi = ci * cq + jnp.arange(cq, dtype=jnp.int32)
        kj = jnp.arange(lo, hi, dtype=jnp.int32)
        m = qi[:, None] >= kj[None, :]
        if cfg.sliding_window:
            m = m & (qi[:, None] - kj[None, :] < cfg.sliding_window)
        m = m[None, None, None]                       # (1, 1, 1, cq, hi-lo)
        if kv_valid is not None:
            m = m & kv_valid[:, None, None, None, lo:hi]
        logits = jnp.where(m, logits, _NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        oc = jnp.einsum("bkgqp,bkpd->bkgqd", probs.astype(cdt),
                        vf[:, :, lo:hi])
        outs.append(oc.astype(jnp.float32))
        if mass is not None:
            pm = probs.sum(axis=(2, 3)) / (hk * g)      # (B, Hkv, hi-lo)
            mass = mass.at[:, :, lo:hi].add(pm)
    return jnp.concatenate(outs, axis=3), mass


def attention_train(p, cfg: ModelConfig, x: jnp.ndarray, *,
                    positions: Optional[jnp.ndarray] = None,
                    kv_valid: Optional[jnp.ndarray] = None,
                    want_token_importance: bool = False,
                    chunk: int = 1024
                    ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray],
                               Tuple[jnp.ndarray, jnp.ndarray]]:
    """Full causal self-attention, q-chunked (Python loop: the per-chunk
    einsums appear explicitly in the HLO, so compiled cost analysis counts
    them — a lax.scan here would be counted once; see EXPERIMENTS.md §Perf).

    With ``cfg.attn_causal_skip`` each query chunk attends only to its
    causal key prefix (and, with a sliding window, only to the window's key
    range), cutting attention FLOPs ~2× (triangle vs square) without
    changing results.

    ``kv_valid`` (B, S) masks keys out per row — False marks padding (a
    right-aligned ragged batch pads rows on the left), so no query ever
    attends to a pad and pads accumulate no received-attention mass.

    Returns (out (B,S,dm), token_importance (B,S) or None, (k, v) for
    prefill cache fill).
    """
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                     (b, s))
    q, k, v = _project_qkv(p, cfg, x, positions)
    d = q.shape[4]
    out, mass = causal_attention(cfg, q, k, v, d ** -0.5, kv_valid=kv_valid,
                                 want_mass=want_token_importance,
                                 chunk=chunk)
    out = out.reshape(b, -1, s, d)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1).astype(x.dtype)
    out = out @ p["wo"]

    token_importance = mass.sum(axis=1) if want_token_importance else None
    return out, token_importance, (k, v)


def attention_decode(p, cfg: ModelConfig, x: jnp.ndarray, cache: KVCache,
                     live: Optional[jnp.ndarray] = None
                     ) -> Tuple[jnp.ndarray, KVCache]:
    """One-token decode. x: (B, 1, dm). ``live`` (B,) freezes finished
    rows' cache writes (see :func:`update_kv_cache`); their attention
    output is computed against the unchanged window and discarded by the
    caller."""
    b = x.shape[0]
    positions = cache.length[:, None]  # (B, 1) absolute position of new token
    q, k_new, v_new = _project_qkv(p, cfg, x, positions)
    cache = update_kv_cache(cache, k_new, v_new, live=live)

    cdt = jnp.dtype(cfg.attn_compute_dtype)
    scale = cfg.head_dim ** -0.5
    logits = jnp.einsum("bkgqd,bkpd->bkgqp", q.astype(cdt),
                        cache.k.astype(cdt)).astype(jnp.float32) * scale
    # Valid slots: filled (pos >= 0) and causal (pos <= current position).
    cur = cache.length[:, None] - 1  # position just written
    valid = (cache.positions >= 0) & (cache.positions <= cur)
    if cfg.sliding_window:
        valid &= cache.positions > (cur - cfg.sliding_window)
    logits = jnp.where(valid[:, None, None, None, :], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqp,bkpd->bkgqd", probs.astype(cdt),
                     cache.v.astype(cdt)).astype(jnp.float32)
    out = out.reshape(b, cfg.num_heads, 1, cfg.head_dim)
    out = out.transpose(0, 2, 1, 3).reshape(b, 1, -1).astype(x.dtype)
    return out @ p["wo"], cache
