"""Decode-time state: full KV cache, ring-buffer (sliding-window) KV cache,
and SSM recurrent state. All pytrees with static shapes.

The ring cache is what makes ``long_500k`` sub-quadratic for attention
architectures: a window of W slots is overwritten cyclically; each slot
remembers the absolute position it holds so masking stays exact.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["KVCache", "SSMCache", "init_kv_cache", "init_latent_cache",
           "update_kv_cache"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """k/v: (B, H_kv, S_slots, D). positions: (B, S_slots) absolute position
    held by each slot (-1 = empty). length: (B,) tokens seen so far.
    offset: (B,) pad slots consumed before the row's content — 0 for the
    usual left-aligned layout; a right-aligned ragged batch (row i padded
    on the LEFT with S_max - s_i pads) sets offset = S_max - s_i so a new
    token at logical position ``length`` lands in slot ``length + offset``
    while attention masks keep reasoning in logical positions.
    ring: static flag — True means S_slots is a sliding window.

    A latent cache (multi-head latent attention) is the same structure
    with one "head": k holds each token's normed latent c_kv (B, 1, S, R)
    and v its rope key (B, 1, S, Dr), so every slot operation (writes,
    freezes, the scheduler's row injection) applies unchanged.
    """

    k: jnp.ndarray
    v: jnp.ndarray
    positions: jnp.ndarray
    length: jnp.ndarray
    offset: jnp.ndarray
    ring: bool = dataclasses.field(metadata=dict(static=True), default=False)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SSMCache:
    """conv_state: (B, d_inner(+extra), conv_width-1); ssm_state: mamba1
    (B, d_inner, N) or mamba2 (B, heads, head_dim, N); length: (B,)."""

    conv_state: jnp.ndarray
    ssm_state: jnp.ndarray
    length: jnp.ndarray


def init_kv_cache(batch: int, num_kv_heads: int, slots: int, head_dim: int,
                  dtype=jnp.bfloat16, ring: bool = False) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, num_kv_heads, slots, head_dim), dtype),
        v=jnp.zeros((batch, num_kv_heads, slots, head_dim), dtype),
        positions=jnp.full((batch, slots), -1, jnp.int32),
        length=jnp.zeros((batch,), jnp.int32),
        offset=jnp.zeros((batch,), jnp.int32),
        ring=ring,
    )


def init_latent_cache(batch: int, slots: int, rank: int, rope_dim: int,
                      dtype=jnp.bfloat16) -> KVCache:
    """A latent cache (see :class:`KVCache`): rank + rope_dim values per
    token and layer."""
    return KVCache(
        k=jnp.zeros((batch, 1, slots, rank), dtype),
        v=jnp.zeros((batch, 1, slots, rope_dim), dtype),
        positions=jnp.full((batch, slots), -1, jnp.int32),
        length=jnp.zeros((batch,), jnp.int32),
        offset=jnp.zeros((batch,), jnp.int32),
    )


def update_kv_cache(cache: KVCache, k_new: jnp.ndarray, v_new: jnp.ndarray,
                    live: Optional[jnp.ndarray] = None) -> KVCache:
    """Insert one decode step. k_new/v_new: (B, H_kv, 1, D).

    ``live`` (B,) bool: False rows are FROZEN — their frontier slot keeps
    its old contents and their position marker / length don't advance, so
    finished or evicted slots in a continuous batch stop growing their
    window. The masking happens at the write site (one (B, H, D) select
    against the gathered old slot values), never as a whole-cache
    ``where``."""
    b, _, slots, _ = cache.k.shape
    pos = cache.length  # (B,) logical position of the incoming token
    frontier = pos + cache.offset  # (B,) slot index it occupies
    slot = frontier % slots if cache.ring \
        else jnp.minimum(frontier, slots - 1)
    bidx = jnp.arange(b)
    kw = k_new[:, :, 0].astype(cache.k.dtype)
    vw = v_new[:, :, 0].astype(cache.v.dtype)
    pw = pos
    length = cache.length + 1
    if live is not None:
        lv = jnp.asarray(live).astype(bool)
        kw = jnp.where(lv[:, None, None], kw, cache.k[bidx, :, slot])
        vw = jnp.where(lv[:, None, None], vw, cache.v[bidx, :, slot])
        pw = jnp.where(lv, pos, cache.positions[bidx, slot])
        length = jnp.where(lv, length, cache.length)
    k = cache.k.at[bidx, :, slot].set(kw)
    v = cache.v.at[bidx, :, slot].set(vw)
    positions = cache.positions.at[bidx, slot].set(pw)
    return KVCache(k=k, v=v, positions=positions, length=length,
                   offset=cache.offset, ring=cache.ring)


def fill_kv_cache(cache: KVCache, k_seq: jnp.ndarray, v_seq: jnp.ndarray,
                  lengths: Optional[jnp.ndarray] = None,
                  offsets: Optional[jnp.ndarray] = None) -> KVCache:
    """Bulk insert a prefill sequence starting at absolute position 0.
    k_seq/v_seq: (B, H_kv, S, D). For ring caches with S > slots only the
    trailing ``slots`` keys are kept (the sliding window semantics); slot
    layout matches ``update_kv_cache``'s ``pos % slots`` rule so decode can
    continue seamlessly.

    ``lengths`` (B,): per-row true token counts for ragged batches.
    ``offsets`` (B,): pad slots BEFORE each row's content (right-aligned
    layout: row i's tokens occupy slots [offset_i, offset_i + length_i));
    slots outside that window are marked empty (-1) so attention never
    reads a pad, and the offset is carried so decode writes land on the
    per-row frontier."""
    b, h, s, d = k_seq.shape
    slots = cache.k.shape[2]
    if s > slots:
        assert cache.ring, (s, slots)
        assert offsets is None, "ragged offsets unsupported for ring caches"
        keep = slots
        abs_pos = jnp.arange(s - keep, s, dtype=jnp.int32)       # kept keys
        slot_of = abs_pos % slots
        k = cache.k.at[:, :, slot_of].set(
            k_seq[:, :, -keep:].astype(cache.k.dtype))
        v = cache.v.at[:, :, slot_of].set(
            v_seq[:, :, -keep:].astype(cache.v.dtype))
        positions = jnp.zeros_like(cache.positions).at[:, slot_of].set(
            abs_pos[None, :])
        length = jnp.full((b,), s, jnp.int32)
        return KVCache(k=k, v=v, positions=positions, length=length,
                       offset=jnp.zeros((b,), jnp.int32), ring=True)
    k = cache.k.at[:, :, :s].set(k_seq.astype(cache.k.dtype))
    v = cache.v.at[:, :, :s].set(v_seq.astype(cache.v.dtype))
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    if offsets is None:
        offsets = jnp.zeros((b,), jnp.int32)
    slot = jnp.arange(slots, dtype=jnp.int32)[None, :]
    pos = slot - offsets[:, None]        # logical position held by a slot
    filled = (pos >= 0) & (pos < lengths[:, None])
    positions = jnp.where(filled, pos, -1)
    return KVCache(k=k, v=v, positions=positions, length=lengths,
                   offset=offsets, ring=cache.ring)
