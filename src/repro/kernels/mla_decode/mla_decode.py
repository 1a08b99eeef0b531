"""Absorbed multi-head latent attention for one decode step (Pallas, TPU).

DeepSeek-V2/V3's latent attention caches, per token, one ``R``-wide latent
``c_kv`` (already normed) and one rope key ``k_pe`` of width ``Dr`` shared
by every head. At decode the key up-projection is absorbed into the
query (``q_lat = q_nope · W_UK``), so every head scores the cache in the
latent space directly:

    s[h, t] = (q_lat[h] · c_kv[t] + q_pe[h] · k_pe[t]) * scale
    o[h]    = softmax_t(s[h]) · c_kv

and the caller takes ``o`` through ``W_UV`` and the output projection.

Grid ``(rows, S / bs)``, the cache axis innermost, with an online softmax
over its blocks in VMEM. Each row's live slots ``[start, end)`` reach the
kernel through scalar prefetch: blocks outside them run no body, and their
index maps repeat the nearest live block, so they fetch nothing either — a
row costs the bytes of its own context, not of the slot window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["mla_decode_attention", "pick_block"]

_NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))     # contract both operands' last dims


def pick_block(s: int, target: int) -> int:
    """Largest cache block <= ``target`` that divides ``s``: a multiple of
    128 (whole lanes of the (H, block) scores) if one does, else of 16 (a
    whole bf16 sublane tile), else ``s`` itself."""
    for step in (128, 16):
        top = min(target, s)
        for b in range(top - top % step, 0, -step):
            if s % b == 0:
                return b
    return s


def _kernel(st_ref, en_ref, q_ref, c_ref, p_ref, o_ref, m_scr, l_scr,
            acc_scr, *, scale, bs, r, ns, cdt):
    i, j = pl.program_id(0), pl.program_id(1)
    st, en = st_ref[i], en_ref[i]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when((j * bs < en) & ((j + 1) * bs > st))
    def _body():
        q = q_ref[0].astype(cdt)                  # (H, R + Dr)
        c = c_ref[0].astype(cdt)                  # (bs, R)
        kp = p_ref[0].astype(cdt)                 # (bs, Dr)
        s = (jax.lax.dot_general(q[:, :r], c, _NT,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(q[:, r:], kp, _NT,
                                   preferred_element_type=jnp.float32)
             ) * scale                            # (H, bs)
        t = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((t >= st) & (t < en), s, _NEG_INF)
        m_prev = m_scr[...]                       # (H, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p.astype(cdt), c, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == ns - 1)
    def _done():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "block_s", "compute_dtype", "interpret"))
def mla_decode_attention(q, ckv, kpe, starts, ends, *, scale: float,
                         block_s: int = 512, compute_dtype=jnp.float32,
                         interpret: bool = False):
    """q (rows, H, R + Dr): latent query then rope query per head;
    ckv (rows, S, R), kpe (rows, S, Dr): the latent cache; starts/ends
    (rows,) int32: each row's live slots. Returns (rows, H, R) float32,
    zero for a row with no live slot. Operands enter the MXU in
    ``compute_dtype``; scores and sums stay float32."""
    rows, h, dq = q.shape
    _, s, r = ckv.shape
    dr = kpe.shape[-1]
    assert dq == r + dr and kpe.shape[:2] == (rows, s), (q.shape, kpe.shape)
    bs = pick_block(s, block_s)
    ns = s // bs

    def kv_map(i, j, st, en):
        first = st[i] // bs
        last = jnp.maximum(en[i] - 1, st[i]) // bs
        return (i, jnp.clip(j, first, last), 0)

    kern = functools.partial(_kernel, scale=scale, bs=bs, r=r, ns=ns,
                             cdt=jnp.dtype(compute_dtype))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(rows, ns),
        in_specs=[
            pl.BlockSpec((1, h, dq), lambda i, j, st, en: (i, 0, 0)),
            pl.BlockSpec((1, bs, r), kv_map),
            pl.BlockSpec((1, bs, dr), kv_map),
        ],
        out_specs=pl.BlockSpec((1, h, r), lambda i, j, st, en: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, r), jnp.float32)],
    )
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, h, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="mla_decode_attention",
    )(jnp.asarray(starts, jnp.int32), jnp.asarray(ends, jnp.int32),
      q, ckv, kpe)
