"""Fused dequantize-matmul Pallas kernel.

Computes ``y = x @ dequant(packed, scales)`` where the weight is stored
bit-packed (int8/int4/int2 codes in uint8 lanes, packed along K) with
group-wise scales along K.

TPU mapping
-----------
* Grid ``(M/bm, N/bn, K/bk)`` — M and N parallel, K ``arbitrary`` (serial
  accumulation into a VMEM scratch accumulator).
* The packed weight tile ``(bn, bk/vpb)`` and its scales ``(bk/gs, bn)`` are
  staged HBM→VMEM by ``pallas_call``; the kernel body unpacks the codes with
  shifts/masks on the VPU, applies the per-group scale, and feeds the MXU via
  ``jnp.dot(..., preferred_element_type=float32)``.
* Because the weight moves over the memory system *packed*, HBM traffic is
  bits/16 of the bf16 baseline — this is exactly DyMoE's I/O-volume argument
  transplanted from PCIe to the HBM→VMEM hop.
* Block defaults (128, 128, 512) keep the working set ≈
  ``bm*bk*2 + bn*bk/vpb + bk/gs*bn*4 + bm*bn*4`` ≈ 260 KB « 16 MB VMEM and
  all matmul dims multiples of the 128-lane MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["quant_matmul_pallas"]


def _unpack_dequant(packed_tile: jnp.ndarray, scales_tile: jnp.ndarray,
                    bits: int, group_size: int) -> jnp.ndarray:
    """(bn, bk/vpb) uint8 codes + (bk/gs, bn) scales -> (bk, bn) f32 weights.

    The codes are transposed first, so K runs along sublanes and N stays
    on the 128 lanes: unpacking interleaves whole sublane rows and the
    per-group scale is a sublane split ``(g, gs, bn)``. No step splits or
    merges the lane dim, which Mosaic cannot relayout.
    """
    offset = 1 << (bits - 1)
    codes = packed_tile.astype(jnp.int32).T                   # (bk/vpb, bn)
    bkp, bn = codes.shape
    vpb = 8 // bits
    if vpb > 1:
        mask = (1 << bits) - 1
        parts = [(codes >> (bits * j)) & mask for j in range(vpb)]
        codes = jnp.stack(parts, axis=1).reshape(bkp * vpb, bn)
    bk = bkp * vpb
    g = bk // group_size
    q = (codes - offset).astype(jnp.float32).reshape(g, group_size, bn)
    return (q * scales_tile[:, None, :]).reshape(bk, bn)


def _kernel(x_ref, p_ref, s_ref, o_ref, acc_ref, *, bits, group_size, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _unpack_dequant(p_ref[...], s_ref[...], bits, group_size)  # (bk, bn)
    x = x_ref[...].astype(jnp.float32)                             # (bm, bk)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "group_size", "block_m", "block_n", "block_k",
                     "interpret", "out_dtype"),
)
def quant_matmul_pallas(x: jnp.ndarray, packed: jnp.ndarray,
                        scales: jnp.ndarray, *, bits: int, group_size: int,
                        block_m: int = 128, block_n: int = 128,
                        block_k: int = 512, interpret: bool = False,
                        out_dtype=jnp.bfloat16) -> jnp.ndarray:
    """y = x @ W for W stored packed.

    Args:
      x: (M, K) activations.
      packed: (N, K / values_per_byte) uint8.
      scales: (K / group_size, N) float32.
    Returns:
      (M, N) in ``out_dtype``.
    """
    m, k = x.shape
    vpb = 8 // bits
    n = packed.shape[0]
    assert packed.shape[1] * vpb == k, (packed.shape, k, bits)
    assert scales.shape == (k // group_size, n)
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    assert bk % group_size == 0, (bk, group_size)
    nk = k // bk

    grid = (m // bm, n // bn, nk)
    return pl.pallas_call(
        functools.partial(_kernel, bits=bits, group_size=group_size, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bk // vpb), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((bk // group_size, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, packed, scales)
