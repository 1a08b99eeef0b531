"""Pure-jnp oracles for the quant_matmul kernels.

``quant_matmul_ref`` is the plain dequantize-then-dot of one packed
matrix. ``expert_quant_matmul_ref`` streams ONE expert block at a time
through a ``lax.map`` and picks the high- or low-bit representation with
a ``lax.cond`` per expert, so — like the Pallas kernel — it never
materializes a dense ``(E, K, N)`` weight tensor.
``expert_quant_matmul_grouped_ref`` is the oracle of the grouped kernel's
combined dual-precision buffer."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.quant.quantize import dequantize_tensor

__all__ = ["quant_matmul_ref", "expert_quant_matmul_ref",
           "expert_quant_matmul_grouped_ref"]


def quant_matmul_ref(x: jnp.ndarray, packed: jnp.ndarray, scales: jnp.ndarray,
                     *, bits: int, group_size: int,
                     out_dtype=jnp.bfloat16) -> jnp.ndarray:
    """y = x @ dequant(W). x: (M, K); packed: (N, K/vpb); scales: (K/gs, N)."""
    w = dequantize_tensor(packed, scales, bits, group_size, jnp.float32)
    return jnp.dot(x.astype(jnp.float32), w,
                   preferred_element_type=jnp.float32).astype(out_dtype)


def expert_quant_matmul_ref(
        x: jnp.ndarray, hi_packed: jnp.ndarray, hi_scales: jnp.ndarray,
        lo_packed: Optional[jnp.ndarray], lo_scales: Optional[jnp.ndarray],
        critical: jnp.ndarray, *, hi_bits: int, lo_bits: int,
        group_size: int, out_dtype=jnp.bfloat16) -> jnp.ndarray:
    """y[e] = x[e] @ W_e at per-expert precision. Shapes as in the kernel:
    x (E, M, K); *_packed (E, N, K/vpb); *_scales (E, K/gs, N);
    critical (E,). ``lo_packed is None`` zeroes sub-critical experts."""
    crit = jnp.asarray(critical).astype(jnp.int32)
    m, n = x.shape[1], hi_packed.shape[1]

    def one_hi(xe, hp, hs):
        w = dequantize_tensor(hp, hs, hi_bits, group_size, jnp.float32)
        return jnp.dot(xe.astype(jnp.float32), w,
                       preferred_element_type=jnp.float32)

    if lo_packed is None:
        def one(args):
            xe, hp, hs, ce = args
            return jax.lax.cond(
                ce > 0,
                lambda: one_hi(xe, hp, hs),
                lambda: jnp.zeros((m, n), jnp.float32))
        y = jax.lax.map(one, (x, hi_packed, hi_scales, crit))
    else:
        def one(args):
            xe, hp, hs, lp, ls, ce = args

            def lo():
                w = dequantize_tensor(lp, ls, lo_bits, group_size,
                                      jnp.float32)
                return jnp.dot(xe.astype(jnp.float32), w,
                               preferred_element_type=jnp.float32)

            return jax.lax.cond(ce > 0, lambda: one_hi(xe, hp, hs), lo)
        y = jax.lax.map(one, (x, hi_packed, hi_scales, lo_packed, lo_scales,
                              crit))
    return y.astype(out_dtype)


def expert_quant_matmul_grouped_ref(
        x: jnp.ndarray, hi_packed: jnp.ndarray, hi_scales: jnp.ndarray,
        lo_packed: Optional[jnp.ndarray], lo_scales: Optional[jnp.ndarray],
        *, cap_hi: int, hi_bits: int, lo_bits: int, group_size: int,
        out_dtype=jnp.bfloat16) -> jnp.ndarray:
    """Oracle for the grouped kernel: ``x`` (E, M, K) is ONE combined
    capacity buffer per expert — high-precision slots in ``[0, cap_hi)``,
    low-precision slots in ``[cap_hi, M)``. Each expert streams once, each
    precision's codes unpack once and multiply their own region slice.
    ``lo_packed is None`` ("4/0"): ``cap_hi == M`` and the whole buffer is
    the high region. The scan is fully unrolled: the expert blocks are
    tiny and independent, and a sequential loop's dispatch would dominate
    them."""
    def mm(xe, packed, scales, bits):
        w = dequantize_tensor(packed, scales, bits, group_size, jnp.float32)
        return jnp.dot(xe.astype(jnp.float32), w,
                       preferred_element_type=jnp.float32)

    if lo_packed is None:
        assert cap_hi == x.shape[1], (cap_hi, x.shape)

        def one(carry, args):
            xe, hp, hs = args
            return carry, mm(xe, hp, hs, hi_bits)
        xs = (x, hi_packed, hi_scales)
    else:
        def one(carry, args):
            xe, hp, hs, lp, ls = args
            return carry, jnp.concatenate(
                [mm(xe[:cap_hi], hp, hs, hi_bits),
                 mm(xe[cap_hi:], lp, ls, lo_bits)], axis=0)
        xs = (x, hi_packed, hi_scales, lo_packed, lo_scales)
    _, y = jax.lax.scan(one, None, xs, unroll=x.shape[0])
    return y.astype(out_dtype)
