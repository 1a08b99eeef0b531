"""Public wrappers for the grouped dequant-matmul kernels.

``expert_quant_matmul`` takes a :class:`repro.quant.MixedPrecisionWeights`
whose leaves carry a leading expert dim plus a ``(E,)`` critical mask, and
executes every expert's matmul straight from the packed codes of the
precision the mask selects (the mask-select kernel; dense quantized layers
reach it as a 1-expert group through
:func:`repro.quant.mixed.mixed_precision_matmul`).
``expert_quant_matmul_grouped`` runs both precision regions of one
combined capacity buffer in a single dispatch (the per-row MoE).

Each dispatches to its Pallas kernel on TPU (or in interpret mode when
requested) and to its pure-jnp oracle elsewhere, so the whole framework
runs everywhere.
"""
from __future__ import annotations

import contextlib
from typing import Iterator, Optional

import jax
import jax.numpy as jnp

from repro.kernels.quant_matmul.expert_quant_matmul import \
    expert_quant_matmul_grouped_pallas, expert_quant_matmul_pallas
from repro.kernels.quant_matmul.ref import \
    expert_quant_matmul_grouped_ref, expert_quant_matmul_ref
from repro.quant.qtensor import MixedPrecisionWeights

__all__ = ["expert_quant_matmul", "expert_quant_matmul_grouped",
           "force_impl"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


_FORCED_IMPL: Optional[str] = None


@contextlib.contextmanager
def force_impl(impl: Optional[str]) -> Iterator[None]:
    """Override auto impl selection (``impl=None`` call sites) in scope.

    ``force_impl("pallas")`` makes the jaxpr linter and the structural
    tests TRACE the Pallas serving path on any backend — tracing never
    lowers, so no TPU is needed to inspect the kernel dispatch structure.
    Explicit ``impl=`` arguments still win.
    """
    global _FORCED_IMPL
    prev = _FORCED_IMPL
    _FORCED_IMPL = impl
    try:
        yield
    finally:
        _FORCED_IMPL = prev


def _resolve_impl(impl: Optional[str]) -> str:
    if impl is not None:
        return impl
    if _FORCED_IMPL is not None:
        return _FORCED_IMPL
    return "pallas" if _on_tpu() else "ref"


def _low_store(weights: MixedPrecisionWeights):
    """(packed, scales, bits) of the low-precision store; (None, None, 0)
    under "4/0"."""
    hi, lo = weights.high, weights.low
    if lo is None:
        return None, None, 0
    assert lo.group_size == hi.group_size, (lo.group_size, hi.group_size)
    return lo.packed, lo.scales, lo.bits


def expert_quant_matmul_grouped(x: jnp.ndarray,
                                weights: MixedPrecisionWeights,
                                counts: Optional[jnp.ndarray] = None, *,
                                cap_hi: int, layer=None,
                                impl: Optional[str] = None,
                                interpret: bool = False,
                                block_m: int = 128, block_k: int = 512,
                                out_dtype=jnp.bfloat16) -> jnp.ndarray:
    """ONE fused dispatch for the dual-buffer per-row MoE.

    ``x`` (E, M, K) packs both precision capacity regions of every expert
    into a single buffer — high-precision slots in ``[0, cap_hi)``,
    low-precision slots in ``[cap_hi, M)`` — and one kernel call executes
    both (the Pallas grid has one precision group per region).
    ``counts`` (E, 2) int32 live-slot watermarks make the grid ragged over
    LIVE rows: blocks beyond a group's occupancy are skipped outright, so
    finished/evicted/padded slots cost no FLOPs and no weight I/O.
    ``counts=None`` means fully occupied. Under "4/0"
    (``weights.low is None``) ``x`` must be the hi region alone
    (``cap_hi == M``) and the lo precision group is elided at grid
    construction.

    ``layer`` (an int32 scalar, may be traced): ``weights`` is the whole
    layer stack, leaves (L, E, ...), and the kernel reads layer ``layer``
    of it in place. None: ``weights`` is one layer's store, leaves
    (E, ...), which the kernel reads as a stack of one.

    The jnp oracle ignores ``counts``: dead slots are zero-filled by the
    dispatch, so their dot is exact zero and the oracle's output is
    bitwise the watermark-pruned kernel's. Returns (E, M, N).
    """
    impl = _resolve_impl(impl)
    if impl not in ("pallas", "ref"):
        raise ValueError(f"unknown impl {impl!r}")
    if impl == "pallas" and layer is None:
        weights, layer = jax.tree.map(lambda a: a[None], weights), 0
    elif impl == "ref" and layer is not None:
        weights = jax.tree.map(lambda a: a[layer], weights)
    hi = weights.high
    lp, ls, lo_bits = _low_store(weights)
    e, m, _ = x.shape
    assert (lp is None) == (cap_hi == m), (cap_hi, m, lp is None)
    if impl == "ref":
        return expert_quant_matmul_grouped_ref(
            x, hi.packed, hi.scales, lp, ls, cap_hi=cap_hi,
            hi_bits=hi.bits, lo_bits=lo_bits, group_size=hi.group_size,
            out_dtype=out_dtype)
    if counts is None:
        counts = jnp.stack(
            [jnp.full((e,), cap_hi, jnp.int32),
             jnp.full((e,), m - cap_hi, jnp.int32)], axis=1)
    return expert_quant_matmul_grouped_pallas(
        x, hi.packed, hi.scales, lp, ls, jnp.asarray(counts, jnp.int32),
        jnp.asarray(layer, jnp.int32), cap_hi=cap_hi, hi_bits=hi.bits,
        lo_bits=lo_bits, group_size=hi.group_size, block_m=block_m,
        block_k=block_k, interpret=interpret, out_dtype=out_dtype)


def expert_quant_matmul(x: jnp.ndarray, weights: MixedPrecisionWeights,
                        critical: jnp.ndarray, *,
                        impl: Optional[str] = None, interpret: bool = False,
                        block_m: int = 128, block_n: int = 128,
                        block_k: int = 512,
                        out_dtype=jnp.bfloat16) -> jnp.ndarray:
    """``y[e] = x[e] @ W_e`` at the per-expert precision ``critical`` picks.

    Args:
      x: (E, M, K) per-expert activation blocks.
      weights: expert-batched mixed-precision store — ``high.packed`` is
        (E, N, K/vpb); ``low`` may be None ("4/0"), in which case
        sub-critical experts' outputs are zero.
      critical: (E,) bool — True => high-bit path.
      impl: "pallas" | "ref" | None (auto: pallas on TPU, ref elsewhere).
    Returns:
      (E, M, N) in ``out_dtype``.
    """
    impl = _resolve_impl(impl)
    hi = weights.high
    lp, ls, lo_bits = _low_store(weights)
    e = hi.packed.shape[0]
    critical = jnp.asarray(critical)
    assert critical.shape == (e,), \
        f"critical mask shape {critical.shape} != ({e},) experts"
    if impl == "pallas":
        return expert_quant_matmul_pallas(
            x, hi.packed, hi.scales, lp, ls, critical, hi_bits=hi.bits,
            lo_bits=lo_bits, group_size=hi.group_size, block_m=block_m,
            block_n=block_n, block_k=block_k, interpret=interpret,
            out_dtype=out_dtype)
    if impl == "ref":
        return expert_quant_matmul_ref(
            x, hi.packed, hi.scales, lp, ls, critical, hi_bits=hi.bits,
            lo_bits=lo_bits, group_size=hi.group_size, out_dtype=out_dtype)
    raise ValueError(f"unknown impl {impl!r}")
