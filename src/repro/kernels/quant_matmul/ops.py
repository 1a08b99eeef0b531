"""Public jit'd wrappers for the fused dequant-matmul kernels.

``quant_matmul`` accepts a :class:`repro.quant.QuantizedTensor` (or raw
packed/scales arrays) and dispatches to the Pallas kernel on TPU (or in
interpret mode when requested) with a pure-jnp fallback — the fallback is
the default on CPU so the whole framework runs everywhere, while the kernel
is exercised by the kernel test-suite in interpret mode and targets TPU.

``expert_quant_matmul`` is the grouped per-expert twin: it takes a
:class:`repro.quant.MixedPrecisionWeights` whose leaves carry a leading
expert dim plus a ``(E,)`` critical mask, and executes every expert's
matmul straight from the packed codes of the precision the mask selects.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Iterator, Optional

import jax
import jax.numpy as jnp

from repro.kernels.quant_matmul.expert_quant_matmul import \
    expert_quant_matmul_grouped_pallas, expert_quant_matmul_pallas
from repro.kernels.quant_matmul.quant_matmul import quant_matmul_pallas
from repro.kernels.quant_matmul.ref import expert_quant_matmul_fixed_ref, \
    expert_quant_matmul_grouped_ref, expert_quant_matmul_grouped_rows_ref, \
    expert_quant_matmul_ref, expert_quant_matmul_rows_ref, quant_matmul_ref
from repro.quant.qtensor import MixedPrecisionWeights, QuantizedTensor

__all__ = ["quant_matmul", "expert_quant_matmul",
           "expert_quant_matmul_fixed", "expert_quant_matmul_grouped",
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


def quant_matmul(x: jnp.ndarray, qt: QuantizedTensor, *,
                 impl: Optional[str] = None, interpret: bool = False,
                 block_m: int = 128, block_n: int = 128, block_k: int = 512,
                 out_dtype=jnp.bfloat16) -> jnp.ndarray:
    """``y = x @ dequant(qt)`` with x of shape (..., K).

    impl: "pallas" | "ref" | None (auto: pallas on TPU, ref elsewhere).
    """
    impl = _resolve_impl(impl)
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    if impl == "pallas":
        y = quant_matmul_pallas(
            x2, qt.packed, qt.scales, bits=qt.bits, group_size=qt.group_size,
            block_m=block_m, block_n=block_n, block_k=block_k,
            interpret=interpret, out_dtype=out_dtype)
    elif impl == "ref":
        y = quant_matmul_ref(x2, qt.packed, qt.scales, bits=qt.bits,
                             group_size=qt.group_size, out_dtype=out_dtype)
    else:
        raise ValueError(f"unknown impl {impl!r}")
    return y.reshape(*lead, -1)


def expert_quant_matmul_fixed(x: jnp.ndarray, qt: QuantizedTensor, *,
                              impl: Optional[str] = None,
                              interpret: bool = False,
                              block_m: int = 128, block_n: int = 128,
                              block_k: int = 512,
                              out_dtype=jnp.bfloat16) -> jnp.ndarray:
    """``y[e] = x[e] @ W_e`` with EVERY expert at ``qt``'s one precision —
    the per-buffer entry point of the dual-buffer per-row MoE dispatch.
    On TPU this is the grouped Pallas kernel with an all-critical mask
    (the mask costs nothing in-kernel); on CPU it is the branch-free
    unrolled streaming oracle. ``block_m/n/k`` size the Pallas tiles
    (edge configs override via :class:`DyMoEPolicy`)."""
    impl = _resolve_impl(impl)
    if impl == "pallas":
        e = qt.packed.shape[0]
        return expert_quant_matmul_pallas(
            x, qt.packed, qt.scales, None, None,
            jnp.ones((e,), jnp.int32), hi_bits=qt.bits, lo_bits=0,
            group_size=qt.group_size, block_m=block_m, block_n=block_n,
            block_k=block_k, interpret=interpret, out_dtype=out_dtype)
    if impl == "ref":
        return expert_quant_matmul_fixed_ref(
            x, qt.packed, qt.scales, bits=qt.bits,
            group_size=qt.group_size, out_dtype=out_dtype)
    raise ValueError(f"unknown impl {impl!r}")


@functools.lru_cache(maxsize=None)
def _grouped_rows_aware(hi_bits: int, lo_bits: int, group_size: int,
                        cap_hi: int, out_dtype_name: str, has_lo: bool):
    """The grouped single-pass oracle wrapped in a ``custom_vmap`` whose
    batch rule routes row-batched calls (a per-slot program vmapped over
    the combined buffer) to
    :func:`expert_quant_matmul_grouped_rows_ref`, so weights unpack once
    per expert per precision regardless of the batch size — the same
    guard :func:`_ref_rows_aware` gives the critical-masked oracle."""
    from jax.custom_batching import custom_vmap

    kw = dict(cap_hi=cap_hi, hi_bits=hi_bits, lo_bits=lo_bits,
              group_size=group_size, out_dtype=jnp.dtype(out_dtype_name))

    if has_lo:
        @custom_vmap
        def f(x, hp, hs, lp, ls):
            return expert_quant_matmul_grouped_ref(x, hp, hs, lp, ls, **kw)

        @f.def_vmap
        def _rule(axis_size, in_batched, x, hp, hs, lp, ls):
            xb, hpb, hsb, lpb, lsb = in_batched
            if hpb or hsb or lpb or lsb:  # batched weights: just stream
                def one(args):
                    return expert_quant_matmul_grouped_ref(
                        args[0], args[1], args[2], args[3], args[4], **kw)
                bc = [a if b else
                      jnp.broadcast_to(a[None], (axis_size,) + a.shape)
                      for a, b in zip((x, hp, hs, lp, ls), in_batched)]
                return jax.lax.map(one, tuple(bc)), True
            if not xb:
                x = jnp.broadcast_to(x[None], (axis_size,) + x.shape)
            return expert_quant_matmul_grouped_rows_ref(x, hp, hs, lp, ls,
                                                        **kw), True
        return f

    @custom_vmap
    def g(x, hp, hs):
        return expert_quant_matmul_grouped_ref(x, hp, hs, None, None, **kw)

    @g.def_vmap
    def _rule_nolo(axis_size, in_batched, x, hp, hs):
        xb, hpb, hsb = in_batched
        if hpb or hsb:
            def one(args):
                return expert_quant_matmul_grouped_ref(
                    args[0], args[1], args[2], None, None, **kw)
            bc = [a if b else
                  jnp.broadcast_to(a[None], (axis_size,) + a.shape)
                  for a, b in zip((x, hp, hs), in_batched)]
            return jax.lax.map(one, tuple(bc)), True
        if not xb:
            x = jnp.broadcast_to(x[None], (axis_size,) + x.shape)
        return expert_quant_matmul_grouped_rows_ref(x, hp, hs, None, None,
                                                    **kw), True
    return g


def expert_quant_matmul_grouped(x: jnp.ndarray,
                                weights: MixedPrecisionWeights,
                                counts: Optional[jnp.ndarray] = None, *,
                                cap_hi: int, impl: Optional[str] = None,
                                interpret: bool = False,
                                block_m: int = 128, block_n: int = 128,
                                block_k: int = 512,
                                out_dtype=jnp.bfloat16) -> jnp.ndarray:
    """ONE fused dispatch for the dual-buffer per-row MoE.

    ``x`` (E, M, K) packs both precision capacity regions of every expert
    into a single buffer — high-precision slots in ``[0, cap_hi)``,
    low-precision slots in ``[cap_hi, M)`` — and one kernel call executes
    both (the Pallas grid has one precision group per region; the second
    dispatch and second weight unpack of the old hi/lo pair are gone).
    ``counts`` (E, 2) int32 live-slot watermarks make the grid ragged over
    LIVE rows: blocks beyond a group's occupancy are skipped outright, so
    finished/evicted/padded slots cost no FLOPs and no weight I/O.
    ``counts=None`` means fully occupied. Under "4/0"
    (``weights.low is None``) ``x`` must be the hi region alone
    (``cap_hi == M``) and the lo precision group is elided at grid
    construction.

    The jnp oracle ignores ``counts``: dead slots are zero-filled by the
    dispatch, so their dot is exact zero and the oracle's output is
    bitwise the watermark-pruned kernel's. Returns (E, M, N).
    """
    impl = _resolve_impl(impl)
    hi, lo = weights.high, weights.low
    lo_bits = lo.bits if lo is not None else 0
    if lo is not None:
        assert lo.group_size == hi.group_size, (lo.group_size, hi.group_size)
    e, m, _ = x.shape
    assert (lo is None) == (cap_hi == m), (cap_hi, m, lo is None)
    if impl == "pallas":
        if counts is None:
            counts = jnp.stack(
                [jnp.full((e,), cap_hi, jnp.int32),
                 jnp.full((e,), m - cap_hi, jnp.int32)], axis=1)
        return expert_quant_matmul_grouped_pallas(
            x, hi.packed, hi.scales,
            lo.packed if lo is not None else None,
            lo.scales if lo is not None else None,
            jnp.asarray(counts, jnp.int32), cap_hi=cap_hi,
            hi_bits=hi.bits, lo_bits=lo_bits, group_size=hi.group_size,
            block_m=block_m, block_n=block_n, block_k=block_k,
            interpret=interpret, out_dtype=out_dtype)
    if impl == "ref":
        f = _grouped_rows_aware(hi.bits, lo_bits, hi.group_size, cap_hi,
                                jnp.dtype(out_dtype).name, lo is not None)
        if lo is not None:
            return f(x, hi.packed, hi.scales, lo.packed, lo.scales)
        return f(x, hi.packed, hi.scales)
    raise ValueError(f"unknown impl {impl!r}")


@functools.lru_cache(maxsize=None)
def _ref_rows_aware(hi_bits: int, lo_bits: int, group_size: int,
                    out_dtype_name: str, has_lo: bool):
    """The ref oracle wrapped in a ``custom_vmap`` whose batch rule routes
    row-batched calls to :func:`expert_quant_matmul_rows_ref`.

    The continuous-batching decode vmaps the whole per-row decode program
    over slots, which batches x AND the per-row critical mask over this
    function while the weight store stays shared. Without the rule, vmap
    turns the oracle's per-expert ``lax.cond`` into a select that unpacks
    both precision variants PER ROW — B× redundant dequantization of
    row-invariant weights (measured ~2-4x slower whole-chunk decode).
    With it, batched rows share one unpack per expert. Unbatched calls
    (solo ``generate``) run the unmodified oracle."""
    from jax.custom_batching import custom_vmap

    kw = dict(hi_bits=hi_bits, lo_bits=lo_bits, group_size=group_size,
              out_dtype=jnp.dtype(out_dtype_name))

    if has_lo:
        @custom_vmap
        def f(x, hp, hs, lp, ls, crit):
            return expert_quant_matmul_ref(x, hp, hs, lp, ls, crit, **kw)

        @f.def_vmap
        def _rule(axis_size, in_batched, x, hp, hs, lp, ls, crit):
            xb, hpb, hsb, lpb, lsb, cb = in_batched
            if hpb or hsb or lpb or lsb:  # batched weights: just stream
                def one(args):
                    return expert_quant_matmul_ref(
                        args[0], args[1], args[2], args[3], args[4],
                        args[5], **kw)
                bc = [a if b else
                      jnp.broadcast_to(a[None], (axis_size,) + a.shape)
                      for a, b in zip((x, hp, hs, lp, ls, crit),
                                      in_batched)]
                return jax.lax.map(one, tuple(bc)), True
            if not xb:
                x = jnp.broadcast_to(x[None], (axis_size,) + x.shape)
            if not cb:
                crit = jnp.broadcast_to(crit[None],
                                        (axis_size,) + crit.shape)
            return expert_quant_matmul_rows_ref(x, hp, hs, lp, ls, crit,
                                                **kw), True
        return f

    @custom_vmap
    def g(x, hp, hs, crit):
        return expert_quant_matmul_ref(x, hp, hs, None, None, crit, **kw)

    @g.def_vmap
    def _rule_nolo(axis_size, in_batched, x, hp, hs, crit):
        xb, hpb, hsb, cb = in_batched
        if hpb or hsb:
            def one(args):
                return expert_quant_matmul_ref(
                    args[0], args[1], args[2], None, None, args[3], **kw)
            bc = [a if b else
                  jnp.broadcast_to(a[None], (axis_size,) + a.shape)
                  for a, b in zip((x, hp, hs, crit), in_batched)]
            return jax.lax.map(one, tuple(bc)), True
        if not xb:
            x = jnp.broadcast_to(x[None], (axis_size,) + x.shape)
        if not cb:
            crit = jnp.broadcast_to(crit[None], (axis_size,) + crit.shape)
        return expert_quant_matmul_rows_ref(x, hp, hs, None, None, crit,
                                            **kw), True
    return g


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
    hi, lo = weights.high, weights.low
    lo_bits = lo.bits if lo is not None else 0
    if lo is not None:
        assert lo.group_size == hi.group_size, (lo.group_size, hi.group_size)
    e = hi.packed.shape[0]
    critical = jnp.asarray(critical)
    assert critical.shape == (e,), \
        f"critical mask shape {critical.shape} != ({e},) experts"
    if impl == "pallas":
        return expert_quant_matmul_pallas(
            x, hi.packed, hi.scales,
            lo.packed if lo is not None else None,
            lo.scales if lo is not None else None,
            critical, hi_bits=hi.bits, lo_bits=lo_bits,
            group_size=hi.group_size, block_m=block_m, block_n=block_n,
            block_k=block_k, interpret=interpret, out_dtype=out_dtype)
    if impl == "ref":
        f = _ref_rows_aware(hi.bits, lo_bits, hi.group_size,
                            jnp.dtype(out_dtype).name, lo is not None)
        if lo is not None:
            return f(x, hi.packed, hi.scales, lo.packed, lo.scales,
                     critical)
        return f(x, hi.packed, hi.scales, critical)
    raise ValueError(f"unknown impl {impl!r}")
