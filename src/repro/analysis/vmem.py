"""Static VMEM-footprint estimation for ``pallas_call`` equations.

A Pallas TPU kernel's working set must fit in ~16 MiB of VMEM per core
(see the Pallas guide's memory-hierarchy table). The pipeline
double-buffers every grid-blocked operand (the next block DMAs while the
current one computes), so the estimate per ``pallas_call`` is

    2 x sum(block_shape x itemsize)   over input/output block mappings
  +     sum(shape x itemsize)         over VMEM scratch operands
  +     sum(bytes)                    over scalar-prefetch operands

Scalar-prefetch operands live in SMEM, but they are counted here anyway:
they are tiny (watermark tables, critical masks) and counting them keeps
the estimate an upper bound. Everything is read off the eqn's
``grid_mapping`` / kernel-jaxpr params — no lowering, no TPU — which is
what lets a bad ``block_m/n/k`` config override be caught before any
hardware run.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional

import jax.numpy as jnp

__all__ = ["VMEM_BUDGET_BYTES", "PallasVmemEstimate",
           "estimate_pallas_vmem"]

# Per-backend VMEM budgets the vmem-footprint rule checks against.
# TPU: ~16 MiB/core (v4/v5e-class, per the Pallas guide); "interpret"
# backends have no real VMEM, but the TPU budget is still enforced so a
# config that would only ever run interpreted cannot hide an oversized
# tile.
VMEM_BUDGET_BYTES: Dict[str, int] = {
    "tpu": 16 * 1024 * 1024,
}


def _dim(d: Any) -> int:
    """A block dim as an int — newer pallas versions wrap dims in
    Blocked/Squeezed markers; both expose the size via int()."""
    if d is None:      # "None" block dim = whole (unblocked) axis marker
        return 1
    try:
        return int(d)
    except TypeError:
        for attr in ("block_size", "size"):
            if hasattr(d, attr):
                return int(getattr(d, attr))
        raise


def _bytes(shape, dtype) -> int:
    return math.prod(_dim(d) for d in shape) * jnp.dtype(dtype).itemsize


@dataclasses.dataclass(frozen=True)
class PallasVmemEstimate:
    """Breakdown of one ``pallas_call``'s estimated VMEM working set."""

    kernel_name: str
    grid: tuple
    block_bytes: int        # sum over in/out block mappings (single copy)
    scratch_bytes: int      # VMEM scratch (accumulators)
    prefetch_bytes: int     # scalar-prefetch operands (SMEM, upper bound)
    blocks: tuple           # ((shape, dtype_name, bytes), ...) per mapping

    @property
    def total_bytes(self) -> int:
        """Double-buffered blocks + scratch + prefetch."""
        return 2 * self.block_bytes + self.scratch_bytes \
            + self.prefetch_bytes

    def describe(self) -> str:
        mb = self.total_bytes / 2 ** 20
        return (f"{self.kernel_name}: ~{mb:.2f} MiB "
                f"(2x{self.block_bytes} block + {self.scratch_bytes} "
                f"scratch + {self.prefetch_bytes} prefetch bytes, "
                f"grid={self.grid})")


def estimate_pallas_vmem(eqn: Any) -> Optional[PallasVmemEstimate]:
    """Estimate one ``pallas_call`` eqn's VMEM footprint, or None when the
    eqn is not a pallas_call / carries no grid mapping (direct VMEM-space
    calls without blocking are not estimated — their whole operands are
    the working set, visible from the eqn's invars instead)."""
    if eqn.primitive.name != "pallas_call":
        return None
    gm = eqn.params.get("grid_mapping")
    if gm is None:  # pragma: no cover - future pallas versions
        return None

    block_total = 0
    blocks: List[tuple] = []
    for bm in gm.block_mappings:
        dtype = bm.array_aval.dtype
        b = _bytes(bm.block_shape, dtype)
        block_total += b
        blocks.append((tuple(_dim(d) for d in bm.block_shape),
                       jnp.dtype(dtype).name, b))

    # kernel jaxpr invars: [scalar-prefetch..., in blocks..., out blocks...,
    # scratch...] — scratch avals (accumulators) come from the tail,
    # scalar-prefetch bytes from the head.
    kjaxpr = eqn.params.get("jaxpr")
    scratch_bytes = 0
    prefetch_bytes = 0
    if kjaxpr is not None:
        invars = getattr(kjaxpr, "jaxpr", kjaxpr).invars
        n_scratch = getattr(gm, "num_scratch_operands", 0)
        n_prefetch = getattr(gm, "num_index_operands", 0)

        def ref_bytes(v) -> int:
            aval = v.aval
            inner = getattr(aval, "inner_aval", aval)
            shape = getattr(inner, "shape", ())
            dtype = getattr(inner, "dtype", jnp.float32)
            return _bytes(shape, dtype)

        if n_scratch:
            scratch_bytes = sum(ref_bytes(v) for v in invars[-n_scratch:])
        if n_prefetch:
            prefetch_bytes = sum(ref_bytes(v) for v in invars[:n_prefetch])

    name_info = eqn.params.get("name_and_src_info")
    kname = getattr(name_info, "name", None) or str(name_info or "pallas")
    return PallasVmemEstimate(
        kernel_name=kname, grid=tuple(gm.grid), block_bytes=block_total,
        scratch_bytes=scratch_bytes, prefetch_bytes=prefetch_bytes,
        blocks=tuple(blocks))
