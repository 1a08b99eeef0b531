"""Grouped per-expert fused dequant-matmul Pallas kernel.

Computes ``y[e] = x[e] @ dequant(W_e^(b_e))`` for a *batch of experts* whose
per-expert bit width is selected at runtime by a ``(E,)`` critical mask:
Critical experts run from the high-bit packed buffer, Sub-critical ones from
the low-bit buffer — or, in the "4/0" deployment (``lo_packed is None``),
their output block is zeroed without the packed codes ever being unpacked.

TPU mapping
-----------
:func:`expert_quant_matmul_pallas`, the critical-mask kernel:

* Grid ``(E, M/bm, N/bn, K/bk)`` — E/M/N parallel, K ``arbitrary`` (serial
  accumulation into a VMEM scratch accumulator).
* The critical mask rides in as a **scalar-prefetch** operand
  (:class:`pltpu.PrefetchScalarGridSpec`), so it is resident in SMEM before
  the grid starts and the *index maps themselves* depend on it: the packed
  buffer an expert does NOT use has its index map pinned to block
  ``(0, 0, 0)``, which the pipeline fetches once and then never re-fetches
  (consecutive identical block indices elide the DMA). Per expert, only the
  selected precision's bytes move over the HBM→VMEM hop — this is DyMoE's
  I/O-volume argument executed directly from the packed representation,
  with no dense ``(E, K, N)`` bf16 intermediate anywhere.
* Inside the body a ``lax.cond`` on the prefetched scalar unpacks exactly
  one of the two tiles (shift/mask on the VPU, per-group scale, MXU matmul
  with f32 accumulation).
* Non-divisible M/N/K are handled by zero-padding in the wrapper: padded
  scale groups are zero, so padded K contributes exactly nothing.

:func:`expert_quant_matmul_grouped_pallas` is the FUSED variant backing the
dual-buffer per-row MoE dispatch: both precision capacity regions ride in
one combined buffer and one ``(E * P, M/bm, N/bn, K/bk)`` grid whose
scalar-prefetch operand is a per-(expert, precision-group) live-slot
watermark table — the second dispatch, the second weight unpack, and every
dead row block (finished/evicted/padded slots) disappear from the grid.
Its tiles come from the shapes (:func:`grouped_tiling`). Where its x is
bf16 and its row blocks are short (decode; admission waves of a few rows;
:func:`_scale_after_dot`), its body feeds the MXU the codes themselves as
bf16 integers and applies the f32 group scales to the dot's per-group
partial sums, and one grid step covers the whole output width N: at
decode most steps are dead, and on a v5e chip a step's fixed cost
(about 0.24 us) outweighs what the wider tile adds (about 0.25 us per
1,024 columns of a dead step).
A dead step, and the precision buffer a group does not read, repeat the
block that buffer already holds (:func:`grouped_index_maps`), so only live
blocks move over HBM. Its weights are the whole layer stack,
``(L, E, ...)``, and the layer rides in the scalar prefetch beside the
watermarks: a layer scan hands the kernel the resident stores and the
layer index, so no layer's store is sliced out or padded before a call.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["expert_quant_matmul_pallas", "expert_quant_matmul_grouped_pallas",
           "grouped_scales_after_dot", "grouped_tiling", "GroupedTiling",
           "grouped_tables", "grouped_index_maps"]


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


def _dual_kernel(crit_ref, x_ref, hp_ref, hs_ref, lp_ref, ls_ref, o_ref,
                 acc_ref, *, hi_bits, lo_bits, group_size, nk):
    e = pl.program_id(0)
    kk = pl.program_id(3)
    crit = crit_ref[e] > 0

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = jax.lax.cond(
        crit,
        lambda: _unpack_dequant(hp_ref[0], hs_ref[0], hi_bits, group_size),
        lambda: _unpack_dequant(lp_ref[0], ls_ref[0], lo_bits, group_size))
    x = x_ref[0].astype(jnp.float32)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _skip_kernel(crit_ref, x_ref, hp_ref, hs_ref, o_ref, acc_ref, *,
                 hi_bits, group_size, nk):
    e = pl.program_id(0)
    kk = pl.program_id(3)
    crit = crit_ref[e] > 0

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(crit)  # skipped experts: output stays zero, codes stay packed
    def _compute():
        w = _unpack_dequant(hp_ref[0], hs_ref[0], hi_bits, group_size)
        x = x_ref[0].astype(jnp.float32)
        acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _scale_after_dot(bm: int, bk: int, group_size: int, dtype) -> bool:
    """Whether the grouped kernel applies its group scales after the dot.

    That body (:func:`_dot_scale_after`) feeds the MXU a block-diagonal x
    of ``G * bm`` rows, ``G = bk / group_size``: one bf16 pass whose cost
    grows with ``G * bm`` while the weight-side dequant it replaces does
    not. On a v5e chip it is faster at 128 to 512 rows and slower at 1024,
    so it is taken up to 512 rows: every decode block and the admission
    waves of a few rows. x must already be bf16, so that feeding it to a
    bf16 pass rounds nothing."""
    return (jnp.dtype(dtype) == jnp.bfloat16
            and bm * (bk // group_size) <= 512)


def _plane_major(x: jnp.ndarray, bk: int, bits: int) -> jnp.ndarray:
    """(E, R, K) -> (E, R, K): within each bk block, the columns in the
    order :func:`_codes_plane_major` unpacks the codes: the value of bit
    plane ``j`` and byte ``b`` is ``k = b * vpb + j`` (quant/packing.py)."""
    vpb = 8 // bits
    if vpb == 1:
        return x
    e, r, k = x.shape
    return x.reshape(e, r, k // bk, bk // vpb, vpb).swapaxes(3, 4).reshape(
        e, r, k)


def _codes_plane_major(packed_tile: jnp.ndarray, bits: int) -> jnp.ndarray:
    """(bn, bk/vpb) uint8 codes -> (bn, bk) bf16 codes minus offset, bit
    planes side by side along the lanes; every value is an integer of at
    most 8 bits, so bf16 holds it exactly."""
    vpb = 8 // bits
    offset = 1 << (bits - 1)
    mask = (1 << bits) - 1
    codes = packed_tile.astype(jnp.int32)
    planes = [((codes >> (bits * j)) & mask) - offset for j in range(vpb)]
    q = planes[0] if vpb == 1 else jnp.concatenate(planes, axis=1)
    return q.astype(jnp.float32).astype(jnp.bfloat16)


def _dot_scale_after(x: jnp.ndarray, packed_tile: jnp.ndarray,
                     scales_tile: jnp.ndarray, bits: int,
                     group_size: int) -> jnp.ndarray:
    """(bm, bk) bf16 x in plane-major column order, (bn, bk/vpb) codes and
    (G, bn) f32 scales -> (bm, bn) f32, the scales applied after the dot.

    Row block ``g`` of the (G * bm, bk) block-diagonal x keeps only the
    columns of scale group ``g``, so one bf16 x bf16 -> f32 pass against
    the codes (contracted on both operands' lanes, no transpose) gives
    every group's partial sum; each is scaled in f32 and summed. Only the
    f32 summation order differs from scaling the weights first."""
    bm, bk = x.shape
    g = scales_tile.shape[0]
    vpb = 8 // bits
    span = group_size // vpb            # a group's columns in one plane
    col = jax.lax.broadcasted_iota(jnp.int32, (g, 1, bk), 2)
    lo = jax.lax.broadcasted_iota(jnp.int32, (g, 1, bk), 0) * span
    keep = functools.reduce(jnp.logical_or, [
        (col >= lo + j * (bk // vpb)) & (col < lo + j * (bk // vpb) + span)
        for j in range(vpb)])
    x_bd = jnp.where(keep, x.astype(jnp.float32)[None], 0.0)
    x_bd = x_bd.reshape(g * bm, bk).astype(jnp.bfloat16)
    part = jax.lax.dot_general(
        x_bd, _codes_plane_major(packed_tile, bits),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return (part.reshape(g, bm, -1) * scales_tile[:, None, :]).sum(axis=0)


def _grouped_tile(x, packed_tile, scales_tile, bits, group_size,
                  scale_after_dot):
    """One live grid step's (bm, bn) f32 partial product."""
    if scale_after_dot:
        return _dot_scale_after(x, packed_tile, scales_tile, bits,
                                group_size)
    w = _unpack_dequant(packed_tile, scales_tile, bits, group_size)
    return jnp.dot(x.astype(jnp.float32), w,
                   preferred_element_type=jnp.float32)


def _grouped_dual_kernel(nb_ref, x_ref, hp_ref, hs_ref, lp_ref, ls_ref,
                         o_ref, acc_ref, *, hi_bits, lo_bits, group_size,
                         nk, scale_after_dot):
    g = pl.program_id(0)
    i = pl.program_id(1)
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # blocks at or beyond the group's live-row watermark: no unpack, no
    # FLOPs, output stays zero (dead/finished slots are zero-filled by the
    # dispatch, so skipping reproduces their dot exactly)
    @pl.when(i < nb_ref[g])
    def _compute():
        acc_ref[...] += jax.lax.cond(
            g % 2 == 0,
            lambda: _grouped_tile(x_ref[0], hp_ref[0, 0], hs_ref[0, 0],
                                  hi_bits, group_size, scale_after_dot),
            lambda: _grouped_tile(x_ref[0], lp_ref[0, 0], ls_ref[0, 0],
                                  lo_bits, group_size, scale_after_dot))

    @pl.when(kk == nk - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _grouped_skip_kernel(nb_ref, x_ref, hp_ref, hs_ref, o_ref, acc_ref, *,
                         hi_bits, group_size, nk, scale_after_dot):
    g = pl.program_id(0)
    i = pl.program_id(1)
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < nb_ref[g])
    def _compute():
        acc_ref[...] += _grouped_tile(x_ref[0], hp_ref[0, 0], hs_ref[0, 0],
                                      hi_bits, group_size, scale_after_dot)

    @pl.when(kk == nk - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _row_block(block_m: int, rows: int, *dtypes) -> int:
    """Row tile of the M axis: at most ``block_m`` (rounded up), no taller
    than ``rows`` needs, and a whole number of sublane tiles of every
    dtype that rides in it (8 rows of f32, 16 of bf16). Mosaic requires
    that of a block's second-minor dim; the wrappers zero-pad M up to it."""
    sub = max(32 // jnp.dtype(d).itemsize for d in dtypes)
    return -(-min(block_m, rows) // sub) * sub


def _k_block(block_k: int, k: int, group_size: int) -> int:
    """Contraction tile: at most ``block_k``, a whole number of groups, or
    K itself where that tile does not divide K: the grouped kernel reads
    its stores in place, unpadded, and a block may span an array's whole
    extent. (The MoE layer stores every K that 512 does not divide padded
    once to one it does: ``models.layers.moe.expert_store_k``.)"""
    bk = max(group_size, (min(block_k, k) // group_size) * group_size)
    return bk if k % bk == 0 else k


def grouped_scales_after_dot(cap: int, k: int, *, group_size: int,
                             block_m: int, block_k: int, dtype) -> bool:
    """The body :func:`expert_quant_matmul_grouped_pallas` runs for regions
    of ``cap`` rows, contraction ``k`` and activations and output of
    ``dtype``: True where it applies the group scales after the dot."""
    return _scale_after_dot(_row_block(block_m, cap, dtype, dtype),
                            _k_block(block_k, k, group_size), group_size,
                            dtype)


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("hi_bits", "lo_bits", "group_size", "block_m",
                     "block_n", "block_k", "interpret", "out_dtype"),
)
def expert_quant_matmul_pallas(
        x: jnp.ndarray, hi_packed: jnp.ndarray, hi_scales: jnp.ndarray,
        lo_packed: Optional[jnp.ndarray], lo_scales: Optional[jnp.ndarray],
        critical: jnp.ndarray, *, hi_bits: int, lo_bits: int,
        group_size: int, block_m: int = 128, block_n: int = 128,
        block_k: int = 512, interpret: bool = False,
        out_dtype=jnp.bfloat16) -> jnp.ndarray:
    """y[e] = x[e] @ W_e at per-expert precision, from packed weights.

    Args:
      x: (E, M, K) activations (the expert capacity buffer).
      hi_packed: (E, N, K / vpb_hi) uint8; hi_scales: (E, K / gs, N) f32.
      lo_packed/lo_scales: low-bit twin, or both None for the "4/0" skip.
      critical: (E,) bool/int — True selects the high-bit buffer.
    Returns:
      (E, M, N) in ``out_dtype``; skipped experts' blocks are zero.
    """
    e, m, k = x.shape
    n = hi_packed.shape[1]
    vpb_hi = 8 // hi_bits
    assert hi_packed.shape == (e, n, k // vpb_hi), (hi_packed.shape, e, n, k)
    assert hi_scales.shape == (e, k // group_size, n)
    has_lo = lo_packed is not None
    if has_lo:
        vpb_lo = 8 // lo_bits
        assert lo_packed.shape == (e, n, k // vpb_lo)
        assert lo_scales.shape == (e, k // group_size, n)

    bm = _row_block(block_m, m, x.dtype, out_dtype)
    bn, bk = min(block_n, n), min(block_k, k)
    bk = max(group_size, (bk // group_size) * group_size)
    assert k % group_size == 0, (k, group_size)

    # zero-pad to block multiples; padded scale groups are zero => padded K
    # dequantizes to exactly 0 and padded M/N rows/cols are sliced off.
    xp = _pad_to(_pad_to(x, 1, bm), 2, bk)
    hp = _pad_to(_pad_to(hi_packed, 1, bn), 2, bk // vpb_hi)
    hs = _pad_to(_pad_to(hi_scales, 1, bk // group_size), 2, bn)
    if has_lo:
        lp = _pad_to(_pad_to(lo_packed, 1, bn), 2, bk // vpb_lo)
        ls = _pad_to(_pad_to(lo_scales, 1, bk // group_size), 2, bn)
    mp_, kp_ = xp.shape[1], xp.shape[2]
    np_ = hp.shape[1]
    nk = kp_ // bk
    grid = (e, mp_ // bm, np_ // bn, nk)

    crit = jnp.asarray(critical).astype(jnp.int32)

    def x_map(ei, i, j, kk, c):
        return (ei, i, kk)

    def hi_map(ei, i, j, kk, c):
        # non-critical experts never read their hi tile: pin it to block
        # (0,0,0) so consecutive grid steps elide the DMA entirely.
        use = c[ei] > 0
        return (jnp.where(use, ei, 0), jnp.where(use, j, 0),
                jnp.where(use, kk, 0))

    def hi_s_map(ei, i, j, kk, c):
        use = c[ei] > 0
        return (jnp.where(use, ei, 0), jnp.where(use, kk, 0),
                jnp.where(use, j, 0))

    def lo_map(ei, i, j, kk, c):
        use = c[ei] == 0
        return (jnp.where(use, ei, 0), jnp.where(use, j, 0),
                jnp.where(use, kk, 0))

    def lo_s_map(ei, i, j, kk, c):
        use = c[ei] == 0
        return (jnp.where(use, ei, 0), jnp.where(use, kk, 0),
                jnp.where(use, j, 0))

    in_specs = [
        pl.BlockSpec((1, bm, bk), x_map),
        pl.BlockSpec((1, bn, bk // vpb_hi), hi_map),
        pl.BlockSpec((1, bk // group_size, bn), hi_s_map),
    ]
    operands = [xp, hp, hs]
    if has_lo:
        in_specs += [
            pl.BlockSpec((1, bn, bk // vpb_lo), lo_map),
            pl.BlockSpec((1, bk // group_size, bn), lo_s_map),
        ]
        operands += [lp, ls]
        kernel = functools.partial(_dual_kernel, hi_bits=hi_bits,
                                   lo_bits=lo_bits, group_size=group_size,
                                   nk=nk)
    else:
        kernel = functools.partial(_skip_kernel, hi_bits=hi_bits,
                                   group_size=group_size, nk=nk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), lambda ei, i, j, kk, c: (ei, i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, mp_, np_), out_dtype),
        interpret=interpret,
    )(crit, *operands)
    return out[:, :m, :n]


# What a grouped call's blocks, double-buffered, and its accumulator may
# take of VMEM: the budget of the vmem-footprint lint (analysis/vmem.py),
# also the v5e compiler's default limit.
_VMEM_BUDGET = 16 * 2 ** 20


def _vmem_bytes(bm: int, bn: int, bk: int, hi_bits: int, lo_bits: int,
                group_size: int, dtype, out_dtype) -> int:
    """One grouped kernel call's blocks, double-buffered, and its f32
    accumulator, as the vmem-footprint lint counts them (scale rows
    padded to whole f32 sublane tiles)."""
    scales = -(-(bk // group_size) // 8) * 8 * bn * 4
    blocks = (bm * bk * jnp.dtype(dtype).itemsize
              + bm * bn * jnp.dtype(out_dtype).itemsize)
    for bits in (hi_bits, lo_bits) if lo_bits else (hi_bits,):
        blocks += bn * bk * bits // 8 + scales
    return 2 * blocks + bm * bn * 4


def _n_block(n: int, fits) -> int:
    """N itself, else the widest multiple of 128 that divides N, for which
    ``fits(bn)``; the narrowest such tile if none fits. Every tile divides
    N, so the stores are read unpadded."""
    tiles = [bn for bn in [n] + list(range(n - n % 128, 127, -128))
             if n % bn == 0]
    return next((bn for bn in tiles if fits(bn)), tiles[-1])


class GroupedTiling(NamedTuple):
    """The static tiling of one grouped kernel call: its row, N and K
    tiles, the grid ``(E * P, row blocks, N tiles, K tiles)``, the body,
    and the VMEM limit the call sets."""
    bm: int
    bn: int
    bk: int
    grid: Tuple[int, int, int, int]
    scale_after_dot: bool
    vmem_limit_bytes: int


def grouped_tiling(e: int, cap_hi: int, cap_lo: int, n: int, k: int, *,
                   hi_bits: int, lo_bits: int, group_size: int,
                   block_m: int, block_k: int, dtype,
                   out_dtype=jnp.bfloat16,
                   n_block: Optional[int] = None) -> GroupedTiling:
    """The tiling :func:`expert_quant_matmul_grouped_pallas` builds for E
    experts with precision regions of ``cap_hi`` and ``cap_lo`` rows
    (``cap_lo`` 0: the "4/0" deployment), contraction K and output width N.

    Where the body applies the scales after the dot (every decode block,
    admission waves of a few rows), one grid step covers the whole output
    width: N itself, or the widest multiple of 128 dividing N whose working
    blocks fit ``_VMEM_BUDGET``. Each grid step has a fixed cost, and a
    decode call's steps are mostly dead, so fewer steps is the gain; K is
    tiled as before, so every output sums the same partial products in the
    same order. The dequantize-first body (128-row blocks) keeps 128
    columns. Both tiles divide their extent (:func:`_k_block`,
    :func:`_n_block`), so the kernel reads the stores unpadded, in place.
    ``n_block`` forces the N tile (measurements and tests)."""
    p_ = 2 if cap_lo else 1
    bm = _row_block(block_m, max(cap_hi, cap_lo), dtype, out_dtype)
    bk = _k_block(block_k, k, group_size)
    after = _scale_after_dot(bm, bk, group_size, dtype)
    lo = lo_bits if cap_lo else 0

    def need(bn):
        return _vmem_bytes(bm, bn, bk, hi_bits, lo, group_size, dtype,
                           out_dtype)

    if n_block is not None:
        bn = min(n_block, n)
    elif after:
        bn = _n_block(n, lambda c: need(c) <= _VMEM_BUDGET)
    else:
        bn = _n_block(n, lambda c: c <= 128)
    assert n % bn == 0 and k % bk == 0, (n, bn, k, bk)
    grid = (e * p_, -(-max(cap_hi, cap_lo) // bm), n // bn, k // bk)
    # as much again for the body's temporaries and the compiler's scratch
    return GroupedTiling(bm, bn, bk, grid, after,
                         max(_VMEM_BUDGET, 2 * need(bn)))


def grouped_tables(counts: jnp.ndarray, cap_hi: int, cap_lo: int,
                   bm: int, layer) -> jnp.ndarray:
    """The grouped kernel's scalar-prefetch table: four (E * P,) int32
    rows laid end to end, then the layer its weights are read from. The
    rows: each group's live row blocks ``nb``, then, for the x blocks, the
    hi and the lo weight blocks, the last group at or before each group
    that reads that buffer (-1 where none does)."""
    p_ = 2 if cap_lo else 1
    caps = jnp.asarray((cap_hi, cap_lo)[:p_], jnp.int32)
    wm = jnp.clip(jnp.asarray(counts, jnp.int32)[:, :p_], 0, caps[None, :])
    nb = ((wm + bm - 1) // bm).reshape(-1)
    g = jnp.arange(nb.shape[0], dtype=jnp.int32)

    def last(live):
        return jax.lax.cummax(jnp.where(live, g, -1), axis=0)

    live = nb > 0
    return jnp.concatenate([nb, last(live), last(live & (g % p_ == 0)),
                            last(live & (g % p_ == 1)),
                            jnp.reshape(jnp.asarray(layer, jnp.int32),
                                        (1,))])


def grouped_index_maps(grid: Tuple[int, int, int, int], p_: int):
    """Block index maps of the grouped kernel over ``grid``, with ``p_``
    precision groups per expert, reading the table of
    :func:`grouped_tables`: x, hi codes, hi scales, lo codes, lo scales
    and the output, each ``f(g, i, j, kk, t)``. The weight maps lead with
    the table's layer: the stores are the whole stack, ``(L, E, ...)``,
    read in place.

    A step that does not read a buffer (a dead row block, or the precision
    its group does not use) maps it to the block it already holds: the
    last block of the last group before it that read it, or block 0 where
    none has, at the same layer. The pipeline elides the copy of a block
    index that repeats, so only live blocks move, plus each buffer's first
    block."""
    groups, nb_cap, nj, nk = grid

    def pick(use, live, held):
        return tuple(jnp.where(use, a, b) for a, b in zip(live, held))

    def last(t, row, g):
        q = t[row * groups + g]
        return jnp.maximum(q, 0), q >= 0

    def x_map(g, i, j, kk, t):
        q, on = last(t, 1, g)
        held = (q // p_, jnp.where(on, (q % p_) * nb_cap + t[q] - 1, 0),
                jnp.where(on, nk - 1, 0))
        return pick(i < t[g], (g // p_, (g % p_) * nb_cap + i, kk), held)

    def weights(region):
        def codes(g, i, j, kk, t):
            q, on = last(t, 2 + region, g)
            use = (g % p_ == region) & (i < t[g])
            return (t[4 * groups],) + pick(
                use, (g // p_, j, kk),
                (q // p_, jnp.where(on, nj - 1, 0),
                 jnp.where(on, nk - 1, 0)))

        def scales(g, i, j, kk, t):
            l_, e_, j_, k_ = codes(g, i, j, kk, t)
            return l_, e_, k_, j_
        return codes, scales

    def out_map(g, i, j, kk, t):
        return g // p_, (g % p_) * nb_cap + i, j

    return (x_map, *weights(0), *weights(1), out_map)


def _grouped_pallas(x, hi_packed, hi_scales, lo_packed, lo_scales, counts,
                    layer, *, cap_hi, hi_bits, lo_bits, group_size, block_m,
                    block_k, interpret, out_dtype, n_block=None):
    e, m, k = x.shape
    n_layers, _, n, _ = hi_packed.shape
    vpb_hi = 8 // hi_bits
    assert hi_packed.shape == (n_layers, e, n, k // vpb_hi), \
        (hi_packed.shape, e, n, k)
    assert hi_scales.shape == (n_layers, e, k // group_size, n)
    has_lo = lo_packed is not None
    cap_lo = m - cap_hi
    assert 0 < cap_hi <= m, (cap_hi, m)
    assert has_lo == (cap_lo > 0), (cap_hi, m, has_lo)
    if has_lo:
        vpb_lo = 8 // lo_bits
        assert lo_packed.shape == (n_layers, e, n, k // vpb_lo)
        assert lo_scales.shape == (n_layers, e, k // group_size, n)
    assert k % group_size == 0, (k, group_size)
    p_ = 2 if has_lo else 1

    tiling = grouped_tiling(e, cap_hi, cap_lo, n, k, hi_bits=hi_bits,
                            lo_bits=lo_bits, group_size=group_size,
                            block_m=block_m, block_k=block_k, dtype=x.dtype,
                            out_dtype=out_dtype, n_block=n_block)
    bm, bn, bk, grid = tiling.bm, tiling.bn, tiling.bk, tiling.grid
    after = tiling.scale_after_dot
    _, nb_cap, nj, nk = grid

    # both regions are padded to the SAME m-block count so every group's
    # output tile index stays in range regardless of the cap split
    rows = nb_cap * bm

    def region(lo_, hi_, bits):
        r = x[:, lo_:hi_]
        pad = rows - r.shape[1]
        r = jnp.pad(r, ((0, 0), (0, pad), (0, 0))) if pad else r
        return _plane_major(r, bk, bits) if after else r

    xp = region(0, cap_hi, hi_bits)
    if has_lo:
        xp = jnp.concatenate([xp, region(cap_hi, m, lo_bits)], axis=1)

    table = grouped_tables(counts, cap_hi, cap_lo, bm, layer)
    x_map, hi_map, hi_s_map, lo_map, lo_s_map, out_map = \
        grouped_index_maps(grid, p_)

    # the weights are the whole (L, E, ...) stack, read in place: a block
    # is one layer's and one expert's tile
    in_specs = [
        pl.BlockSpec((1, bm, bk), x_map),
        pl.BlockSpec((1, 1, bn, bk // vpb_hi), hi_map),
        pl.BlockSpec((1, 1, bk // group_size, bn), hi_s_map),
    ]
    operands = [xp, hi_packed, hi_scales]
    if has_lo:
        in_specs += [
            pl.BlockSpec((1, 1, bn, bk // vpb_lo), lo_map),
            pl.BlockSpec((1, 1, bk // group_size, bn), lo_s_map),
        ]
        operands += [lo_packed, lo_scales]
        kernel = functools.partial(_grouped_dual_kernel, hi_bits=hi_bits,
                                   lo_bits=lo_bits, group_size=group_size,
                                   nk=nk, scale_after_dot=after)
    else:
        kernel = functools.partial(_grouped_skip_kernel, hi_bits=hi_bits,
                                   group_size=group_size, nk=nk,
                                   scale_after_dot=after)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), out_map),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, p_ * rows, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=tiling.vmem_limit_bytes),
        interpret=interpret,
    )(table, *operands)
    if has_lo:
        return jnp.concatenate(
            [out[:, :cap_hi], out[:, rows:rows + cap_lo]], axis=1)
    return out[:, :m]


@functools.partial(
    jax.jit,
    static_argnames=("cap_hi", "hi_bits", "lo_bits", "group_size",
                     "block_m", "block_k", "interpret", "out_dtype"),
)
def expert_quant_matmul_grouped_pallas(
        x: jnp.ndarray, hi_packed: jnp.ndarray, hi_scales: jnp.ndarray,
        lo_packed: Optional[jnp.ndarray], lo_scales: Optional[jnp.ndarray],
        counts: jnp.ndarray, layer, *, cap_hi: int, hi_bits: int,
        lo_bits: int, group_size: int, block_m: int = 128,
        block_k: int = 512, interpret: bool = False,
        out_dtype=jnp.bfloat16) -> jnp.ndarray:
    """ONE dispatch over a combined dual-precision capacity buffer with a
    live-row ragged grid, reading layer ``layer`` of a layer-stacked store
    in place.

    The stores are the whole stack: ``hi_packed`` (L, E, N, K / vpb),
    ``hi_scales`` (L, E, K / gs, N), the low twins alike. ``layer`` (an
    int32 scalar, traced) rides in the scalar prefetch and leads every
    weight block index, so no layer's store is sliced out, and none is
    padded, before the call.

    ``x`` (E, M, K) packs BOTH precision regions of the dual-buffer per-row
    MoE dispatch per expert: high-precision slots occupy ``[0, cap_hi)``
    and low-precision slots ``[cap_hi, M)``. The grid is
    ``(E * P, M_region/bm, N/bn, K/bk)`` with P precision groups (2, or 1
    when ``lo_packed is None`` — the "4/0" lo group is elided at grid
    construction): each grid step streams exactly one precision's packed
    codes, so both buffers execute in a single ``pallas_call`` with no
    second dispatch and no second weight unpack. The tiles come from the
    shapes (:func:`grouped_tiling`).

    ``counts`` (E, 2) int32 are per-(expert, precision-group) live-slot
    watermarks: a group's m-blocks at or beyond ``ceil(count/bm)`` are
    DEAD — their index maps repeat the blocks already held
    (:func:`grouped_index_maps`), so they move no bytes, and the kernel
    body skips the unpack + MXU work outright, so finished/evicted/padded
    rows cost no FLOPs and no weight I/O. Contract: slots at or beyond a
    group's watermark must be zero-filled (the dispatch scatter guarantees
    this), so a skipped block's zero output equals its dot exactly.

    Returns (E, M, N) in ``out_dtype``, region layout matching ``x``.
    """
    return _grouped_pallas(
        x, hi_packed, hi_scales, lo_packed, lo_scales, counts, layer,
        cap_hi=cap_hi, hi_bits=hi_bits, lo_bits=lo_bits,
        group_size=group_size, block_m=block_m, block_k=block_k,
        interpret=interpret, out_dtype=out_dtype)
