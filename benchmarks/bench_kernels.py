"""Kernel-level microbenchmark: quant_matmul traffic model + oracle match.

On this CPU container the Pallas kernels run in interpret mode (Python), so
wall-clock is meaningless for the TPU target; what IS meaningful and
reported here:
  * correctness (max |err| vs the jnp oracle) across bit widths — the ref
    and interpret-mode timings are reported SEPARATELY and labeled as such,
  * the HBM traffic each configuration implies (the quantity DyMoE's
    latency model rides on). For the grouped ``expert_quant_matmul`` rows
    the bytes-moved column follows the critical mask: each Critical expert
    moves its high-bit packed blob, each Sub-critical one its low-bit blob
    (or nothing in the "4/0" skip deployment) — ≈ bits/16 of the bf16
    baseline per expert plus scales, versus the 2x-bf16 the old
    dequantize-everything-and-where path materialized.
"""
from __future__ import annotations

import functools
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.quant_matmul.ops import (expert_quant_matmul,
                                            expert_quant_matmul_fixed,
                                            expert_quant_matmul_grouped,
                                            quant_matmul)
from repro.quant import MixedPrecisionWeights, QuantizedTensor


def _time_us(fn, *args, **kwargs):
    """(steady-state us of one jitted call, its output) — compile paid in
    warmup; the warmup output doubles as the value for the oracle check."""
    jfn = jax.jit(functools.partial(fn, **kwargs))
    out = jfn(*args)
    out.block_until_ready()                          # warmup / compile
    t0 = time.perf_counter()
    jfn(*args).block_until_ready()
    return (time.perf_counter() - t0) * 1e6, out


def run_dense() -> List[dict]:
    rng = np.random.default_rng(0)
    m, k, n = 64, 1024, 256
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    bf16_bytes = k * n * 2
    rows = []
    for bits in (8, 4, 2):
        qt = QuantizedTensor.quantize(w, bits, 64)
        t_ref, ref = _time_us(quant_matmul, x, qt, impl="ref",
                              out_dtype=jnp.float32)
        t_int, pal = _time_us(quant_matmul, x, qt, impl="pallas",
                              interpret=True, block_m=32, block_n=64,
                              block_k=256, out_dtype=jnp.float32)
        err = float(jnp.abs(ref - pal).max())
        rows.append(dict(
            bench="kernels", kernel="quant_matmul", bits=bits,
            us_per_call_ref=round(t_ref, 1),
            us_per_call_interpret=round(t_int, 1),
            max_err_vs_oracle=err,
            bytes_moved=qt.nbytes(),
            hbm_traffic_ratio=round(qt.nbytes() / bf16_bytes, 4)))
    return rows


def run_grouped() -> List[dict]:
    rng = np.random.default_rng(1)
    e, m, k, n = 8, 32, 512, 128
    x = jnp.asarray(rng.standard_normal((e, m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((e, k, n)), jnp.float32)
    bf16_bytes = e * k * n * 2                 # one dense bf16 copy
    legacy_bytes = 2 * bf16_bytes              # old path: hi AND lo dense
    rows = []
    for hi_bits, lo_bits in ((4, 2), (8, 4), (4, 0)):
        mp = MixedPrecisionWeights.build(w, hi_bits, lo_bits or None, 64)
        per_hi = mp.high.nbytes() // e
        per_lo = (mp.low.nbytes() // e) if mp.low is not None else 0
        for crit_frac in (1.0, 0.5, 0.0):
            n_hi = int(round(e * crit_frac))
            mask = jnp.arange(e) < n_hi
            t_ref, ref = _time_us(expert_quant_matmul, x, mp, mask,
                                  impl="ref", out_dtype=jnp.float32)
            t_int, pal = _time_us(expert_quant_matmul, x, mp, mask,
                                  impl="pallas", interpret=True, block_m=32,
                                  block_n=64, block_k=256,
                                  out_dtype=jnp.float32)
            err = float(jnp.abs(ref - pal).max())
            moved = n_hi * per_hi + (e - n_hi) * per_lo
            rows.append(dict(
                bench="kernels", kernel="expert_quant_matmul",
                hi_bits=hi_bits, lo_bits=lo_bits, crit_frac=crit_frac,
                us_per_call_ref=round(t_ref, 1),
                us_per_call_interpret=round(t_int, 1),
                max_err_vs_oracle=err,
                bytes_moved=moved,
                hbm_traffic_ratio=round(moved / bf16_bytes, 4),
                legacy_dense_ratio=round(legacy_bytes / bf16_bytes, 4)))
    return rows


def run_fused() -> List[dict]:
    """Fused single-dispatch dual-buffer kernel vs the two-launch pair it
    replaces. Reported per (bit-mix, live fraction):
      * dispatches: 1 vs 2 kernel launches per expert matmul,
      * weight bytes: each live row-block of a (expert, precision) group
        streams that expert's packed blob once (the grid is
        (groups, M/bm, N/bn, K/bk); blocks at/beyond the live-slot
        watermark skip their weight tiles outright), while the dual path
        runs every group at FULL capacity — ``weight_bytes_ratio`` is
        fused/dual, the modeled traffic win for a draining batch,
      * parity (max |err| of the fused output vs the dual composition on
        the region slices — bitwise 0.0 for the ref leg by contract).
    """
    rng = np.random.default_rng(2)
    e, cap, k, n = 8, 16, 512, 128
    w = jnp.asarray(rng.standard_normal((e, k, n)), jnp.float32)
    rows = []
    for hi_bits, lo_bits in ((4, 2), (8, 4), (4, 0)):
        mp = MixedPrecisionWeights.build(w, hi_bits, lo_bits or None, 64)
        has_lo = mp.low is not None
        m = 2 * cap if has_lo else cap
        per_hi = mp.high.nbytes() // e
        per_lo = (mp.low.nbytes() // e) if has_lo else 0
        for live_frac in (1.0, 0.5, 0.125):
            n_live = max(1, int(round(cap * live_frac)))
            x = np.zeros((e, m, k), np.float32)
            counts = np.zeros((e, 2), np.int32)
            for ei in range(e):           # live slots pack from 0
                counts[ei, 0] = n_live
                x[ei, :n_live] = rng.standard_normal((n_live, k))
                if has_lo:
                    counts[ei, 1] = n_live
                    x[ei, cap:cap + n_live] = rng.standard_normal(
                        (n_live, k))
            x = jnp.asarray(x)
            cj = jnp.asarray(counts)
            t_ref, ref = _time_us(expert_quant_matmul_grouped, x, mp, cj,
                                  cap_hi=cap, impl="ref",
                                  out_dtype=jnp.float32)
            t_int, pal = _time_us(expert_quant_matmul_grouped, x, mp, cj,
                                  cap_hi=cap, impl="pallas", interpret=True,
                                  block_m=4, block_k=256,
                                  out_dtype=jnp.float32)
            y_hi = expert_quant_matmul_fixed(x[:, :cap], mp.high,
                                             impl="ref",
                                             out_dtype=jnp.float32)
            dual = (jnp.concatenate(
                [y_hi, expert_quant_matmul_fixed(x[:, cap:], mp.low,
                                                 impl="ref",
                                                 out_dtype=jnp.float32)],
                axis=1) if has_lo else y_hi)
            err_ref = float(jnp.abs(ref - dual).max())
            err_int = float(jnp.abs(pal - dual).max())
            bm = 4                       # block_m of the timed call
            nb_live = -(-n_live // bm)
            nb_full = -(-cap // bm)
            fused_bytes = e * nb_live * (per_hi + per_lo)
            dual_bytes = e * nb_full * (per_hi + per_lo)
            rows.append(dict(
                bench="kernels", kernel="expert_quant_matmul_grouped",
                hi_bits=hi_bits, lo_bits=lo_bits, live_frac=live_frac,
                dispatches_fused=1, dispatches_dual=2 if has_lo else 1,
                us_per_call_ref=round(t_ref, 1),
                us_per_call_interpret=round(t_int, 1),
                max_err_fused_vs_dual_ref=err_ref,
                max_err_fused_vs_dual_interpret=err_int,
                weight_bytes_fused=fused_bytes,
                weight_bytes_dual=dual_bytes,
                weight_bytes_ratio=round(fused_bytes / dual_bytes, 4)))
    return rows


def run() -> List[dict]:
    return run_dense() + run_grouped() + run_fused()


if __name__ == "__main__":
    for r in run():
        print(r)
