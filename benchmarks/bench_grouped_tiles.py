"""Grouped expert kernel on the chip: time of one call by N tile and live
share, at the decode shapes of the served configurations.

    python benchmarks/bench_grouped_tiles.py [--out FILE] [--reps N]

Each call is one expert matmul of a decode step: E experts, a 4-bit and a
2-bit region of ``rows`` rows each, the 2-bit region dead (as at decode),
and the 4-bit region live in none, in a cell's share, or in all of the
experts. The kernel reads one layer in place from a stack of ``LAYERS``,
at the K the stores are kept at (``expert_store_k``: Qwen3's and
Moonlight's ``w_down`` padded once to 1024 and 1536). The N tile is
forced to each of 128, 256, 512 and N that divide N, so the time of a
dead grid step and the extra time of a live one can be read apart: a
dead step's cost from the calls with nothing live, a live step's from
the difference to the calls with everything live. It also checks that
every tile's output equals the 128-column tile's, bit for bit. Prints
one JSON object per call and exits non-zero without a TPU.
"""
from __future__ import annotations

import argparse
import functools
import importlib
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

eqm = importlib.import_module(
    "repro.kernels.quant_matmul.expert_quant_matmul")
store_k = importlib.import_module("repro.models.layers.moe").expert_store_k

GROUP = 64
LAYERS = 4
# (config, experts, rows a region, experts live at a decode step, (K, N)s)
SHAPES = (
    ("olmoe-1b-7b", 64, 16, 55, ((2048, 1024), (1024, 2048))),
    ("qwen3-30b-a3b", 128, 16, 81, ((2048, 768), (768, 2048))),
    ("moonlight-16b-a3b", 64, 8, 35, ((2048, 1408), (1408, 2048))),
)


def tiles(n: int):
    return [b for b in (128, 256, 512) if n % b == 0 and b < n] + [n]


def operands(rng, e, rows, k, n):
    """x, then the stacked 4-bit and 2-bit codes and scales."""
    def store(bits):
        return (jnp.asarray(rng.integers(
                    0, 256, (LAYERS, e, n, k * bits // 8), dtype=np.uint8)),
                jnp.asarray(rng.uniform(
                    0.01, 0.02, (LAYERS, e, k // GROUP, n)), jnp.float32))
    x = jnp.asarray(rng.standard_normal((e, 2 * rows, k)), jnp.bfloat16)
    return (x, *store(4), *store(2))


def counts_for(rng, e, rows, live):
    c = np.zeros((e, 2), np.int32)
    c[rng.choice(e, live, replace=False), 0] = rows
    return jnp.asarray(c)


def timed(f, args, reps):
    """Seconds a call, from ``reps`` calls enqueued back to back (the best
    of three such runs); the output of the first call."""
    out = f(*args)
    out.block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            y = f(*args)
        y.block_until_ready()
        best = min(best, (time.perf_counter() - t0) / reps)
    return best, np.asarray(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=None)
    ap.add_argument("--reps", type=int, default=50)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: {dev.platform}", file=sys.stderr)
        return 1
    sink = open(args.out, "w") if args.out else None
    rng = np.random.default_rng(0)
    ok = True
    for cfg, e, rows, live_cell, shapes in SHAPES:
        for k, n in shapes:
            k = store_k(k, GROUP)
            ops = operands(rng, e, rows, k, n)
            lives = {0: counts_for(rng, e, rows, 0),
                     live_cell: counts_for(rng, e, rows, live_cell),
                     e: counts_for(rng, e, rows, e)}
            base = {}
            for bn in tiles(n):
                f = jax.jit(functools.partial(
                    eqm._grouped_pallas, cap_hi=rows, hi_bits=4, lo_bits=2,
                    group_size=GROUP, block_m=32, block_k=512,
                    interpret=False, out_dtype=jnp.bfloat16, n_block=bn))
                t = eqm.grouped_tiling(
                    e, rows, rows, n, k, hi_bits=4, lo_bits=2,
                    group_size=GROUP, block_m=32, block_k=512,
                    dtype=jnp.bfloat16, n_block=bn)
                steps = int(np.prod(t.grid))
                for live, c in lives.items():
                    sec, out = timed(f, (*ops[:5], c, jnp.int32(1)),
                                     args.reps)
                    if bn == 128:
                        base[live] = out
                    same = bool(np.array_equal(out, base[live]))
                    ok &= same
                    rec = dict(config=cfg, k=k, n=n, experts=e, rows=rows,
                               bn=bn, live_experts=live, grid=t.grid,
                               steps=steps,
                               live_steps=live * steps // (2 * e),
                               us_per_call=sec * 1e6,
                               equal_to_bn128=same,
                               device=dev.device_kind)
                    print(json.dumps(rec), flush=True)
                    if sink:
                        sink.write(json.dumps(rec) + "\n")
    if sink:
        sink.close()
    return 0 if ok else 2


if __name__ == "__main__":
    sys.exit(main())
