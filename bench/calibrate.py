#!/usr/bin/env python3
"""Readings for the limits of ``correct``: runs one cell on several seeds
in one process, each with the numbers compared (``logit_gap`` and
``mean_gap_ratio``, ``bench/harness/correct.py``), and per served position
the gaps and the reference's top-two margins; with ``--control``, the same
numbers for each control at the same positions: the reference with its
matmul operands in float8 (``fp8``) and with every expert at the low
precision (``experts_low``), each judged by the cell's limits as the
program is (``readings.<control>.correct``, which has to come out false).

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 \\
        --seconds 20 [--control] [--trace-first] [--out <file.json>]

The first seed is a whole run, as ``bench/run.py`` makes it; later seeds
skip the shape warm-up (their programs load from the compilation cache as
traffic reaches them), which changes the timing, not what is served. The
benchmark's own runs never run this. ``PERF.md`` lists the readings each
limit in ``bench/limits/`` was set from.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
# readings per served position: kept in --out, left out of stdout
PER_POSITION = ("gaps", "margins")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", action="store_true",
                    help="also read the controls: experts_low and fp8")
    ap.add_argument("--trace-first", action="store_true",
                    help="trace the first seed's window and keep an excerpt")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from bench.harness import cell as cell_mod
    from bench.run import find, load_benchmark

    bench = load_benchmark()
    cell = find(bench["workloads"], args.workload, "workload")
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        trace = args.trace_first and i == 0
        save = (str(Path(args.out).with_suffix(".trace.json.gz"))
                if trace and args.out else None)
        out = cell_mod.run(bench, cell, seed=seed, seconds=args.seconds,
                           trace=trace, t_process=T_PROCESS if i == 0 else t,
                           controls=(("experts_low", "fp8")
                                     if args.control else ()),
                           save_trace=save, warm=i == 0)
        row = {"seed": seed, "correct": out["result"]["correct"],
               "checks": out["result"]["checks"],
               "readings": out["readings"],
               "metrics": out["result"]["metrics"],
               "device": out["result"]["device"],
               "breakdown": out["result"].get("breakdown"),
               "reference_s": out["reference_s"],
               "run_s": time.perf_counter() - t}
        rows.append(row)
        print(json.dumps({**row, "readings": {
            k: {kk: vv for kk, vv in v.items() if kk not in PER_POSITION}
            for k, v in row["readings"].items()}}), flush=True)
        if args.out:
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
