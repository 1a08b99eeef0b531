# One function per paper table/figure. Prints a flat CSV of every row.
"""Benchmark harness entrypoint: ``PYTHONPATH=src python -m benchmarks.run``

| module                  | paper artifact                         |
|-------------------------|----------------------------------------|
| bench_kernels           | §5 quantized expert kernel             |
| bench_uniform_quant     | Table 1 (uniform Int2/Int4/BF16)       |
| bench_retention         | Table 2 / Fig. 11 (4/2 vs 4/0 × r)     |
| bench_strategies        | Fig. 3 (retention strategies)          |
| bench_layer_sensitivity | Fig. 5 (layer-wise Int2 sensitivity)   |
| bench_layer_similarity  | Fig. 6 (adjacent-layer similarity)     |
| bench_e2e_latency       | Fig. 10 (TTFT/TPOT vs baselines)       |
| bench_ablation          | Table 3 (component ablation)           |
"""
from __future__ import annotations

import csv
import importlib
import io
import sys
import time

MODULES = [
    "bench_kernels",
    "bench_uniform_quant",
    "bench_retention",
    "bench_strategies",
    "bench_layer_sensitivity",
    "bench_layer_similarity",
    "bench_e2e_latency",
    "bench_ablation",
]


def main() -> None:
    only = sys.argv[1] if len(sys.argv) > 1 else None
    all_rows = []
    for name in MODULES:
        if only and only not in name:
            continue
        mod = importlib.import_module(f"benchmarks.{name}")
        t0 = time.perf_counter()
        try:
            rows = mod.run()
        except Exception as e:  # report, keep going
            rows = [dict(bench=name, error=str(e)[:200])]
        dt = time.perf_counter() - t0
        print(f"# {name}: {len(rows)} rows in {dt:.1f}s", file=sys.stderr)
        all_rows.extend(rows)

    keys = []
    for r in all_rows:
        for k in r:
            if k not in keys:
                keys.append(k)
    buf = io.StringIO()
    writer = csv.DictWriter(buf, fieldnames=keys)
    writer.writeheader()
    writer.writerows(all_rows)
    print(buf.getvalue())


if __name__ == "__main__":
    main()
