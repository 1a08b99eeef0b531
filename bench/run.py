#!/usr/bin/env python3
"""Run one benchmark cell of DyMoE's serving path on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``). The run builds the weights on the device
from the seed, opens ``DyMoEEngine.serve()`` with the engine's defaults,
warms every admission-wave and decode shape the mix can produce, runs the
mix for its warm-up time, then measures for ``--seconds``. With
``--trace 1`` the window is traced and the cell's per-layer metrics
(``bench/metrics/<metric>.py``) are read from the trace instead of the
end-to-end ones. Afterwards the plain reference
(``bench/references/<family>.py``) checks a sample of the served tokens.

The last stdout line is the result, one JSON object; the numbers compared
for ``correct`` are the last lines of stderr and the last key of the
result. Without a TPU (or fewer chips than the cell asks for), or outside a
checkout of the repository, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _setup_paths() -> None:
    if not (ROOT / "src" / "repro").is_dir():
        raise FileNotFoundError(
            f"no program beside the benchmark: {ROOT / 'src' / 'repro'} is "
            "missing (run from a checkout of the repository)")
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(path.read_text())


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        _setup_paths()
        bench = load_benchmark()
        cell = find(bench["workloads"], args.workload, "workload")
        from bench.harness import cell as cell_mod
    except Exception as e:   # noqa: BLE001 — not a checkout: no result
        print(f"run: {e}", file=sys.stderr, flush=True)
        return 2
    try:
        out = cell_mod.run(bench, cell, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), t_process=T_PROCESS)
    except cell_mod.NoChip as e:
        print(f"run: {e}", file=sys.stderr, flush=True)
        return 3
    except Exception as e:   # noqa: BLE001 — a failed run prints no result
        import traceback
        traceback.print_exc()
        print(f"run: failed: {e!r}", file=sys.stderr, flush=True)
        return 1
    for line in out["check_lines"]:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
