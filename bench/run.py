#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the accelerator this machine has.

    python3 bench/run.py --workload sift1m.batch --seed 7 --seconds 10 --trace 0

Set-up (counted in ``setup_s`` from process start): the corpus and
query pool from ``--seed``, generated on the device; the program's index
build; warm-up of every executable the cell's traffic uses (served from
JAX's persistent compilation cache after the first run in a checkout).
Then the window of ``--seconds``, the check of every answer against the
exact reference, and one JSON line on standard output (``--trace 1``:
the per-layer metrics from a profiler trace of the window instead of
the end-to-end ones).

Exits 2, printing no result, when JAX finds no TPU, fewer chips than the
cell asks for, a device kind missing from ``bench/peaks.json``, or no
program next to the benchmark.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    try:
        bench = harness.load_benchmark(ROOT)
        cell = harness.find_cell(bench, args.workload)
    except (OSError, ValueError, harness.HarnessError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    try:
        devices, peaks = harness.require_chip(cell["chips"])
        cache_dir = harness.enable_compile_cache()
    except harness.HarnessError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.log(f"device {devices[0].device_kind} x{len(devices)}, "
                f"compile cache {cache_dir}")
    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              trace=bool(args.trace), t_start=T_START,
                              bench=bench, peaks=peaks)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
