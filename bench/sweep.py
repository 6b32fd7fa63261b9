#!/usr/bin/env python3
"""One-off sweeps that fix numbers written into the benchmark's files.
None is run by the benchmark itself.

    python3 bench/sweep.py knee --workload sift1m.serve --rates 100,150,200 --seconds 10
    python3 bench/sweep.py nprobe --workload sift1m.batch --nprobes 16,32,64 --seconds 8
    python3 bench/sweep.py seeds --workload sift1m.batch --seeds 1,2,3 --seconds 3 [--system control]

``knee``: builds the cell's index once, opens its gateway, and offers
each rate in turn through the open-loop generator for ``--seconds``.
Per rate it prints the achieved rate, p50/p99 from due time, generator
lateness and whether a backlog grew (the median latency of the last
quarter of requests against the first).  The knee is the highest rate
at which achieved ~ offered (97%) and no backlog grows (the last
quarter's median latency within 1.5x the first's, plus 5 ms), with every
lower rate so too; a serve mix offers 0.8 of it.

``nprobe``: builds the cell's index once and runs its batch window at
each nprobe (the program's default scan budget for each), printing
recall@10 against the exact reference and the rate.

``seeds``: runs the cell once per seed in one process, as the program or
as the control, and prints each run's compared numbers.  The program's
largest reading of a number over a dozen seeds and the control's
smallest set that number's limit (PERF.md).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("knee", "nprobe", "seeds"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--nprobes", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--system", choices=("program", "control"),
                    default="program")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np
    from bench import harness, reference, traffic
    from bench.corpus import make_corpus
    from bench.system import ProgramSystem
    bench = harness.load_benchmark(ROOT)
    cell = harness.find_cell(bench, args.workload)
    try:
        _, peaks = harness.require_chip(cell["chips"])
        harness.enable_compile_cache()
    except harness.HarnessError as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    if args.mode == "seeds":
        for seed in [int(s) for s in args.seeds.split(",")]:
            r = harness.run_cell(ROOT, args.workload, seed, args.seconds,
                                 system=args.system, bench=bench, peaks=peaks)
            print(json.dumps({"seed": seed, "system": args.system,
                              "correct": r["correct"], "checks": r["checks"],
                              "attempted": r["attempted"],
                              "failed": r["failed"]}), flush=True)
        return 0
    cfg = harness.load_config(ROOT, bench, cell["config"])
    mix = harness.load_mix(ROOT, cell["traffic"])
    x, pool_dev = make_corpus(cfg, args.seed)
    pool = np.asarray(pool_dev)
    system = ProgramSystem(cfg, x)
    gt = reference.exact_topk(x, pool_dev, cfg["search"]["k"], cfg["metric"])
    x_host = np.asarray(x)

    def recall(win):
        return reference.compare(
            win.qidx, win.ids, win.dists, gt, x_host, pool, cfg["metric"],
            win.failed, cfg["correct"])["recall_at_10"]["value"]

    if args.mode == "knee":
        gw = system.open_gateway(mix.get("gateway", {}))
        system.warm_flushes(pool, int(mix.get("warm_flush_sizes", 1 << 30)))
        traffic.run_open_loop(gw.submit, pool, dict(mix, rate_qps=200.0),
                              1.0)
        for rate in [float(r) for r in args.rates.split(",")]:
            m = dict(mix, rate_qps=rate)
            t = time.perf_counter()
            before = system.counters()["dispatches"]
            win = traffic.run_open_loop(gw.submit, pool, m, args.seconds)
            lat = win.latency_s
            q = max(len(lat) // 4, 1)
            first = float(np.median(lat[:q]))
            last = float(np.median(lat[-q:]))
            print(json.dumps({
                "rate_qps": rate, "requests": win.attempted,
                "failed": win.failed,
                "achieved_qps": len(win.qidx) / win.seconds,
                "p50_ms": traffic.percentile(lat, 50) * 1e3,
                "p99_ms": traffic.percentile(lat, 99) * 1e3,
                "first_quarter_p50_ms": first * 1e3,
                "last_quarter_p50_ms": last * 1e3,
                "lateness_p99_ms": traffic.percentile(win.lateness_s, 99) * 1e3,
                "mean_batch": float(len(win.qidx)) / max(
                    system.counters()["dispatches"] - before, 1),
                "recall_at_10": recall(win),
                "wall_s": time.perf_counter() - t}), flush=True)
    else:
        for nprobe in [int(p) for p in args.nprobes.split(",")]:
            params = dataclasses.replace(system.params, nprobe=nprobe,
                                         max_scan=None)
            sess = system.index.searcher(params)

            def search(q, sess=sess):
                r = sess(q)
                return r.ids, r.dists
            np.asarray(search(pool[:int(mix["batch"])])[0])
            win = traffic.run_batch(search, pool, mix, args.seconds)
            print(json.dumps({
                "nprobe": nprobe, "max_scan": sess.params.max_scan,
                "qps": len(win.qidx) / win.seconds,
                "recall_at_10": recall(win)}), flush=True)
    system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
