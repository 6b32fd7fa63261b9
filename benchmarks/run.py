"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One function per paper table/figure (see DESIGN.md §6).  Prints
``name,us_per_call,derived`` CSV; raw rows go to benchmarks/results/.
``--full`` widens datasets/queries; ``--only fig8`` runs one bench.

The engine bench additionally writes a machine-readable
``BENCH_engine.json`` at the repo root (recall / QPS / DCO per
exec-mode x nprobe config, plus searcher compile-cache stats) so the
perf trajectory is tracked across PRs instead of only printed.  The
stream bench does the same with ``BENCH_stream.json`` (append
throughput delta-path vs legacy rebuild, layout-build count — must be
0 on the delta path —, compaction cost, recall under churn), and the
distributed bench with ``BENCH_dist.json`` (recall / QPS / DCO of
``ShardedIndex`` sessions vs device count for both exec modes; sweep
wider by setting ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
before the run), and the fused scan->top-k bench with
``BENCH_fused.json`` (modeled scan-stage HBM traffic fused vs unfused
plus QPS per exec mode — the CI ``kernel-smoke`` guard), and the
gateway serving bench with ``BENCH_serve.json`` (deadline-batched vs
per-request throughput and p50/p99 latency per open-loop offered load
point — the CI ``gateway-smoke`` guard), and the stage-trace bench
with ``BENCH_trace.json`` (per-stage wall-time/DCO breakdown from
tracer spans with >= 95% dispatch-time attribution asserted,
single-host and sharded — the stage-attributed view of the
BENCH_dist.json multi-device cliff; DESIGN.md §11), and the two-tier
quantization-ladder bench with ``BENCH_refine.json`` (backend x
refine_factor x nprobe sweep: recall and the weighted total-ops model
vs single-tier, rf=1 bitwise-parity count, and the frontier config —
the CI ``refine-smoke`` guard; DESIGN.md §12), and the
overload-resilience bench with ``BENCH_overload.json`` (unbounded vs
bounded-admission vs degradation-ladder serving at 0.5/1/2x the
measured saturating load: typed shed/deadline accounting, answered
recall vs the documented floor, ladder engagement — the CI
``chaos-smoke`` guard; DESIGN.md §13).

``benchmarks/check_regression.py`` consumes the committed BENCH_*.json
files and gates CI on machine-checkable invariants (never wall-clock).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

from . import suite

BENCH_JSON_DEFAULT = os.path.join(
    os.path.dirname(__file__), "..", "BENCH_engine.json")
STREAM_JSON_DEFAULT = os.path.join(
    os.path.dirname(__file__), "..", "BENCH_stream.json")
DIST_JSON_DEFAULT = os.path.join(
    os.path.dirname(__file__), "..", "BENCH_dist.json")
PLAN_JSON_DEFAULT = os.path.join(
    os.path.dirname(__file__), "..", "BENCH_plan.json")
FUSED_JSON_DEFAULT = os.path.join(
    os.path.dirname(__file__), "..", "BENCH_fused.json")
SERVE_JSON_DEFAULT = os.path.join(
    os.path.dirname(__file__), "..", "BENCH_serve.json")
TRACE_JSON_DEFAULT = os.path.join(
    os.path.dirname(__file__), "..", "BENCH_trace.json")
REFINE_JSON_DEFAULT = os.path.join(
    os.path.dirname(__file__), "..", "BENCH_refine.json")
OVERLOAD_JSON_DEFAULT = os.path.join(
    os.path.dirname(__file__), "..", "BENCH_overload.json")
BENCH_JSON_SCHEMA_VERSION = 1
STREAM_JSON_SCHEMA_VERSION = 1
DIST_JSON_SCHEMA_VERSION = 1
PLAN_JSON_SCHEMA_VERSION = 1
FUSED_JSON_SCHEMA_VERSION = 1
SERVE_JSON_SCHEMA_VERSION = 1
TRACE_JSON_SCHEMA_VERSION = 1
REFINE_JSON_SCHEMA_VERSION = 1
OVERLOAD_JSON_SCHEMA_VERSION = 1


def _write_summary_json(label: str, schema_version: int, body: dict,
                        dataset: str, path: str) -> None:
    """Shared writer for every committed BENCH_*.json (one format:
    schema_version + dataset + bench body, trailing newline)."""
    payload = {
        "schema_version": schema_version,
        "dataset": dataset,
        **body,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
        f.write("\n")
    sys.stderr.write(f"[{label} json -> {os.path.abspath(path)}]\n")


def write_bench_json(engine_out: dict, dataset: str, path: str) -> None:
    """Flatten the exec-mode sweep into per-config rows and persist."""
    configs = []
    for mode in ("paged", "grouped"):
        for row in engine_out.get(mode, ()):
            configs.append({
                "config": f"{mode}/nprobe{row['nprobe']}",
                "exec_mode": mode,
                "nprobe": row["nprobe"],
                "recall": row["recall"],
                "qps": row["qps"],
                "dco": row["dco"],
            })
    _write_summary_json("bench", BENCH_JSON_SCHEMA_VERSION, {
        "id_mismatch_points": engine_out.get("id_mismatch_points"),
        "searcher": engine_out.get("searcher", {}),
        "configs": configs,
    }, dataset, path)


def write_stream_json(stream_out: dict, dataset: str, path: str) -> None:
    """Persist the streaming bench (append/compact/churn) summary."""
    _write_summary_json("stream", STREAM_JSON_SCHEMA_VERSION, stream_out,
                        dataset, path)


def write_dist_json(dist_out: dict, dataset: str, path: str) -> None:
    """Persist the distributed scaling bench summary."""
    import jax
    _write_summary_json("dist", DIST_JSON_SCHEMA_VERSION, {
        "devices_available": len(jax.devices()),
        "platform": jax.devices()[0].platform,
        **dist_out,
    }, dataset, path)


def write_plan_json(plan_out: dict, dataset: str, path: str) -> None:
    """Persist the locality-aware planning bench (union sizes, plan-cache
    hit rates, clustered-vs-paged QPS, delta-routing cost)."""
    _write_summary_json("plan", PLAN_JSON_SCHEMA_VERSION, plan_out,
                        dataset, path)


def write_fused_json(fused_out: dict, dataset: str, path: str) -> None:
    """Persist the fused scan->top-k bench (modeled scan-stage HBM
    traffic fused vs unfused + QPS per exec mode)."""
    _write_summary_json("fused", FUSED_JSON_SCHEMA_VERSION, fused_out,
                        dataset, path)


def write_serve_json(serve_out: dict, dataset: str, path: str) -> None:
    """Persist the gateway serving bench (deadline-batched vs
    per-request throughput + p50/p99 per offered load point)."""
    _write_summary_json("serve", SERVE_JSON_SCHEMA_VERSION, serve_out,
                        dataset, path)


def write_trace_json(trace_out: dict, dataset: str, path: str) -> None:
    """Persist the stage-trace bench (per-stage time/DCO breakdown and
    attribution, single-host + sharded — DESIGN.md §11)."""
    import jax
    _write_summary_json("trace", TRACE_JSON_SCHEMA_VERSION, {
        "devices_available": len(jax.devices()),
        "platform": jax.devices()[0].platform,
        **trace_out,
    }, dataset, path)


def write_refine_json(refine_out: dict, dataset: str, path: str) -> None:
    """Persist the two-tier quantization-ladder bench (backend x
    refine_factor x nprobe sweep: recall vs modeled total-ops reduction
    against single-tier, plus the rf=1 bitwise-parity count)."""
    _write_summary_json("refine", REFINE_JSON_SCHEMA_VERSION, refine_out,
                        dataset, path)


def write_overload_json(overload_out: dict, dataset: str, path: str) -> None:
    """Persist the overload-resilience bench (bounded admission vs
    unbounded at 0.5/1/2x saturating load: typed shed/deadline
    accounting, degradation-ladder engagement, answered recall vs the
    documented floor — DESIGN.md §13)."""
    _write_summary_json("overload", OVERLOAD_JSON_SCHEMA_VERSION,
                        overload_out, dataset, path)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", type=str, default=None)
    ap.add_argument("--bench-json", type=str, default=BENCH_JSON_DEFAULT,
                    help="where the engine bench writes its machine-readable "
                         "summary ('' disables)")
    ap.add_argument("--stream-json", type=str, default=STREAM_JSON_DEFAULT,
                    help="where the stream bench writes its machine-readable "
                         "summary ('' disables)")
    ap.add_argument("--dist-json", type=str, default=DIST_JSON_DEFAULT,
                    help="where the distributed bench writes its machine-"
                         "readable summary ('' disables)")
    ap.add_argument("--plan-json", type=str, default=PLAN_JSON_DEFAULT,
                    help="where the planning bench writes its machine-"
                         "readable summary ('' disables)")
    ap.add_argument("--fused-json", type=str, default=FUSED_JSON_DEFAULT,
                    help="where the fused scan->top-k bench writes its "
                         "machine-readable summary ('' disables)")
    ap.add_argument("--serve-json", type=str, default=SERVE_JSON_DEFAULT,
                    help="where the gateway serving bench writes its "
                         "machine-readable summary ('' disables)")
    ap.add_argument("--trace-json", type=str, default=TRACE_JSON_DEFAULT,
                    help="where the stage-trace bench writes its machine-"
                         "readable summary ('' disables)")
    ap.add_argument("--refine-json", type=str, default=REFINE_JSON_DEFAULT,
                    help="where the quantization-ladder bench writes its "
                         "machine-readable summary ('' disables)")
    ap.add_argument("--overload-json", type=str,
                    default=OVERLOAD_JSON_DEFAULT,
                    help="where the overload-resilience bench writes its "
                         "machine-readable summary ('' disables)")
    ap.add_argument("--bench-dataset", type=str, default="sift1m",
                    help="dataset for the engine/stream benches and their "
                         "BENCH_*.json files")
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.enable()

    benches = _bench_list(args)
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in benches:
        if args.only and args.only not in name:
            continue
        t0 = time.perf_counter()
        try:
            out = fn()
            if name == "engine_modes" and args.bench_json:
                write_bench_json(out, args.bench_dataset, args.bench_json)
            if name == "stream" and args.stream_json:
                write_stream_json(out, args.bench_dataset, args.stream_json)
            if name == "dist" and args.dist_json:
                write_dist_json(out, args.bench_dataset, args.dist_json)
            if name == "plan" and args.plan_json:
                write_plan_json(out, args.bench_dataset, args.plan_json)
            if name == "fused" and args.fused_json:
                write_fused_json(out, args.bench_dataset, args.fused_json)
            if name == "serve" and args.serve_json:
                write_serve_json(out, args.bench_dataset, args.serve_json)
            if name == "trace" and args.trace_json:
                write_trace_json(out, args.bench_dataset, args.trace_json)
            if name == "refine" and args.refine_json:
                write_refine_json(out, args.bench_dataset, args.refine_json)
            if name == "overload" and args.overload_json:
                write_overload_json(out, args.bench_dataset,
                                    args.overload_json)
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{name},NaN,FAILED")
        sys.stderr.write(f"[bench {name}: {time.perf_counter()-t0:.1f}s]\n")
    if failures:
        sys.exit(1)


def _bench_list(args):
    main_sets = ("sift1m", "msong", "gist", "openai") if args.full \
        else ("sift1m",)
    return [
        ("fig5", lambda: suite.bench_cells()),
        ("fig7_k10", lambda: suite.bench_recall_curves(main_sets, k=10,
                                                       quick=not args.full)),
        ("fig7_k1", lambda: suite.bench_recall_curves(("sift1m",), k=1,
                                                      quick=True)),
        ("fig8", lambda: suite.bench_nprobe()),
        ("fig9", lambda: suite.bench_cdf()),
        ("fig10", lambda: suite.bench_top100()),
        ("fig11", lambda: suite.bench_latency()),
        ("fig12", lambda: suite.bench_insert_delete()),
        ("fig13a", lambda: suite.bench_ablation()),
        ("table4", lambda: suite.bench_memory(
            main_sets if args.full else ("sift1m",))),
        ("fig14", lambda: suite.bench_multi_assign()),
        ("fig15a", lambda: suite.bench_lambda()),
        ("fig15b", lambda: suite.bench_ncands()),
        ("fig16", lambda: suite.bench_block_size()),
        ("fig17", lambda: suite.bench_seil_soar()),
        ("table3", lambda: suite.bench_match_table(
            main_sets if args.full else ("sift1m",))),
        ("engine_modes",
         lambda: suite.bench_exec_modes(dataset=args.bench_dataset)),
        ("stream", lambda: suite.bench_stream(dataset=args.bench_dataset)),
        ("plan", lambda: suite.bench_plan(dataset=args.bench_dataset)),
        ("dist", lambda: suite.bench_dist(dataset=args.bench_dataset)),
        ("fused", lambda: suite.bench_fused(dataset=args.bench_dataset)),
        ("serve", lambda: suite.bench_serve(dataset=args.bench_dataset)),
        ("trace", lambda: suite.bench_trace(dataset=args.bench_dataset)),
        ("refine", lambda: suite.bench_refine(dataset=args.bench_dataset)),
        ("overload",
         lambda: suite.bench_overload(dataset=args.bench_dataset)),
        ("kernels", lambda: suite.bench_kernels()),
    ]


if __name__ == "__main__":
    main()
