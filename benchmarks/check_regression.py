"""Perf-regression gate over the committed BENCH_*.json summaries.

``PYTHONPATH=src python -m benchmarks.check_regression``            # all
``... check_regression plan=/tmp/BENCH_plan_unit.json fused=...``   # some

Each committed benchmark summary carries machine-checkable invariants
— per-stage DCO splits, union-cut ratios, plan reuse rates, modeled
HBM traffic reductions, id-parity counts — that hold at ANY scale and
on ANY machine.  This gate asserts those,
and deliberately never a wall-clock number: CI runners are noisy, but
"the fused scan writes >= 4x fewer bytes", "refine_factor=1
returned identical ids", and "the clustered tile union is a strict cut
of the batch union" are exact at unit scale and at sift1m alike.

CI smoke jobs run a unit-scale bench into a temp file and gate it with
``kind=/path.json``; with no arguments the gate re-validates every
committed repo-root baseline, so a PR that regenerates a BENCH_*.json
with a regressed invariant fails even if no smoke re-runs that bench.

Pure stdlib on purpose (no jax, no repro import): the gate must be
runnable before, after, and regardless of the accelerator stack.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Callable, Dict

_REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
_SCHEMA_EXPECTED = {"engine": 1, "stream": 1, "dist": 1, "plan": 1,
                    "fused": 1, "serve": 1, "refine": 1,
                    "overload": 1}


class Gate:
    """Collects named invariant checks; remembers every failure."""

    def __init__(self):
        self.checks = 0
        self.failures = []

    def check(self, ok: bool, label: str, detail: str = "") -> None:
        self.checks += 1
        if ok:
            print(f"  ok   {label}")
        else:
            self.failures.append(f"{label}: {detail}" if detail else label)
            print(f"  FAIL {label}  {detail}")


def _schema(g: Gate, kind: str, d: dict) -> None:
    want = _SCHEMA_EXPECTED[kind]
    g.check(d.get("schema_version") == want,
            f"{kind}.schema_version == {want}",
            f"got {d.get('schema_version')!r}")


def check_engine(g: Gate, d: dict) -> None:
    g.check(d.get("id_mismatch_points") == 0,
            "engine: exec modes agree on ids at every config",
            f"id_mismatch_points={d.get('id_mismatch_points')}")
    g.check(all(0.0 <= c["recall"] <= 1.0 and c["dco"] > 0
                for c in d.get("configs", [])),
            "engine: every config has sane recall and nonzero DCO")


def check_stream(g: Gate, d: dict) -> None:
    g.check(d.get("delta_layout_builds") == 0,
            "stream: delta appends never rebuild the layout",
            f"delta_layout_builds={d.get('delta_layout_builds')}")
    g.check(d.get("append_speedup", 0) > 1.0,
            "stream: delta append beats legacy rebuild",
            f"append_speedup={d.get('append_speedup')}")
    g.check(d.get("recall_post_compact", 0) >=
            d.get("recall_churn", 1) - 0.02,
            "stream: compaction does not lose recall",
            f"churn={d.get('recall_churn')} "
            f"post_compact={d.get('recall_post_compact')}")


def check_dist(g: Gate, d: dict) -> None:
    g.check(d.get("one_dev_id_mismatch_points") == 0,
            "dist: 1-device sharded session matches plain searcher bitwise",
            f"one_dev_id_mismatch_points="
            f"{d.get('one_dev_id_mismatch_points')}")
    by_mode = {}
    for c in d.get("configs", []):
        by_mode.setdefault(c["exec_mode"], []).append(c["dco"])
    # shard-count padding moves a few blocks between shards, so DCO
    # drifts a fraction of a percent — but it must never *scale* with
    # device count (work moves across the mesh, it does not grow)
    g.check(all(max(dcos) / min(dcos) < 1.05
                for dcos in by_mode.values() if dcos),
            "dist: total DCO stays flat across device counts",
            f"dco spread={ {m: (min(v), max(v)) for m, v in by_mode.items()} }")


def check_plan(g: Gate, d: dict) -> None:
    g.check(d.get("id_mismatch_points") == 0,
            "plan: clustered/planned scans agree with paged ids",
            f"id_mismatch_points={d.get('id_mismatch_points')}")
    for name, s in d.get("streams", {}).items():
        g.check(s.get("union_reduction", 0) > 1.0,
                f"plan[{name}]: tile union is a strict cut of the "
                f"batch union",
                f"union_reduction={s.get('union_reduction')}")
        p = s.get("plan", {})
        tiles = p.get("tiles", 0)
        reuse = (p.get("hits", 0) + p.get("extends", 0)) / tiles \
            if tiles else 0.0
        g.check(p.get("hits", 0) + p.get("extends", 0) +
                p.get("misses", 0) == tiles,
                f"plan[{name}]: hit/extend/miss partition the tiles",
                f"plan={p}")
        g.check(reuse > 0.0,
                f"plan[{name}]: plan cache reuses at least one tile",
                f"reuse_rate={reuse:.3f}")
    dr = d.get("delta_routing", {})
    g.check(dr.get("dco_reduction", 0) > 1.0,
            "plan: routed delta scan cuts delta DCO vs exhaustive",
            f"dco_reduction={dr.get('dco_reduction')}")


def check_fused(g: Gate, d: dict) -> None:
    m = d.get("modeled_bytes_per_query", {})
    g.check(m.get("write_reduction_x", 0) >= 4.0,
            "fused: modeled scan-stage HBM write reduction >= 4x",
            f"write_reduction_x={m.get('write_reduction_x')}")
    g.check(m.get("roundtrip_reduction_x", 0) >= 4.0,
            "fused: modeled scan/finalize roundtrip reduction >= 4x",
            f"roundtrip_reduction_x={m.get('roundtrip_reduction_x')}")
    g.check(m.get("fused_scan_write", 1) < m.get("unfused_scan_write", 0),
            "fused: fused write strictly below unfused")
    g.check(all(row.get("ids_equal") for row in d.get("modes", [])),
            "fused: fused top-k returns identical ids in every exec mode",
            f"modes={[r.get('ids_equal') for r in d.get('modes', [])]}")


def check_serve(g: Gate, d: dict) -> None:
    errs = sum(pt["batched"].get("errors", 1) +
               pt["per_request"].get("errors", 1)
               for pt in d.get("points", []))
    g.check(errs == 0, "serve: no request failed or timed out",
            f"errors={errs}")
    g.check(d.get("batched", {}).get("batch_fill", 0) > 1.0,
            "serve: the deadline batcher actually coalesces",
            f"batch_fill={d.get('batched', {}).get('batch_fill')}")
    g.check(max((pt.get("speedup", 0) for pt in d.get("points", [])),
                default=0) >= 2.0,
            "serve: batched >= 2x per-request at some offered load",
            f"speedups="
            f"{[round(pt.get('speedup', 0), 2) for pt in d.get('points', [])]}")


def check_refine(g: Gate, d: dict) -> None:
    g.check(d.get("rf1_id_mismatch_points") == 0,
            "refine: refine_factor=1 is bitwise-identical to single-tier",
            f"rf1_id_mismatch_points={d.get('rf1_id_mismatch_points')}")
    configs = d.get("configs", [])
    # the sweep deliberately includes losing operating points (large
    # refine factors overshoot), so per-config checks are structural:
    # tier-1 must scan a strictly narrower plane than the full codes
    g.check(bool(configs) and all(
        0.0 <= c["recall"] <= 1.0
        and c["m_compact"] < c["m_full"]
        and 0 < c["tier1_ops"] < c["single_tier_ops"]
        for c in configs),
            "refine: every config scans a strictly narrower tier-1 plane")
    # the headline claim of the ladder, exact on any machine: on the
    # iso-recall frontier, some two-tier config must match the best
    # single-tier recall (within the summary's tolerance) at >= 2x
    # fewer modeled total ops than that single-tier point spends
    # (sift1m holds the committed claim; smoke scales run a looser
    # floor — at D=32 the compact plane is only 2-4x narrower)
    floor = 2.0 if d.get("dataset") == "sift1m" else 1.2
    tol = d.get("tolerance", 0.005)
    fr = d.get("frontier")
    g.check(fr is not None
            and fr.get("total_ops_reduction_x", 0) >= floor
            and fr.get("recall_drop", 1) <= tol
            and fr.get("total_ops", 0) > 0
            and abs(fr.get("target_single_tier_ops", 0)
                    - fr.get("total_ops_reduction_x", 0)
                    * fr.get("total_ops", 1)) < 1.0,
            f"refine: iso-recall frontier >= {floor}x total-ops "
            f"reduction within {tol:.3f} of the best single-tier recall",
            f"frontier={fr}")


def check_overload(g: Gate, d: dict) -> None:
    modes = d.get("modes", {})
    # every submission accounted for with a result or a *typed* error —
    # the no-silent-drops contract, exact on any machine at any scale
    for mode, md in sorted(modes.items()):
        for pt in md.get("points", []):
            total = (pt["n_ok"] + pt["shed"] + pt["deadline_failed"]
                     + pt["closed"] + pt["errors"])
            g.check(total == pt["n_requests"],
                    f"overload[{mode}/x{pt.get('load_factor')}]: every "
                    f"request resolves typed",
                    f"ok+shed+deadline+closed+errors={total} "
                    f"!= n_requests={pt['n_requests']}")
            g.check(pt["errors"] == 0,
                    f"overload[{mode}/x{pt.get('load_factor')}]: zero "
                    f"untyped failures", f"errors={pt['errors']}")
    # shed fraction monotone in offered load for the bounded modes; the
    # plain bounded queue must actually shed at top load (the degrade
    # mode may legitimately absorb it all — that is what the ladder is
    # for — so only engagement is asserted there, below)
    for mode in ("shed", "degrade"):
        pts = modes.get(mode, {}).get("points", [])
        fr = [pt["shed"] / pt["n_requests"] for pt in pts] or [0.0]
        g.check(all(b >= a - 0.01 for a, b in zip(fr, fr[1:])),
                f"overload[{mode}]: shed fraction monotone in offered "
                f"load", f"shed_fractions={[round(f, 3) for f in fr]}")
        if mode == "shed":
            g.check(fr[-1] > 0.0,
                    f"overload[{mode}]: top offered load actually sheds",
                    f"shed_fractions={[round(f, 3) for f in fr]}")
    g.check(all(pt["shed"] == 0
                for pt in modes.get("unbounded", {}).get("points", [])),
            "overload[unbounded]: the unbounded gateway never sheds")
    # degradation has a documented price: answered recall stays above
    # the floor at every load point, ladder fully engaged or not
    floor = d.get("recall_floor", 0.0)
    want_floor = 0.4 if d.get("dataset") == "sift1m" else 0.2
    recalls = [pt["recall"] for pt in modes.get("degrade", {})
               .get("points", []) if pt["n_ok"]]
    g.check(floor >= want_floor,
            f"overload: documented recall floor >= {want_floor}",
            f"recall_floor={floor}")
    g.check(bool(recalls) and min(recalls) >= floor,
            "overload[degrade]: answered recall above the documented "
            "floor at every load point",
            f"recalls={[round(r, 3) for r in recalls]} floor={floor}")
    g.check(bool(d.get("ladder_engaged")),
            "overload[degrade]: the quality ladder engaged at top load",
            f"counters={modes.get('degrade', {}).get('counters')}")


_CHECKERS: Dict[str, Callable[[Gate, dict], None]] = {
    "engine": check_engine, "stream": check_stream, "dist": check_dist,
    "plan": check_plan, "fused": check_fused, "serve": check_serve,
    "refine": check_refine,
    "overload": check_overload,
}


def run(targets: Dict[str, str]) -> int:
    g = Gate()
    for kind, path in sorted(targets.items()):
        print(f"[{kind}] {path}")
        try:
            with open(path) as f:
                d = json.load(f)
        except (OSError, ValueError) as e:
            g.checks += 1
            g.failures.append(f"{kind}: unreadable {path}: {e}")
            print(f"  FAIL unreadable: {e}")
            continue
        _schema(g, kind, d)
        _CHECKERS[kind](g, d)
    print(f"{g.checks} invariant checks, {len(g.failures)} failure(s)")
    for f in g.failures:
        print(f"REGRESSION: {f}", file=sys.stderr)
    return 1 if g.failures else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Gate machine-checkable BENCH_*.json invariants "
                    "(never wall-clock).")
    ap.add_argument("targets", nargs="*", metavar="KIND=PATH",
                    help="bench summaries to gate, e.g. "
                         "plan=/tmp/BENCH_plan_unit.json; with no "
                         "targets, validates every committed repo-root "
                         "BENCH_*.json baseline")
    args = ap.parse_args(argv)
    if args.targets:
        targets = {}
        for t in args.targets:
            kind, sep, path = t.partition("=")
            if not sep or kind not in _CHECKERS:
                ap.error(f"target {t!r} is not KIND=PATH with KIND in "
                         f"{sorted(_CHECKERS)}")
            targets[kind] = path
    else:
        targets = {k: p for k in _CHECKERS
                   if os.path.exists(p := os.path.join(_REPO,
                                                       f"BENCH_{k}.json"))}
        missing = sorted(set(_CHECKERS) - set(targets))
        if missing:
            print(f"(no committed baseline yet for: {', '.join(missing)})")
    return run(targets)


if __name__ == "__main__":
    raise SystemExit(main())
