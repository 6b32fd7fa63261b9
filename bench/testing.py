"""Tiny cells for the CPU tests: a stand-in checkout root holding a
``BENCHMARK.json`` with the real cells' shape at a size the CPU runs in
seconds (Pallas in interpret mode), plus copies of the real per-layer
readers.  ``make_root(tmp)`` returns the root; ``run`` drives one cell
through ``harness.run_cell`` without the look for a chip."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from . import harness

BENCH_DIR = Path(__file__).resolve().parent

TINY_CORPUS = {"n": 3000, "d": 16, "n_queries": 96, "n_components": 16,
               "latent": 8, "zipf": 1.2, "spread": 0.35, "query_noise": 1.0}

TINY_CONFIGS = {
    "tiny_l2": dict(
        TINY_CORPUS, name="tiny_l2", metric="l2", modality_gap=False,
        index={"nlist": 16, "strategy": "rair", "seil": True, "lam": 0.5,
               "n_cands": 4, "block": 32, "m_pq": 8, "nbits": 4,
               "metric": "l2", "kmeans_iters": 4, "pq_iters": 4},
        search={"k": 10, "k_factor": 4, "nprobe": 4, "exec_mode": "paged",
                "use_kernel": True, "fused_topk": True, "max_scan": 24},
        correct={"recall_at_10": 0.5, "dist_gap": 1e-4, "bad_ids": 0,
                 "unanswered": 0}),
    "tiny_ip": dict(
        TINY_CORPUS, name="tiny_ip", metric="ip", modality_gap=True,
        index={"nlist": 16, "strategy": "soar", "seil": True, "lam": 0.5,
               "n_cands": 4, "block": 32, "m_pq": 8, "nbits": 4,
               "metric": "ip", "kmeans_iters": 4, "pq_iters": 4},
        search={"k": 10, "k_factor": 4, "nprobe": 4, "exec_mode": "paged",
                "use_kernel": True, "fused_topk": True, "max_scan": 24},
        correct={"recall_at_10": 0.3, "dist_gap": 1e-4, "bad_ids": 0,
                 "unanswered": 0}),
}

TINY_MIXES = {
    "tiny_batch": {"kind": "batch", "batch": 32},
    "tiny_serve": {"kind": "open_loop", "rate_qps": 40.0,
                   "gateway": {"max_batch": 4, "max_delay_ms": 2.0,
                               "admission": "signature"}},
}


def make_root(tmp: Path, configs=("tiny_l2", "tiny_ip")) -> Path:
    """A checkout-like root with tiny cells ``<config>.batch`` and
    ``<config>.serve`` and the real ``BENCHMARK.json``'s metrics."""
    real = json.loads((BENCH_DIR.parent / "BENCHMARK.json").read_text())
    root = Path(tmp)
    for sub in ("configs", "traffic"):
        (root / "bench" / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH_DIR / "metrics", root / "bench" / "metrics",
                    dirs_exist_ok=True)
    bench = dict(real, configs=[], workloads=[])
    for name in configs:
        cfg = TINY_CONFIGS[name]
        path = f"bench/configs/{name}.json"
        (root / path).write_text(json.dumps(cfg))
        bench["configs"].append({"name": name, "source": "tiny test corpus",
                                 "file": path, "reduced": [], "why": "test"})
    for mix, body in TINY_MIXES.items():
        (root / "bench" / "traffic" / f"{mix}.json").write_text(
            json.dumps(body))
    cells = []
    for name in configs:
        for kind in ("batch", "serve"):
            cells.append({"name": f"{name}.{kind}", "config": name,
                          "traffic": f"tiny_{kind}", "chips": 1,
                          "why": "test"})
    bench["workloads"] = cells
    tiny = {c["name"] for c in cells}

    def remap(group):
        out = []
        for m in group:
            m = dict(m)
            if "workloads" in m:
                kinds = {w.split(".")[-1] for w in m["workloads"]}
                m["workloads"] = sorted(c for c in tiny
                                        if c.split(".")[-1] in kinds)
            out.append(m)
        return out
    bench["end_to_end"] = remap(real["end_to_end"])
    bench["per_layer"] = remap(real["per_layer"])
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


CPU_PEAKS = {"ops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def run(root: Path, cell: str, seed: int = 3, seconds: float = 1.0,
        trace: bool = False, system: str = "program") -> dict:
    return harness.run_cell(root, cell, seed, seconds, trace=trace,
                            system=system, peaks=CPU_PEAKS)
