"""One benchmark per paper table/figure (see DESIGN.md §6 for the map).

Each ``bench_*`` function emits ``name,us_per_call,derived`` CSV rows and
saves raw rows to benchmarks/results/*.json for EXPERIMENTS.md.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (IndexConfig, build_index, dco_summary, insert_batch,
                        per_query_recall, recall_at_k)
from repro.core.assign import candidate_lists, rair_assign
from repro.core.seil import cell_stats, vectors_in_large_cells

from .common import (NPROBES, at_recall, curve, emit, get_context, qps_at,
                     save_json, timed_search)

# paper-name -> (strategy, seil) presets
SOLUTIONS = {
    "IVFPQfs": ("single", False),
    "NaiveRA": ("naive", False),
    "SOARL2": ("soar", False),
    "RAIR": ("rair", False),
    "SRAIR": ("srair", False),
    "RAIRS": ("rair", True),
    "SRAIRS": ("srair", True),
}


def bench_recall_curves(datasets=("sift1m",), k=10, quick=True):
    """Fig 7a/7b/7c: recall-QPS and recall-DCO across solutions."""
    out = {}
    names = ("IVFPQfs", "NaiveRA", "SOARL2", "SRAIRS", "RAIRS") if quick \
        else tuple(SOLUTIONS)
    for ds in datasets:
        ctx = get_context(ds, n_queries=500 if quick else None)
        for name in names:
            strat, seil = SOLUTIONS[name]
            rows = curve(ctx, ctx.index(strat, seil), k=k)
            out[f"{ds}/{name}"] = rows
        target = 0.99 if k == 1 else 0.9
        base = at_recall(out[f"{ds}/IVFPQfs"], target, "dco")
        ours = at_recall(out[f"{ds}/RAIRS"], target, "dco")
        dr = (base / ours) if (base and ours) else float("nan")
        # wall-clock speedup at the target-recall operating point (blocked
        # deployment path, matched-recall nprobes)
        pb = at_recall(out[f"{ds}/IVFPQfs"], target, "nprobe")
        pr = at_recall(out[f"{ds}/RAIRS"], target, "nprobe")
        if pb and pr:
            usb = qps_at(ctx, ctx.index("single", False),
                         nprobe=max(1, round(pb)), k=k)
            usr = qps_at(ctx, ctx.index("rair", True),
                         nprobe=max(1, round(pr)), k=k)
            qr = usb / usr
        else:
            qr = float("nan")
        emit(f"fig7_recall_curves/{ds}/k{k}", 0.0,
             f"dco_speedup@{target}={dr:.3f}x qps_speedup@{target}={qr:.3f}x")
    save_json(f"fig7_recall_curves_k{k}", out)
    return out


def bench_nprobe(dataset="sift1m"):
    """Fig 8: recall vs nprobe — RAIRS reaches target recall at ~half the
    nprobe of single assignment."""
    ctx = get_context(dataset, n_queries=500)
    out = {}
    for name in ("IVFPQfs", "NaiveRA", "RAIRS", "SRAIRS"):
        strat, seil = SOLUTIONS[name]
        rows = curve(ctx, ctx.index(strat, seil), k=10)
        out[name] = [{"nprobe": r["nprobe"], "recall": r["recall"]}
                     for r in rows]
    # nprobe (interpolated) to hit recall 0.9
    def probe_at(name):
        return at_recall([{"recall": r["recall"], "nprobe": r["nprobe"]}
                          for r in out[name]], 0.9, "nprobe")
    pb, pr = probe_at("IVFPQfs"), probe_at("RAIRS")
    ratio = (pr / pb) if (pb and pr) else float("nan")
    emit("fig8_nprobe", 0.0, f"nprobe_ratio_RAIRS/IVFPQfs@0.9={ratio:.3f}")
    save_json("fig8_nprobe", out)
    return out


def bench_cdf(dataset="sift1m"):
    """Fig 9: per-query recall and DCO CDFs at matched ~0.9 recall."""
    from repro.core.dense import dense_search
    ctx = get_context(dataset, n_queries=1000)
    out = {}
    for name, probe in (("IVFPQfs", 16), ("RAIRS", 8)):
        strat, seil = SOLUTIONS[name]
        res = dense_search(ctx.index(strat, seil), ctx.q, k=10,
                           nprobe=probe)
        rec = per_query_recall(res.ids, ctx.gt(10))
        dco = np.asarray(res.approx_dco) + np.asarray(res.refine_dco)
        out[name] = {
            "recall_mean": float(rec.mean()),
            "recall_p10": float(np.percentile(rec, 10)),
            "frac_recall_ge_0.8": float((rec >= 0.8).mean()),
            "dco_mean": float(dco.mean()),
            "dco_p99": float(np.percentile(dco, 99)),
            "dco_p99_over_mean": float(np.percentile(dco, 99) / dco.mean()),
        }
    emit("fig9_cdf", 0.0,
         f"rairs_p99/mean={out['RAIRS']['dco_p99_over_mean']:.2f} "
         f"dco_mean_ratio={out['RAIRS']['dco_mean']/out['IVFPQfs']['dco_mean']:.3f}")
    save_json("fig9_cdf", out)
    return out


def bench_top100(dataset="sift1m"):
    """Fig 10: top-100 queries (K_FACTOR=4 per paper §6.1)."""
    ctx = get_context(dataset, n_queries=300)
    out = {}
    for name in ("IVFPQfs", "NaiveRA", "SOARL2", "RAIRS"):
        strat, seil = SOLUTIONS[name]
        out[name] = curve(ctx, ctx.index(strat, seil), k=100, k_factor=4,
                          nprobes=(4, 8, 16, 32, 64))
    b = at_recall(out["IVFPQfs"], 0.9, "dco")
    r = at_recall(out["RAIRS"], 0.9, "dco")
    emit("fig10_top100", 0.0,
         f"dco_speedup@0.9={(b / r) if (b and r) else float('nan'):.3f}x")
    save_json("fig10_top100", out)
    return out


def bench_latency(dataset="sift1m"):
    """Fig 11: one-query-at-a-time latency (B=1, no batch amortization)."""
    ctx = get_context(dataset, n_queries=64)
    out = {}
    probes = {"IVFPQfs": 16, "NaiveRA": 16, "SRAIRS": 8, "RAIRS": 8}
    for name in ("IVFPQfs", "NaiveRA", "SRAIRS", "RAIRS"):
        strat, seil = SOLUTIONS[name]
        idx = ctx.index(strat, seil)
        res, us = timed_search(idx, ctx.q, k=10, nprobe=probes[name], chunk=1)
        out[name] = {"us_per_query": us,
                     "recall": recall_at_k(res.ids, ctx.gt(10))}
    emit("fig11_latency", out["RAIRS"]["us_per_query"],
         f"latency_ratio_vs_IVFPQfs="
         f"{out['RAIRS']['us_per_query']/out['IVFPQfs']['us_per_query']:.3f}")
    save_json("fig11_latency", out)
    return out


def bench_insert_delete(dataset="sift1m"):
    """Fig 12: insertion/deletion throughput, RAIRS vs IVFPQfs — both
    routed through the streaming subsystem (core/stream/): inserts land
    in the delta segment via the `insert_batch` compat wrapper, deletes
    flip tombstone bits (the old layout-level `seil.delete_ids` path is
    measurement-only and left consistency-incoherent by design)."""
    ctx = get_context(dataset)
    n = ctx.x.shape[0]
    n0 = int(n * 0.8)
    batch = (n - n0) // 5
    out = {}
    for name in ("IVFPQfs", "RAIRS"):
        strat, seil = SOLUTIONS[name]
        cfg = IndexConfig(nlist=ctx.nlist, strategy=strat, seil=seil,
                          metric=ctx.metric)
        idx = build_index(jax.random.PRNGKey(0), ctx.x[:n0], cfg,
                          centroids=ctx.centroids, codebook=ctx.codebook)
        t0 = time.perf_counter()
        for b in range(5):
            s = n0 + b * batch
            idx = insert_batch(idx, ctx.x[s:s + batch])  # -> StreamingIndex
        t_ins = time.perf_counter() - t0
        rng = np.random.default_rng(0)
        victims = rng.choice(idx.n_total, size=5 * batch, replace=False)
        t0 = time.perf_counter()
        for b in range(5):
            idx.delete(victims[b * batch:(b + 1) * batch])
        t_del = time.perf_counter() - t0
        out[name] = {"insert_vec_per_s": 5 * batch / t_ins,
                     "delete_vec_per_s": 5 * batch / t_del}
    rel_i = out["RAIRS"]["insert_vec_per_s"] / out["IVFPQfs"]["insert_vec_per_s"]
    rel_d = out["RAIRS"]["delete_vec_per_s"] / out["IVFPQfs"]["delete_vec_per_s"]
    emit("fig12_insert_delete", 0.0,
         f"insert_rel={rel_i:.3f} delete_rel={rel_d:.3f}")
    save_json("fig12_insert_delete", out)
    return out


def bench_stream(dataset="sift1m", batches=8):
    """Streaming-subsystem bench (-> BENCH_stream.json): append
    throughput through the delta path vs the legacy pooled full-layout
    rebuild, deletion throughput, compaction cost, and recall under
    churn vs a brute-force oracle over the surviving corpus."""
    import repro.core.index as index_mod
    from repro.core import StreamingIndex, build_seil_call_count
    from repro.core.seil import build_seil

    ctx = get_context(dataset, n_queries=200)
    n = ctx.x.shape[0]
    n0 = int(n * 0.8)
    batch = max(1, (n - n0) // batches)
    cfg = IndexConfig(nlist=ctx.nlist, strategy="rair", seil=True,
                      metric=ctx.metric)
    idx = build_index(jax.random.PRNGKey(0), ctx.x[:n0], cfg,
                      centroids=ctx.centroids, codebook=ctx.codebook)

    # legacy baseline: one pooled re-add, i.e. what insert_batch did per
    # call before the delta path (assign+encode the batch, then rebuild
    # the whole SEIL layout from pooled items)
    xb = ctx.x[n0:n0 + batch]
    t0 = time.perf_counter()
    a_new = index_mod.compute_assignments(xb, idx.centroids, cfg)
    c_new = np.asarray(index_mod.pq_encode(idx.codebook, xb))
    all_a = np.concatenate([idx.assigns, a_new], axis=0)
    all_c = np.concatenate([idx.codes, c_new], axis=0)
    build_seil(all_a, all_c, np.arange(all_a.shape[0], dtype=np.int32),
               cfg.nlist, block=cfg.block, shared=True, code_bits=cfg.nbits)
    rebuild_vps = batch / (time.perf_counter() - t0)

    stream = StreamingIndex(idx)
    layout_calls0 = build_seil_call_count()
    t0 = time.perf_counter()
    inserted = 0
    for b in range(batches):
        s = n0 + b * batch
        inserted += len(stream.insert(ctx.x[s:s + batch]))
    delta_vps = inserted / (time.perf_counter() - t0)
    delta_layout_builds = build_seil_call_count() - layout_calls0

    rng = np.random.default_rng(0)
    victims = rng.choice(stream.n_total, size=max(1, stream.n_total // 10),
                         replace=False)
    t0 = time.perf_counter()
    deleted = stream.delete(victims)
    delete_vps = deleted / (time.perf_counter() - t0)

    from repro.core import ground_truth
    live = stream.live_ids()
    gt = live[ground_truth(stream.live_vectors(), ctx.q, 10,
                           metric=ctx.metric)]
    r = stream.search(ctx.q, k=10, nprobe=16)
    recall_churn = recall_at_k(np.asarray(r.ids), gt)

    info = stream.compact()
    gt2 = stream.live_ids()[ground_truth(stream.live_vectors(), ctx.q, 10,
                                         metric=ctx.metric)]
    r2 = stream.search(ctx.q, k=10, nprobe=16)
    recall_post = recall_at_k(np.asarray(r2.ids), gt2)

    out = {
        "n_base": n0, "append_batch": batch, "append_batches": batches,
        "append_vec_per_s_delta": delta_vps,
        "append_vec_per_s_rebuild": rebuild_vps,
        "append_speedup": delta_vps / rebuild_vps,
        "delta_layout_builds": int(delta_layout_builds),  # must be 0
        "delete_vec_per_s": delete_vps,
        "deleted": int(deleted),
        "compact_seconds": info["seconds"],
        "compact_layout_seconds": info["layout_seconds"],
        "recall_churn": recall_churn,
        "recall_post_compact": recall_post,
        "n_live": stream.n_live,
        "searcher": stream.searcher_stats(),
    }
    emit("stream", 0.0,
         f"append_speedup={out['append_speedup']:.1f}x "
         f"layout_builds={delta_layout_builds} "
         f"recall_churn={recall_churn:.3f} "
         f"recall_post_compact={recall_post:.3f}")
    save_json("stream", out)
    return out


def bench_dist(dataset="sift1m", k=10, nprobe=16,
               exec_modes=("paged", "grouped")):
    """Distributed scaling bench (-> BENCH_dist.json): recall / QPS /
    DCO of ``ShardedIndex`` sessions vs device count, both exec modes.

    Device counts sweep the powers of two up to ``len(jax.devices())``
    — on a stock CPU host that is just ndev=1 (the parity point, still
    asserted bitwise vs the plain Searcher); run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for a
    scaling curve.  QPS on a virtual-device CPU mesh measures overhead
    trends, not TPU throughput (see DESIGN.md §4)."""
    from jax.sharding import Mesh

    from repro.core import SearchParams

    ctx = get_context(dataset, n_queries=256)
    idx = ctx.index("rair", True)
    gt = ctx.gt(k)
    devs = jax.devices()
    ndevs = [n for n in (1, 2, 4, 8, 16) if n <= len(devs)]
    max_scan = idx.default_max_scan(nprobe)
    params0 = SearchParams(k=k, nprobe=nprobe, max_scan=max_scan,
                           batch_buckets=(64,))
    rows, mismatches = [], 0
    for nd in ndevs:
        mesh = Mesh(np.asarray(devs[:nd]), ("data",))
        sharded = idx.shard(mesh)
        for mode in exec_modes:
            import dataclasses as _dc
            searcher = sharded.searcher(_dc.replace(params0, exec_mode=mode))
            searcher(ctx.q[:64]).ids.block_until_ready()   # compile
            t0 = time.perf_counter()
            outs = [jax.tree.map(np.asarray, searcher(ctx.q[s:s + 64]))
                    for s in range(0, ctx.q.shape[0], 64)]
            dt = time.perf_counter() - t0
            res = jax.tree.map(lambda *a: np.concatenate(a, 0), *outs)
            if nd == 1:
                ref = idx.searcher(
                    _dc.replace(params0, exec_mode=mode))(ctx.q)
                if not np.array_equal(np.asarray(ref.ids), res.ids):
                    mismatches += 1
            rows.append({
                "ndev": nd, "exec_mode": mode,
                "recall": recall_at_k(res.ids, gt),
                "qps": ctx.q.shape[0] / dt,
                "us_per_query": dt / ctx.q.shape[0] * 1e6,
                "dco": dco_summary(res)["total_dco"],
            })
            emit(f"dist/{dataset}/ndev{nd}/{mode}",
                 rows[-1]["us_per_query"],
                 f"recall={rows[-1]['recall']:.4f} "
                 f"qps={rows[-1]['qps']:.0f} dco={rows[-1]['dco']:.0f}")
    out = {"ndev_swept": ndevs, "nprobe": nprobe,
           "one_dev_id_mismatch_points": mismatches, "configs": rows}
    emit(f"dist/{dataset}/parity", 0.0,
         f"one_dev_id_mismatch_points={mismatches}")
    save_json("dist_scaling", out)
    assert mismatches == 0, \
        "1-device ShardedIndex must match the plain Searcher bitwise"
    return out


def _dco_at(ctx, name, target=0.9, k=10, **over):
    strat, seil = SOLUTIONS[name]
    rows = curve(ctx, ctx.index(strat, seil, **over), k=k)
    return at_recall(rows, target, "approx_dco")


def bench_ablation(dataset="sift1m"):
    """Fig 13a: DCO at ~target recall for NaiveRA/SRAIR/RAIR x (SEIL on/off)."""
    ctx = get_context(dataset, n_queries=500)
    out = {}
    for base, strat in (("NaiveRA", "naive"), ("SRAIR", "srair"),
                        ("RAIR", "rair")):
        for seil in (False, True):
            rows = curve(ctx, ctx.index(strat, seil), k=10)
            out[f"{base}{'+SEIL' if seil else ''}"] = {
                "dco@0.9": at_recall(rows, 0.9, "approx_dco"),
                "rows": rows,
            }
    try:
        gain = 1 - (out["RAIR+SEIL"]["dco@0.9"] / out["RAIR"]["dco@0.9"])
    except TypeError:
        gain = float("nan")
    emit("fig13a_ablation", 0.0, f"seil_dco_cut_on_RAIR={gain:.3%}")
    save_json("fig13a_ablation", out)
    return out


def bench_memory(datasets=("sift1m", "msong", "gist")):
    """Table 4 / Fig 13b: IVF-PQ module memory across solutions."""
    out = {}
    for ds in datasets:
        ctx = get_context(ds)
        row = {}
        for name in ("IVFPQfs", "NaiveRA", "RAIR", "RAIRS"):
            strat, seil = SOLUTIONS[name]
            idx = ctx.index(strat, seil)
            row[name] = idx.stats.logical_bytes
        strat, seil = SOLUTIONS["NaiveRA"]
        idx = ctx.index("naive", True)
        row["NaiveRA+SEIL"] = idx.stats.logical_bytes
        out[ds] = row
        emit(f"table4_memory/{ds}", 0.0,
             f"rairs/naive={row['RAIRS']/row['NaiveRA']:.3f} "
             f"naive+seil/naive={row['NaiveRA+SEIL']/row['NaiveRA']:.3f}")
    save_json("table4_memory", out)
    return out


def bench_multi_assign(dataset="sift1m"):
    """Fig 14: aggr functions for 3-assignment; m in {1,2,3,4} (strict,
    SEIL off per paper)."""
    ctx = get_context(dataset, n_queries=300)
    out = {}
    for aggr in ("max", "min", "avg"):
        rows = curve(ctx, ctx.index("srair", False, multi_m=3, aggr=aggr),
                     k=10, nprobes=(2, 4, 8, 16, 32))
        out[f"aggr_{aggr}"] = {"dco@0.9": at_recall(rows, 0.9, "approx_dco"),
                               "rows": rows}
    for m, name in ((1, "IVFPQfs"), (2, "SRAIR")):
        strat, seil = SOLUTIONS[name]
        rows = curve(ctx, ctx.index(strat, seil), k=10,
                     nprobes=(2, 4, 8, 16, 32))
        out[f"m{m}"] = {"dco@0.9": at_recall(rows, 0.9, "approx_dco"),
                        "rows": rows}
    for m in (3, 4):
        rows = curve(ctx, ctx.index("srair", False, multi_m=m, aggr="max"),
                     k=10, nprobes=(2, 4, 8, 16, 32))
        out[f"m{m}"] = {"dco@0.9": at_recall(rows, 0.9, "approx_dco"),
                        "rows": rows}
    d = {k: v["dco@0.9"] for k, v in out.items()}
    emit("fig14_multi_assign", 0.0,
         " ".join(f"{k}={v:.0f}" if v else f"{k}=NA" for k, v in d.items()))
    save_json("fig14_multi_assign", out)
    return out


def bench_lambda(dataset="sift1m"):
    """Fig 15a: lambda sweep for RAIRS."""
    ctx = get_context(dataset, n_queries=300)
    out = {}
    for lam in (0.0, 0.25, 0.5, 0.75, 1.0):
        rows = curve(ctx, ctx.index("rair", True, lam=lam), k=10,
                     nprobes=(2, 4, 8, 16, 32))
        out[f"lam{lam}"] = {"dco@0.9": at_recall(rows, 0.9, "approx_dco"),
                            "rows": rows}
    d = {k: v["dco@0.9"] for k, v in out.items()}
    emit("fig15a_lambda", 0.0,
         " ".join(f"{k}={v:.0f}" if v else f"{k}=NA" for k, v in d.items()))
    save_json("fig15a_lambda", out)
    return out


def bench_ncands(dataset="sift1m", lam=0.5):
    """Fig 15b: CDF of the true AIR-argmin rank among distance-sorted lists."""
    ctx = get_context(dataset)
    x = ctx.x[:20000]
    cid, cd2 = candidate_lists(x, ctx.centroids, ctx.nlist)
    c = ctx.centroids[cid]
    r = c - x[:, None, :]
    loss = cd2 + lam * jnp.einsum("nd,ncd->nc", r[:, 0], r)
    true_rank = np.asarray(jnp.argmin(loss, axis=1))
    cdf = {f"rank<={t}": float((true_rank <= t).mean())
           for t in (1, 2, 5, 10, 20, 50)}
    emit("fig15b_ncands", 0.0, f"rank<=10={cdf['rank<=10']:.4f}")
    save_json("fig15b_ncands", cdf)
    return cdf


def bench_block_size(dataset="sift1m"):
    """Fig 16: block-size sweep — misc fraction grows, SEIL saving shrinks."""
    from repro.core.dense import dense_search
    ctx = get_context(dataset, n_queries=300)
    out = {}
    for blk in (16, 32, 64, 128):
        idx = ctx.index("rair", True, block=blk)
        misc_frac = idx.stats.n_misc_items / max(idx.stats.n_items_stored, 1)
        res = dense_search(idx, ctx.q, k=10, nprobe=16)
        out[f"blk{blk}"] = {
            "misc_item_frac": misc_frac,
            "large_cell_frac": vectors_in_large_cells(idx.assigns, blk),
            "dco@nprobe16": dco_summary(res)["approx_dco"],
        }
    emit("fig16_block_size", 0.0,
         " ".join(f"blk{b}_misc={out[f'blk{b}']['misc_item_frac']:.3f}"
                  for b in (16, 32, 64, 128)))
    save_json("fig16_block_size", out)
    return out


def bench_seil_soar(dataset="t2i"):
    """Fig 17: SEIL applied to SOAR under inner product."""
    ctx = get_context(dataset, n_queries=500)
    out = {}
    for seil in (False, True):
        rows = curve(ctx, ctx.index("soar", seil), k=10,
                     nprobes=(2, 4, 8, 16, 32))
        out[f"SOAR{'+SEIL' if seil else ''}"] = rows
    b = at_recall(out["SOAR"], 0.7, "approx_dco")
    s = at_recall(out["SOAR+SEIL"], 0.7, "approx_dco")
    emit("fig17_seil_soar", 0.0,
         f"seil_dco_cut={1 - (s / b) if (b and s) else float('nan'):.3%}")
    save_json("fig17_seil_soar", out)
    return out


def bench_match_table(datasets=("sift1m", "msong", "gist")):
    """Table 3: %% of vectors with identical 2nd choice under SOARL2 vs AIR."""
    out = {}
    for ds in datasets:
        ctx = get_context(ds)
        x = ctx.x[:30000]
        a_air = np.asarray(rair_assign(x, ctx.centroids, metric="air",
                                       strict=True))
        a_soar = np.asarray(rair_assign(x, ctx.centroids, metric="soar",
                                        strict=True))
        match = float((a_air == a_soar).all(axis=1).mean())
        out[ds] = match
        emit(f"table3_match/{ds}", 0.0, f"match={match:.4f}")
    save_json("table3_match", out)
    return out


def bench_cells(dataset="sift1m"):
    """Fig 5: cell-size skew after redundant assignment."""
    ctx = get_context(dataset)
    idx = ctx.index("rair", True)
    sizes = cell_stats(idx.assigns)["cell_sizes"]
    out = {
        "n_cells": int(len(sizes)),
        "frac_vectors_in_large_cells": vectors_in_large_cells(idx.assigns),
        "max_cell": int(sizes.max()),
        "p99_cell": float(np.percentile(sizes, 99)),
    }
    emit("fig5_cells", 0.0,
         f"large_cell_frac={out['frac_vectors_in_large_cells']:.3f} "
         f"max_cell={out['max_cell']}")
    save_json("fig5_cells", out)
    return out


def bench_exec_modes(dataset="sift1m", k=10, nprobes=(4, 8, 16, 32)):
    """Engine exec-mode study (paper §5.3): recall vs QPS for per-query
    paged scanning vs list-major grouped (batch-union) execution of the
    same RAIRS index.  Also asserts result equivalence at every point —
    the modes differ only in memory-access schedule, never in output."""
    ctx = get_context(dataset, n_queries=256)
    idx = ctx.index("rair", True)
    gt = ctx.gt(k)
    out = {"paged": [], "grouped": []}
    mismatches = 0
    for nprobe in nprobes:
        per_mode = {}
        for mode in ("paged", "grouped"):
            res, us = timed_search(idx, ctx.q, k=k, nprobe=nprobe,
                                   chunk=64, exec_mode=mode)
            per_mode[mode] = res
            out[mode].append({
                "nprobe": nprobe,
                "recall": recall_at_k(res.ids, gt),
                "qps": 1e6 / us,
                "us_per_query": us,
                "dco": dco_summary(res)["total_dco"],
            })
        if not np.array_equal(per_mode["paged"].ids, per_mode["grouped"].ids):
            mismatches += 1
    rows_p, rows_g = out["paged"], out["grouped"]
    for rp, rg in zip(rows_p, rows_g):
        emit(f"engine_exec_modes/{dataset}/nprobe{rp['nprobe']}",
             rp["us_per_query"],
             f"paged_qps={rp['qps']:.0f} grouped_qps={rg['qps']:.0f} "
             f"recall={rp['recall']:.4f} "
             f"grouped/paged_qps={rg['qps'] / rp['qps']:.3f}")
    emit(f"engine_exec_modes/{dataset}/equivalence", 0.0,
         f"id_mismatch_points={mismatches}")
    out["id_mismatch_points"] = mismatches
    # compile-cache accounting across every session the sweep created
    out["searcher"] = idx.searcher_stats()
    save_json("engine_exec_modes", out)
    assert mismatches == 0, "grouped mode must return identical ids"
    return out


def _query_streams(ctx, batch, n_batches, seed=0, hot=16, zipf_a=1.1,
                   jitter=0.02):
    """Two serving traces of `n_batches` x `batch` queries over the
    context's query pool: ``uniform`` draws iid, ``zipf`` draws from a
    `hot`-query pool with Zipf(a) popularity — the cache-hot
    steady-state traffic the locality-aware planner targets (think the
    head of a search-query distribution: a small set of hot queries
    dominating each serving batch).  Every draw gets small Gaussian
    jitter so batches are near-duplicates, not exact repeats."""
    rng = np.random.default_rng(seed)
    pool = np.asarray(ctx.q)
    scale = float(pool.std()) * jitter
    h = min(hot, pool.shape[0])
    p = 1.0 / np.arange(1, h + 1) ** zipf_a
    p /= p.sum()
    streams = {"uniform": [], "zipf": []}
    for _ in range(n_batches):
        for name, picks in (
                ("uniform", rng.integers(0, pool.shape[0], batch)),
                ("zipf", rng.choice(h, batch, p=p))):
            q = pool[picks] + rng.normal(0.0, scale, (batch, pool.shape[1]))
            streams[name].append(jnp.asarray(q, jnp.float32))
    return streams


def _union_sizes(idx, qb, nprobe, query_tile):
    """(batch-wide union live, mean per-tile union live) for one batch —
    plan-only, no scan, so QPS timings stay uncontaminated."""
    from repro.core import plan_blocks, select_lists
    from repro.core.engine import (cluster_order, fit_tile,
                                   tables_from_arrays)
    selection = select_lists(qb, idx.centroids, nprobe=nprobe,
                             metric=idx.config.metric)
    plan = plan_blocks(tables_from_arrays(idx.arrays), selection,
                       max_scan=idx.default_max_scan(nprobe))
    blocks, valid = np.asarray(plan.blocks), np.asarray(plan.valid)
    batch_live = len(np.unique(blocks[valid]))
    perm = np.asarray(cluster_order(selection.sel))
    qt = fit_tile(qb.shape[0], query_tile)
    t = qb.shape[0] // qt
    pb = blocks[perm].reshape(t, qt, -1)
    pv = valid[perm].reshape(t, qt, -1)
    tiles = [len(np.unique(pb[i][pv[i]])) for i in range(t)]
    return batch_live, float(np.mean(tiles))


def bench_plan(dataset="sift1m", k=10, nprobe=16, batch=256, n_batches=12,
               query_tile=16):
    """Locality-aware planning bench (-> BENCH_plan.json): per-tile vs
    batch-wide union sizes, incremental plan-cache hit rates, and QPS of
    paged / grouped (batch union) / clustered (+plan reuse) on a
    Zipf-skewed and a uniform query stream, plus routed-vs-exhaustive
    delta scan cost once the delta outgrows ``nlist * block``.

    Asserts the optimization's core claims so CI's ``plan-smoke`` step
    guards them at toy scale: clustered tile unions at least 2x smaller
    than the batch-wide union on the skewed stream, a majority plan-cache
    hit rate at steady state, and bitwise-identical results across
    modes."""
    import dataclasses as _dc

    from repro.core import SearchParams, Searcher, StreamingIndex

    nlist = 64 if dataset.startswith("unit") else 256
    ctx = get_context(dataset, nlist=nlist)
    idx = ctx.index("rair", True)
    streams = _query_streams(ctx, batch, n_batches)
    out = {"nlist": nlist, "batch": batch, "n_batches": n_batches,
           "nprobe": nprobe, "query_tile": query_tile, "streams": {}}
    mismatches = 0
    for stream_name, batches in streams.items():
        row = {}
        # union geometry (plan-only, over the first few batches)
        sizes = [_union_sizes(idx, qb, nprobe, query_tile)
                 for qb in batches[:4]]
        row["batch_union_live_mean"] = float(np.mean([s[0] for s in sizes]))
        row["tile_union_live_mean"] = float(np.mean([s[1] for s in sizes]))
        row["union_reduction"] = (row["batch_union_live_mean"]
                                  / max(row["tile_union_live_mean"], 1.0))
        # QPS per mode (fresh session per mode; compile excluded).  The
        # batch-wide-union grouped baseline is stateless and an order of
        # magnitude slower on the CPU oracle (that is the point of
        # clustering) — timing a prefix of the stream suffices.
        results = {}
        for mode, reuse in (("paged", False), ("grouped", False),
                            ("clustered", True)):
            params = SearchParams(k=k, nprobe=nprobe, exec_mode=mode,
                                  plan_reuse=reuse, query_tile=query_tile,
                                  batch_buckets=(batch,))
            timed = batches if mode != "grouped" else batches[:4]
            # fresh session per (stream, mode): the index-level session
            # cache is keyed by params and would carry one stream's plan
            # cache — and its settled scan widths — into the other
            # stream's measurement
            searcher = Searcher(idx, params)
            # warmup/compile; the reuse path gets a second untimed batch
            # so the plan cache and its width bucket settle before the
            # clock starts (compile is excluded from every mode's timing)
            for qb in (timed[:2] if reuse else timed[:1]):
                searcher(qb).ids.block_until_ready()
            t0 = time.perf_counter()
            last = None
            for qb in timed:
                last = searcher(qb)
            last.ids.block_until_ready()
            dt = time.perf_counter() - t0
            row[f"{mode}_qps"] = len(timed) * batch / dt
            # equivalence checked on a common batch (untimed)
            results[mode] = np.asarray(searcher(batches[0]).ids)
            if reuse:
                row["plan"] = searcher.compile_stats()["plan"]
        row["clustered_over_paged_qps"] = (row["clustered_qps"]
                                           / row["paged_qps"])
        if not (np.array_equal(results["paged"], results["grouped"])
                and np.array_equal(results["paged"], results["clustered"])):
            mismatches += 1
        out["streams"][stream_name] = row
        emit(f"plan/{dataset}/{stream_name}", 1e6 / row["clustered_qps"],
             f"union_cut={row['union_reduction']:.2f}x "
             f"hit_rate={row['plan']['hit_rate']:.2f} "
             f"clustered/paged_qps={row['clustered_over_paged_qps']:.3f}")

    # -- routed delta scans: DCO/QPS once delta > nlist * block ----------
    # The "routed" stream pins delta_route_min=0 so the comparison runs
    # at any corpus scale; ``auto_would_route`` records whether the
    # default nlist*block threshold fires for this delta size (it does
    # at sift1m scale — the committed benchmark's operating point).
    n = ctx.x.shape[0]
    n0 = int(n * 0.8)
    cfg = IndexConfig(nlist=nlist, strategy="rair", seil=True,
                      metric=ctx.metric, delta_route_min=0)
    base = build_index(jax.random.PRNGKey(0), ctx.x[:n0], cfg,
                       centroids=ctx.centroids, codebook=ctx.codebook)
    base_ex = _dc.replace(base, config=_dc.replace(
        cfg, delta_route_min=10 ** 9))
    routed, exhaust = StreamingIndex(base), StreamingIndex(base_ex)
    routed.insert(ctx.x[n0:])
    exhaust.insert(ctx.x[n0:])
    qd = streams["zipf"][0]
    drow = {"threshold_auto": nlist * cfg.block,
            "delta_rows": n - n0,
            "delta_capacity": routed._delta.capacity,
            "routed_active": routed.delta_routed,
            "auto_would_route": routed._delta.capacity > nlist * cfg.block}
    for name, st in (("exhaustive", exhaust), ("routed", routed)):
        sess = st.searcher(SearchParams(k=k, nprobe=nprobe,
                                        batch_buckets=(batch,)))
        sess(qd).ids.block_until_ready()
        t0 = time.perf_counter()
        r = sess(qd)
        r.ids.block_until_ready()
        drow[f"qps_{name}"] = batch / (time.perf_counter() - t0)
        drow[f"dco_{name}"] = float(np.asarray(r.approx_dco).mean()
                                    + np.asarray(r.refine_dco).mean())
    drow["dco_reduction"] = drow["dco_exhaustive"] / drow["dco_routed"]
    out["delta_routing"] = drow
    emit(f"plan/{dataset}/delta_routing", 0.0,
         f"routed={drow['routed_active']} "
         f"dco_cut={drow['dco_reduction']:.2f}x "
         f"qps_routed/exhaustive="
         f"{drow['qps_routed'] / drow['qps_exhaustive']:.2f}")

    out["id_mismatch_points"] = mismatches
    save_json("plan", out)
    zrow = out["streams"]["zipf"]
    assert mismatches == 0, "exec modes must return identical ids"
    # toy corpora cap the batch union at their tiny block store, which
    # flattens the ratio; the full >= 2x bar applies at bench scale
    min_cut = 1.2 if dataset.startswith("unit") else 2.0
    assert zrow["union_reduction"] >= min_cut, \
        f"clustered unions should be >= {min_cut}x tighter on the skewed " \
        f"stream (got {zrow['union_reduction']:.2f}x)"
    assert zrow["plan"]["hit_rate"] > 0.5, \
        f"steady-state plan-cache hit rate should exceed 50% " \
        f"(got {zrow['plan']['hit_rate']:.2f})"
    assert drow["dco_reduction"] > 1.0, "routing must cut delta DCO"
    return out


def bench_kernels():
    """Kernel microbench: jnp oracle vs Pallas path on one workload.
    (CPU interpret-mode timing is NOT TPU perf — roofline covers that.)"""
    from repro.kernels.ops import pq_scan_paged
    from repro.kernels.ref import pq_scan_paged_ref
    key = jax.random.PRNGKey(0)
    b, m, kk, tb, blk, s = 8, 64, 16, 512, 32, 64
    k1, k2, k3 = jax.random.split(key, 3)
    lut = jax.random.normal(k1, (b, m, kk), jnp.float32)
    codes = jax.random.randint(k2, (tb, blk, m), 0, kk).astype(jnp.uint8)
    idx = jax.random.randint(k3, (b, s), 0, tb, jnp.int32)
    out = {}
    for name, fn in (("jnp_ref", pq_scan_paged_ref),
                     ("pallas_interpret", pq_scan_paged)):
        fn(lut, codes, idx).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(3):
            fn(lut, codes, idx).block_until_ready()
        us = (time.perf_counter() - t0) / 3 * 1e6
        out[name] = us
        emit(f"kernel_pq_scan/{name}", us,
             f"items={b * s * blk} us_per_item={us / (b * s * blk):.3f}")
    save_json("kernel_pq_scan", out)
    return out


def bench_fused(dataset="sift1m", k=10, nprobe=16, chunk=64,
                exec_modes=("paged", "grouped", "clustered")):
    """Fused scan->top-k bench (-> BENCH_fused.json): modeled scan-stage
    HBM traffic and wall-clock QPS, fused vs unfused, per exec mode.

    Traffic model (roofline.py accounting style — analytic minimum
    bytes the scan stage exchanges with HBM around the scan/finalize
    boundary, per query):

      unfused: the scan materializes the full (S, BLK) candidate stream
        for finalize to re-read — ``S*BLK`` candidates x 8 B
        (f32 distance + i32 id), written once and read once;
      fused:   only the top-``fetch`` accumulator leaves the scan —
        ``fetch`` candidates x 12 B written (f32 distance + i32 flat
        position + i32 id), 8 B of which finalize reads back.

    On-TPU the fused kernel additionally keeps the accumulator VMEM-
    resident across the whole scan grid; this model counts only the
    boundary traffic, which is what shrinks.  Asserts the modeled write
    reduction >= 4x (the CI ``kernel-smoke`` guard) and fused==unfused
    result ids at every operating point.
    """
    from repro.core import SearchParams
    from repro.core.search import finalize_fetch

    ctx = get_context(dataset, n_queries=256)
    idx = ctx.index("rair", True)
    gt = ctx.gt(k)
    max_scan = idx.default_max_scan(nprobe)
    blk = idx.arrays.block_codes.shape[1]
    fetch = finalize_fetch(k * 10, idx.result_oversample,
                           idx.needs_result_dedup)
    fetch = min(fetch, max_scan * blk)
    scan_width = max_scan * blk

    from .roofline import scan_traffic_model
    out = {
        "k": k, "nprobe": nprobe, "max_scan": max_scan, "block": blk,
        "fetch": fetch, "scan_width": scan_width,
        "modeled_bytes_per_query": scan_traffic_model(
            scan_width=scan_width, fetch=fetch),
        "modes": [],
    }

    def run(exec_mode, fused):
        p = SearchParams(k=k, nprobe=nprobe, exec_mode=exec_mode,
                         fused_topk=fused,
                         batch_buckets=(min(chunk, ctx.q.shape[0]),))
        searcher = idx.searcher(p)
        nq = ctx.q.shape[0]
        searcher(ctx.q[:chunk]).ids.block_until_ready()  # warmup/compile
        t0 = time.perf_counter()
        outs = [jax.tree.map(np.asarray, searcher(ctx.q[s:s + chunk]))
                for s in range(0, nq, chunk)]
        us = (time.perf_counter() - t0) / nq * 1e6
        return jax.tree.map(lambda *a: np.concatenate(a, 0), *outs), us

    mismatches = 0
    for mode in exec_modes:
        base, us_b = run(mode, False)
        fused, us_f = run(mode, True)
        equal = bool(np.array_equal(base.ids, fused.ids))
        mismatches += not equal
        row = {
            "exec_mode": mode,
            "unfused_qps": 1e6 / us_b,
            "fused_qps": 1e6 / us_f,
            "fused_over_unfused_qps": us_b / us_f,
            "recall": recall_at_k(fused.ids, gt),
            "ids_equal": equal,
        }
        out["modes"].append(row)
        emit(f"fused_topk/{dataset}/{mode}", us_f,
             f"fused_qps={row['fused_qps']:.0f} "
             f"unfused_qps={row['unfused_qps']:.0f} "
             f"ratio={row['fused_over_unfused_qps']:.3f} "
             f"recall={row['recall']:.4f} ids_equal={equal}")
    red = out["modeled_bytes_per_query"]["write_reduction_x"]
    emit(f"fused_topk/{dataset}/hbm_model", 0.0,
         f"scan_width={scan_width} fetch={fetch} write_reduction={red:.1f}x")
    save_json("fused_topk", out)
    assert mismatches == 0, "fused path must return identical ids"
    assert red >= 4.0, (
        f"modeled scan-stage HBM write reduction {red:.1f}x < 4x — "
        f"fetch={fetch} grew relative to the scan width {scan_width}")
    return out


def bench_refine(dataset="sift1m", k=10, nprobes=(8, 16, 32),
                 backends=("pq4", "binary"), refine_factors=(2, 4, 8),
                 chunk=64):
    """Two-tier quantization ladder bench (-> BENCH_refine.json):
    backend x refine_factor x nprobe sweep against the single-tier
    baseline (DESIGN.md §12).

    Reports, per operating point, measured recall@k and the weighted
    total-ops model — tier-1 LUT lookups (scan_width x m_compact) plus
    tier-2 exact dims (bigk_eff x D) against the single-tier cost
    (scan_width x m_full + bigk x D).  The accounting comes from
    ``session_traffic_model`` so serving snapshots, this bench, and the
    ``check_regression`` gate can never disagree.  Also asserts the
    refine_factor=1 degenerate ladder returns bitwise-identical results
    (the acceptance guarantee that enabling the subsystem cannot change
    answers until it is actually asked to trade).

    The committed sift1m baseline is gated on the iso-recall frontier:
    some two-tier config must reach within 0.5% absolute recall@10 of
    the best single-tier operating point at >= 2x fewer modeled total
    ops than that point spends.
    """
    import dataclasses

    from repro.core import RefineParams, SearchParams
    from repro.obs.stats import session_traffic_model

    ctx = get_context(dataset, n_queries=256)
    idx = ctx.index("rair", True)
    gt = ctx.gt(k)
    nprobes = tuple(p for p in nprobes if p <= ctx.nlist)
    # sift1m holds the committed-baseline claim; smoke scales loosen it
    # (at D=32 the compact plane is only 2-4x narrower than full)
    tolerance = 0.005 if dataset == "sift1m" else 0.03

    def run(params):
        searcher = idx.searcher(params)
        nq = ctx.q.shape[0]
        searcher(ctx.q[:chunk]).ids.block_until_ready()  # warmup/compile
        t0 = time.perf_counter()
        outs = [jax.tree.map(np.asarray, searcher(ctx.q[s:s + chunk]))
                for s in range(0, nq, chunk)]
        us = (time.perf_counter() - t0) / nq * 1e6
        merged = jax.tree.map(lambda *a: np.concatenate(a, 0), *outs)
        return merged, us, searcher

    out = {"k": k, "tolerance": tolerance, "baselines": [], "configs": [],
           "rf1_id_mismatch_points": 0}
    base_by_nprobe = {}
    for nprobe in nprobes:
        p0 = SearchParams(k=k, nprobe=nprobe,
                          batch_buckets=(min(chunk, ctx.q.shape[0]),))
        res0, us0, _ = run(p0)
        r0 = recall_at_k(res0.ids, gt)
        base_by_nprobe[nprobe] = r0
        out["baselines"].append({"nprobe": nprobe, "recall": r0,
                                 "qps": 1e6 / us0})
        # degenerate ladder: rf=1 must be bitwise the single-tier path
        res1, _, _ = run(dataclasses.replace(
            p0, refine=RefineParams(plane=backends[0], refine_factor=1)))
        if not (np.array_equal(res0.ids, res1.ids)
                and np.array_equal(res0.dists, res1.dists)):
            out["rf1_id_mismatch_points"] += 1
        for backend in backends:
            for rf in refine_factors:
                p2 = dataclasses.replace(
                    p0, refine=RefineParams(plane=backend, refine_factor=rf))
                res2, us2, s2 = run(p2)
                model = session_traffic_model(s2)["refine"]
                row = {
                    "backend": backend, "refine_factor": rf,
                    "nprobe": nprobe,
                    "recall": recall_at_k(res2.ids, gt),
                    "qps": 1e6 / us2,
                    "m_compact": model["m_compact"],
                    "m_full": model["m_full"],
                    "tier1_ops": model["tier1_ops"],
                    "tier2_ops": model["tier2_ops"],
                    "total_ops": model["total_ops"],
                    "single_tier_ops": model["single_tier_ops"],
                    "total_ops_reduction_x": model["total_ops_reduction_x"],
                }
                row["recall_drop"] = r0 - row["recall"]
                out["configs"].append(row)
                emit(f"refine/{dataset}/{backend}/rf{rf}/nprobe{nprobe}",
                     us2,
                     f"recall={row['recall']:.4f} (drop "
                     f"{row['recall_drop']:+.4f}) "
                     f"ops_reduction={row['total_ops_reduction_x']:.2f}x "
                     f"qps={row['qps']:.0f}")
    # iso-recall frontier (the paper's own methodology — recall-vs-cost
    # curves, not same-knob points): the target is the best single-tier
    # recall anywhere in the sweep, and the frontier is the cheapest
    # two-tier config within `tolerance` of it; the claimed reduction is
    # against the single-tier ops AT that target operating point
    ops_by_nprobe = {c["nprobe"]: c["single_tier_ops"]
                     for c in out["configs"]}
    for b in out["baselines"]:
        b["single_tier_ops"] = ops_by_nprobe[b["nprobe"]]
    best = max(out["baselines"], key=lambda b: b["recall"])
    eligible = [c for c in out["configs"]
                if c["recall"] >= best["recall"] - tolerance]
    if eligible:
        fr = dict(min(eligible, key=lambda c: c["total_ops"]))
        fr["target_recall"] = best["recall"]
        fr["target_nprobe"] = best["nprobe"]
        fr["target_single_tier_ops"] = best["single_tier_ops"]
        fr["recall_drop"] = best["recall"] - fr["recall"]
        fr["total_ops_reduction_x"] = \
            best["single_tier_ops"] / fr["total_ops"]
        out["frontier"] = fr
        emit(f"refine/{dataset}/frontier", 0.0,
             f"{fr['backend']}/rf{fr['refine_factor']}/nprobe"
             f"{fr['nprobe']} reduction={fr['total_ops_reduction_x']:.2f}x "
             f"vs single-tier nprobe{fr['target_nprobe']} "
             f"drop={fr['recall_drop']:+.4f}")
    save_json("refine", out)
    assert out["rf1_id_mismatch_points"] == 0, \
        "refine_factor=1 must be bitwise-identical to single-tier"
    return out


def bench_serve(dataset="sift1m", k=10, nprobe=4, max_scan=16,
                load_factors=(1.5, 20.0), n_requests=384,
                max_batch=32, max_delay_ms=2.0):
    """Async gateway serving bench (-> BENCH_serve.json): the same
    open-loop Poisson arrival stream served two ways — through the
    deadline-batched gateway (requests coalesced into compiled batch
    buckets) and per-request (``max_batch=1``: identical queue and
    sessions, every dispatch carries one query) — with p50/p99 latency
    at each offered load point.

    The serving config is latency-budgeted (small nprobe, capped
    ``max_scan`` block budget) — the operating point a front-end
    actually serves, and the regime where per-dispatch overhead is
    worth amortizing.  Offered loads are calibrated to the machine: a
    back-to-back warmup
    run measures the per-request sustainable throughput, and each load
    point offers ``load_factor`` times that rate.  Below 1.0 both paths
    keep up and coalescing (by design) buys nothing; above it the
    per-request path saturates while the batched gateway keeps
    absorbing the stream — the regime a serving front-end exists for.

    Asserts the gateway's core claim so CI's ``gateway-smoke`` step
    fails loudly if coalescing regresses: at the highest offered load
    the batched gateway sustains >= 2x the per-request throughput."""
    from repro.gateway import Gateway, GatewayConfig, run_open_loop

    ctx = get_context(dataset, n_queries=256)
    idx = ctx.index("rair", True)
    q = np.asarray(ctx.q)
    modes = {
        "batched": GatewayConfig(max_delay_ms=max_delay_ms,
                                 max_batch=max_batch),
        "per_request": GatewayConfig(max_delay_ms=0.0, max_batch=1,
                                     admission="fifo"),
    }
    # calibrate: per-request capacity under back-to-back arrivals
    with Gateway(idx, k=k, nprobe=nprobe, max_scan=max_scan,
                 config=modes["per_request"]) as gw:
        cal = run_open_loop(gw, q, 1e6, max(n_requests // 3, 32), seed=99)
    per_req_cap = cal["achieved_qps"]
    offered = tuple(f * per_req_cap for f in load_factors)
    emit(f"serve_gateway/{dataset}/calibration", 0.0,
         f"per_request_capacity={per_req_cap:.0f}qps "
         f"offered={[f'{o:.0f}' for o in offered]}")

    runs = {}
    for mode, cfg in modes.items():
        with Gateway(idx, k=k, nprobe=nprobe, max_scan=max_scan,
                     config=cfg) as gw:
            rows = [run_open_loop(gw, q, qps, n_requests, seed=i)
                    for i, qps in enumerate(offered)]
            tel = gw.stats()["telemetry"]
        runs[mode] = {"points": rows,
                      "batch_fill": tel["batch_fill"],
                      "bucket_fill": tel["bucket_fill"],
                      "counters": tel["counters"]}

    points = []
    for i, qps in enumerate(offered):
        b = runs["batched"]["points"][i]
        p = runs["per_request"]["points"][i]
        speedup = b["achieved_qps"] / max(p["achieved_qps"], 1e-9)
        points.append({"offered_qps": qps, "speedup": speedup,
                       "batched": b, "per_request": p})
        emit(f"serve_gateway/{dataset}/qps{qps:g}", 0.0,
             f"batched={b['achieved_qps']:.0f} "
             f"per_request={p['achieved_qps']:.0f} "
             f"speedup={speedup:.2f}x "
             f"p50={b['p50_ms']:.1f}ms p99={b['p99_ms']:.1f}ms "
             f"mean_batch={b['mean_batch']:.1f}")
    out = {"k": k, "nprobe": nprobe, "max_scan": max_scan,
           "max_batch": max_batch,
           "max_delay_ms": max_delay_ms, "n_requests": n_requests,
           "per_request_capacity_qps": per_req_cap,
           "load_factors": list(load_factors),
           "points": points,
           "batched": {m: runs["batched"][m] for m in
                       ("batch_fill", "bucket_fill", "counters")},
           "per_request": {m: runs["per_request"][m] for m in
                           ("batch_fill", "bucket_fill", "counters")}}
    save_json("serve_gateway", out)
    errs = sum(pt["batched"]["errors"] + pt["per_request"]["errors"]
               for pt in points)
    assert errs == 0, f"{errs} gateway requests failed or timed out"
    top = max(pt["speedup"] for pt in points)
    assert top >= 2.0, (
        f"deadline-batched gateway only {top:.2f}x per-request dispatch "
        f"at its best offered load point — coalescing regressed")
    return out


def bench_overload(dataset="sift1m", k=10, nprobe=8, max_scan=16,
                   load_factors=(0.5, 1.0, 2.0), n_requests=512,
                   max_batch=32, max_delay_ms=2.0, max_queue=64,
                   recall_floor=None):
    """Overload-resilience bench (-> BENCH_overload.json, DESIGN.md
    §13): the same open-loop Poisson stream at 0.5x / 1x / 2x the
    *measured* saturating throughput, served three ways —

      unbounded   today's default: no admission bound, queueing delay
                  grows without limit past saturation
      shed        bounded queue (``max_queue``), reject policy: excess
                  arrivals fail fast with ``Overloaded``
      degrade     bounded queue + the quality ladder: under sustained
                  pressure the gateway steps down a pre-compiled
                  reduced-effort ``SearchParams`` rung instead of (or
                  before) shedding, and steps back up when load recedes

    Each point carries a full typed accounting (ok / shed / deadline /
    closed / untyped) plus recall@k of every answered query against the
    offline ground truth — degradation has a *price*, and the bench
    publishes it next to the latency it buys.  The regression gate
    asserts the machine-independent invariants: nothing dropped without
    a typed error, shed fraction monotone in offered load, the
    unbounded mode never sheds, answered recall above the documented
    floor, and the ladder actually engaging at top load — never a
    wall-clock number.
    """
    from repro.core import SearchParams
    from repro.gateway import (Gateway, GatewayConfig, degrade_ladder,
                               run_open_loop)

    ctx = get_context(dataset, n_queries=256)
    idx = ctx.index("rair", True)
    q = np.asarray(ctx.q)
    gt = np.asarray(ctx.gt(k))
    if recall_floor is None:
        # documented floors (DESIGN.md §13): the deepest ladder rung
        # (nprobe/4, max_scan/4) stays above these on answered queries
        # (the level-0 operating point itself is latency-budgeted:
        # nprobe=8/max_scan=16 sits near 0.48 recall@10 on sift1m)
        recall_floor = 0.4 if dataset == "sift1m" else 0.2
    params = SearchParams(k=k, nprobe=nprobe, max_scan=max_scan)
    ladder = degrade_ladder(params, levels=2)
    modes = {
        "unbounded": GatewayConfig(max_delay_ms=max_delay_ms,
                                   max_batch=max_batch),
        "shed": GatewayConfig(max_delay_ms=max_delay_ms,
                              max_batch=max_batch,
                              max_queue=max_queue, overload="reject"),
        "degrade": GatewayConfig(max_delay_ms=max_delay_ms,
                                 max_batch=max_batch,
                                 max_queue=max_queue, overload="reject",
                                 degrade=ladder[1:], degrade_hold=2),
    }
    # calibrate: saturating throughput of the (batched) serving config.
    # One search first so session creation + width warmup compile
    # outside the measured window — calibrating against cold-compile
    # wall time understates capacity and the "2x" sweep never overloads
    with Gateway(idx, params, config=modes["unbounded"]) as gw:
        gw.search(q[0])
        cal = run_open_loop(gw, q, 1e6, max(n_requests // 3, 32), seed=99)
    sat_qps = cal["achieved_qps"]
    offered = tuple(f * sat_qps for f in load_factors)
    emit(f"overload/{dataset}/calibration", 0.0,
         f"saturating={sat_qps:.0f}qps "
         f"offered={[f'{o:.0f}' for o in offered]}")

    out_modes = {}
    for mode, cfg in modes.items():
        points = []
        with Gateway(idx, params, config=cfg) as gw:
            gw.search(q[0])       # compile outside the measured points
            for i, qps in enumerate(offered):
                pt = run_open_loop(gw, q, qps, n_requests, seed=i,
                                   collect=True)
                ids = pt.pop("ok_ids")
                qi = pt.pop("ok_query_idx")
                pt["load_factor"] = load_factors[i]
                pt["recall"] = (float(per_query_recall(
                    ids, gt[qi]).mean()) if len(qi) else 0.0)
                points.append(pt)
                emit(f"overload/{dataset}/{mode}/x{load_factors[i]:g}", 0.0,
                     f"ok={pt['n_ok']} shed={pt['shed']} "
                     f"recall={pt['recall']:.3f} "
                     f"p99={pt['p99_ms']:.1f}ms levels={pt['levels']}")
            tel = gw.stats()["telemetry"]
        out_modes[mode] = {"points": points, "counters": tel["counters"]}

    top = len(offered) - 1
    p99_u = out_modes["unbounded"]["points"][top]["p99_ms"]
    p99_d = out_modes["degrade"]["points"][top]["p99_ms"]
    out = {"k": k, "nprobe": nprobe, "max_scan": max_scan,
           "max_batch": max_batch, "max_delay_ms": max_delay_ms,
           "max_queue": max_queue, "n_requests": n_requests,
           "saturating_qps": sat_qps,
           "load_factors": list(load_factors),
           "ladder": [{"nprobe": p.nprobe, "max_scan": p.max_scan}
                      for p in ladder],
           "recall_floor": recall_floor,
           "ladder_engaged": out_modes["degrade"]["counters"].get(
               "degrade_steps_down", 0) >= 1,
           "p99_top_load_degrade_over_unbounded": p99_d / max(p99_u, 1e-9),
           "modes": out_modes}
    save_json("overload", out)
    emit(f"overload/{dataset}/summary", 0.0,
         f"p99@2x degrade/unbounded="
         f"{out['p99_top_load_degrade_over_unbounded']:.3f} "
         f"ladder_engaged={out['ladder_engaged']}")
    return out
