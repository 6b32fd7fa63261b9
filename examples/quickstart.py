"""Quickstart: build a RAIRS index, open a compiled searcher session,
persist the index, and see why RAIR+SEIL win.

Run: PYTHONPATH=src python examples/quickstart.py
"""
import os
import tempfile

import jax
import numpy as np

from repro.core import (IndexConfig, SearchParams, build_index, dco_summary,
                        ground_truth, load_index, recall_at_k, save_index,
                        vectors_in_large_cells)
from repro.data import make_dataset

# 1. a SIFT-like corpus (clustered, low intrinsic dimension)
x, queries, spec = make_dataset("unit")
gt = ground_truth(x, queries, k=10)

# 2. the paper's index: RAIR (AIR metric) redundant assignment + SEIL lists
index = build_index(jax.random.PRNGKey(0), x,
                    IndexConfig(nlist=64, strategy="rair", seil=True))
print(f"cells: {vectors_in_large_cells(index.assigns):.0%} of vectors live "
      f"in shared cells >= 1 block (the skew SEIL exploits)")

# 3. open a compiled searcher session (params validated + resolved once,
#    executables cached per batch-size bucket) and compare against the
#    single-assignment baseline at equal nprobe
params = SearchParams(k=10, nprobe=6)
baseline = build_index(jax.random.PRNGKey(0), x,
                       IndexConfig(nlist=64, strategy="single"),
                       centroids=index.centroids, codebook=index.codebook)
for name, idx in [("IVFPQfs (single)", baseline), ("RAIRS", index)]:
    searcher = idx.searcher(params)
    res = searcher(queries)
    rec = recall_at_k(np.asarray(res.ids), gt)
    s = dco_summary(res)
    print(f"{name:18s} nprobe=6: recall@10={rec:.3f} "
          f"distance-computations/query={s['total_dco']:.0f}")

# 4. sessions absorb varying batch sizes without retracing: every batch
#    pads to a cached bucket executable (watch the compile counters)
searcher = index.searcher(params)
for bs in (200, 64, 100, 200):
    searcher(queries[:bs])
print(f"session stats after mixed batches: {searcher.compile_stats()}")

# 5. persistence: save/load round-trips the whole index (config, centroids,
#    codebook, SEIL arrays, cached codes) — no re-training on restart
with tempfile.TemporaryDirectory() as td:
    bundle = os.path.join(td, "rairs_unit.npz")
    save_index(index, bundle)
    restored = load_index(bundle)
    res2 = restored.searcher(params)(queries)
    assert np.array_equal(np.asarray(res.ids), np.asarray(res2.ids))
    print(f"save/load round-trip: identical results "
          f"({os.path.getsize(bundle) / 1e6:.1f}MB bundle)")

# 6. the same search through the Pallas TPU kernel (interpret on CPU): the
#    scan stage emits only the bigK*oversample candidates finalize
#    selects, kept by a VMEM-resident bitonic accumulator inside the
#    kernel, instead of writing the full (S, BLK) score tensor to HBM
#    (DESIGN.md §9)
kp = SearchParams(k=10, nprobe=6, use_kernel=True)
res_k = index.searcher(kp)(queries[:8])
res_j = index.searcher(params)(queries[:8])
assert np.array_equal(np.asarray(res_k.ids), np.asarray(res_j.ids))
print("pallas scan->top-k kernel path == jnp path (8 queries checked)")

# 7. a mesh is a deployment detail: shard the index and serve through the
#    *same* session API (1-device mesh here; bitwise-identical results —
#    on a real pod only the mesh constructor changes)
mesh = jax.make_mesh((len(jax.devices()),), ("data",))
sharded = index.shard(mesh)
res_m = sharded.searcher(params)(queries[:64])
assert np.array_equal(np.asarray(res_m.ids), np.asarray(res.ids[:64]))
print(f"sharded ({sharded.ndev}-device) session == single-host session; "
      f"stats: {sharded.searcher_stats()}")

# 8. steady-state serving with the locality-aware planner: clustered
#    execution buckets each batch by probed-list overlap (per-tile block
#    unions) and plan_reuse carries those unions across adjacent batches
#    — watch the plan-cache hit rate climb while results stay bitwise
#    identical to the paged scan
rng = np.random.default_rng(0)
hot = np.asarray(queries[:16])                    # a skewed "hot query" pool
serving = index.searcher(SearchParams(k=10, nprobe=6, exec_mode="clustered",
                                      plan_reuse=True))
for step in range(5):                             # the serving loop
    batch = hot[rng.integers(0, len(hot), 64)] + \
        rng.normal(0, 0.01, (64, hot.shape[1])).astype(np.float32)
    res_c = serving(batch)
    assert np.array_equal(
        np.asarray(res_c.ids),
        np.asarray(index.search(batch, k=10, nprobe=6).ids))
plan = serving.compile_stats()["plan"]
print(f"steady-state plan cache after 5 batches: "
      f"hit_rate={plan['hit_rate']:.0%} "
      f"tile_union~{plan['mean_union_live']:.0f} blocks "
      f"(scan width {plan['mean_width']:.0f})")

# 9. serving *traffic* instead of batches: the async gateway coalesces
#     single-query submissions into the same compiled buckets (flush on
#     a 2ms deadline or a full bucket) and keeps first-class telemetry —
#     batch_fill > 1 is the whole point (DESIGN.md §10).  On a
#     StreamingIndex, gw.compact_async() folds a new epoch in the
#     background and installs it between batches: zero downtime, and the
#     external ids in responses stay valid across the swap.
from repro.gateway import Gateway, GatewayConfig

with Gateway(index, params,
             config=GatewayConfig(max_delay_ms=2.0, max_batch=32)) as gw:
    pending = [gw.submit(q) for q in np.asarray(queries[:64])]
    answers = [p.result(timeout=30.0) for p in pending]
    assert np.array_equal(np.asarray(answers[0].ids), np.asarray(res.ids[0]))
    snap = gw.stats()["telemetry"]
    print(f"gateway: {len(answers)} requests coalesced into "
          f"{snap['counters']['batches']} dispatches "
          f"(batch_fill={snap['batch_fill']:.1f}, "
          f"p99={snap['latency']['p99_ms']:.1f}ms)")

# 10. x-ray the dispatch: with a tracer active every engine stage is a
#     fenced span (device time + per-stage DCO); off, tracing costs
#     literally nothing and results are bitwise identical either way.
#     write_trace emits Chrome/Perfetto trace-event JSON — drop it on
#     ui.perfetto.dev — and snapshot_all unifies session, gateway, HBM-
#     model, and per-stage trace stats in one dict (DESIGN.md §11)
from repro import obs

searcher = index.searcher(params)
ref = searcher(queries[:64])
with obs.trace():
    searcher(queries[:64])              # first traced call compiles stages
with obs.trace() as tr:
    traced = searcher(queries[:64])
assert np.array_equal(np.asarray(traced.ids), np.asarray(ref.ids))
trace_path = os.path.join(tempfile.mkdtemp(), "quickstart_trace.json")
obs.write_trace(tr, trace_path)
snap = obs.snapshot_all(searcher=searcher, tracer=tr)
stages = {n.removeprefix("stage."): f"{v['mean_ms']:.2f}ms"
          for n, v in sorted(tr.stage_summary().items())
          if n.startswith("stage.")}
print(f"traced dispatch == untraced (64 queries); per-stage {stages}; "
      f"attribution={snap['trace']['stage_attribution']:.0%} -> "
      f"{trace_path}")

# 11. the two-tier quantization ladder (DESIGN.md §12): tier-1 scans a
#     compact code plane (here coarse 4-bit PQ) keeping a widened
#     bigK * refine_factor survivor set, tier-2 re-ranks the survivors
#     exactly — same engine, same sessions, just cheaper scanning.
#     refine_factor=1 degenerates to the single-tier program *bitwise*;
#     snapshot_all reports the modeled tier split
from repro.core import RefineParams

two_tier = index.searcher(SearchParams(
    k=10, nprobe=6, refine=RefineParams(plane="pq4", refine_factor=4)))
res_2t = two_tier(queries)
model = obs.snapshot_all(searcher=two_tier)["hbm_model"]["refine"]
res_rf1 = index.searcher(SearchParams(
    k=10, nprobe=6, refine=RefineParams(plane="pq4", refine_factor=1)))(queries)
assert np.array_equal(np.asarray(res_rf1.ids),
                      np.asarray(index.searcher(params)(queries).ids))
print(f"two-tier pq4/rf4: recall@10="
      f"{recall_at_k(np.asarray(res_2t.ids), gt):.3f} "
      f"(single-tier {recall_at_k(np.asarray(res.ids), gt):.3f}); "
      f"tier-1 scans {model['m_compact']} of {model['m_full']} "
      f"subquantizers -> modeled total-ops "
      f"{model['total_ops_reduction_x']:.2f}x cheaper; rf=1 == single-tier")

# 12. overload resilience (DESIGN.md §13): the same gateway, now with a
#     bounded queue.  Unbounded, a burst past capacity just queues (and
#     p99 grows with the backlog); bounded with overload="reject", the
#     excess fails *fast and typed* — submit returns an already-failed
#     handle carrying Overloaded, so every request resolves either way.
#     Add degrade= (a pre-compiled reduced-effort ladder) and sustained
#     pressure steps quality down instead of shedding, stepping back up
#     when the burst passes — each answer is tagged with the level that
#     served it.
from repro.gateway import Overloaded, degrade_ladder

burst = np.asarray(queries[:192])
with Gateway(index, params,
             config=GatewayConfig(max_delay_ms=2.0, max_batch=32)) as gw:
    answered = [gw.submit(q).result(30.0) for q in burst]   # all served
with Gateway(index, params,
             config=GatewayConfig(max_delay_ms=2.0, max_batch=32,
                                  max_queue=16, overload="reject",
                                  degrade=degrade_ladder(params)[1:],
                                  degrade_hold=1)) as gw:
    pending = [gw.submit(q) for q in burst]
    ok, shed = [], 0
    for p in pending:
        try:
            ok.append(p.result(30.0))
        except Overloaded:
            shed += 1
    levels = sorted({r.level for r in ok})
print(f"overload: unbounded served {len(answered)}/{len(burst)}; "
      f"bounded served {len(ok)} + shed {shed} typed "
      f"(quality levels used: {levels}) — nothing dropped silently")
