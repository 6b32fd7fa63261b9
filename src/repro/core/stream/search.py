"""Streaming query pipeline: base engine stages + delta scan + tombstones.

``streaming_search`` runs the same four engine stages as ``seil_search``
over the immutable base layout, then extends the candidate stream with
the mutable epoch state before the shared finalize stage:

  * the **delta segment** is scanned in one of two ways.  While it is
    small (capacity <= the routing threshold, ``IndexConfig.
    delta_route_min``, default ``nlist * block``) every live slot of the
    padded flat code buffer gets one ADC distance per query — no
    routing, the exhaustive fast path.  Once capacity outgrows the
    threshold the scan is **routed**: each probed list contributes only
    the delta slots assigned to it (the per-list posting map maintained
    on append, stream/delta.py), deduplicated to the lowest-ranked
    probed assigned list — the delta-side analogue of Alg. 5's
    ``listVisited`` probe — so the per-query cost drops from O(capacity)
    to O(nprobe x list occupancy).  Routing narrows reach to the probed
    lists (exactly the base layout's semantics, i.e. what the same items
    get after compaction); with every assigned list probed the candidate
    set — and the results — are identical to the exhaustive path
    (asserted in tests/test_plan.py).  Either way delta candidates enter
    ``finalize_candidates`` through its ``extra_d/extra_i`` merge and
    compete with base candidates under the exact same top-bigK /
    refinement rules;
  * the **tombstone mask** (``live``, over the whole id space base +
    delta) is applied inside finalize — deleted items are forced to
    +inf before selection instead of being rewritten out.

DCO accounting stays paper-faithful: the exhaustive path counts one ADC
distance per live slot per query; the routed path counts one per live
slot *reachable through the probed lists* (computed once, at its
lowest-ranked probed list).  Dead slots cost nothing; refinement counts
once per surviving unique candidate.

All shapes are static given (batch bucket, delta capacity, posting
width): the delta buffers are padded to fixed capacity buckets and the
posting map to power-of-two per-list widths (stream/delta.py), so
steady-state churn dispatches to cached executables without retracing.

``scan_finalize_stream`` is the streaming scan half of the split
(incremental-plan) pipeline — the counterpart of
``core/search.py::scan_finalize`` dispatched by ``StreamingSearcher``
sessions with ``SearchParams(plan_reuse=True)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from ..engine import (PlanProbe, finalize_candidates, plan_blocks,
                      scan_blocks, scan_blocks_topk, select_lists,
                      store_from_arrays, tables_from_arrays)
from ..pq import PQCodebook, pq_lut, pq_lut_ip
from ..search import (SearchResult, _stage_plan, _stage_scan, _stage_select,
                      finalize_fetch)
from ..seil import SeilArrays


def delta_adc(lut: jnp.ndarray, delta_codes: jnp.ndarray) -> jnp.ndarray:
    """ADC distances of every delta slot: (B, M, K) lut x (C, M) codes
    -> (B, C).  d[b, c] = sum_m lut[b, m, codes[c, m]]."""
    m = delta_codes.shape[1]
    g = lut[:, jnp.arange(m)[None, :], delta_codes.astype(jnp.int32)]
    return jnp.sum(g, axis=-1)


def routed_delta_candidates(lut, delta_codes, delta_ids, delta_post,
                            delta_assigns, sel, rank_of):
    """Delta candidates reached through the probed lists only.

    lut (B, M, K); delta_post (nlist, L) slot ids (-1 pad);
    delta_assigns (cap, m); sel (B, P) ranked probed lists; rank_of
    (B, nlist).  Returns ``(dd, di, dco)``: (B, P*L) distances/ids and
    the per-query routed DCO.  A slot assigned to several probed lists
    is computed exactly once — at its lowest-ranked probed assigned
    list (the delta-side ``listVisited``), so SEIL-exact result streams
    stay duplicate-free.
    """
    b, p = sel.shape
    slots = delta_post[sel]                               # (B, P, L)
    s0 = jnp.maximum(slots, 0)
    sids = jnp.where(slots >= 0, delta_ids[s0], -1)       # (B, P, L)
    al = delta_assigns[s0]                                # (B, P, L, m)
    r = jnp.take_along_axis(rank_of, al.reshape(b, -1), axis=1
                            ).reshape(al.shape)
    min_rank = jnp.min(r, axis=-1)                        # (B, P, L)
    keep = (sids >= 0) & (min_rank
                          == jnp.arange(p, dtype=jnp.int32)[None, :, None])
    codes = delta_codes[s0]                               # (B, P, L, M)
    g = jnp.take_along_axis(lut[:, None, None, :, :],
                            codes.astype(jnp.int32)[..., None], axis=-1)
    d = jnp.sum(g[..., 0], axis=-1)                       # (B, P, L)
    dd = jnp.where(keep, d, jnp.inf).reshape(b, -1)
    di = jnp.where(keep, sids, -1).reshape(b, -1)
    return dd, di, jnp.sum(keep, axis=(1, 2)).astype(jnp.int32)


def _delta_candidates(lut, delta_codes, delta_ids, delta_post,
                      delta_assigns, sel, rank_of, route_delta: bool):
    """(dd, di, per-query delta DCO) via the routed or exhaustive path."""
    if route_delta:
        return routed_delta_candidates(lut, delta_codes, delta_ids,
                                       delta_post, delta_assigns, sel,
                                       rank_of)
    alive = delta_ids >= 0                                # (cap,)
    dd = jnp.where(alive[None, :], delta_adc(lut, delta_codes), jnp.inf)
    di = jnp.broadcast_to(delta_ids[None, :], dd.shape)
    dco = jnp.broadcast_to(jnp.sum(alive).astype(jnp.int32),
                           (lut.shape[0],))
    return dd, di, dco


@functools.partial(
    jax.jit,
    static_argnames=("nprobe", "bigk", "k", "max_scan", "metric",
                     "dedup_results", "use_kernel", "oversample",
                     "exec_mode", "query_tile", "route_delta",
                     "fused_topk", "packed_codes"))
def streaming_search(
    arrays: SeilArrays,
    centroids: jnp.ndarray,       # (nlist, D)
    codebook: PQCodebook,
    vectors: jnp.ndarray,         # (n_base + cap, D) refine store, id-aligned
    delta_codes: jnp.ndarray,     # (cap, M) uint8 padded delta buffer
    delta_ids: jnp.ndarray,       # (cap,) int32 global ids, -1 dead/unused
    delta_post: jnp.ndarray,      # (nlist, L) int32 slot postings, -1 pad
    delta_assigns: jnp.ndarray,   # (cap, m) int32 assigned lists per slot
    live: jnp.ndarray,            # (n_base + cap,) bool tombstone mask
    queries: jnp.ndarray,         # (B, D)
    *,
    nprobe: int,
    bigk: int,
    k: int,
    max_scan: int,
    metric: str = "l2",
    dedup_results: bool = True,
    use_kernel: bool = False,
    oversample: int = 2,
    exec_mode: str = "paged",
    query_tile: int = 8,
    route_delta: bool = False,
    fused_topk: bool = False,
    packed_codes: bool = False,   # arrays carry a nibble-packed quant plane
) -> SearchResult:
    with jax.named_scope("select_lists"):
        selection = select_lists(queries, centroids, nprobe=nprobe,
                                 metric=metric)
    with jax.named_scope("plan_blocks"):
        plan = plan_blocks(tables_from_arrays(arrays), selection,
                           max_scan=max_scan)
        lut = (pq_lut(codebook, queries) if metric == "l2"
               else pq_lut_ip(codebook, queries))            # (B, M, 16)
    with jax.named_scope("scan"):
        if fused_topk:
            # live is applied pre-selection so tombstoned base candidates
            # cannot occupy top-fetch slots; finalize's re-mask is
            # idempotent
            scan = scan_blocks_topk(
                store_from_arrays(arrays), plan, lut, selection.rank_of,
                fetch=finalize_fetch(bigk, oversample, dedup_results),
                exec_mode=exec_mode, use_kernel=use_kernel,
                query_tile=query_tile, sel=selection.sel, live=live,
                packed=packed_codes)
        else:
            scan = scan_blocks(store_from_arrays(arrays), plan, lut,
                               selection.rank_of, exec_mode=exec_mode,
                               use_kernel=use_kernel, query_tile=query_tile,
                               sel=selection.sel, packed=packed_codes)
        dd, di, delta_dco = _delta_candidates(
            lut, delta_codes, delta_ids, delta_post, delta_assigns,
            selection.sel, selection.rank_of, route_delta)
    with jax.named_scope("finalize"):
        out_ids, out_d, refine_dco = finalize_candidates(
            scan.flat_d, scan.flat_i, bigk=bigk, k=k, vectors=vectors,
            queries=queries, metric=metric, dedup_results=dedup_results,
            oversample=oversample, extra_d=dd, extra_i=di, live=live)
    return SearchResult(
        ids=out_ids, dists=out_d, approx_dco=scan.approx_dco + delta_dco,
        refine_dco=refine_dco, scanned_blocks=scan.scanned_blocks,
        dropped_blocks=plan.dropped)


# ---------------------------------------------------------------------------
# traced pipeline — streaming_search cut at its stage boundaries
# (DESIGN.md §11): the base stage programs from core/search.py plus a
# separate delta-scan stage, so the delta-vs-base scan split shows up
# directly as span counters.  Bitwise-identical to streaming_search
# (asserted in tests/test_obs.py).
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("route_delta",))
def _stage_delta(lut, delta_codes, delta_ids, delta_post, delta_assigns,
                 sel, rank_of, *, route_delta):
    return _delta_candidates(lut, delta_codes, delta_ids, delta_post,
                             delta_assigns, sel, rank_of, route_delta)


@functools.partial(
    jax.jit,
    static_argnames=("bigk", "k", "metric", "dedup_results", "oversample"))
def _stage_finalize_stream(vectors, queries, flat_d, flat_i, dd, di, live,
                           *, bigk, k, metric, dedup_results, oversample):
    return finalize_candidates(
        flat_d, flat_i, bigk=bigk, k=k, vectors=vectors, queries=queries,
        metric=metric, dedup_results=dedup_results, oversample=oversample,
        extra_d=dd, extra_i=di, live=live)


def streaming_search_traced(
    arrays, centroids, codebook, vectors, delta_codes, delta_ids,
    delta_post, delta_assigns, live, queries, *, nprobe, bigk, k, max_scan,
    metric="l2", dedup_results=True, use_kernel=False, oversample=2,
    exec_mode="paged", query_tile=8, route_delta=False, fused_topk=False,
    packed_codes=False,
) -> SearchResult:
    """Stage-fenced ``streaming_search`` for tracing: identical
    composition, span + fence per stage, delta DCO on its own span."""
    with obs.span("stage.select_lists", cat="device", nprobe=nprobe):
        selection = obs.fence(_stage_select(centroids, queries,
                                            nprobe=nprobe, metric=metric))
    with obs.span("stage.plan_blocks", cat="device", max_scan=max_scan):
        plan, lut = obs.fence(_stage_plan(arrays, codebook, selection,
                                          queries, max_scan=max_scan,
                                          metric=metric))
    name = "stage.scan_blocks_topk" if fused_topk else "stage.scan_blocks"
    with obs.span(name, cat="device", exec_mode=exec_mode) as sp:
        # fused applies the tombstone mask pre-selection (has_live)
        scan = obs.fence(_stage_scan(
            arrays, plan, lut, selection, live,
            fetch=finalize_fetch(bigk, oversample, dedup_results),
            exec_mode=exec_mode, use_kernel=use_kernel,
            query_tile=query_tile, fused_topk=fused_topk,
            has_live=fused_topk, packed_codes=packed_codes))
        sp.add(approx_dco=int(np.sum(np.asarray(scan.approx_dco))),
               scanned_blocks=int(np.sum(np.asarray(scan.scanned_blocks))))
    with obs.span("stage.delta_scan", cat="device",
                  routed=bool(route_delta)) as sp:
        dd, di, delta_dco = obs.fence(_stage_delta(
            lut, delta_codes, delta_ids, delta_post, delta_assigns,
            selection.sel, selection.rank_of, route_delta=route_delta))
        sp.add(delta_dco=int(np.sum(np.asarray(delta_dco))))
    with obs.span("stage.finalize", cat="device") as sp:
        out_ids, out_d, refine_dco = obs.fence(_stage_finalize_stream(
            vectors, queries, scan.flat_d, scan.flat_i, dd, di, live,
            bigk=bigk, k=k, metric=metric, dedup_results=dedup_results,
            oversample=oversample))
        sp.add(refine_dco=int(np.sum(np.asarray(refine_dco))))
    return SearchResult(
        ids=out_ids, dists=out_d, approx_dco=scan.approx_dco + delta_dco,
        refine_dco=refine_dco, scanned_blocks=scan.scanned_blocks,
        dropped_blocks=plan.dropped)


@functools.partial(
    jax.jit,
    static_argnames=("bigk", "k", "metric", "dedup_results", "use_kernel",
                     "oversample", "exec_mode", "query_tile", "route_delta",
                     "fused_topk", "packed_codes"))
def scan_finalize_stream(
    arrays: SeilArrays,
    vectors: jnp.ndarray,
    delta_codes: jnp.ndarray,
    delta_ids: jnp.ndarray,
    delta_post: jnp.ndarray,
    delta_assigns: jnp.ndarray,
    live: jnp.ndarray,
    queries: jnp.ndarray,
    probe: PlanProbe,
    unions: jnp.ndarray,          # (T, W') width-bucketed unions to scan
    *,
    bigk: int,
    k: int,
    metric: str = "l2",
    dedup_results: bool = True,
    use_kernel: bool = False,
    oversample: int = 2,
    exec_mode: str = "grouped",
    query_tile: int = 8,
    route_delta: bool = False,
    fused_topk: bool = False,
    packed_codes: bool = False,
) -> SearchResult:
    """Streaming stages 3-4 against caller-provided (reused) unions —
    the probe half is the base ``probe_plan`` (the delta needs no block
    planning), so incremental plans compose with churn unchanged."""
    with jax.named_scope("scan"):
        if fused_topk:
            scan = scan_blocks_topk(
                store_from_arrays(arrays), probe.plan, probe.lut,
                probe.rank_of,
                fetch=finalize_fetch(bigk, oversample, dedup_results),
                exec_mode=exec_mode, use_kernel=use_kernel,
                query_tile=query_tile, perm=probe.perm, unions=unions,
                live=live, packed=packed_codes)
        else:
            scan = scan_blocks(store_from_arrays(arrays), probe.plan,
                               probe.lut, probe.rank_of, exec_mode=exec_mode,
                               use_kernel=use_kernel, query_tile=query_tile,
                               perm=probe.perm, unions=unions,
                               packed=packed_codes)
        dd, di, delta_dco = _delta_candidates(
            probe.lut, delta_codes, delta_ids, delta_post, delta_assigns,
            probe.sel, probe.rank_of, route_delta)
    with jax.named_scope("finalize"):
        out_ids, out_d, refine_dco = finalize_candidates(
            scan.flat_d, scan.flat_i, bigk=bigk, k=k, vectors=vectors,
            queries=queries, metric=metric, dedup_results=dedup_results,
            oversample=oversample, extra_d=dd, extra_i=di, live=live)
    return SearchResult(
        ids=out_ids, dists=out_d, approx_dco=scan.approx_dco + delta_dco,
        refine_dco=refine_dco, scanned_blocks=scan.scanned_blocks,
        dropped_blocks=probe.plan.dropped)
