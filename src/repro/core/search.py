"""SEIL-optimized ANNS query pipeline (paper Alg. 2 + Alg. 5), static-shape.

``seil_search`` is a thin composition of the staged query engine
(core/engine/, DESIGN.md §5):

  1. ``select_lists``  — score list centroids, take top-nprobe (ranked);
  2. ``plan_blocks``   — gather owned / referenced / misc block tables,
     apply cell-level dedup (the vectorized ``listVisited`` probe) and
     compact candidates to a static scan budget;
  3. ``scan_blocks``   — ADC distances for every surviving block (Pallas
     kernel on TPU, jnp oracle elsewhere) + item-level masks, in either
     ``exec_mode="paged"`` (per-query paging) or ``"grouped"`` (the
     paper's §5.3 list-major batch mode: one batch-union block plan,
     each block fetched once per query tile);
  4. ``finalize_candidates`` — top-bigK (+ id-dedup for layouts without
     SEIL), exact refinement over the original vectors, top-K.

DCO accounting is paper-faithful: every *valid* item in a scanned block
counts one distance computation (misc duplicates included — SEIL cannot
avoid them, Alg. 5 L15), skipped reference blocks count zero, refine
adds one exact DCO per unique candidate.  Both exec modes produce
bitwise-identical results and counters (tests/test_engine.py).

The distributed serving step (core/distributed.py) composes the same
stages over a sharded ``BlockStore`` — improvements to any stage apply
to both paths.

``seil_search`` is the unit Searcher sessions compile: a session AOT-
lowers this exact jitted function per batch-size bucket
(``seil_search.lower(...).compile()``, core/searcher.py), which is why
session results are bitwise identical to direct calls.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from .engine import (PlanProbe, cluster_order, finalize_candidates,
                     plan_blocks, scan_blocks, scan_blocks_topk,
                     select_lists, store_from_arrays, tables_from_arrays,
                     tile_unions, union_dims)
from .pq import PQCodebook, pq_lut, pq_lut_ip
from .seil import SeilArrays


def finalize_fetch(bigk: int, oversample: int, dedup_results: bool) -> int:
    """The candidate width ``finalize_candidates`` selects before exact
    refinement — the budget a fused scan must deliver for bitwise parity
    (``preselect_candidates``' covering-width invariant)."""
    return bigk * (oversample if dedup_results else 1)


class SearchResult(NamedTuple):
    ids: jnp.ndarray          # (B, K) int32 final ids (-1 pad)
    dists: jnp.ndarray        # (B, K) f32 exact distances
    approx_dco: jnp.ndarray   # (B,) int32 ADC distance computations
    refine_dco: jnp.ndarray   # (B,) int32 exact distance computations
    scanned_blocks: jnp.ndarray  # (B,) int32
    dropped_blocks: jnp.ndarray  # (B,) int32 budget overflow (should be 0)


@functools.partial(
    jax.jit,
    static_argnames=("nprobe", "bigk", "k", "max_scan", "metric",
                     "dedup_results", "use_kernel", "oversample",
                     "exec_mode", "query_tile", "fused_topk",
                     "packed_codes"))
def seil_search(
    arrays: SeilArrays,
    centroids: jnp.ndarray,       # (nlist, D)
    codebook: PQCodebook,
    vectors: jnp.ndarray,         # (n, D) refine store
    queries: jnp.ndarray,         # (B, D)
    *,
    nprobe: int,
    bigk: int,
    k: int,
    max_scan: int,                # static per-query block budget
    metric: str = "l2",
    dedup_results: bool = True,
    use_kernel: bool = False,
    oversample: int = 2,
    exec_mode: str = "paged",
    query_tile: int = 8,
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
            scan = scan_blocks_topk(
                store_from_arrays(arrays), plan, lut, selection.rank_of,
                fetch=finalize_fetch(bigk, oversample, dedup_results),
                exec_mode=exec_mode, use_kernel=use_kernel,
                query_tile=query_tile, sel=selection.sel,
                packed=packed_codes)
        else:
            scan = scan_blocks(store_from_arrays(arrays), plan, lut,
                               selection.rank_of, exec_mode=exec_mode,
                               use_kernel=use_kernel, query_tile=query_tile,
                               sel=selection.sel, packed=packed_codes)
    with jax.named_scope("finalize"):
        out_ids, out_d, refine_dco = finalize_candidates(
            scan.flat_d, scan.flat_i, bigk=bigk, k=k, vectors=vectors,
            queries=queries, metric=metric, dedup_results=dedup_results,
            oversample=oversample)
    return SearchResult(
        ids=out_ids, dists=out_d, approx_dco=scan.approx_dco,
        refine_dco=refine_dco, scanned_blocks=scan.scanned_blocks,
        dropped_blocks=plan.dropped)


# ---------------------------------------------------------------------------
# traced pipeline — seil_search cut at its four stage boundaries
# (DESIGN.md §11).
#
# With a tracer active (repro/obs/) sessions dispatch through
# ``seil_search_traced`` instead of the monolithic executable: the same
# four engine stages, one jitted program each, with an obs span + device
# fence at every boundary so each span's duration covers that stage's
# device time.  Splitting at jit boundaries preserves bitwise results —
# the same invariant the plan_reuse split (probe_plan + scan_finalize)
# already relies on — asserted against seil_search in tests/test_obs.py.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("nprobe", "metric"))
def _stage_select(centroids, queries, *, nprobe, metric):
    return select_lists(queries, centroids, nprobe=nprobe, metric=metric)


@functools.partial(jax.jit, static_argnames=("max_scan", "metric"))
def _stage_plan(arrays, codebook, selection, queries, *, max_scan, metric):
    plan = plan_blocks(tables_from_arrays(arrays), selection,
                       max_scan=max_scan)
    lut = (pq_lut(codebook, queries) if metric == "l2"
           else pq_lut_ip(codebook, queries))
    return plan, lut


@functools.partial(
    jax.jit,
    static_argnames=("fetch", "exec_mode", "use_kernel", "query_tile",
                     "fused_topk", "has_live", "packed_codes"))
def _stage_scan(arrays, plan, lut, selection, live, *, fetch, exec_mode,
                use_kernel, query_tile, fused_topk, has_live,
                packed_codes=False):
    if fused_topk:
        return scan_blocks_topk(
            store_from_arrays(arrays), plan, lut, selection.rank_of,
            fetch=fetch, exec_mode=exec_mode, use_kernel=use_kernel,
            query_tile=query_tile, sel=selection.sel,
            live=live if has_live else None, packed=packed_codes)
    return scan_blocks(store_from_arrays(arrays), plan, lut,
                       selection.rank_of, exec_mode=exec_mode,
                       use_kernel=use_kernel, query_tile=query_tile,
                       sel=selection.sel, packed=packed_codes)


@functools.partial(
    jax.jit,
    static_argnames=("bigk", "k", "metric", "dedup_results", "oversample"))
def _stage_finalize(vectors, queries, flat_d, flat_i, *, bigk, k, metric,
                    dedup_results, oversample):
    return finalize_candidates(
        flat_d, flat_i, bigk=bigk, k=k, vectors=vectors, queries=queries,
        metric=metric, dedup_results=dedup_results, oversample=oversample)


def seil_search_traced(
    arrays: SeilArrays,
    centroids: jnp.ndarray,
    codebook: PQCodebook,
    vectors: jnp.ndarray,
    queries: jnp.ndarray,
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
    fused_topk: bool = False,
    packed_codes: bool = False,
) -> SearchResult:
    """Stage-fenced ``seil_search`` for tracing: identical composition,
    one program per stage, span + fence at each boundary."""
    with obs.span("stage.select_lists", cat="device", nprobe=nprobe):
        selection = obs.fence(_stage_select(centroids, queries,
                                            nprobe=nprobe, metric=metric))
    with obs.span("stage.plan_blocks", cat="device", max_scan=max_scan):
        plan, lut = obs.fence(_stage_plan(arrays, codebook, selection,
                                          queries, max_scan=max_scan,
                                          metric=metric))
    name = "stage.scan_blocks_topk" if fused_topk else "stage.scan_blocks"
    with obs.span(name, cat="device", exec_mode=exec_mode) as sp:
        scan = obs.fence(_stage_scan(
            arrays, plan, lut, selection, lut,   # live unused (has_live=F)
            fetch=finalize_fetch(bigk, oversample, dedup_results),
            exec_mode=exec_mode, use_kernel=use_kernel,
            query_tile=query_tile, fused_topk=fused_topk, has_live=False,
            packed_codes=packed_codes))
        sp.add(approx_dco=int(np.sum(np.asarray(scan.approx_dco))),
               scanned_blocks=int(np.sum(np.asarray(scan.scanned_blocks))))
    with obs.span("stage.finalize", cat="device") as sp:
        out_ids, out_d, refine_dco = obs.fence(_stage_finalize(
            vectors, queries, scan.flat_d, scan.flat_i, bigk=bigk, k=k,
            metric=metric, dedup_results=dedup_results,
            oversample=oversample))
        sp.add(refine_dco=int(np.sum(np.asarray(refine_dco))))
    return SearchResult(
        ids=out_ids, dists=out_d, approx_dco=scan.approx_dco,
        refine_dco=refine_dco, scanned_blocks=scan.scanned_blocks,
        dropped_blocks=plan.dropped)


# ---------------------------------------------------------------------------
# split pipeline — the incremental planner's two halves (DESIGN.md §5).
#
# With ``SearchParams(plan_reuse=True)`` a Searcher session dispatches
# each batch as probe -> host plan-cache merge -> scan: ``probe_plan``
# runs stages 1-2 plus union construction, the session merges this
# batch's tile unions with its cached ones (engine/cluster.py) and picks
# the smallest geometric width bucket covering the live entries, and
# ``scan_finalize`` runs stages 3-4 against the provided unions.  Both
# halves together perform exactly the stages of ``seil_search`` once, so
# results stay bitwise identical (tests/test_plan.py).
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("nprobe", "max_scan", "metric", "exec_mode",
                     "query_tile"))
def probe_plan(
    arrays: SeilArrays,
    centroids: jnp.ndarray,
    codebook: PQCodebook,
    queries: jnp.ndarray,
    *,
    nprobe: int,
    max_scan: int,
    metric: str = "l2",
    exec_mode: str = "grouped",
    query_tile: int = 8,
) -> PlanProbe:
    """Stages 1-2 + cluster order + this batch's own tile unions."""
    b = queries.shape[0]
    with jax.named_scope("select_lists"):
        selection = select_lists(queries, centroids, nprobe=nprobe,
                                 metric=metric)
    with jax.named_scope("plan_blocks"):
        plan = plan_blocks(tables_from_arrays(arrays), selection,
                           max_scan=max_scan)
        lut = (pq_lut(codebook, queries) if metric == "l2"
               else pq_lut_ip(codebook, queries))
        if exec_mode == "clustered":
            perm = cluster_order(selection.sel)
        else:
            perm = jnp.arange(b, dtype=jnp.int32)
        t, w = union_dims(b, plan.blocks.shape[1],
                          arrays.block_codes.shape[0], exec_mode, query_tile)
        unions = tile_unions(plan.blocks[perm], plan.valid[perm], t, w)
    return PlanProbe(sel=selection.sel, rank_of=selection.rank_of, lut=lut,
                     plan=plan, perm=perm, unions=unions)


@functools.partial(
    jax.jit,
    static_argnames=("bigk", "k", "metric", "dedup_results", "use_kernel",
                     "oversample", "exec_mode", "query_tile", "fused_topk",
                     "packed_codes"))
def scan_finalize(
    arrays: SeilArrays,
    vectors: jnp.ndarray,
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
    fused_topk: bool = False,
    packed_codes: bool = False,
) -> SearchResult:
    """Stages 3-4 against caller-provided (possibly reused) unions."""
    with jax.named_scope("scan"):
        if fused_topk:
            scan = scan_blocks_topk(
                store_from_arrays(arrays), probe.plan, probe.lut,
                probe.rank_of,
                fetch=finalize_fetch(bigk, oversample, dedup_results),
                exec_mode=exec_mode, use_kernel=use_kernel,
                query_tile=query_tile, perm=probe.perm, unions=unions,
                packed=packed_codes)
        else:
            scan = scan_blocks(store_from_arrays(arrays), probe.plan,
                               probe.lut, probe.rank_of, exec_mode=exec_mode,
                               use_kernel=use_kernel, query_tile=query_tile,
                               perm=probe.perm, unions=unions,
                               packed=packed_codes)
    with jax.named_scope("finalize"):
        out_ids, out_d, refine_dco = finalize_candidates(
            scan.flat_d, scan.flat_i, bigk=bigk, k=k, vectors=vectors,
            queries=queries, metric=metric, dedup_results=dedup_results,
            oversample=oversample)
    return SearchResult(
        ids=out_ids, dists=out_d, approx_dco=scan.approx_dco,
        refine_dco=refine_dco, scanned_blocks=scan.scanned_blocks,
        dropped_blocks=probe.plan.dropped)
