"""Distributed lowering backend: the per-device shard_map serve step
behind ``ShardedIndex`` sessions (core/sharded.py, DESIGN.md §4).

Sharding scheme:
  * flat block arrays shard over the mesh axes by block-id range —
    balanced by construction (straggler mitigation is structural: every
    device owns TB/ndev blocks and scans at most the same static budget
    per query);
  * centroids + per-list block tables + PQ codebooks replicate
    (nlist x maxb int32 — MBs, not GBs);
  * refine vectors shard by vector-id range over the same axes;
  * streaming state replicates: the delta segment is tiny by
    construction (folded into the base at compaction) and the tombstone
    mask is one bit per id, so every device scans the full delta and
    masks with the full bitmap — but only the ``slot % ndev`` owner
    *contributes* each delta candidate to the merge, so SEIL-exact
    (dedup-free) result streams stay duplicate-free across shards.

Per query batch each device composes the SAME engine stages as the
single-host searcher (core/engine/, DESIGN.md §5): ``select_lists``
runs replicated, ``plan_blocks`` windows the deduplicated candidate
set to the device's block range, ``scan_blocks`` scans the local
``BlockStore`` in either exec mode, and the shared finalize tail is
split around two small collectives: a local stable top-fetch
(``preselect_candidates``) + one ``all_gather`` merges candidate
streams, then ``finalize_candidates`` refines owner-scored exact
distances with one ``pmin`` — two collectives per batch instead of
moving vector data.

``build_serve_step`` is the only lowering entry point; ``ShardedSearcher``
AOT-compiles it per batch bucket through the ``Searcher._lower`` hook.
``distributed_search`` remains as a thin session wrapper over the
unified API (the legacy ``make_distributed_serve_step`` shim is gone).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh  # noqa: F401  (re-export for callers)

from .engine import (BlockStore, ListTables, finalize_candidates,
                     plan_blocks, preselect_candidates, scan_blocks,
                     scan_blocks_topk, select_lists)
from .params import SearchParams
from .pq import PQCodebook, pq_lut, pq_lut_ip
from .search import SearchResult
from .stream.search import delta_adc


def build_serve_step(*, nprobe: int, bigk: int, k: int, max_scan_local: int,
                     metric: str = "l2", dedup_results: bool = False,
                     oversample: int = 2, exec_mode: str = "paged",
                     query_tile: int = 8, axes=("data",), ndev: int = 1,
                     streaming: bool = False, use_kernel: bool = False,
                     fused_topk: bool = False, stage: str = "all",
                     packed_codes: bool = False):
    """Build the per-device serve step for shard_map.

    Returns ``serve(block_codes, block_ids, block_other, owned,
    owned_other, refs, refs_other, misc, centroids, codebooks, vectors,
    vec_lo, block_lo, dev_rank, delta_codes, delta_ids, live, queries)
    -> SearchResult`` where the first three arrays and ``vectors`` are
    the device's shard, ``vec_lo/block_lo/dev_rank`` are per-device
    scalars (sharded (ndev,) arrays), and everything else replicates.
    With ``streaming=False`` the delta/live arguments are zero-width
    placeholders and the streaming merge is compiled out.

    ``stage`` is the tracing split (DESIGN.md §11): ``"all"`` (default)
    is the production fused program; ``"scan"`` runs everything through
    the local preselect and returns the per-device candidate streams
    ``(l_d, l_ids, approx_dco, scanned, dropped)`` (counters psum'd);
    ``"tail"`` takes ``(vectors, vec_lo, queries, l_d, l_ids)`` and runs
    the all_gather + shared finalize.  ``"scan"`` then ``"tail"``
    composes to exactly ``"all"`` — same per-device ops, same
    collectives — so results stay bitwise identical (asserted in
    tests/test_obs.py).
    """
    if stage not in ("all", "scan", "tail"):
        raise ValueError(f"stage must be all|scan|tail, got {stage!r}")
    fetch = bigk * (oversample if dedup_results else 1)
    axes = tuple(axes)

    def scan_half(block_codes, block_ids, block_other, owned, owned_other,
                  refs, refs_other, misc, centroids, codebooks, vectors,
                  vec_lo, block_lo, dev_rank, delta_codes, delta_ids, live,
                  queries):
        # -- replicated control path: list selection + dedup + local plan
        # (identical on every device; no collective needed)
        with jax.named_scope("select_lists"):
            selection = select_lists(queries, centroids, nprobe=nprobe,
                                     metric=metric)
        with jax.named_scope("plan_blocks"):
            tables = ListTables(owned=owned, owned_other=owned_other,
                                refs=refs, refs_other=refs_other, misc=misc)
            plan = plan_blocks(tables, selection, max_scan=max_scan_local,
                               local_lo=block_lo[0],
                               local_count=block_ids.shape[0])
            cb = PQCodebook(codebooks)
            lut = (pq_lut(cb, queries) if metric == "l2"
                   else pq_lut_ip(cb, queries))
        with jax.named_scope("scan"):
            # -- local ADC scan over the device's block shard
            store = BlockStore(block_codes=block_codes,
                               block_ids=block_ids,
                               block_other=block_other)
            # sel feeds the clustered exec mode: the cluster order is derived
            # from the replicated selection, so every device permutes its
            # (locally windowed) plan identically — per-device plans ride the
            # same clustering with their own per-tile local unions
            if fused_topk:
                # the fused scan's width-fetch output IS the per-device
                # preselect — tombstones applied pre-selection via ``live``
                scan = scan_blocks_topk(
                    store, plan, lut, selection.rank_of, fetch=fetch,
                    exec_mode=exec_mode, use_kernel=use_kernel,
                    query_tile=query_tile, sel=selection.sel,
                    live=live if streaming else None, packed=packed_codes)
            else:
                scan = scan_blocks(store, plan, lut, selection.rank_of,
                                   exec_mode=exec_mode, use_kernel=use_kernel,
                                   query_tile=query_tile, sel=selection.sel,
                                   packed=packed_codes)
            flat_d, flat_i = scan.flat_d, scan.flat_i
            approx_dco = scan.approx_dco

            if streaming:
                # delta scanned on every device (replicated compute, no extra
                # collective) but each slot has one owner (slot % ndev) so the
                # gathered candidate stream holds each delta id exactly once
                # — and logical DCO is counted exactly once per live slot.
                cap = delta_ids.shape[0]
                alive = delta_ids >= 0
                mine = alive & ((jnp.arange(cap, dtype=jnp.int32) % ndev)
                                == dev_rank[0])
                dd = jnp.where(mine[None, :], delta_adc(lut, delta_codes),
                               jnp.inf)
                di = jnp.broadcast_to(delta_ids[None, :], dd.shape)
                flat_d = jnp.concatenate([flat_d, dd], axis=1)
                flat_i = jnp.concatenate([flat_i, di], axis=1)
                # tombstone mask over the whole id space, replicated (the
                # fused base stream is already live-masked; re-masking it
                # here is idempotent, and the delta needs it either way)
                dead = (flat_i >= 0) & ~live[jnp.maximum(flat_i, 0)]
                flat_d = jnp.where(dead, jnp.inf, flat_d)
                approx_dco = approx_dco + jnp.sum(mine).astype(jnp.int32)

            # -- collective 1 (first half): local stable top-fetch.  (With
            # fused_topk + no streaming merge the stream is already the
            # stable top-fetch; the preselect is then a width-preserving
            # stable sort, harmless and shape-identical.)
            l_d, l_ids = preselect_candidates(flat_d, flat_i, fetch=fetch)
            return (l_d, l_ids,
                    jax.lax.psum(approx_dco, axes),
                    jax.lax.psum(scan.scanned_blocks, axes),
                    jax.lax.psum(plan.dropped, axes))

    def tail_half(vectors, vec_lo, queries, l_d, l_ids):
        # -- collective 1 (second half): all_gather the candidate streams
        g_d = jax.lax.all_gather(l_d, axes, axis=1, tiled=True)
        g_ids = jax.lax.all_gather(l_ids, axes, axis=1, tiled=True)
        # -- shared finalize tail; collective 2: pmin of owner-scored
        # exact distances (vec_lo windows the row shard)
        with jax.named_scope("finalize"):
            return finalize_candidates(
                g_d, g_ids, bigk=bigk, k=k, vectors=vectors, queries=queries,
                metric=metric, dedup_results=dedup_results,
                oversample=oversample, vec_lo=vec_lo[0], reduce_axes=axes)

    if stage == "scan":
        return scan_half
    if stage == "tail":
        return tail_half

    def serve(block_codes, block_ids, block_other, owned, owned_other,
              refs, refs_other, misc, centroids, codebooks, vectors,
              vec_lo, block_lo, dev_rank, delta_codes, delta_ids, live,
              queries):
        l_d, l_ids, approx_dco, scanned, dropped = scan_half(
            block_codes, block_ids, block_other, owned, owned_other,
            refs, refs_other, misc, centroids, codebooks, vectors,
            vec_lo, block_lo, dev_rank, delta_codes, delta_ids, live,
            queries)
        out_ids, out_d, refine_dco = tail_half(vectors, vec_lo, queries,
                                               l_d, l_ids)
        return SearchResult(
            ids=out_ids, dists=out_d, approx_dco=approx_dco,
            refine_dco=refine_dco, scanned_blocks=scanned,
            dropped_blocks=dropped)

    return serve


# ---------------------------------------------------------------------------
# compat session wrapper (pre-ShardedIndex entry point)
# ---------------------------------------------------------------------------

def distributed_search(index, mesh, queries, *,
                       params: SearchParams = None,
                       nprobe: int = None, k: int = None,
                       k_factor: int = None, max_scan_local: int = 512,
                       axes=("data",), exec_mode: str = None,
                       query_tile: int = None):
    """Deprecated host-callable wrapper, now a thin shim: shards `index`
    over `mesh` via ``index.shard(...)`` and serves one batch through a
    ``ShardedIndex`` session.  Prefer holding the session::

        sharded  = index.shard(mesh, axes=axes, max_scan_local=...)
        searcher = sharded.searcher(SearchParams(...))
        result   = searcher(queries)

    Query-side knobs come from `params` (individual kwargs override its
    fields); without `params`, `nprobe` and `k` are required.
    ``max_scan_local`` stays separate — it is the per-device plan
    budget, a property of the shard layout rather than of the query.
    Returns the unified ``SearchResult`` (the legacy ``local_dco`` field
    is ``approx_dco``)."""
    import dataclasses as _dc
    if params is None:
        if nprobe is None or k is None:
            raise TypeError(
                "distributed_search requires nprobe= and k= when no "
                "params=SearchParams(...) is given")
        params = SearchParams()
    over = {name: v for name, v in (("nprobe", nprobe), ("k", k),
                                    ("k_factor", k_factor),
                                    ("exec_mode", exec_mode),
                                    ("query_tile", query_tile))
            if v is not None}
    if over:
        params = _dc.replace(params, **over)
    if params.max_scan is not None:
        # the wrapper always pins a per-device budget, which would
        # silently override the per-query field — refuse instead
        raise ValueError(
            "distributed_search does not support SearchParams.max_scan; "
            "use max_scan_local= for the per-device plan budget (or hold "
            "a session: index.shard(mesh, max_scan_local=...)"
            ".searcher(params))")
    sharded = index.shard(mesh, axes=axes, max_scan_local=max_scan_local)
    return sharded.searcher(params)(queries)
