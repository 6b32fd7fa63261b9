"""Compiled searcher sessions — the query-side public API (DESIGN.md §7).

A ``Searcher`` binds one ``RairsIndex`` to one ``SearchParams`` and
AOT-compiles the four-stage pipeline (``seil_search``) per batch-size
bucket.  Arbitrary batch sizes are padded up to the nearest bucket and
dispatched to a cached executable, so steady-state serving traffic with
varying batch shapes hits a small fixed set of XLA programs instead of
retracing the jit per shape.

Padding is row-safe: every pipeline stage is per-query (row-wise top-k,
gathers, reductions), so the first B rows of a padded batch are bitwise
identical to an unpadded run — asserted in tests/test_searcher.py.

Sessions are long-lived by design: they hold the lowered executables,
the resolved params, and compile/cache statistics, and they are the
natural home for the follow-on serving state (incremental batch-union
plans, query-tile clustering — ROADMAP.md).

Mutable indexes extend this machinery (DESIGN.md §8): a session records
the ``epoch`` of the index it compiled against, and the ``_lower`` /
``_call_inputs`` / ``_check_current`` hooks let ``StreamingSearcher``
(core/stream/) swap in the streaming pipeline and fail deterministically
once the owning ``StreamingIndex`` has mutated past the session.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..kernels.pq_scan import blocks_per_step
from ..kernels.topk import pow2_ceil
from .engine import (BIG, merge_unions_host, plan_width, tile_signatures,
                     union_live, width_buckets)
from .params import SearchParams
from .search import (SearchResult, finalize_fetch, probe_plan, scan_finalize,
                     seil_search, seil_search_traced)


@dataclasses.dataclass
class SearcherStats:
    """Compile/dispatch accounting for one session."""
    compiles: int = 0        # executables built (one per bucket)
    warmup_compiles: int = 0  # subset of compiles paid up-front by
                              # warmup/warmup_widths, not by live traffic
    calls: int = 0           # searcher invocations
    dispatches: int = 0      # chunk dispatches (>= calls)
    cache_hits: int = 0      # executable fetches served from the cache
                             # (plan_reuse chunks fetch two: probe + scan)
    padded_rows: int = 0     # total pad rows added across dispatches
    refined_rows: int = 0    # candidate rows the exact re-rank scores:
                             # padded batch x bigk_eff per dispatch

    def as_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class PlanStats:
    """Incremental-plan accounting (``SearchParams.plan_reuse``) — the
    plan-cache counterpart of the compile-cache stats above.

    A *tile* is one block union (the whole batch for ``grouped``, one
    query tile for ``clustered``); every dispatched batch classifies
    each of its tiles as hit (own union covered by the cache), extend
    (cache grew, still fits the width) or miss (first sight / overflow,
    cache replaced)."""
    batches: int = 0          # probe->scan dispatches
    tiles: int = 0            # unions processed (batches x tiles/batch)
    hits: int = 0             # reused unchanged
    extends: int = 0          # merged into the cache
    misses: int = 0           # replaced (cold cache or width overflow)
    union_live_sum: int = 0   # live entries actually scanned (per tile)
    own_live_sum: int = 0     # live entries this batch needed (per tile)
    width_sum: int = 0        # dispatched union-width buckets (per tile)
    sig_deep_split: int = 0   # tiles the deep (beyond-lead) signature
                              # separated from a lead-sharing neighbor —
                              # the collisions a lead-only key would eat

    def summary(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        t = max(self.tiles, 1)
        d["hit_rate"] = self.hits / t
        d["mean_union_live"] = self.union_live_sum / t
        d["mean_own_live"] = self.own_live_sum / t
        d["mean_width"] = self.width_sum / t
        return d


class Searcher:
    """A compiled search session over one index (create via
    ``RairsIndex.searcher(params)``).

    Calling the session with a ``(B, D)`` query batch returns a
    ``SearchResult`` identical to the legacy ``index.search`` kwarg path
    for the same parameters.  ``stats`` exposes compile-cache counters;
    ``buckets`` lists the batch sizes with a live executable.
    """

    def __init__(self, index, params: SearchParams):
        if not isinstance(params, SearchParams):
            raise TypeError(f"params must be SearchParams, got {type(params)}")
        self.index = index
        self.params = params.resolve(index)
        self.epoch = getattr(index, "epoch", 0)
        # two-tier scan (params.refine, DESIGN.md §12): resolve the
        # compact plane once — sessions pin it like everything else
        ap = self.params.active_plane
        self._plane = index.plane(ap) if ap is not None else None
        self.stats = SearcherStats()
        self.plan_stats = PlanStats()
        self._compiled: Dict[Any, Any] = {}
        # incremental plans (params.plan_reuse): per dispatch bucket, a
        # signature-keyed map of cached tile unions ((list, run) ->
        # (W,) row; engine/cluster.py tile_signatures) — keyed by what a
        # tile probes, not where it sits, so popularity drift shifting a
        # tile boundary does not orphan the cache.  It lives on the
        # session, so it invalidates with it — a mutation that stales
        # the session drops the plans too.
        self._plan_cache: Dict[int, "collections.OrderedDict"] = {}

    @property
    def buckets(self):
        """Batch-size buckets with a compiled executable, ascending.
        (With plan_reuse a bucket holds probe/scan executable pairs; the
        probe store may live outside ``_compiled`` — core/stream/.)"""
        keys = set(self._compiled) | set(self._probe_exe_store())
        return tuple(sorted({k if isinstance(k, int) else k[1]
                             for k in keys}))

    def compile_stats(self) -> Dict[str, Any]:
        d = self.stats.as_dict()
        d["buckets"] = list(self.buckets)
        d["topk_width"] = self.topk_width()
        d.update(self.scan_grouping())
        if self.params.plan_reuse:
            d["plan"] = self.plan_stats.summary()
        return d

    def topk_width(self) -> Optional[int]:
        """Lane width F of the scan kernel's top-k accumulator (the
        power of two covering the candidates finalize fetches, capped by
        the scan width), or None when the session does not run it."""
        p = self.params
        if not p.use_kernel:
            return None
        idx = self.index
        fetch = finalize_fetch(p.bigk_eff, idx.result_oversample,
                               idx.needs_result_dedup)
        return pow2_ceil(min(fetch,
                             p.max_scan * idx.arrays.block_codes.shape[1]))

    def scan_grouping(self) -> Dict[str, Any]:
        """``blocks_per_step``: scan positions G one grid step of the
        session's scan kernel pages (``kernels/pq_scan.py::
        blocks_per_step``), and ``padded_positions_share``: (S' - S) / S'
        of the paged scan list, S = ``max_scan`` padded to S', a multiple
        of G.  Both None when the session runs no kernel; the share is
        None too outside paged mode, whose union widths vary per batch."""
        p = self.params
        if not p.use_kernel:
            return {"blocks_per_step": None, "padded_positions_share": None}
        g = blocks_per_step(self.index.arrays.block_codes.shape[1])
        padded = -(-p.max_scan // g) * g
        share = ((padded - p.max_scan) / padded if p.exec_mode == "paged"
                 else None)
        return {"blocks_per_step": g, "padded_positions_share": share}

    # -- overridable hooks (core/stream/ swaps in the streaming pipeline) --
    def _check_current(self) -> None:
        """Raise if the underlying index has mutated past this session.
        A plain ``RairsIndex`` is immutable, so the base hook is a no-op;
        ``StreamingSearcher`` raises ``StaleSessionError`` here."""

    def _scan_state(self) -> tuple:
        """(arrays, codebook, packed) the scan stages run over: the
        compact-plane substitution when a refine tier is active —
        plane-packed block codes, the plane codec's LUT — else the
        index's own full-width pair.  Everything downstream (vectors,
        finalize) is untouched: tier-2 IS the existing exact re-rank,
        just over the ``bigk_eff`` widened survivor set."""
        idx = self.index
        if self._plane is None:
            return idx.arrays, idx.codebook, False
        return (dataclasses.replace(idx.arrays,
                                    block_codes=self._plane.block_codes),
                self._plane.codec, True)

    def _lower(self, bucket: int):
        """Lower the search pipeline for one batch-size bucket."""
        p = self.params
        idx = self.index
        arrays, codebook, packed = self._scan_state()
        q_spec = jax.ShapeDtypeStruct(
            (bucket, idx.vectors.shape[1]), jnp.float32)
        return seil_search.lower(
            arrays, idx.centroids, codebook, idx.vectors, q_spec,
            nprobe=p.nprobe, bigk=p.bigk_eff, k=p.k, max_scan=p.max_scan,
            metric=idx.config.metric,
            dedup_results=idx.needs_result_dedup,
            use_kernel=p.use_kernel, oversample=idx.result_oversample,
            exec_mode=p.exec_mode, query_tile=p.query_tile,
            packed_codes=packed)

    def _call_inputs(self) -> tuple:
        """Runtime arguments preceding the query batch at dispatch."""
        idx = self.index
        arrays, codebook, _ = self._scan_state()
        return (arrays, idx.centroids, codebook, idx.vectors)

    # -- incremental-plan hooks (probe -> plan-cache merge -> scan) --------
    def _lower_probe(self, bucket: int):
        """Lower the probe half (stages 1-2 + own unions) for one bucket."""
        p = self.params
        idx = self.index
        arrays, codebook, _ = self._scan_state()
        q_spec = jax.ShapeDtypeStruct(
            (bucket, idx.vectors.shape[1]), jnp.float32)
        return probe_plan.lower(
            arrays, idx.centroids, codebook, q_spec,
            nprobe=p.nprobe, max_scan=p.max_scan, metric=idx.config.metric,
            exec_mode=p.exec_mode, query_tile=p.query_tile)

    def _probe_inputs(self) -> tuple:
        idx = self.index
        arrays, codebook, _ = self._scan_state()
        return (arrays, idx.centroids, codebook)

    def _lower_scan(self, bucket: int, probe_spec, unions_spec):
        """Lower the scan half (stages 3-4) at one union width."""
        p = self.params
        idx = self.index
        arrays, _, packed = self._scan_state()
        q_spec = jax.ShapeDtypeStruct(
            (bucket, idx.vectors.shape[1]), jnp.float32)
        return scan_finalize.lower(
            arrays, idx.vectors, q_spec, probe_spec, unions_spec,
            bigk=p.bigk_eff, k=p.k, metric=idx.config.metric,
            dedup_results=idx.needs_result_dedup,
            use_kernel=p.use_kernel, oversample=idx.result_oversample,
            exec_mode=p.exec_mode, query_tile=p.query_tile,
            packed_codes=packed)

    def _scan_inputs(self) -> tuple:
        idx = self.index
        arrays, _, _ = self._scan_state()
        return (arrays, idx.vectors)

    def _get_exe(self, key, lower_fn, cache=None):
        cache = self._compiled if cache is None else cache
        hit = key in cache
        if not hit:
            with obs.span("searcher.compile", cat="compile", key=str(key)):
                cache[key] = lower_fn().compile()
            self.stats.compiles += 1
        else:
            self.stats.cache_hits += 1
        return cache[key]

    def _probe_exe_store(self) -> dict:
        """Where plan_reuse probe executables live.  The probe half never
        consumes mutable-segment buffers, so subclasses whose _compiled
        dict is keyed by delta shapes (core/stream/) point this at a
        longer-lived store to survive capacity-bucket jumps."""
        return self._compiled

    def _executable(self, bucket: int):
        return self._get_exe(bucket, lambda: self._lower(bucket))

    def _dispatch_traced(self, bucket: int, qc: jnp.ndarray):
        """Stage-fenced dispatch used while a tracer is active
        (repro/obs/): the same engine stages as the monolithic
        executable, one jitted program each, span + device fence per
        stage — bitwise identical results.  Subclasses without a staged
        pipeline return ``NotImplemented`` and ``_dispatch`` falls back
        to fencing the monolithic executable as one span."""
        p = self.params
        idx = self.index
        arrays, codebook, packed = self._scan_state()
        return seil_search_traced(
            arrays, idx.centroids, codebook, idx.vectors, qc,
            nprobe=p.nprobe, bigk=p.bigk_eff, k=p.k, max_scan=p.max_scan,
            metric=idx.config.metric,
            dedup_results=idx.needs_result_dedup,
            use_kernel=p.use_kernel, oversample=idx.result_oversample,
            exec_mode=p.exec_mode, query_tile=p.query_tile,
            packed_codes=packed)

    def _dispatch(self, bucket: int, qc: jnp.ndarray) -> SearchResult:
        """One padded chunk through either the monolithic executable or
        the incremental probe -> merge -> scan pipeline.  With a fenced
        tracer active (repro/obs/) the monolithic path reroutes through
        the stage-fenced ``_dispatch_traced`` and the plan_reuse path
        fences its (already natural) probe / host-merge / scan
        boundaries; a profiler-mode tracer changes neither."""
        self.stats.dispatches += 1
        self.stats.refined_rows += bucket * self.params.bigk_eff
        if not self.params.plan_reuse:
            if obs.fencing():
                r = self._dispatch_traced(bucket, qc)
                if r is not NotImplemented:
                    return r
                with obs.span("stage.execute", cat="device", bucket=bucket):
                    return obs.fence(
                        self._executable(bucket)(*self._call_inputs(), qc))
            return self._executable(bucket)(*self._call_inputs(), qc)
        probe = self._get_exe(("probe", bucket),
                              lambda: self._lower_probe(bucket),
                              cache=self._probe_exe_store())
        with obs.span("stage.probe_plan", cat="device", bucket=bucket):
            pr = obs.fence(probe(*self._probe_inputs(), qc))
        with obs.span("stage.merge_unions_host", cat="host") as msp:
            own = np.asarray(pr.unions)
            t, w = own.shape
            deep_split = 0
            if t == 1:                 # grouped: one batch-wide union
                sigs = [(0, 0)]
            else:                      # clustered: name tiles by working set
                rows = np.asarray(pr.sel)[np.asarray(pr.perm)][::bucket // t]
                sigs = tile_signatures(rows[:, 0], deep=rows)
                # how many tiles the beyond-lead prefix disambiguated —
                # distinct deep keys minus distinct leads this dispatch
                deep_split = (len({(s[0], s[1]) for s in sigs})
                              - len({s[0] for s in sigs}))
            cache = self._plan_cache.setdefault(bucket,
                                                collections.OrderedDict())
            rows = [cache.get(s) for s in sigs]
            present = np.array([r is not None for r in rows])
            if present.any():
                pad = np.full(w, int(BIG), own.dtype)
                cached = np.stack([pad if r is None else r for r in rows])
                used, hit, ext = merge_unions_host(cached, own, present)
            else:
                used, hit, ext = merge_unions_host(None, own)
            for s, row in zip(sigs, used):
                cache[s] = row
                cache.move_to_end(s)
            while len(cache) > max(64, 4 * t):  # bound drifting signatures
                cache.popitem(last=False)
            live = union_live(used)
            wp = plan_width(int(live.max(initial=1)), w)
            ps = self.plan_stats
            ps.batches += 1
            ps.tiles += t
            ps.hits += int(hit.sum())
            ps.extends += int(ext.sum())
            ps.misses += t - int(hit.sum()) - int(ext.sum())
            ps.union_live_sum += int(live.sum())
            ps.own_live_sum += int(union_live(own).sum())
            ps.width_sum += wp * t
            ps.sig_deep_split += deep_split
            msp.add(tiles=t, hits=int(hit.sum()), extends=int(ext.sum()),
                    misses=t - int(hit.sum()) - int(ext.sum()),
                    union_live=int(live.sum()), width=wp,
                    sig_deep_split=deep_split)
            unions_w = jnp.asarray(used[:, :wp])
        probe_spec = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), pr)
        unions_spec = jax.ShapeDtypeStruct(unions_w.shape, unions_w.dtype)
        scan = self._get_exe(
            ("scan", bucket, wp),
            lambda: self._lower_scan(bucket, probe_spec, unions_spec))
        with obs.span("stage.scan_finalize", cat="device", bucket=bucket,
                      width=wp):
            return obs.fence(scan(*self._scan_inputs(), qc, pr, unions_w))

    def warmup(self, *batch_sizes: int) -> "Searcher":
        """Pre-compile the buckets covering `batch_sizes` (chainable).
        With plan_reuse only the probe half pre-compiles — the scan
        half's union width is a property of the traffic (use
        ``warmup_widths`` to pre-pay the whole width ladder).  Compiles
        triggered here count as ``warmup_compiles``."""
        before = self.stats.compiles
        for b in batch_sizes:
            bucket = self.params.bucket_for(min(b, self.params.max_chunk))
            if self.params.plan_reuse:
                self._get_exe(("probe", bucket),
                              lambda: self._lower_probe(bucket),
                              cache=self._probe_exe_store())
            else:
                self._executable(bucket)
        self.stats.warmup_compiles += self.stats.compiles - before
        return self

    def warmup_widths(self, *batch_sizes: int) -> "Searcher":
        """Pre-compile the plan_reuse scan executables at every
        geometric union-width bucket for `batch_sizes` (chainable).

        A plan_reuse session dispatches its scan half at the smallest
        ``plan_width`` bucket covering the live union, so the set of
        executables traffic can demand is the ``width_buckets`` ladder —
        finite and known up-front.  Compiling it at gateway startup (or
        right after an epoch swap) means the first requests never eat
        compile latency.  Without plan_reuse this is plain ``warmup``.
        Compiles triggered here count as ``warmup_compiles`` in
        ``compile_stats()``, separate from traffic-driven compiles."""
        if not self.params.plan_reuse:
            return self.warmup(*batch_sizes)
        before = self.stats.compiles
        dim = int(self.index.vectors.shape[1])
        for b in batch_sizes:
            bucket = self.params.bucket_for(min(b, self.params.max_chunk))
            probe = self._get_exe(("probe", bucket),
                                  lambda: self._lower_probe(bucket),
                                  cache=self._probe_exe_store())
            # one throwaway probe dispatch yields the exact output spec
            # (tile count and full union width) for this bucket
            pr = probe(*self._probe_inputs(),
                       jnp.zeros((bucket, dim), jnp.float32))
            probe_spec = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), pr)
            t, w = pr.unions.shape
            udt = pr.unions.dtype
            for wp in width_buckets(w):
                spec = jax.ShapeDtypeStruct((t, wp), udt)
                self._get_exe(
                    ("scan", bucket, wp),
                    lambda s=spec: self._lower_scan(bucket, probe_spec, s))
        self.stats.warmup_compiles += self.stats.compiles - before
        return self

    def __call__(self, queries: jnp.ndarray) -> SearchResult:
        self._check_current()
        q = jnp.asarray(queries)
        if q.ndim != 2:
            raise ValueError(f"queries must be (B, D), got shape {q.shape}")
        if q.shape[0] == 0:
            raise ValueError("empty query batch (B=0)")
        if q.dtype != jnp.float32:
            q = q.astype(jnp.float32)
        n = q.shape[0]
        outs = []
        s = 0
        while s < n:
            b = min(n - s, self.params.max_chunk)
            bucket = self.params.bucket_for(b)
            with obs.span("searcher.dispatch", cat="searcher",
                          bucket=bucket, rows=b, pad=bucket - b):
                qc = q[s:s + b]
                if b < bucket:
                    with obs.span("searcher.pad", cat="searcher"):
                        qc = jnp.concatenate(
                            [qc, jnp.zeros((bucket - b, q.shape[1]),
                                           q.dtype)], axis=0)
                    self.stats.padded_rows += bucket - b
                with obs.span("searcher.execute", cat="searcher"):
                    r = self._dispatch(bucket, qc)
                if b < bucket:
                    with obs.span("searcher.slice", cat="searcher"):
                        r = jax.tree.map(lambda a: a[:b], r)
            outs.append(r)
            s += b
        self.stats.calls += 1
        if len(outs) == 1:
            return outs[0]
        return jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *outs)

    # explicit alias for callers that prefer a method name
    search = __call__
