"""SearchParams — the frozen, validated query-side parameter object.

All knobs of the four-stage query pipeline live here (DESIGN.md §7).
A ``SearchParams`` is hashable, so it keys compiled-searcher caches:
``RairsIndex.searcher(params)`` returns a long-lived session that
AOT-compiles the pipeline once per batch-size bucket and is reused for
every identical params object.

``max_scan=None`` means "derive the per-query block budget from the
index" (``RairsIndex.default_max_scan``); ``resolve`` pins it so a
session never re-derives per call.

Mutable indexes key on params too: ``StreamingIndex.searcher(params)``
(core/stream/, DESIGN.md §8) caches sessions per params object and
shares compiled streaming executables keyed by ``(params, delta
capacity)``, so the same hashability contract lets churn-driven session
turnover reuse executables instead of recompiling.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from .engine import EXEC_MODES

# default pad-and-dispatch buckets: powers of two up to this cap; larger
# batches are chunked so the executable set stays small and bounded.
MAX_AUTO_BUCKET = 1024

# compact-plane backends the quant subsystem implements, plus "full":
# scan the full-width codes but keep the widened survivor set — the
# pure-widening ablation (monotone-recall baseline, tests/test_refine.py)
REFINE_PLANES = ("pq4", "binary", "full")


@dataclasses.dataclass(frozen=True)
class RefineParams:
    """Two-tier scan knobs (quantization ladder, DESIGN.md §12).

    plane          tier-1 code plane: "pq4" (coarse 4-bit PQ, packed two
                   codes per byte), "binary" (RaBitQ-style sign codes
                   behind the same interface), or "full" (no compact
                   plane — widen the survivor set over the full-width
                   codes; the recall-monotone ablation)
    refine_factor  survivor widening: tier-1 keeps ``bigk * refine_factor``
                   candidates for tier-2's exact re-rank.  A factor of 1
                   leaves no margin for a coarser tier, so the ladder
                   degenerates to the exact single-tier program —
                   bitwise-identical to ``refine=None`` (asserted in
                   tests/test_refine.py).
    """
    plane: str = "pq4"
    refine_factor: int = 4

    def __post_init__(self):
        if self.plane not in REFINE_PLANES:
            raise ValueError(
                f"plane must be one of {REFINE_PLANES}, got {self.plane!r}")
        if self.refine_factor < 1:
            raise ValueError(
                f"refine_factor must be >= 1, got {self.refine_factor}")


@dataclasses.dataclass(frozen=True)
class SearchParams:
    """Validated query parameters (paper Alg. 2 knobs + engine controls).

    k            final neighbours per query
    nprobe       probed lists (Alg. 2 L1)
    k_factor     refinement oversampling: bigK = k * k_factor
    max_scan     static per-query block budget (None -> index default)
    exec_mode    "paged" (per-query) | "grouped" (§5.3 list-major batch)
                 | "clustered" (grouped with query-tile clustering:
                 per-tile block unions in probe-overlap order)
    use_kernel   route the scan stage through the Pallas kernel, whose
                 VMEM-resident bitonic top-k accumulator keeps each
                 query's ``fetch`` best candidates (DESIGN.md §9); off,
                 the jnp scorer plus a stable preselect.  Results are
                 bitwise identical either way (tests/test_fused.py).
    fused_topk   always True: the scan stage always selects the top
                 ``fetch`` candidates.  The field is pinned only by the
                 benchmark's configs (``bench/configs/*.json``), which
                 pass ``"fused_topk": true``; it goes when they drop it.
    query_tile   grouped/clustered query tile (VMEM residency per fetch;
                 the clustered union granularity)
    plan_reuse   incremental plans (grouped/clustered only): the session
                 splits each dispatch into probe -> plan-cache merge ->
                 scan, reusing/extending the previous batch's block
                 unions when adjacent batches probe overlapping lists,
                 and scanning at the smallest geometric width bucket
                 covering the live entries.  Results stay bitwise
                 identical; ``compile_stats()['plan']`` exposes
                 hit/extend/miss counters and union sizes.
    batch_buckets  optional ascending pad-and-dispatch bucket sizes;
                 None -> powers of two up to MAX_AUTO_BUCKET
    refine       two-tier scan (``RefineParams``): tier-1 scans the
                 compact code plane keeping ``bigk * refine_factor``
                 survivors, tier-2 exactly re-ranks them in finalize.
                 None (default) is the single-tier exact path.
    """
    k: int = 10
    nprobe: int = 16
    k_factor: int = 10
    max_scan: Optional[int] = None
    exec_mode: str = "paged"
    use_kernel: bool = False
    fused_topk: bool = True
    query_tile: int = 8
    plan_reuse: bool = False
    batch_buckets: Optional[Tuple[int, ...]] = None
    refine: Optional[RefineParams] = None

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"k must be >= 1, got {self.k}")
        if self.nprobe < 1:
            raise ValueError(f"nprobe must be >= 1, got {self.nprobe}")
        if self.k_factor < 1:
            raise ValueError(f"k_factor must be >= 1, got {self.k_factor}")
        if self.max_scan is not None and self.max_scan < 1:
            raise ValueError(f"max_scan must be >= 1 or None, got {self.max_scan}")
        if self.exec_mode not in EXEC_MODES:
            raise ValueError(
                f"exec_mode must be one of {EXEC_MODES}, got {self.exec_mode!r}")
        if not self.fused_topk:
            raise ValueError(
                "fused_topk must be True: the scan stage always selects "
                f"the top fetch candidates, got {self.fused_topk!r}")
        if self.query_tile < 1:
            raise ValueError(f"query_tile must be >= 1, got {self.query_tile}")
        if self.plan_reuse and self.exec_mode == "paged":
            raise ValueError(
                "plan_reuse needs a union-based exec_mode ('grouped' or "
                "'clustered'); paged scans have no batch union to reuse")
        if self.batch_buckets is not None:
            bb = tuple(int(b) for b in self.batch_buckets)
            if not bb or any(b < 1 for b in bb) or list(bb) != sorted(set(bb)):
                raise ValueError(
                    "batch_buckets must be a non-empty ascending tuple of "
                    f"positive sizes, got {self.batch_buckets!r}")
            object.__setattr__(self, "batch_buckets", bb)
        if self.refine is not None and not isinstance(self.refine,
                                                      RefineParams):
            raise ValueError(
                f"refine must be a RefineParams or None, got "
                f"{self.refine!r}")

    @property
    def bigk(self) -> int:
        return self.k * self.k_factor

    @property
    def bigk_eff(self) -> int:
        """Tier-1 survivor budget: bigK widened by the refine factor."""
        if self.refine is None:
            return self.bigk
        return self.bigk * self.refine.refine_factor

    @property
    def active_plane(self) -> Optional[str]:
        """The compact-plane backend the scan substitutes, or None when
        the program is the plain single-tier one (no refine, the "full"
        widening ablation, or refine_factor=1 — which degenerates to the
        exact path bitwise)."""
        r = self.refine
        if r is None or r.plane == "full" or r.refine_factor == 1:
            return None
        return r.plane

    def resolve(self, index) -> "SearchParams":
        """Pin index-dependent defaults and cross-check against the index."""
        nlist = index.config.nlist
        if self.nprobe > nlist:
            raise ValueError(
                f"nprobe={self.nprobe} exceeds the index's nlist={nlist}")
        if self.max_scan is not None:
            return self
        return dataclasses.replace(
            self, max_scan=index.default_max_scan(self.nprobe))

    def bucket_for(self, batch: int) -> int:
        """Smallest dispatch bucket that fits `batch` (after chunking)."""
        if self.batch_buckets is not None:
            for b in self.batch_buckets:
                if b >= batch:
                    return b
            return self.batch_buckets[-1]
        if batch >= MAX_AUTO_BUCKET:
            return MAX_AUTO_BUCKET
        b = 1
        while b < batch:
            b *= 2
        return b

    @property
    def max_chunk(self) -> int:
        """Largest batch a single executable handles; bigger batches chunk."""
        if self.batch_buckets is not None:
            return self.batch_buckets[-1]
        return MAX_AUTO_BUCKET
