"""Staged ANN query engine (DESIGN.md §5).

Composable, individually-jittable stages over static-shape pytrees:

    select_lists  -> ListSelection   centroid top-nprobe
    plan_blocks   -> QueryPlan       cell-level dedup + budget compaction
    scan_blocks_topk -> ScanOut      ADC scan + stable top-fetch, exec_mode
                                     "paged"|"grouped"|"clustered"
                                     (``scan_blocks``: its jnp scorer)
    finalize_candidates              top-bigK + id-dedup + exact refine

``core/search.py`` (single host) and ``core/distributed.py`` (the
shard_map serve step behind ``core/sharded.py``) are thin compositions
of these stages; they differ only in which ``BlockStore`` they scan and
in the plan's block-range window.
"""
from .cluster import (cluster_order, fit_tile, merge_unions_host,  # noqa: F401
                      plan_width, tile_signatures, tile_unions, union_dims,
                      union_live, width_buckets)
from .finalize import finalize_candidates, preselect_candidates  # noqa: F401
from .fused import plan_slot_maps, scan_blocks_topk  # noqa: F401
from .plan import compact_plan, gather_candidates, plan_blocks  # noqa: F401
from .scan import EXEC_MODES, batch_union, scan_blocks  # noqa: F401
from .select import rank_table, select_lists  # noqa: F401
from .types import (BIG, BlockStore, ListSelection, ListTables,  # noqa: F401
                    PlanProbe, QueryPlan, ScanOut, store_from_arrays,
                    tables_from_arrays)
