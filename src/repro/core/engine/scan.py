"""Stage 3 — ADC scan of the planned blocks, the jnp scorer.

``scan_blocks`` is the reference the scan stage is held to: the
engine's compositions call ``scan_blocks_topk`` (engine/fused.py), which
with ``use_kernel=False`` composes this scorer with
``preselect_candidates`` and with ``use_kernel=True`` runs the Pallas
kernel, bitwise the same selection.  Three exec modes:

``paged``   : every query pages its own scan list — one (block, query)
              fetch per plan entry.  This is the classic per-query IVF
              scan.

``grouped`` : the paper's §5.3 cache optimization ("group tasks by
              list"), batch-union form.  The union of all blocks planned
              by *any* query in the batch is materialized once, sorted
              by physical block id; each union block is fetched once per
              query tile and scored for the whole tile while resident.
              Per-query distances are then scattered back into the plan
              layout via a sorted-union ``searchsorted``, so everything
              downstream — item masks, DCO counters, top-K — is
              byte-for-byte the same computation as paged mode.  HBM
              traffic drops from sum_q |plan_q| block fetches to
              |union_batch| * ceil(B/QT); logical DCO accounting is
              unchanged by construction.

``clustered``: the locality-aware refinement of grouped (engine/
              cluster.py).  Queries are permuted into probe-overlap
              order (stable signature sort), the union is built *per
              query tile* instead of per batch, each tile scans only
              its own working set, and the per-query distances are
              scattered back through the same sorted-union
              ``searchsorted`` and un-permuted.  The
              redundant-compute term shrinks from B x U_batch to
              B x U_tile; on skewed traffic U_tile -> |plan_q| and the
              mode matches paged compute while keeping grouped's
              amortized fetches.  Callers holding incremental plans
              (core/searcher.py) pass ``perm``/``unions`` explicitly —
              possibly width-bucketed and extended with a previous
              batch's unions — otherwise both are derived here.

The union budget is ``min(B*S, TB)`` (``min(tile*S, TB)`` per clustered
tile) — an upper bound on the number of distinct planned blocks — so
neither mode can drop a block the paged plan would scan: results are
bitwise identical (asserted in tests/test_engine.py, tests/test_plan.py).

Item-level masks (shared by every mode): invalid slots, and misc items
whose co-assigned list was scanned at an earlier rank (their cell was
already computed — Alg. 5 L15-16; the DCO is still counted, SEIL cannot
avoid computing a misc duplicate before discarding it).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...kernels.ref import adc_gather as _adc_gather
from ...quant.nibbles import unpack_nibbles
from .cluster import cluster_order, tile_unions, union_dims
from .types import BIG, BlockStore, QueryPlan, ScanOut

EXEC_MODES = ("paged", "grouped", "clustered")


def _codes_for(codes: jnp.ndarray, m: int, packed: bool) -> jnp.ndarray:
    """Gathered code tiles -> scannable codes.  A packed store (quant
    plane: two 4-bit codes per byte) is unpacked in-register to the LUT
    width ``m`` right after the gather; full-width stores pass through."""
    return unpack_nibbles(codes, m) if packed else codes


def batch_union(plan: QueryPlan, total_blocks: int) -> jnp.ndarray:
    """Sorted union of all valid planned block ids across the batch,
    padded with BIG.  Static width min(B*S, TB) >= |union| always.
    The one-tile case of ``tile_unions`` — shared so the monolithic
    grouped scan and the plan_reuse probe half can never diverge."""
    b, s = plan.blocks.shape
    u = min(b * s, total_blocks)
    return tile_unions(plan.blocks, plan.valid, 1, u)[0]


def _scan_paged(store: BlockStore, plan: QueryPlan, lut,
                packed: bool = False):
    codes = _codes_for(store.block_codes[plan.blocks], lut.shape[1],
                       packed)                     # (B, S, BLK, M)
    return _adc_gather(lut, codes)


def _scan_grouped(store: BlockStore, plan: QueryPlan, lut, union=None,
                  packed: bool = False):
    b, s = plan.blocks.shape
    if union is None:
        union = batch_union(plan, store.block_codes.shape[0])   # (U,)
    safe_union = jnp.where(union < BIG, union, 0)
    codes_u = _codes_for(store.block_codes[safe_union], lut.shape[1],
                         packed)                            # (U, BLK, M)
    dists_u = _adc_gather(
        lut, jnp.broadcast_to(codes_u[None], (b,) + codes_u.shape))
    # scatter back to the plan layout: every valid plan block is in the
    # sorted union, so searchsorted finds its exact position
    pos = jnp.searchsorted(union, plan.blocks.reshape(-1)).reshape(b, s)
    pos = jnp.minimum(pos, union.shape[0] - 1)
    return jnp.take_along_axis(dists_u, pos[:, :, None], axis=1)


def _scan_clustered(store: BlockStore, plan: QueryPlan, lut,
                    query_tile: int, sel=None, perm=None, unions=None,
                    packed: bool = False):
    """Per-tile-union scan in cluster order; returns (B, S, BLK) dists
    in the *original* batch order — byte-for-byte the paged values."""
    b, s = plan.blocks.shape
    if perm is None:
        perm = cluster_order(sel)
    pb = plan.blocks[perm]                                  # (B, S)
    if unions is None:
        t, w = union_dims(b, s, store.block_codes.shape[0], "clustered",
                          query_tile)
        unions = tile_unions(pb, plan.valid[perm], t, w)    # (T, W)
    t, w = unions.shape
    qt = b // t
    safe_u = jnp.where(unions < BIG, unions, 0)
    codes_u = _codes_for(store.block_codes[safe_u], lut.shape[1],
                         packed)                           # (T, W, BLK, M)
    m, k = lut.shape[1], lut.shape[2]
    g = jnp.take_along_axis(
        lut[perm].reshape(t, qt, 1, 1, m, k),
        codes_u[:, None].astype(jnp.int32)[..., None], axis=-1)
    d_u = jnp.sum(g[..., 0], axis=-1).reshape(b, w, -1)     # (B, W, BLK)
    # per-tile sorted-union scatter: exact positions, then un-permute
    pos = jax.vmap(jnp.searchsorted)(unions, pb.reshape(t, qt * s))
    pos = jnp.minimum(pos.reshape(b, s), w - 1)
    dists_p = jnp.take_along_axis(d_u, pos[:, :, None], axis=1)
    return dists_p[jnp.argsort(perm)]


def scan_blocks(store: BlockStore, plan: QueryPlan, lut: jnp.ndarray,
                rank_of: jnp.ndarray, *, exec_mode: str = "paged",
                query_tile: int = 8, sel=None, perm=None, unions=None,
                packed: bool = False) -> ScanOut:
    """ADC distances + item masks + DCO for the planned blocks.

    lut: (B, M, K) per-query subspace tables; rank_of: (B, nlist).
    ``sel`` (the stage-1 ranked probed lists) is required by
    ``exec_mode="clustered"`` unless ``perm``/``unions`` are provided by
    a caller holding incremental plans (core/searcher.py); ``unions``
    alone also overrides the batch union of ``"grouped"`` ((1, U) row).
    ``packed`` marks ``store.block_codes`` as a nibble-packed quant
    plane (two 4-bit codes per byte) — the tier-1 compact scan; the
    LUT width stays the logical M and ids/masks/DCO are untouched.
    """
    assert exec_mode in EXEC_MODES, exec_mode
    bq = plan.blocks.shape[0]
    if exec_mode == "grouped":
        dists = _scan_grouped(store, plan, lut,
                              union=None if unions is None else unions[0],
                              packed=packed)
    elif exec_mode == "clustered":
        dists = _scan_clustered(store, plan, lut, query_tile, sel=sel,
                                perm=perm, unions=unions, packed=packed)
    else:
        dists = _scan_paged(store, plan, lut, packed=packed)

    ids = store.block_ids[plan.blocks]             # (B, S, BLK)
    other = store.block_other[plan.blocks]
    o_rank = jnp.take_along_axis(
        rank_of, jnp.maximum(other, 0).reshape(bq, -1), axis=1
    ).reshape(other.shape)
    dup_item = (other >= 0) & (o_rank < plan.ranks[:, :, None])
    item_ok = (ids >= 0) & plan.valid[:, :, None]
    keep = item_ok & ~dup_item
    # DCO: SEIL computes misc duplicates then discards them (Alg.5 L15-16)
    approx_dco = jnp.sum(item_ok, axis=(1, 2)).astype(jnp.int32)
    return ScanOut(
        flat_d=jnp.where(keep, dists, jnp.inf).reshape(bq, -1),
        flat_i=ids.reshape(bq, -1),
        approx_dco=approx_dco,
        scanned_blocks=jnp.sum(plan.valid, axis=1).astype(jnp.int32))
