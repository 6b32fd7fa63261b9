"""Stage 3 — the scan stage: ADC scan -> stable partial top-``fetch``.

``scan_blocks_topk`` is the scan stage of every engine composition.  It
returns a ``ScanOut`` whose candidate stream is already the stable
top-``fetch`` of the plan layout — width ``fetch`` instead of
``S * BLK`` — so the scan stage never writes a (B, S, BLK) score tensor
to HBM for finalize to re-read and discard.  Contract (both paths,
bitwise):

  * ``flat_d``/``flat_i`` are the ascending stable selection of the
    full candidate stream — exactly ``preselect_candidates`` over
    ``scan_blocks``' output with ties broken by flat plan position —
    with masked/overflow entries normalized to ``(+inf, -1)``;
  * ``approx_dco``/``scanned_blocks`` keep the logical accounting of
    ``scan_blocks`` unchanged (masked misc duplicates still count one
    ADC computation each, Alg. 5 L15-16);
  * with ``live`` (streaming tombstones) dead candidates are forced out
    *before* selection — they can neither be returned nor displace live
    candidates; the re-mask in ``finalize_candidates`` is idempotent.

``use_kernel=True`` routes through the fused Pallas kernel
(``kernels/pq_scan.py::pq_scan_topk_kernel``): the keep mask moves
in-kernel (``rank_of`` rides the query-tile prefetch, ``block_ids`` /
``block_other`` tiles are DMA'd alongside the code tiles) and the
top-``fetch`` accumulator lives in VMEM across the scan grid.  The
kernel iterates *scan positions* (per-query plan slots in paged mode,
sorted-union positions in grouped/clustered), so the plan layout is
carried in as two (B, S) sidecars built here: ``slot_of`` (the plan
slot scanned at that position, -1 if the query does not plan it) and
``rank_u`` (that slot's probe rank) — one scatter through the same
sorted-union ``searchsorted`` ``scan_blocks`` uses, which is exact
because SEIL plans are per-query duplicate-free.

``use_kernel=False`` is the oracle: the jnp scan (``scan_blocks``) plus
an in-stage stable preselect.  Identical output contract, no kernel —
a session without ``use_kernel`` runs it.
Distances are assumed finite (a +/-inf ADC distance would be
indistinguishable from a masked slot in the oracle's normalization).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .cluster import cluster_order, fit_tile, tile_unions, union_dims
from .finalize import preselect_candidates
from .scan import EXEC_MODES, batch_union, scan_blocks
from .types import BIG, BlockStore, QueryPlan, ScanOut


def plan_slot_maps(blocks: jnp.ndarray, ranks: jnp.ndarray,
                   valid: jnp.ndarray, unions: jnp.ndarray):
    """Invert the sorted-union scatter: which plan slot does scan
    position ``w`` of query ``b`` correspond to?

    blocks/ranks/valid: (B, S) plan rows, already tiled in the same row
    order as ``unions`` (T, W) with B == T * qt.  Returns ``slot_of`` /
    ``rank_u`` (B, W): the plan slot index (-1 if the union position is
    not in that query's plan) and its probe rank.  Exact because every
    valid plan block is present in its tile's sorted union and SEIL
    plans are per-query duplicate-free, so the scatter is injective.
    """
    b, s = blocks.shape
    t, w = unions.shape
    qt = b // t
    pos = jax.vmap(jnp.searchsorted)(unions, blocks.reshape(t, qt * s))
    pos = pos.reshape(b, s)
    # invalid slots scatter out of bounds (w) and are dropped
    posc = jnp.where(valid, jnp.minimum(pos, w - 1), w)
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    slots = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    slot_of = jnp.full((b, w), -1, jnp.int32).at[rows, posc].set(
        slots, mode="drop")
    rank_u = jnp.zeros((b, w), jnp.int32).at[rows, posc].set(
        ranks, mode="drop")
    return slot_of, rank_u


def _fused_kernel_scan(store: BlockStore, plan: QueryPlan, lut, rank_of,
                       *, fetch: int, exec_mode: str, query_tile: int,
                       sel, perm, unions, dead, packed: bool = False):
    """Per-exec-mode kernel dispatch: build (tile_idx, slot_of, rank_u)
    and run the fused Pallas kernel.  Returns (flat_d, flat_i, dco)."""
    from ...kernels.ops import pq_scan_topk
    b, s = plan.blocks.shape
    if exec_mode == "paged":
        # scan position == plan slot; every query pages its own list
        slots = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
        slot_of = jnp.where(plan.valid, slots, -1)
        d, _, ids, dco = pq_scan_topk(
            lut, store.block_codes, store.block_ids, store.block_other,
            plan.blocks, rank_of, slot_of, plan.ranks, dead,
            fetch=fetch, query_tile=1, packed=packed)
        return d, ids, dco

    if exec_mode == "grouped":
        qt = fit_tile(b, query_tile)
        union = (batch_union(plan, store.block_codes.shape[0])
                 if unions is None else unions[0])           # (U,)
        safe_union = jnp.where(union < BIG, union, 0)
        tile_idx = jnp.broadcast_to(safe_union[None, :],
                                    (b // qt, union.shape[0]))
        slot_of, rank_u = plan_slot_maps(plan.blocks, plan.ranks,
                                         plan.valid, union[None, :])
        d, _, ids, dco = pq_scan_topk(
            lut, store.block_codes, store.block_ids, store.block_other,
            tile_idx, rank_of, slot_of, rank_u, dead,
            fetch=fetch, query_tile=qt, packed=packed)
        return d, ids, dco

    # clustered: per-tile unions in probe-overlap order, then un-permute
    if perm is None:
        perm = cluster_order(sel)
    pb, pr, pv = plan.blocks[perm], plan.ranks[perm], plan.valid[perm]
    if unions is None:
        t, w = union_dims(b, s, store.block_codes.shape[0], "clustered",
                          query_tile)
        unions = tile_unions(pb, pv, t, w)
    t, w = unions.shape
    qt = b // t
    safe_u = jnp.where(unions < BIG, unions, 0)
    slot_of, rank_u = plan_slot_maps(pb, pr, pv, unions)
    d, _, ids, dco = pq_scan_topk(
        lut[perm], store.block_codes, store.block_ids, store.block_other,
        safe_u, rank_of[perm], slot_of, rank_u, dead,
        fetch=fetch, query_tile=qt, packed=packed)
    inv = jnp.argsort(perm)
    return d[inv], ids[inv], dco[inv]


def scan_blocks_topk(store: BlockStore, plan: QueryPlan, lut: jnp.ndarray,
                     rank_of: jnp.ndarray, *, fetch: int,
                     exec_mode: str = "paged", use_kernel: bool = False,
                     query_tile: int = 8, sel=None, perm=None, unions=None,
                     live=None, packed: bool = False) -> ScanOut:
    """Scan + stable top-``fetch`` selection (see module docstring).

    Same arguments as ``scan_blocks`` plus ``use_kernel``, ``fetch`` (the
    candidate budget finalize needs: ``bigk * oversample`` for
    dedup-required layouts, ``bigk`` otherwise) and ``live`` (optional
    tombstone mask over the id space, applied pre-selection).
    ``packed`` marks the code store as a nibble-packed quant plane,
    exactly as in ``scan_blocks``.
    """
    assert exec_mode in EXEC_MODES, exec_mode
    b, s = plan.blocks.shape
    blk = store.block_codes.shape[1]
    fetch = min(fetch, s * blk)
    if not use_kernel:
        out = scan_blocks(store, plan, lut, rank_of, exec_mode=exec_mode,
                          query_tile=query_tile, sel=sel, perm=perm,
                          unions=unions, packed=packed)
        d = out.flat_d
        if live is not None:
            dead = (out.flat_i >= 0) & ~live[jnp.maximum(out.flat_i, 0)]
            d = jnp.where(dead, jnp.inf, d)
        ids = jnp.where(jnp.isfinite(d), out.flat_i, -1)
        cd, ci = preselect_candidates(d, ids, fetch=fetch)
        return ScanOut(flat_d=cd, flat_i=ci, approx_dco=out.approx_dco,
                       scanned_blocks=out.scanned_blocks)

    dead = None
    if live is not None:
        # per-block tombstone tiles, DMA'd alongside the code tiles —
        # the (TB, BLK) analogue of finalize's id-space lookup
        dead = ((store.block_ids >= 0)
                & ~live[jnp.maximum(store.block_ids, 0)]).astype(jnp.uint8)
    d, ids, dco = _fused_kernel_scan(
        store, plan, lut, rank_of, fetch=fetch, exec_mode=exec_mode,
        query_tile=query_tile, sel=sel, perm=perm, unions=unions, dead=dead,
        packed=packed)
    return ScanOut(
        flat_d=d, flat_i=ids, approx_dco=dco,
        scanned_blocks=jnp.sum(plan.valid, axis=1).astype(jnp.int32))
