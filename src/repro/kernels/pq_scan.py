"""Paged PQ fast-scan Pallas TPU kernel — the DCO hot spot of the paper.

CPU PQ Fast Scan keeps 16-entry LUTs in SIMD registers and uses the
AVX2 ``pshufb`` 16-way shuffle to score 32 packed items at once.  TPUs
have no shuffle unit, so we adapt the insight (block-wise LUT scoring
with no per-item scalar work) to the MXU:

  * the 4-bit code of item i, subspace m selects ``lut[m, code]``; we
    materialize the selection as a one-hot tile and contract
    ``(BLK, M*K) @ (M*K, 1)`` on the MXU — one systolic pass scores a
    whole block (the TPU idiom for small-table gathers);
  * SEIL's reference-entry indirection becomes *paging*: the per-query
    deduplicated block-id list is scalar-prefetched
    (``PrefetchScalarGridSpec``) and drives the BlockSpec ``index_map``,
    so the HBM->VMEM DMA fetches each shared cell block exactly once —
    skipping a reference entry never issues its loads, the DMA-level
    analogue of Alg. 5's ``listVisited`` probe;
  * grid order is (query-block, scan-position): consecutive grid steps
    for the *same* scan position across the query tile reuse the code
    tile already resident in VMEM — the TPU analogue of the paper's
    "group tasks by list" cache optimization (§5.3).

Production tiling notes (TPU v5e): native block size 128 (lane width)
instead of the paper's 32 — ``block`` stays a config knob and the
paper's Fig. 16 block-size study covers the sweep.  uint8 code tiles
want (32, 128) alignment, so M is zero-padded to a multiple of 128 by
``ops.pq_scan_paged`` (padded codes select lut[m_pad, 0] == 0).

Block layout rules of the TPU lowering: the last two dimensions of a
block must be multiples of (8, 128) or equal the array's.  Every
operand paged one row at a time (per-block ids/other/tombstones) and
every per-query array tiled by a query tile that may be 1, 2 or 4 rows
therefore carries a singleton axis in front of its minor dimension —
``(TB, 1, BLK)``, ``(B, 1, F)``, ``(B, S, 1, BLK)`` — so that a block's
last two dimensions equal the array's.  The scalar-prefetched scan
lists live in SMEM (1 MiB on v5e); ``_map_query_chunks`` splits a batch
whose lists would not fit into sequential kernel calls over query
chunks.  Validated against ``ref.py`` in interpret mode on CPU and
compiled for a described v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import checkify
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .topk import PAD_POS, bitonic_sort, merge_topf, pow2_ceil

# bytes of scalar-prefetched scan lists one kernel call may hold in SMEM
# (1 MiB on v5e, shared with the kernel's own scalars)
SMEM_PREFETCH_BYTES = 256 * 1024


def _tiles_per_call(qb: int, s: int) -> int:
    """Largest divisor of the query-tile count whose (tiles, S) int32
    scan lists fit ``SMEM_PREFETCH_BYTES``."""
    d = max(1, min(qb, SMEM_PREFETCH_BYTES // (4 * max(s, 1))))
    while qb % d:
        d -= 1
    return d


def _map_query_chunks(call, tile_idx, per_query, query_tile: int):
    """Run ``call(tile_idx, *per_query)`` over query chunks small enough
    for SMEM.  ``per_query`` arrays lead with the batch axis; outputs do
    too.  One chunk is a plain call; more run as one ``lax.map`` over
    the same kernel, so results are bitwise those of a single call."""
    qb, s = tile_idx.shape
    d = _tiles_per_call(qb, s)
    if d == qb:
        return call(tile_idx, *per_query)
    nc = qb // d
    chunked = [x.reshape((nc, d * query_tile) + x.shape[1:])
               for x in per_query]
    out = jax.lax.map(lambda a: call(a[0], *a[1:]),
                      (tile_idx.reshape(nc, d, s), *chunked))
    return jax.tree.map(lambda y: y.reshape((-1,) + y.shape[2:]), out)


def _tile_codes(codes_ref, packed: bool) -> jnp.ndarray:
    """Code tile -> (BLK, M) int32 codes, unpacking nibble pairs in-VMEM.

    A packed tile (quant plane, two 4-bit codes per byte) carries MB =
    M/2 bytes; the lo nibble is the even subquantizer, hi the odd —
    the single layout defined by ``quant/nibbles.py``.  Interleaving
    the nibbles back does not lower on TPU, so the unpacked tile is
    ``[lo | hi]`` — every even subquantizer, then every odd one — and
    the kernel wrappers permute the LUT rows to match
    (``_even_odd_lut``).  Callers guarantee 2*MB == lut M (ops wrappers
    zero-pad the LUT so a padded byte's two zero codes select zero rows
    and contribute nothing).
    """
    raw = codes_ref[0].astype(jnp.int32)                       # (BLK, MB)
    if not packed:
        return raw
    return jnp.concatenate([raw & 15, raw >> 4], axis=-1)


def _even_odd_lut(lut: jnp.ndarray, packed: bool) -> jnp.ndarray:
    """(B, M, K) LUT in the row order ``_tile_codes`` unpacks to."""
    if not packed:
        return lut
    return jnp.concatenate([lut[:, 0::2], lut[:, 1::2]], axis=1)


def _score_block(lut_ref, codes_ref, packed: bool) -> jnp.ndarray:
    """(QT, BLK) ADC distances of one paged code block for a query tile.

    The 4-bit code of item i, subspace m selects ``lut[m, code]``; the
    selection is a one-hot tile over the K table entries, flattened
    (M, K) -> MK and contracted on the MXU, so every query of the tile
    scores the whole block in one pass.  Both kernels score through
    here, so their distances are bitwise identical.  The contraction is
    pinned to full f32 (HIGHEST): the one-hot is exact in bf16 but the
    LUT is not, and the distances must match the jnp reference's."""
    qt, m, k = lut_ref.shape
    codes = _tile_codes(codes_ref, packed)                     # (BLK, M)
    blk = codes.shape[0]
    sel = (codes[:, :, None]
           == jax.lax.broadcasted_iota(jnp.int32, (1, 1, k), 2))
    oh = sel.astype(jnp.float32).reshape(blk, m * k)           # (BLK, MK)
    lut = lut_ref[...].reshape(qt, m * k)                      # (QT, MK)
    return jax.lax.dot_general(lut, oh, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _make_kernel(packed: bool):
    """Body factory for the unfused scan (packed-ness is static)."""

    def _kernel(idx_ref, lut_ref, codes_ref, out_ref):
        """One grid step: score one code block for QT queries.

        lut_ref:   (QT, M, K) f32 in VMEM
        codes_ref: (1, BLK, MB) uint8 in VMEM (the paged block; MB = M,
                   or M/2 when nibble-packed)
        out_ref:   (QT, 1, 1, BLK) f32
        """
        d = _score_block(lut_ref, codes_ref, packed)
        out_ref[...] = d[:, None, None, :]

    return _kernel


@functools.partial(jax.jit,
                   static_argnames=("query_tile", "interpret", "packed"))
def pq_scan_tiled_kernel(lut: jnp.ndarray, block_codes: jnp.ndarray,
                         tile_idx: jnp.ndarray, *, query_tile: int = 8,
                         interpret: bool = False,
                         packed: bool = False) -> jnp.ndarray:
    """Per-tile paged scan: every query tile pages its *own* scan list.

    lut (B, M, K) f32, block_codes (TB, BLK, M) uint8, tile_idx
    (B // query_tile, S) -> (B, S, BLK) f32.  The scalar-prefetched
    ``tile_idx`` drives the BlockSpec index_map directly at tile
    granularity — the clustered exec mode hands each tile its own
    (tile-padded) block union with no re-broadcast to a batch-wide
    list.  B % query_tile == 0; entries must be valid (callers clamp
    padding to 0 and mask downstream).  With ``packed=True`` the code
    tile carries two 4-bit codes per byte (quant plane) and M must be
    2x the byte width — half the DMA bytes per block."""
    b, m, k = lut.shape
    qb, s = tile_idx.shape
    tb, blk, mb = block_codes.shape
    assert (2 * mb if packed else mb) == m, (mb, m, packed)
    assert b == qb * query_tile, (b, qb, query_tile)
    lut = _even_odd_lut(lut, packed)

    def call(idx, lut_c):
        kernel = pl.pallas_call(
            _make_kernel(packed),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=idx.shape,
                in_specs=[
                    pl.BlockSpec((query_tile, m, k),
                                 lambda qi, si, idx: (qi, 0, 0)),
                    pl.BlockSpec((1, blk, mb),
                                 lambda qi, si, idx: (idx[qi, si], 0, 0)),
                ],
                out_specs=pl.BlockSpec((query_tile, 1, 1, blk),
                                       lambda qi, si, idx: (qi, si, 0, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((lut_c.shape[0], s, 1, blk),
                                           jnp.float32),
            interpret=interpret,
        )
        return kernel(idx, lut_c, block_codes)

    out = _map_query_chunks(call, tile_idx, [lut], query_tile)
    return out.reshape(b, s, blk)


def pq_scan_paged_kernel(lut: jnp.ndarray, block_codes: jnp.ndarray,
                         block_idx: jnp.ndarray, *, query_tile: int = 8,
                         interpret: bool = False, packed: bool = False,
                         debug: bool = False) -> jnp.ndarray:
    """lut (B, M, K) f32, block_codes (TB, BLK, M) uint8, block_idx (B, S)
    -> (B, S, BLK) f32.  B % query_tile == 0; block_idx entries must be
    valid (callers clamp padding to 0 and mask downstream).

    Paging is per (query-tile, position): with query_tile == 1 every query
    pages its own scan list; with query_tile > 1 every query of a tile
    MUST carry the same scan list (the paper's §5.3 list-major batch mode
    — see ops.pq_scan_grouped / ops.pq_scan_tiled), because only row 0 of
    each tile drives the paging index_map.  The invariant is enforced:
    eager calls raise ``ValueError`` on mismatched tile rows, and traced
    calls with ``debug=True`` emit a ``checkify.check`` (run the caller
    under ``checkify.checkify`` and ``err.throw()``) — misuse fails
    loudly instead of silently scoring the wrong blocks."""
    b = lut.shape[0]
    assert b % query_tile == 0, (b, query_tile)
    qb = b // query_tile
    s = block_idx.shape[1]
    rows = block_idx.reshape(qb, query_tile, s)
    if query_tile > 1:
        shared = jnp.all(rows == rows[:, :1, :])
        if not isinstance(block_idx, jax.core.Tracer):
            if not bool(shared):
                raise ValueError(
                    f"pq_scan_paged_kernel: query_tile={query_tile} but the "
                    "tile rows of block_idx disagree — per-tile paging "
                    "scores row 0's list for the whole tile.  Use "
                    "query_tile=1 (per-query paging) or a tile-shared scan "
                    "list (ops.pq_scan_grouped / ops.pq_scan_tiled).")
        elif debug:
            checkify.check(
                shared, "pq_scan_paged_kernel: tile rows of block_idx "
                "disagree under query_tile > 1 (tile-shared-list invariant)")
    return pq_scan_tiled_kernel(lut, block_codes, rows[:, 0, :],
                                query_tile=query_tile, interpret=interpret,
                                packed=packed)


def _make_topk_kernel(query_tile: int, blk: int, f: int, with_dead: bool,
                      packed: bool = False):
    """Kernel body factory for the fused scan->top-k (shapes are static)."""

    def kernel(idx_ref, lut_ref, codes_ref, bids_ref, bother_ref, rank_ref,
               slot_ref, ranku_ref, *rest):
        if with_dead:
            (dead_ref, acc_d_ref, acc_pos_ref, acc_id_ref, dco_ref) = rest
        else:
            (acc_d_ref, acc_pos_ref, acc_id_ref, dco_ref) = rest
        qt = lut_ref.shape[0]
        si = pl.program_id(1)

        # the accumulator blocks map to (qi, 0, 0) for every scan
        # position, so they stay resident in VMEM across the inner grid
        # dimension; first visit initializes them to the empty top-F
        @pl.when(si == 0)
        def _init():
            acc_d_ref[...] = jnp.full((qt, 1, f), jnp.inf, jnp.float32)
            acc_pos_ref[...] = jnp.full((qt, 1, f), PAD_POS, jnp.int32)
            acc_id_ref[...] = jnp.full((qt, 1, f), -1, jnp.int32)
            dco_ref[...] = jnp.zeros((qt, 1, 1), jnp.int32)

        # -- score the paged block: the unfused kernel's contraction, so
        # distances are bitwise identical
        d = _score_block(lut_ref, codes_ref, packed)           # (QT, BLK)

        # -- in-kernel keep mask (Alg. 5 L15-16, scan_blocks' post-hoc
        # logic moved here): invalid slots/absent union positions
        # (slot < 0), invalid items (id < 0), and misc duplicates whose
        # co-assigned list was scanned at an earlier probe rank
        ids = bids_ref[0]                                      # (1, BLK)
        other = bother_ref[0]                                  # (1, BLK)
        # this scan position's column of the resident (QT, S) sidecars
        s_lane = jax.lax.broadcasted_iota(jnp.int32, slot_ref.shape, 2)
        slot = jnp.sum(jnp.where(s_lane == si, slot_ref[...], 0),
                       axis=2)                                 # (QT, 1)
        ranku = jnp.sum(jnp.where(s_lane == si, ranku_ref[...], 0), axis=2)
        # rank_of[q, other[i]]: a lane gather over nlist does not lower,
        # so contract the rank rows with a one-hot of `other` instead —
        # exact at HIGHEST (ranks < 2^24, BIG = 2^30)
        nlist = rank_ref.shape[2]
        o_hot = (jax.lax.broadcasted_iota(jnp.int32, (nlist, blk), 0)
                 == jnp.maximum(other, 0)).astype(jnp.float32)
        orank = jnp.dot(rank_ref[:, 0, :].astype(jnp.float32), o_hot,
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)    # (QT, BLK)
        dup = (other >= 0) & (orank < ranku.astype(jnp.float32))
        item_ok = (ids >= 0) & (slot >= 0)
        keep = item_ok & ~dup
        if with_dead:
            # tombstoned candidates must not consume accumulator slots
            # (they are ADC-computed — DCO counts them — then discarded)
            keep &= dead_ref[0].astype(jnp.int32) == 0
        dco_ref[...] += jnp.sum(item_ok.astype(jnp.int32), axis=1,
                                keepdims=True)[:, None, :]

        # -- candidate triple in plan layout: pos = slot*BLK + lane is the
        # flat position of the unfused stream, the lax.top_k tie-break.
        # The block is sorted descending so its best F sit at the end,
        # ready for merge_topf's half-cleaner against the ascending
        # accumulator
        lane = jax.lax.broadcasted_iota(jnp.int32, (qt, blk), 1)
        pos = slot * blk + lane
        new = bitonic_sort([jnp.where(keep, d, jnp.inf),
                            jnp.where(keep, pos, PAD_POS),
                            jnp.where(keep, ids, -1)], descending=True)
        if blk >= f:
            # candidates beyond a block's own top-F can never survive
            new = [x[:, blk - f:] for x in new]
        else:
            pad = ((0, 0), (f - blk, 0))
            new = [jnp.pad(new[0], pad, constant_values=jnp.inf),
                   jnp.pad(new[1], pad, constant_values=PAD_POS),
                   jnp.pad(new[2], pad, constant_values=-1)]
        acc = merge_topf([acc_d_ref[:, 0, :], acc_pos_ref[:, 0, :],
                          acc_id_ref[:, 0, :]], new)
        acc_d_ref[...] = acc[0][:, None, :]
        acc_pos_ref[...] = acc[1][:, None, :]
        acc_id_ref[...] = acc[2][:, None, :]

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("query_tile", "fetch", "interpret",
                                    "packed"))
def pq_scan_topk_kernel(lut: jnp.ndarray, block_codes: jnp.ndarray,
                        block_ids: jnp.ndarray, block_other: jnp.ndarray,
                        tile_idx: jnp.ndarray, rank_of: jnp.ndarray,
                        slot_of: jnp.ndarray, rank_u: jnp.ndarray,
                        dead=None, *, query_tile: int = 8, fetch: int = 64,
                        interpret: bool = False, packed: bool = False):
    """Fused paged scan -> partial top-``fetch``: only ``fetch`` candidates
    per query ever leave the kernel, instead of (S, BLK) scores.

    lut        (B, M, K) f32     per-query ADC tables
    block_codes(TB, BLK, M) u8   physical code blocks
    block_ids  (TB, BLK) i32     item ids (-1 invalid)
    block_other(TB, BLK) i32     co-assigned list of shared items (-1 none)
    tile_idx   (B//QT, S) i32    scalar-prefetched per-tile scan lists
    rank_of    (B, nlist) i32    probe rank table (BIG if unprobed)
    slot_of    (B, S) i32        plan slot of scan position s for query b
                                 (-1: not in this query's plan -> masked)
    rank_u     (B, S) i32        probe rank of that slot's scan
    dead       (TB, BLK) u8?     optional tombstone tile (1 = dead)

    Returns ``(acc_d, acc_pos, acc_id, dco)``: (B, fetch) ascending
    distances / plan-layout flat positions / ids, plus the (B,) logical
    DCO counter (one per valid item of a planned block, duplicates
    included — exactly ``scan_blocks``' accounting).  The accumulator
    triple lives in VMEM for the whole inner grid pass (out BlockSpecs
    constant in the scan dimension), as do the query tile's rank table
    and (S,) sidecar rows; each step is one bitonic sort of the block +
    one bitonic merge against the accumulator (kernels/topk.py), keyed
    lexicographically by (d, pos) so the result is bitwise the stable
    ``preselect_candidates`` selection over the unfused stream with
    masked entries at ``(+inf, PAD_POS, -1)``.
    """
    b, m, k = lut.shape
    qb, s = tile_idx.shape
    tb, blk, mb = block_codes.shape
    assert (2 * mb if packed else mb) == m, (mb, m, packed)
    assert b == qb * query_tile, (b, qb, query_tile)
    assert blk == pow2_ceil(blk), f"block size must be a power of 2: {blk}"
    assert slot_of.shape == (b, s), (slot_of.shape, (b, s))
    assert rank_u.shape == (b, s), (rank_u.shape, (b, s))
    f = pow2_ceil(max(fetch, 1))
    nlist = rank_of.shape[1]
    with_dead = dead is not None

    def row(x):   # per-block (TB, BLK) plane -> (TB, 1, BLK)
        return x.reshape(tb, 1, blk)

    def paged(qi, si, idx):
        return (idx[qi, si], 0, 0)

    def tile(qi, si, idx):
        return (qi, 0, 0)

    in_specs = [
        pl.BlockSpec((query_tile, m, k), tile),
        pl.BlockSpec((1, blk, mb), paged),
        pl.BlockSpec((1, 1, blk), paged),
        pl.BlockSpec((1, 1, blk), paged),
        pl.BlockSpec((query_tile, 1, nlist), tile),
        pl.BlockSpec((query_tile, 1, s), tile),
        pl.BlockSpec((query_tile, 1, s), tile),
    ]
    blocks = [block_codes, row(block_ids.astype(jnp.int32)),
              row(block_other.astype(jnp.int32))]
    per_query = [_even_odd_lut(lut, packed), rank_of.astype(jnp.int32).reshape(b, 1, nlist),
                 slot_of.astype(jnp.int32).reshape(b, 1, s),
                 rank_u.astype(jnp.int32).reshape(b, 1, s)]
    if with_dead:
        in_specs.append(pl.BlockSpec((1, 1, blk), paged))
        dead_row = row(dead.astype(jnp.uint8))

    def call(idx, lut_c, rank_c, slot_c, ranku_c):
        bc = lut_c.shape[0]
        kernel = pl.pallas_call(
            _make_topk_kernel(query_tile, blk, f, with_dead, packed),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=idx.shape,
                in_specs=in_specs,
                out_specs=[pl.BlockSpec((query_tile, 1, f), tile)] * 3
                + [pl.BlockSpec((query_tile, 1, 1), tile)]),
            out_shape=[
                jax.ShapeDtypeStruct((bc, 1, f), jnp.float32),
                jax.ShapeDtypeStruct((bc, 1, f), jnp.int32),
                jax.ShapeDtypeStruct((bc, 1, f), jnp.int32),
                jax.ShapeDtypeStruct((bc, 1, 1), jnp.int32),
            ],
            interpret=interpret,
        )
        operands = [lut_c, *blocks, rank_c, slot_c, ranku_c]
        if with_dead:
            operands.append(dead_row)
        return tuple(kernel(idx, *operands))

    acc_d, acc_pos, acc_id, dco = _map_query_chunks(
        call, tile_idx.astype(jnp.int32), per_query, query_tile)
    return (acc_d[:, 0, :fetch], acc_pos[:, 0, :fetch], acc_id[:, 0, :fetch],
            dco[:, 0, 0])
