"""Paged PQ fast-scan Pallas TPU kernel — the DCO hot spot of the paper.

CPU PQ Fast Scan keeps 16-entry LUTs in SIMD registers and uses the
AVX2 ``pshufb`` 16-way shuffle to score 32 packed items at once.  TPUs
have no shuffle unit, so we adapt the insight (block-wise LUT scoring
with no per-item scalar work) to the MXU:

  * the 4-bit code of item i, subspace m selects ``lut[m, code]``; we
    materialize the selection as a one-hot tile and contract
    ``(BLK, M*K) @ (M*K, 1)`` on the MXU — one systolic pass scores a
    whole block (the TPU idiom for small-table gathers).  The one-hot
    is built directly in the lane layout the contraction reads (codes
    spread to lanes by an exact 0/1 bf16 contraction, then compared
    with ``lane % K``) and the LUT arrives flattened to ``(B, 1, M*K)``
    from the wrappers, so no grid step reshapes a ``(.., M, K)`` tile
    to ``M*K`` lanes: Mosaic lowers that lane merge as sublane rotates
    and shuffles, which cost more than the scoring itself;
  * SEIL's reference-entry indirection becomes *paging*: the per-query
    deduplicated block-id list is scalar-prefetched
    (``PrefetchScalarGridSpec``) and drives the BlockSpec ``index_map``,
    so the HBM->VMEM DMA fetches each shared cell block exactly once —
    skipping a reference entry never issues its loads, the DMA-level
    analogue of Alg. 5's ``listVisited`` probe;
  * grid order is (query-block, scan-position): consecutive grid steps
    for the *same* scan position across the query tile reuse the code
    tile already resident in VMEM — the TPU analogue of the paper's
    "group tasks by list" cache optimization (§5.3);
  * a grid step pages G = ``blocks_per_step(BLK)`` consecutive scan
    positions (four blocks of 32, one block from 128 up), one paged
    operand per block, and scores them as one (G*BLK)-row tile: a
    step's fixed cost (pipeline bookkeeping, the small paged DMAs)
    outweighs its work at 32 items, and G*BLK fills the 128 lanes.

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
# lanes of a vreg: the width one grid step fills with candidates
_LANES = 128


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


def _cat(parts, axis: int):
    """Concatenate the per-block pieces of one grid step; a one-block step
    takes its piece as is, so it lowers exactly as a single block."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=axis)


def _tile_codes(codes_refs, packed: bool) -> jnp.ndarray:
    """Code tiles -> (G*BLK, M) int32 codes, unpacking nibble pairs in-VMEM.

    The G paged tiles of a grid step are stacked along rows (sublanes),
    block ``g`` in rows ``g*BLK .. (g+1)*BLK``.  A packed tile (quant
    plane, two 4-bit codes per byte) carries MB = M/2 bytes; the lo
    nibble is the even subquantizer, hi the odd — the single layout
    defined by ``quant/nibbles.py``.  Interleaving the nibbles back does
    not lower on TPU, so the unpacked tile is ``[lo | hi]`` — every even
    subquantizer, then every odd one — and the kernel wrappers permute
    the LUT rows to match (``_kernel_lut``).  Callers guarantee 2*MB ==
    lut M (ops wrappers zero-pad the LUT so a padded byte's two zero
    codes select zero rows and contribute nothing).
    """
    raw = _cat([r[0].astype(jnp.int32) for r in codes_refs], 0)
    if not packed:
        return raw
    return jnp.concatenate([raw & 15, raw >> 4], axis=-1)


def _kernel_lut(lut: jnp.ndarray, packed: bool) -> jnp.ndarray:
    """(B, M, K) LUT -> the (B, 1, M*K) rows the kernels read.

    Flattened once per call in XLA, so no grid step merges the K lanes
    of the table; for a packed plane the rows are first put in the
    order ``_tile_codes`` unpacks to (every even subquantizer, then
    every odd one)."""
    if packed:
        lut = jnp.concatenate([lut[:, 0::2], lut[:, 1::2]], axis=1)
    b, m, k = lut.shape
    return lut.reshape(b, 1, m * k)


def _score_block(lut_ref, codes_refs, packed: bool) -> jnp.ndarray:
    """(QT, G*BLK) ADC distances of a grid step's G paged code blocks
    for a query tile, block ``g`` in lanes ``g*BLK .. (g+1)*BLK``.

    The 4-bit code of item i, subspace m selects ``lut[m, code]``; the
    selection is a (G*BLK, M*K) one-hot over the flat table, contracted
    on the MXU, so every query of the tile scores the step's blocks in
    one pass.  The one-hot is built in the lane layout the contraction reads:
    an exact bf16 contraction with the 0/1 matrix ``R[m, j] = (j // K ==
    m)`` copies code (i, m) to the K lanes of subspace m (one nonzero
    product per output, codes < 256), and a compare with the lane
    constant ``j % K`` selects.  A (BLK, M, K) tile reshaped to
    (BLK, M*K) would merge lanes, which Mosaic lowers as sublane
    rotates and shuffles that spill on every grid step.  Both kernels
    score through here, so their distances are bitwise identical.  The
    scoring contraction is pinned to full f32 (HIGHEST): the one-hot is
    exact in bf16 but the LUT is not, and the distances must match the
    jnp reference's."""
    codes = _tile_codes(codes_refs, packed)                    # (G*BLK, M)
    m = codes.shape[1]
    mk = lut_ref.shape[-1]
    k = mk // m
    col = jax.lax.broadcasted_iota(jnp.int32, (m, mk), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (m, mk), 0)
    spread = (col // k == row).astype(jnp.bfloat16)            # (M, MK)
    lanes = jnp.dot(codes.astype(jnp.bfloat16), spread,
                    preferred_element_type=jnp.float32)        # (G*BLK, MK)
    entry = jax.lax.broadcasted_iota(jnp.int32, (1, mk), 1) % k
    oh = (lanes == entry.astype(jnp.float32)).astype(jnp.float32)
    return jax.lax.dot_general(lut_ref[:, 0, :], oh,
                               (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _make_kernel(packed: bool):
    """Body factory for the unfused scan (packed-ness is static)."""

    def _kernel(idx_ref, lut_ref, *refs):
        """One grid step: score a step's G code blocks for QT queries.

        lut_ref:   (QT, 1, M*K) f32 in VMEM (``_kernel_lut``)
        refs:      G (1, BLK, MB) uint8 paged blocks in VMEM (MB = M, or
                   M/2 when nibble-packed), then the (QT, 1, 1, G*BLK)
                   f32 output block
        """
        *codes_refs, out_ref = refs
        d = _score_block(lut_ref, codes_refs, packed)
        out_ref[...] = d[:, None, None, :]

    return _kernel


def blocks_per_step(blk: int) -> int:
    """Scan positions G that one grid step of either scan kernel pages
    and scores: as many blocks of ``blk`` items as fill the 128 lanes of
    a vreg, so the step's fixed cost (pipeline bookkeeping, the small
    paged DMAs) is paid once per 128 candidates.  One at ``blk >= 128``."""
    return max(1, _LANES // blk)


def _pad_positions(x: jnp.ndarray, g: int, value: int = 0) -> jnp.ndarray:
    """Pad the scan-position axis of a (rows, S) list to a multiple of g."""
    pad = -x.shape[1] % g
    return jnp.pad(x, ((0, 0), (0, pad)), constant_values=value) if pad else x


def _position(si, j: int, g: int):
    """Scan position of block j of grid step ``si``: ``si * g + j``, and
    ``si`` itself at g == 1, so a one-block step lowers as it always did."""
    return si * g + j if g > 1 else si


def _paged_specs(shape, g: int):
    """BlockSpecs of a grid step's g paged blocks of one plane."""
    def paged(j):
        return lambda qi, si, idx: (idx[qi, _position(si, j, g)], 0, 0)
    return [pl.BlockSpec(shape, paged(j)) for j in range(g)]


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
    lut = _kernel_lut(lut, packed)
    g = blocks_per_step(blk)

    def call(idx, lut_c):
        steps = idx.shape[1] // g
        kernel = pl.pallas_call(
            _make_kernel(packed),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(idx.shape[0], steps),
                in_specs=[
                    pl.BlockSpec((query_tile, 1, m * k),
                                 lambda qi, si, idx: (qi, 0, 0)),
                    *_paged_specs((1, blk, mb), g),
                ],
                out_specs=pl.BlockSpec((query_tile, 1, 1, g * blk),
                                       lambda qi, si, idx: (qi, si, 0, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((lut_c.shape[0], steps, 1,
                                            g * blk), jnp.float32),
            interpret=interpret,
            name="pq_scan",
        )
        return kernel(idx, lut_c, *[block_codes] * g)

    out = _map_query_chunks(call, _pad_positions(tile_idx, g), [lut],
                            query_tile)
    return out.reshape(b, -1, blk)[:, :s]


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


def _make_topk_kernel(blk: int, f: int, g: int, with_dead: bool,
                      packed: bool = False):
    """Kernel body factory for the fused scan->top-k (shapes are static).

    One grid step scores the ``g`` scan positions ``si*g .. si*g+g-1``:
    their blocks arrive as ``g`` paged operands per plane, and lane
    ``l`` of the step's (QT, g*BLK) rows is item ``l % BLK`` of block
    ``l // BLK``."""
    n = g * blk

    def kernel(idx_ref, lut_ref, *refs):
        codes_refs, bids_refs, bother_refs = (refs[:g], refs[g:2 * g],
                                              refs[2 * g:3 * g])
        rank_ref, slot_ref, ranku_ref = refs[3 * g:3 * g + 3]
        dead_refs = refs[3 * g + 3:4 * g + 3] if with_dead else ()
        acc_d_ref, acc_pos_ref, acc_id_ref, dco_ref = refs[-4:]
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

        # -- score the paged blocks: the unfused kernel's contraction, so
        # distances are bitwise identical
        d = _score_block(lut_ref, codes_refs, packed)          # (QT, n)

        # -- in-kernel keep mask (Alg. 5 L15-16, scan_blocks' post-hoc
        # logic moved here): invalid slots/absent union positions
        # (slot < 0), invalid items (id < 0), and misc duplicates whose
        # co-assigned list was scanned at an earlier probe rank
        ids = _cat([r[0] for r in bids_refs], 1)               # (1, n)
        other = _cat([r[0] for r in bother_refs], 1)           # (1, n)
        # each scan position's column of the resident (QT, S) sidecars,
        # spread over its block's lanes: (QT, 1) at g == 1, else (QT, n)
        s_lane = jax.lax.broadcasted_iota(jnp.int32, slot_ref.shape, 2)
        if g > 1:
            group = jax.lax.broadcasted_iota(jnp.int32, (qt, n), 1) // blk

        def per_lane(ref):
            cols = [jnp.sum(jnp.where(s_lane == _position(si, j, g),
                                      ref[...], 0), axis=2)
                    for j in range(g)]                         # (QT, 1) each
            out = cols[-1]
            for j in range(g - 2, -1, -1):
                out = jnp.where(group == j, cols[j], out)
            return out

        slot = per_lane(slot_ref)
        ranku = per_lane(ranku_ref)
        # rank_of[q, other[i]]: a lane gather over nlist does not lower,
        # so contract the rank rows with a one-hot of `other` instead —
        # exact at HIGHEST (ranks < 2^24, BIG = 2^30)
        nlist = rank_ref.shape[2]
        o_hot = (jax.lax.broadcasted_iota(jnp.int32, (nlist, n), 0)
                 == jnp.maximum(other, 0)).astype(jnp.float32)
        orank = jnp.dot(rank_ref[:, 0, :].astype(jnp.float32), o_hot,
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)    # (QT, n)
        dup = (other >= 0) & (orank < ranku.astype(jnp.float32))
        item_ok = (ids >= 0) & (slot >= 0)
        keep = item_ok & ~dup
        if with_dead:
            # tombstoned candidates must not consume accumulator slots
            # (they are ADC-computed — DCO counts them — then discarded)
            keep &= _cat([r[0].astype(jnp.int32) for r in dead_refs], 1) == 0
        dco_ref[...] += jnp.sum(item_ok.astype(jnp.int32), axis=1,
                                keepdims=True)[:, None, :]

        # -- candidate triple in plan layout: pos = slot*BLK + item is the
        # flat position of the unfused stream, the lax.top_k tie-break.
        # The step's candidates are sorted descending so their best F sit
        # at the end, ready for merge_topf's half-cleaner against the
        # ascending accumulator
        lane = jax.lax.broadcasted_iota(jnp.int32, (qt, n), 1)
        if g > 1:
            lane &= blk - 1                       # item within its block
        pos = slot * blk + lane
        new = bitonic_sort([jnp.where(keep, d, jnp.inf),
                            jnp.where(keep, pos, PAD_POS),
                            jnp.where(keep, ids, -1)], descending=True)
        if n >= f:
            # candidates beyond a step's own top-F can never survive
            new = [x[:, n - f:] for x in new]
        else:
            pad = ((0, 0), (f - n, 0))
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
    and (S,) sidecar rows.  Each grid step pages G = ``blocks_per_step
    (BLK)`` scan positions, one paged operand per block and plane, so a
    step fills the 128 lanes with candidates; the lists are padded to a
    multiple of G with block 0 at ``slot = -1``, masked and uncounted
    like an absent union position.  Each step is one bitonic sort of its
    G*BLK candidates + one bitonic merge against the accumulator
    (kernels/topk.py), keyed lexicographically by (d, pos) so the result
    is bitwise the stable ``preselect_candidates`` selection over the
    unfused stream with masked entries at ``(+inf, PAD_POS, -1)``.
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
    g = blocks_per_step(blk)
    # padding positions page block 0 at slot -1: masked, never counted
    tile_idx = _pad_positions(tile_idx.astype(jnp.int32), g)
    slot_of = _pad_positions(slot_of.astype(jnp.int32), g, -1)
    rank_u = _pad_positions(rank_u.astype(jnp.int32), g)
    s = tile_idx.shape[1]

    def row(x):   # per-block (TB, BLK) plane -> (TB, 1, BLK)
        return x.reshape(tb, 1, blk)

    def tile(qi, si, idx):
        return (qi, 0, 0)

    in_specs = [pl.BlockSpec((query_tile, 1, m * k), tile),
                *_paged_specs((1, blk, mb), g),
                *_paged_specs((1, 1, blk), g),
                *_paged_specs((1, 1, blk), g),
                pl.BlockSpec((query_tile, 1, nlist), tile),
                pl.BlockSpec((query_tile, 1, s), tile),
                pl.BlockSpec((query_tile, 1, s), tile)]
    blocks = [block_codes] * g + [row(block_ids.astype(jnp.int32))] * g \
        + [row(block_other.astype(jnp.int32))] * g
    per_query = [_kernel_lut(lut, packed),
                 rank_of.astype(jnp.int32).reshape(b, 1, nlist),
                 slot_of.reshape(b, 1, s), rank_u.reshape(b, 1, s)]
    if with_dead:
        in_specs += _paged_specs((1, 1, blk), g)
        dead_rows = [row(dead.astype(jnp.uint8))] * g

    def call(idx, lut_c, rank_c, slot_c, ranku_c):
        bc = lut_c.shape[0]
        kernel = pl.pallas_call(
            _make_topk_kernel(blk, f, g, with_dead, packed),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(idx.shape[0], idx.shape[1] // g),
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
            name="pq_scan_topk",
        )
        operands = [lut_c, *blocks, rank_c, slot_c, ranku_c]
        if with_dead:
            operands += dead_rows
        return tuple(kernel(idx, *operands))

    acc_d, acc_pos, acc_id, dco = _map_query_chunks(
        call, tile_idx, per_query, query_tile)
    return (acc_d[:, 0, :fetch], acc_pos[:, 0, :fetch], acc_id[:, 0, :fetch],
            dco[:, 0, 0])
