"""Jitted public wrappers around the Pallas kernels.

The backend decides how a kernel runs, explicitly (``_interpret``):

  * ``tpu`` — compiled through Mosaic, always;
  * ``cpu`` — interpret mode: the kernel body executes as plain XLA ops
    on the host.  This is the test path (``JAX_PLATFORMS=cpu``), which
    validates results, never speed;
  * any other backend raises — a kernel path never falls back to the
    interpreter or to a jnp reference in silence.

On TPU ``M`` (PQ subspaces) is zero-padded to the uint8 lane tile so
production shapes are alignment-clean; padded codes are 0 and padded
LUT rows are 0, so they contribute nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pq_scan import (pq_scan_paged_kernel, pq_scan_tiled_kernel,
                      pq_scan_topk_kernel)

_LANE = 128


def _interpret() -> bool:
    """True on CPU (interpret mode), False on TPU (compiled); raises on
    any other backend."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas scan kernels run compiled on 'tpu' or interpreted on "
        f"'cpu'; the default backend is {backend!r}")


def _pad_m(lut: jnp.ndarray, block_codes: jnp.ndarray, align: int):
    m = lut.shape[1]
    pad = (-m) % align
    if pad:
        lut = jnp.pad(lut, ((0, 0), (0, pad), (0, 0)))
        block_codes = jnp.pad(block_codes, ((0, 0), (0, 0), (0, pad)))
    return lut, block_codes


def _pad_m_packed(lut: jnp.ndarray, block_codes: jnp.ndarray, on_tpu: bool):
    """Align a nibble-packed code plane with its LUT.

    The kernel unpacks each byte into two codes, so its effective M is
    always 2x the byte width: the LUT is zero-padded to that width on
    every backend (this also absorbs an odd Mc's phantom hi nibble),
    and on TPU the byte width is first padded to half the uint8 lane
    tile so the unpacked M lands on the lane boundary.  Padded bytes
    are 0 -> both nibbles select zero LUT rows -> contribute nothing.
    """
    mb = block_codes.shape[-1]
    if on_tpu:
        pad_b = (-mb) % (_LANE // 2)
        if pad_b:
            block_codes = jnp.pad(block_codes,
                                  ((0, 0), (0, 0), (0, pad_b)))
            mb += pad_b
    pad = 2 * mb - lut.shape[1]
    if pad:
        lut = jnp.pad(lut, ((0, 0), (0, pad), (0, 0)))
    return lut, block_codes


def _align(lut, block_codes, packed: bool, on_tpu: bool):
    if packed:
        return _pad_m_packed(lut, block_codes, on_tpu)
    if on_tpu:
        return _pad_m(lut, block_codes, _LANE)
    return lut, block_codes


@functools.partial(jax.jit, static_argnames=("packed",))
def pq_scan_paged(lut: jnp.ndarray, block_codes: jnp.ndarray,
                  block_idx: jnp.ndarray, *,
                  packed: bool = False) -> jnp.ndarray:
    """Per-query paged ADC scan.  lut (B, M, K) f32, block_codes
    (TB, BLK, M) uint8, block_idx (B, S) int32 (>= 0) -> (B, S, BLK) f32."""
    interpret = _interpret()
    lut, block_codes = _align(lut, block_codes, packed, not interpret)
    return pq_scan_paged_kernel(lut, block_codes, block_idx.astype(jnp.int32),
                                query_tile=1, interpret=interpret,
                                packed=packed)


def pq_scan_grouped(lut: jnp.ndarray, block_codes: jnp.ndarray,
                    shared_idx: jnp.ndarray, query_tile: int = 8,
                    *, packed: bool = False) -> jnp.ndarray:
    """List-major batch mode (paper §5.3 cache optimization): all B queries
    score the SAME scan list.  lut (B, M, K), shared_idx (S,) -> (B, S, BLK).
    The code tile for each position stays resident in VMEM across the
    query-tile grid steps."""
    b = lut.shape[0]
    interpret = _interpret()
    lut, block_codes = _align(lut, block_codes, packed, not interpret)
    idx = jnp.broadcast_to(shared_idx[None, :],
                           (b // query_tile, shared_idx.shape[0]))
    return pq_scan_tiled_kernel(lut, block_codes, idx.astype(jnp.int32),
                                query_tile=query_tile, interpret=interpret,
                                packed=packed)


def pq_scan_tiled(lut: jnp.ndarray, block_codes: jnp.ndarray,
                  tile_idx: jnp.ndarray, query_tile: int = 8,
                  *, packed: bool = False) -> jnp.ndarray:
    """Clustered mode (locality-aware §5.3): each query *tile* scores its
    own scan list — the tile's block union, padded per tile rather than
    to the batch-wide maximum.  lut (B, M, K) in cluster order, tile_idx
    (B // query_tile, W) -> (B, W, BLK).  The scalar-prefetched tile
    lists feed the kernel index_map directly (no (B, W) re-broadcast);
    the code tile for each union position stays resident in VMEM across
    its tile's grid steps."""
    interpret = _interpret()
    lut, block_codes = _align(lut, block_codes, packed, not interpret)
    return pq_scan_tiled_kernel(lut, block_codes, tile_idx.astype(jnp.int32),
                                query_tile=query_tile, interpret=interpret,
                                packed=packed)


def pq_scan_topk(lut: jnp.ndarray, block_codes: jnp.ndarray,
                 block_ids: jnp.ndarray, block_other: jnp.ndarray,
                 tile_idx: jnp.ndarray, rank_of: jnp.ndarray,
                 slot_of: jnp.ndarray, rank_u: jnp.ndarray, dead=None,
                 *, fetch: int, query_tile: int = 8, packed: bool = False):
    """Fused scan -> top-``fetch``: the paged ADC scan with the keep mask
    and the stable partial top-k folded into the kernel, so only
    ``fetch`` candidates per query cross the HBM boundary instead of
    (S, BLK) scores.  tile_idx (B // query_tile, S) pages per-tile scan
    lists exactly like ``pq_scan_tiled``; ``slot_of``/``rank_u`` (B, S)
    map each scan position back to the query's plan slot (see
    ``core/engine/fused.py`` for the per-exec-mode construction).
    Returns (acc_d, acc_pos, acc_id, dco) — (B, fetch) sorted candidate
    triple + (B,) logical DCO."""
    interpret = _interpret()
    lut, block_codes = _align(lut, block_codes, packed, not interpret)
    return pq_scan_topk_kernel(
        lut, block_codes, block_ids, block_other,
        tile_idx.astype(jnp.int32), rank_of, slot_of, rank_u, dead,
        query_tile=query_tile, fetch=fetch, interpret=interpret,
        packed=packed)
