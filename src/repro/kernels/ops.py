"""The public wrapper around the Pallas scan kernel.

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

import jax
import jax.numpy as jnp

from .pq_scan import pq_scan_topk_kernel

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


def pq_scan_topk(lut: jnp.ndarray, block_codes: jnp.ndarray,
                 block_ids: jnp.ndarray, block_other: jnp.ndarray,
                 tile_idx: jnp.ndarray, rank_of: jnp.ndarray,
                 slot_of: jnp.ndarray, rank_u: jnp.ndarray, dead=None,
                 *, fetch: int, query_tile: int = 8, packed: bool = False):
    """Fused scan -> top-``fetch``: the paged ADC scan with the keep mask
    and the stable partial top-k folded into the kernel, so only
    ``fetch`` candidates per query cross the HBM boundary instead of
    (S, BLK) scores.  tile_idx (B // query_tile, S) pages per-tile scan
    lists (at query_tile 1, every query its own); ``slot_of``/``rank_u``
    (B, S) map each scan position back to the query's plan slot (see
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
