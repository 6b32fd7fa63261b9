"""The scan's work, counted from what the algorithm must touch.

For one batch the count starts from the lists each query probes, ranked
by the benchmark's own exact centroid distances, and the items the index
holds in those lists (its SEIL tables and blocks):

* per query, the candidate blocks of the probed lists in scan order
  (owned, then referenced, then miscellaneous blocks, each by probe
  rank), less each shared block whose co-assigned list was probed at an
  earlier rank (SEIL's compute-once rule), cut at the ``max_scan``
  budget the search parameters state;
* ops: one per (query, scanned item, subspace), where a scanned item is
  a valid slot of a scanned block that is not the twin of an item
  already scored at an earlier probe rank;
* bytes: each distinct block of the batch read once, at the index's
  ``nbits`` per code, counting only its valid items; plus each query's
  f32 lookup table (M x 2^nbits) and the ``bigk`` candidates (distance
  and id, 8 bytes) each query hands to the refine step.

The count ignores how a kernel is written: its grid, its padding, codes
stored a byte each or padded to 128 subspaces.  A kernel that reads less
shows a higher share, and none can show more than 100%.
"""
from __future__ import annotations

import numpy as np

BIG = np.int64(2 ** 30)


def probed_lists(queries: np.ndarray, centroids: np.ndarray, nprobe: int,
                 metric: str) -> np.ndarray:
    """(B, nprobe) list ids by exact centroid distance, nearest first."""
    q = np.asarray(queries, np.float64)
    c = np.asarray(centroids, np.float64)
    if metric == "l2":
        d = (c * c).sum(1)[None, :] - 2.0 * q @ c.T
    else:
        d = -(q @ c.T)
    sel = np.argsort(d, axis=1, kind="stable")[:, :nprobe]
    return sel


def scanned_blocks(sel: np.ndarray, t: dict, max_scan: int):
    """Per query, the blocks the search scans and the probe rank of each
    scan: (B, W) block ids (-1 where none) and (B, W) ranks."""
    b, p = sel.shape
    nlist = t["owned"].shape[0]
    rank = np.full((b, nlist), BIG, np.int64)
    rank[np.arange(b)[:, None], sel] = np.arange(p)[None, :]
    rows = np.arange(b)[:, None, None]
    r = np.arange(p)[None, :, None]

    def earlier(other):
        return (other >= 0) & (rank[rows, np.maximum(other, 0)] < r)

    owned = t["owned"][sel]
    owned_other = np.where(owned >= 0,
                           t["block_other"][np.maximum(owned, 0), 0], -1)
    owned = np.where(earlier(owned_other), -1, owned)
    refs = np.where(earlier(t["refs_other"][sel]), -1, t["refs"][sel])
    misc = t["misc"][sel]
    cand = np.concatenate([a.reshape(b, -1) for a in (owned, refs, misc)], 1)
    cand_rank = np.concatenate(
        [np.broadcast_to(r, a.shape).reshape(b, -1)
         for a in (owned, refs, misc)], 1)
    valid = cand >= 0
    keep = valid & (np.cumsum(valid, axis=1) <= max_scan)
    return np.where(keep, cand, -1), np.where(keep, cand_rank, BIG), rank


def scan_work(queries: np.ndarray, t: dict, *, nprobe: int, max_scan: int,
              metric: str, m: int, nbits: int, bigk: int) -> dict:
    """{"ops", "bytes"} of one batch's scan."""
    sel = probed_lists(queries, t["centroids"], nprobe, metric)
    blocks, ranks, rank = scanned_blocks(sel, t, max_scan)
    b = sel.shape[0]
    on = blocks >= 0
    bi = np.maximum(blocks, 0)
    slot_ok = (t["block_ids"][bi] >= 0) & on[:, :, None]
    other = t["block_other"][bi]
    o_rank = rank[np.arange(b)[:, None, None], np.maximum(other, 0)]
    twin = (other >= 0) & (o_rank < ranks[:, :, None])
    items = int((slot_ok & ~twin).sum())
    distinct = np.unique(blocks[on])
    stored = int((t["block_ids"][distinct] >= 0).sum())
    code_bytes = stored * m * nbits / 8.0
    lut_bytes = b * m * (2 ** nbits) * 4
    out_bytes = b * bigk * 8
    return {"ops": float(items * m),
            "bytes": float(code_bytes + lut_bytes + out_bytes)}


def min_time_s(work: dict, peak: dict) -> tuple:
    """(least seconds the chip could take, "ops" or "bytes": the bound)."""
    t_ops = work["ops"] / peak["ops_per_s"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
