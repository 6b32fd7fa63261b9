"""The scan kernel's full candidate stream, for tests.

``pq_scan_topk`` keeps only each query's best ``fetch`` candidates.  At
full width — ``fetch = W * BLK``, every scan position a valid slot, no
co-assigned lists, unique ids — it keeps all of them, and each
candidate's plan-layout position ``acc_pos = slot * BLK + item`` says
where it came from.  Scattered back by ``acc_pos``, the stream is the
(B, W, BLK) distance tensor the kernel scored, rounded exactly as the
kernel rounds, so tests can hold the kernel to the jnp oracle
(``pq_scan_paged_ref``) and build bitwise references of the scan stage.
"""
import jax.numpy as jnp

from repro.kernels.ops import pq_scan_topk


def kernel_stream(lut, block_codes, tile_idx, *, query_tile=1,
                  packed=False):
    """(B, W, BLK) distances of every position of the per-tile scan
    lists ``tile_idx`` (B // query_tile, W), scored by the kernel."""
    b, w = lut.shape[0], tile_idx.shape[1]
    tb, blk = block_codes.shape[:2]
    ids = jnp.arange(tb * blk, dtype=jnp.int32).reshape(tb, blk)
    other = jnp.full((tb, blk), -1, jnp.int32)
    sel = jnp.full((b, 1), -1, jnp.int32)      # no list co-assigned
    slot_of = jnp.broadcast_to(jnp.arange(w, dtype=jnp.int32)[None], (b, w))
    d, pos, _, _ = pq_scan_topk(
        lut, block_codes, ids, other, tile_idx, sel,
        slot_of, jnp.zeros((b, w), jnp.int32), fetch=w * blk,
        query_tile=query_tile, packed=packed)
    rows = jnp.arange(b)[:, None]
    out = jnp.full((b, w * blk), jnp.nan, jnp.float32).at[rows, pos].set(d)
    return out.reshape(b, w, blk)
