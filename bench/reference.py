"""The plain reference and the comparison that decides ``correct``.

The reference is exact search by brute force over the benchmark's own
corpus, with nothing taken from the program: the top-k of every query
in the pool at full f32 (``Precision.HIGHEST``) on the device, in blocks
of queries, and the exact distance of every returned (query, id) pair in
float64 on the host.

``compare`` judges every answer the window returned against it:

* ``recall_at_10``  mean |returned ∩ exact top-10| / 10 over every
  answered query; at least the recall the configuration states;
* ``dist_gap``      the widest gap between a returned distance and the
  exact distance of that (query, id), relative to the largest exact
  distance among that query's answers; the refine step re-ranks against
  the f32 store, so the program's distances are exact to f32 rounding;
* ``bad_ids``       returned ids outside the corpus, or repeated within
  one answer (SEIL scans a shared cell once, so no id comes twice);
* ``unanswered``    requests due in the window that never got an answer.

``LowPrecisionSearch`` is the reference computed one precision lower
(bfloat16 inputs), the step that would tempt a later change: put in the
program's place (``system.ControlSystem``) it has to come out not
correct.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHECK_NAMES = ("recall_at_10", "dist_gap", "bad_ids", "unanswered")


@functools.partial(jax.jit, static_argnames=("k", "metric", "low"))
def _topk_block(x, x2, q, *, k: int, metric: str, low: bool):
    if low:
        prod = jnp.matmul(q.astype(jnp.bfloat16), x.T,
                          preferred_element_type=jnp.float32)
    else:
        prod = jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)
    if metric == "l2":
        q2 = jnp.sum(q * q, axis=1, keepdims=True)
        d = q2 - 2.0 * prod + x2[None, :]
    else:
        d = -prod
    neg, idx = jax.lax.top_k(-d, k)
    return idx.astype(jnp.int32), -neg


def _sq_norms(x):
    return jnp.sum(x.astype(jnp.float32) ** 2, axis=1)


def exact_topk(x, q, k: int, metric: str, block: int = 256) -> np.ndarray:
    """Exact top-k ids of every query (full f32), (nq, k) int32."""
    x2 = jax.jit(_sq_norms)(x)
    out = []
    for s in range(0, q.shape[0], block):
        qb = q[s:s + block]
        if qb.shape[0] < block:          # one compiled block shape
            qb = jnp.pad(qb, ((0, block - qb.shape[0]), (0, 0)))
        ids, _ = _topk_block(x, x2, qb, k=k, metric=metric, low=False)
        out.append(np.asarray(ids)[:min(block, q.shape[0] - s)])
    return np.concatenate(out, axis=0)


class LowPrecisionSearch:
    """The control: brute force with bfloat16 inputs, returning its ids
    and the distances it computed.  ``search(q)`` takes a (B, d) batch."""

    def __init__(self, x, k: int, metric: str):
        self.x = jnp.asarray(x).astype(jnp.bfloat16)
        self.x2 = jax.jit(_sq_norms)(self.x)
        self.k = k
        self.metric = metric

    def search(self, q, block: int = 256):
        q = jnp.asarray(q, jnp.float32)
        ids, dists = [], []
        for s in range(0, q.shape[0], block):
            i, d = _topk_block(self.x, self.x2, q[s:s + block], k=self.k,
                               metric=self.metric, low=True)
            ids.append(i)
            dists.append(d)
        return jnp.concatenate(ids), jnp.concatenate(dists)


def exact_dists(x_host: np.ndarray, q_host: np.ndarray, ids: np.ndarray,
                metric: str, block: int = 4096) -> np.ndarray:
    """float64 distance of each (query row, returned id); ids (nq, k),
    entries outside [0, n) give NaN."""
    n = x_host.shape[0]
    out = np.full(ids.shape, np.nan, np.float64)
    for s in range(0, ids.shape[0], block):
        i = ids[s:s + block]
        ok = (i >= 0) & (i < n)
        v = x_host[np.where(ok, i, 0)].astype(np.float64)
        q = q_host[s:s + block, None, :].astype(np.float64)
        d = ((v - q) ** 2).sum(-1) if metric == "l2" else -(v * q).sum(-1)
        out[s:s + block] = np.where(ok, d, np.nan)
    return out


def compare(qidx: np.ndarray, ids: np.ndarray, dists: np.ndarray,
            gt: np.ndarray, x_host: np.ndarray, pool_host: np.ndarray,
            metric: str, n_unanswered: int, limits: dict) -> dict:
    """Judge every answer: qidx (A,) pool rows, ids/dists (A, k) what the
    timed path returned.  Returns {name: {value, limit, ok}}, in
    ``CHECK_NAMES`` order."""
    k = gt.shape[1]
    n = x_host.shape[0]
    if len(qidx):
        g = gt[qidx]
        hits = (ids[:, :, None] == g[:, None, :]).any(axis=1).sum(axis=1)
        recall = float(hits.mean() / k)
        ref = exact_dists(x_host, pool_host[qidx], ids, metric)
        scale = np.nanmax(np.abs(ref), axis=1, initial=0.0)[:, None]
        with np.errstate(invalid="ignore", divide="ignore"):
            gap = np.abs(dists.astype(np.float64) - ref) / scale
        valid = (ids >= 0) & (ids < n)
        gap = float(np.max(np.where(valid, gap, 0.0), initial=0.0))
        if not np.isfinite(gap):
            gap = float("inf")
        srt = np.sort(ids, axis=1)
        dup = np.concatenate([np.zeros((len(ids), 1), bool),
                              srt[:, 1:] == srt[:, :-1]], axis=1)
        bad = int((~valid).sum() + (dup & (srt >= 0)).sum())
    else:
        recall, gap, bad = 0.0, float("inf"), 0
    values = {"recall_at_10": recall, "dist_gap": gap, "bad_ids": bad,
              "unanswered": int(n_unanswered)}
    out = {}
    for name in CHECK_NAMES:
        lim = limits[name]
        v = values[name]
        ok = v >= lim if name == "recall_at_10" else v <= lim
        out[name] = {"value": v, "limit": lim, "ok": bool(ok)}
    return out
