"""Recall / DCO metrics and ground-truth computation (paper §6.1)."""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from .kmeans import pairwise_sq_l2


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _gt_chunk(x, q, k, metric):
    # the exact reference is exact on every backend: full-f32 matmul
    hi = jax.lax.Precision.HIGHEST
    d = (pairwise_sq_l2(q, x, precision=hi) if metric == "l2"
         else -jnp.matmul(q, x.T, precision=hi))
    _, idx = jax.lax.top_k(-d, k)
    return idx.astype(jnp.int32)


def ground_truth(x: jnp.ndarray, q: jnp.ndarray, k: int,
                 metric: str = "l2", chunk: int = 256) -> np.ndarray:
    """Exact top-k ids by brute force, chunked over queries."""
    outs = []
    for s in range(0, q.shape[0], chunk):
        outs.append(np.asarray(_gt_chunk(x, q[s:s + chunk], k, metric)))
    return np.concatenate(outs, axis=0)


def recall_at_k(result_ids: np.ndarray, gt_ids: np.ndarray) -> float:
    """Average |result ∩ gt| / K (paper's recall k@K)."""
    r = np.asarray(result_ids)
    g = np.asarray(gt_ids)
    k = g.shape[1]
    hits = (r[:, :, None] == g[:, None, :]).any(axis=1).sum(axis=1)
    return float(hits.mean() / k)


def per_query_recall(result_ids: np.ndarray, gt_ids: np.ndarray) -> np.ndarray:
    r, g = np.asarray(result_ids), np.asarray(gt_ids)
    return (r[:, :, None] == g[:, None, :]).any(axis=1).sum(axis=1) / g.shape[1]


def dco_summary(res) -> Dict[str, float]:
    a = np.asarray(res.approx_dco, np.float64)
    r = np.asarray(res.refine_dco, np.float64)
    return {
        "approx_dco": float(a.mean()),
        "refine_dco": float(r.mean()),
        "total_dco": float((a + r).mean()),
        "p99_dco": float(np.percentile(a + r, 99)),
        "dropped_blocks": float(np.asarray(res.dropped_blocks).mean()),
    }
