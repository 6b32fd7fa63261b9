"""K-means clustering (Lloyd's) for IVF list training — jittable, chunkable.

Used for: IVF coarse centroids (nlist lists), PQ sub-codebooks, and the
KV-cache clustering of the RAIRS-kNN attention path.  The distributed
variant exposes one Lloyd step as a shard_map-compatible function with
psum'd sufficient statistics (classic data-parallel k-means).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def pairwise_sq_l2(x: jnp.ndarray, c: jnp.ndarray,
                   precision=None) -> jnp.ndarray:
    """Squared L2 distances ||x - c||^2, shapes (n, D) x (k, D) -> (n, k).

    ``precision`` goes to the ``x @ c.T`` matmul: the default is one
    bf16 pass on TPU; ``Precision.HIGHEST`` is full f32 there (exact
    references).  On CPU every precision computes the same f32 dot."""
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)          # (n, 1)
    c2 = jnp.sum(c * c, axis=-1)                          # (k,)
    xc = jnp.matmul(x, c.T, precision=precision)          # (n, k)
    return jnp.maximum(x2 - 2.0 * xc + c2[None, :], 0.0)


def assign_nearest(x: jnp.ndarray, c: jnp.ndarray, chunk: int = 16384) -> jnp.ndarray:
    """argmin_k ||x - c_k||^2, chunked over n to bound the (n,k) buffer."""
    n = x.shape[0]
    if n <= chunk:
        return jnp.argmin(pairwise_sq_l2(x, c), axis=-1).astype(jnp.int32)
    pad = (-n) % chunk
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    xs = xp.reshape(-1, chunk, x.shape[1])

    def body(_, xb):
        return None, jnp.argmin(pairwise_sq_l2(xb, c), axis=-1).astype(jnp.int32)

    _, out = jax.lax.scan(body, None, xs)
    return out.reshape(-1)[:n]


def _update_centroids(x, assign, k, old_c):
    sums = jax.ops.segment_sum(x, assign, num_segments=k)
    counts = jax.ops.segment_sum(jnp.ones((x.shape[0],), x.dtype), assign, num_segments=k)
    new_c = sums / jnp.maximum(counts, 1.0)[:, None]
    # keep empty clusters where they were (Faiss splits them; we freeze them)
    return jnp.where((counts > 0)[:, None], new_c, old_c), counts


@functools.partial(jax.jit, static_argnames=("k", "iters", "chunk"))
def _kmeans_loop(x, init_c, k, iters, chunk):
    def step(c, _):
        a = assign_nearest(x, c, chunk)
        c2, counts = _update_centroids(x, a, k, c)
        return c2, counts

    c, _ = jax.lax.scan(step, init_c, None, length=iters)
    return c


def kmeans_fit(
    key: jax.Array,
    x: jnp.ndarray,
    k: int,
    iters: int = 20,
    chunk: int = 16384,
    sample: Optional[int] = None,
) -> jnp.ndarray:
    """Fit k centroids.  Random-point init (Faiss default for IVF training)."""
    n = x.shape[0]
    if sample is not None and sample < n:
        idx = jax.random.choice(key, n, shape=(sample,), replace=False)
        xt = x[idx]
    else:
        xt = x
    perm = jax.random.permutation(key, xt.shape[0])[:k]
    init_c = xt[perm]
    return _kmeans_loop(xt, init_c, k, iters, chunk)


# ----------------------------------------------------------------------------
# Distributed Lloyd step (per-shard body; wrap in shard_map over the data axis)
# ----------------------------------------------------------------------------
def kmeans_step_sharded(x_local: jnp.ndarray, c: jnp.ndarray, *, axis_names) -> jnp.ndarray:
    """One Lloyd step where each device holds a shard of x.

    Must run inside shard_map with `axis_names` spanning the data axes;
    centroids replicated.  psum of (sums, counts) is the only collective.
    """
    a = assign_nearest(x_local, c)
    k = c.shape[0]
    sums = jax.ops.segment_sum(x_local, a, num_segments=k)
    counts = jax.ops.segment_sum(jnp.ones((x_local.shape[0],), x_local.dtype), a, num_segments=k)
    sums = jax.lax.psum(sums, axis_names)
    counts = jax.lax.psum(counts, axis_names)
    new_c = sums / jnp.maximum(counts, 1.0)[:, None]
    return jnp.where((counts > 0)[:, None], new_c, c)
