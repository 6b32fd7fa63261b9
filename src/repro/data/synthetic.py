"""Synthetic datasets statistically shaped like the paper's corpora.

The container is offline, so SIFT/GIST/MSong/OpenAI/T2I are replaced by
latent-manifold Gaussian mixtures: cluster structure in a low-dim latent
space (power-law mixture mass, anisotropic covariance) projected to the
ambient dimension plus small ambient noise.  This reproduces the two
properties that make the paper's setting meaningful and that uniform
data would destroy:

  * clusteredness — IVF lists, the Fig. 5 cell-size skew, AIR geometry;
  * low intrinsic dimension — real descriptors/embeddings concentrate
    near a manifold, which is what makes 4-bit PQ + refine reach high
    recall (on iid-dim data PQ error swamps NN distances and *no* IVF
    method reaches 0.9; calibrated in EXPERIMENTS.md §Datasets).

Queries are perturbed data points (in-distribution, as in SIFT/GIST);
the T2I stand-in (`modality_gap=True`) draws queries from a shifted
mixture sharing the projection, mimicking the text-vs-image gap, with
Zipf-ish data norms for inner-product skew.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class DatasetSpec:
    name: str
    n: int
    d: int
    n_queries: int
    n_components: int = 64
    latent: int = 24            # intrinsic dimension of the manifold
    zipf: float = 1.2           # power-law exponent of mixture weights
    spread: float = 0.35        # within-cluster sigma (latent space)
    query_noise: float = 1.0    # query perturbation scale
    metric: str = "l2"
    modality_gap: bool = False  # T2I-like: query distribution shifted


DATASETS = {
    # stand-ins mirroring paper Table 2 (scaled to 1-core CPU budget)
    "sift1m": DatasetSpec("sift1m", 100_000, 128, 2_000),
    "msong": DatasetSpec("msong", 60_000, 128, 1_000, n_components=48),
    "gist": DatasetSpec("gist", 50_000, 256, 1_000, n_components=48,
                        latent=32),
    "openai": DatasetSpec("openai", 60_000, 256, 1_000, n_components=96,
                          latent=40, zipf=1.0),
    "t2i": DatasetSpec("t2i", 80_000, 128, 2_000, metric="ip",
                       modality_gap=True),
    # SIFT1M at its published scale (1M x 128 base, L2; 1,000 of its
    # 10,000 queries) — the one-chip corpus of chip_smoke.py, generated
    # on the device
    "sift1m_full": DatasetSpec("sift1m_full", 1_000_000, 128, 1_000),
    # tiny configs for tests
    "unit": DatasetSpec("unit", 6_000, 32, 200, n_components=16, latent=12),
    "unit_ip": DatasetSpec("unit_ip", 6_000, 32, 200, n_components=16,
                           latent=12, metric="ip", modality_gap=True),
}


def _latent_mixture(key, n, k, latent, zipf, spread):
    kc, kw, kx, ka = jax.random.split(key, 4)
    centers = jax.random.normal(kc, (k, latent))
    w = 1.0 / jnp.arange(1, k + 1) ** zipf
    w = w / w.sum()
    comp = jax.random.choice(kw, k, shape=(n,), p=w)
    scales = jax.random.uniform(ka, (k, latent), minval=0.4, maxval=1.6) * spread
    z = centers[comp] + jax.random.normal(kx, (n, latent)) * scales[comp]
    return z


def make_dataset(name: str, seed: int = 0
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, DatasetSpec]:
    """Returns (data (n,D), queries (nq,D), spec)."""
    spec = DATASETS[name]
    # crc32, NOT hash(): the builtin is salted per process, which would
    # regenerate a different corpus on every run and break index
    # persistence (save in one process, serve from another).
    key = jax.random.PRNGKey(zlib.crc32(name.encode()) % (2 ** 31) + seed)
    kd, kq, kp, ks, kw, kn = jax.random.split(key, 6)
    z = _latent_mixture(kd, spec.n, spec.n_components, spec.latent,
                        spec.zipf, spec.spread)
    proj = jax.random.normal(kw, (spec.latent, spec.d)) / jnp.sqrt(spec.latent)
    # full-f32 projections: the same seed makes the same corpus on CPU
    # and TPU (a default-precision TPU matmul rounds through bf16)
    hi = jax.lax.Precision.HIGHEST
    x = (jnp.matmul(z, proj, precision=hi)
         + jax.random.normal(kn, (spec.n, spec.d)) * 0.02)
    if spec.modality_gap:
        zq = _latent_mixture(kq, spec.n_queries, spec.n_components,
                             spec.latent, spec.zipf, spec.spread * 1.3)
        shift = jax.random.normal(ks, (spec.latent,)) * 0.3
        q = jnp.matmul(zq + shift, proj, precision=hi)
        if spec.metric == "ip":  # Zipf-ish norms on data side (MIPS skew)
            norms = 1.0 + jax.random.gamma(kp, 2.0, (spec.n, 1)) * 0.3
            x = x * norms
    else:
        base = jax.random.choice(kp, spec.n, shape=(spec.n_queries,))
        scale = spec.spread * spec.query_noise / jnp.sqrt(spec.d / spec.latent)
        q = x[base] + jax.random.normal(kq, (spec.n_queries, spec.d)) * scale
    return x.astype(jnp.float32), q.astype(jnp.float32), spec
