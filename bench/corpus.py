"""Seeded corpus and query pool, generated on the device in one call.

A copy of the latent-mixture generator of ``repro/data/synthetic.py``
(kept with the benchmark so that no later change to the program can
change the yardstick), parametrised by a configuration file:

* base vectors: a power-law Gaussian mixture in a low-dimensional latent
  space, projected to the ambient width at full f32 precision, plus a
  little ambient noise;
* in-distribution queries (``modality_gap: false``): perturbed base
  vectors, as SIFT1M's queries are;
* out-of-distribution queries (``modality_gap: true``): a shifted, wider
  mixture through the same projection, as text queries against image
  vectors in Text-to-Image; under ``metric: ip`` the base vectors get
  gamma-distributed norms (the skew inner-product search sees).

The configuration's ``corpus_seed`` fixes the base vectors, in one order,
and the set of queries: a deployment holds one corpus, and the index the
program builds over it has the same layout, and so runs the same
compiled programs, in every run.  ``--seed`` draws the order of the
query pool, which is the order the traffic sends the queries in.  Any
whole ``--seed`` (negative or wider than 64 bits included) maps to a
32-bit key through ``numpy.random.SeedSequence``.
"""
from __future__ import annotations

import dataclasses
import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class CorpusSpec:
    n: int
    d: int
    n_queries: int
    metric: str = "l2"
    n_components: int = 64
    latent: int = 24
    zipf: float = 1.2
    spread: float = 0.35
    query_noise: float = 1.0
    modality_gap: bool = False

    @classmethod
    def from_config(cls, cfg: dict) -> "CorpusSpec":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: cfg[k] for k in fields if k in cfg})


def seed32(seed: int, tag: str) -> int:
    """A 32-bit key for (seed, tag); the same pair always gives the same."""
    ss = np.random.SeedSequence([int(seed) % (1 << 64), zlib.crc32(tag.encode())])
    return int(ss.generate_state(1)[0])


def _latent_mixture(key, n, k, latent, zipf, spread):
    kc, kw, kx, ka = jax.random.split(key, 4)
    centers = jax.random.normal(kc, (k, latent))
    w = 1.0 / jnp.arange(1, k + 1) ** zipf
    w = w / w.sum()
    comp = jax.random.choice(kw, k, shape=(n,), p=w)
    scales = jax.random.uniform(ka, (k, latent), minval=0.4,
                                maxval=1.6) * spread
    return centers[comp] + jax.random.normal(kx, (n, latent)) * scales[comp]


@functools.partial(jax.jit, static_argnames=("spec",))
def _generate(key, order_key, spec: CorpusSpec):
    kd, kq, kp, ks, kw, kn = jax.random.split(key, 6)
    z = _latent_mixture(kd, spec.n, spec.n_components, spec.latent,
                        spec.zipf, spec.spread)
    proj = jax.random.normal(kw, (spec.latent, spec.d)) / jnp.sqrt(spec.latent)
    hi = jax.lax.Precision.HIGHEST
    x = (jnp.matmul(z, proj, precision=hi)
         + jax.random.normal(kn, (spec.n, spec.d)) * 0.02)
    if spec.modality_gap:
        zq = _latent_mixture(kq, spec.n_queries, spec.n_components,
                             spec.latent, spec.zipf, spec.spread * 1.3)
        shift = jax.random.normal(ks, (spec.latent,)) * 0.3
        q = jnp.matmul(zq + shift, proj, precision=hi)
        if spec.metric == "ip":
            norms = 1.0 + jax.random.gamma(kp, 2.0, (spec.n, 1)) * 0.3
            x = x * norms
    else:
        base = jax.random.choice(kp, spec.n, shape=(spec.n_queries,))
        scale = spec.spread * spec.query_noise / jnp.sqrt(spec.d / spec.latent)
        q = x[base] + jax.random.normal(kq, (spec.n_queries, spec.d)) * scale
    q = q[jax.random.permutation(order_key, spec.n_queries)]
    return x.astype(jnp.float32), q.astype(jnp.float32)


def make_corpus(cfg: dict, seed: int):
    """(base (n, d) f32, query pool (n_queries, d) f32) on the default
    device, from one jitted call: the configuration's corpus, with the
    query pool in the seed's order."""
    spec = CorpusSpec.from_config(cfg)
    tag = "corpus/" + cfg["name"]
    key = jax.random.PRNGKey(seed32(cfg.get("corpus_seed", 0), tag))
    order_key = jax.random.PRNGKey(seed32(seed, "order/" + cfg["name"]))
    return _generate(key, order_key, spec)
