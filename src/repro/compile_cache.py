"""JAX's persistent compilation cache, set once by each entry point.

``enable()`` is called from ``main`` of ``chip_smoke.py`` and
``repro.launch.serve``, and by the benchmark's ``bench/harness.py`` —
never at import of a library module.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it from the environment
and this sets no other directory.  Otherwise the cache lives at the
fixed ``<checkout>/.jax_cache``: the directory is part of the cache
key, so it is never derived from a temp dir, a PID or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
