"""Distributed runtime layer: logical-axis sharding rules, host
checkpointing with retention, and elastic mesh replanning.

Also home of ``shard_map``: ``jax.shard_map`` with replication
checking (``check_vma``) off by default, as every caller here wants.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, **kw):
    kw.setdefault("check_vma", False)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)
