"""Bitonic partial-sort primitives for the fused scan->top-k kernel.

The fused kernel (pq_scan.py::pq_scan_topk_kernel) keeps a per-query
top-``F`` candidate accumulator resident in VMEM across grid steps, so
it needs a selection network built from vector ops only — no gathers,
no data-dependent control flow, nothing Mosaic cannot lower.  Every
routine here is a compare-exchange network over the *trailing* (lane)
axis of a (distance, position, id) triple:

  * keys are lexicographic ``(d, pos)`` ascending — ``pos`` is the flat
    plan-layout position ``slot * BLK + lane`` of a candidate, which is
    exactly the tie-break order of ``jax.lax.top_k`` over the unfused
    candidate stream (``preselect_candidates``' stability contract), so
    a merge network over these keys reproduces the unfused selection
    *bitwise*, ties included;
  * masked/padding entries carry ``(+inf, BIG, -1)``; with pos unique
    among real candidates the key is a total order, so the network
    needs no stability of its own;
  * a compare-exchange at distance ``g`` pairs lane ``i`` with lane
    ``i ^ g`` through two lane rotations (``pltpu.roll``) and
    ``jnp.where`` selects — no reshape or reversal of the lane axis,
    neither of which the TPU lowering supports.

The same functions run inside the Pallas kernel body (interpret mode on
CPU, Mosaic on TPU) and in the pure-jnp oracle, so kernel and reference
can never diverge on the network itself.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

# lane rotation; jitted so the network also runs eagerly outside a kernel
_roll = jax.jit(pltpu.roll, static_argnames=("shift", "axis"))

# padding position for masked candidates — matches engine BIG
# (core/engine/types.py) without importing across the package boundary.
PAD_POS = 2 ** 30


def pow2_ceil(n: int) -> int:
    """Smallest power of two >= n (network widths must be powers of 2)."""
    p = 1
    while p < n:
        p *= 2
    return p


def _lex_le(ad, ap, bd, bp):
    """a precedes-or-equals b under the ascending (d, pos) lex key."""
    return (ad < bd) | ((ad == bd) & (ap <= bp))


def _partners(arrs: Sequence[jnp.ndarray], g: int) -> List[jnp.ndarray]:
    """Each lane's partner ``i ^ g`` along the trailing axis.  Built from
    the two rotations by ``g`` and the rotation direction is read off a
    rotated lane iota, so the result does not depend on which way the
    backend's rotate turns."""
    ax = arrs[0].ndim - 1
    n = arrs[0].shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, arrs[0].shape, ax)
    fwd = _roll(lane, shift=g, axis=ax) == (lane ^ g)
    return [jnp.where(fwd, _roll(x, shift=g, axis=ax),
                      _roll(x, shift=n - g, axis=ax)) for x in arrs]


def _compare_exchange(arrs: Sequence[jnp.ndarray], kk: int, j: int,
                      descending: bool = False) -> List[jnp.ndarray]:
    """One bitonic substage: exchange at distance 2^j inside 2^kk blocks.

    arrs: [d, pos, ...] arrays of shape (..., n); the first two are the
    sort key, the rest ride along.  Block direction alternates with the
    block index (the standard bitonic schedule): ascending iff bit kk of
    the lane index is 0 — every direction flipped when ``descending``.
    Lane ``i`` keeps its own entry iff it belongs on its side of the
    pair: the pair's low lane takes the lesser key when ascending.
    """
    g = 1 << j
    lane = jax.lax.broadcasted_iota(jnp.int32, arrs[0].shape,
                                    arrs[0].ndim - 1)
    low = (lane & g) == 0
    part = _partners(arrs, g)
    a_d = jnp.where(low, arrs[0], part[0])
    a_p = jnp.where(low, arrs[1], part[1])
    b_d = jnp.where(low, part[0], arrs[0])
    b_p = jnp.where(low, part[1], arrs[1])
    asc = ((lane >> kk) & 1) == 0
    if descending:
        asc = ~asc
    keep_own = _lex_le(a_d, a_p, b_d, b_p) == asc
    return [jnp.where(keep_own, x, p) for x, p in zip(arrs, part)]


def bitonic_sort(arrs: Sequence[jnp.ndarray], descending: bool = False
                 ) -> List[jnp.ndarray]:
    """Full sort of (..., n) triples by the (d, pos) lex key, ascending
    (or descending).  n must be a power of two;
    log2(n)*(log2(n)+1)/2 substages."""
    n = arrs[0].shape[-1]
    logn = n.bit_length() - 1
    assert 1 << logn == n, f"bitonic_sort needs a power-of-two width, got {n}"
    arrs = list(arrs)
    for kk in range(1, logn + 1):
        for j in range(kk - 1, -1, -1):
            arrs = _compare_exchange(arrs, kk, j, descending)
    return arrs


def bitonic_merge(arrs: Sequence[jnp.ndarray]) -> List[jnp.ndarray]:
    """Sort a *bitonic* (..., n) sequence ascending — log2(n) substages."""
    n = arrs[0].shape[-1]
    logn = n.bit_length() - 1
    assert 1 << logn == n, f"bitonic_merge needs a power-of-two width, got {n}"
    arrs = list(arrs)
    for j in range(logn - 1, -1, -1):
        arrs = _compare_exchange(arrs, logn, j)
    return arrs


def merge_topf(acc: Sequence[jnp.ndarray], new: Sequence[jnp.ndarray]
               ) -> List[jnp.ndarray]:
    """Merge an ascending (..., F) triple with a *descending* one into
    the top-F of their union (ascending).  ``concat(acc, new)`` is
    bitonic, and the first half-cleaner of its merge leaves the F
    smallest in the low half — the lane-wise lesser of ``acc[i]`` and
    ``new[i]`` — which stays bitonic, so one log2(F)-stage merge sorts
    the survivors.  This is the per-grid-step accumulator update of the
    fused kernel: O(F log F) compares, no HBM round-trip."""
    take_acc = _lex_le(acc[0], acc[1], new[0], new[1])
    low = [jnp.where(take_acc, a, x) for a, x in zip(acc, new)]
    return bitonic_merge(low)
