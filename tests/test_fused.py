"""Fused scan -> top-k parity (engine/fused.py + kernels/topk.py).

The contract under test: ``scan_blocks_topk`` — oracle or Pallas kernel
— returns bitwise the stable ``preselect_candidates`` selection over
``scan_blocks``' unfused candidate stream (ties broken by flat plan
position, masked entries normalized to ``(+inf, -1)``), with logical
DCO accounting unchanged.  Covered across exec modes, tombstones,
synthetic adversarial plans (duplicate distances, duplicate ids, dead
items), and end-to-end through the frozen / streaming / sharded
pipelines.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_stub import given, settings, st

from repro.core import IndexConfig, build_index
from repro.core.engine import (BlockStore, QueryPlan, preselect_candidates,
                               scan_blocks, scan_blocks_topk)
from repro.core.params import SearchParams
from repro.core.search import seil_search
from repro.kernels.topk import PAD_POS, bitonic_sort, merge_topf, pow2_ceil

EXEC_MODES = ("paged", "grouped", "clustered")


# ---------------------------------------------------------------------------
# kernels/topk.py primitives vs numpy lexsort ground truth
# ---------------------------------------------------------------------------

def _lexsorted(d, p, i):
    """Ascending by (d, p) — np ground truth for the bitonic networks."""
    order = np.lexsort((p, d), axis=-1)
    return (np.take_along_axis(d, order, -1),
            np.take_along_axis(p, order, -1),
            np.take_along_axis(i, order, -1))


@pytest.mark.parametrize("n", [2, 8, 32, 128])
def test_bitonic_sort_matches_lexsort(n):
    rng = np.random.default_rng(n)
    # few distinct distances -> plenty of exact ties for the pos key
    d = rng.integers(0, 5, (3, n)).astype(np.float32)
    d[0, : n // 2] = np.inf                       # masked entries sort last
    p = rng.permutation(n)[None, :].repeat(3, 0).astype(np.int32)
    i = rng.integers(-1, 50, (3, n)).astype(np.int32)
    out = bitonic_sort([jnp.asarray(d), jnp.asarray(p), jnp.asarray(i)])
    ref = _lexsorted(d, p, i)
    for o, r in zip(out, ref):
        np.testing.assert_array_equal(np.asarray(o), r)
    # the descending network is the exact mirror (keys are a total order)
    out = bitonic_sort([jnp.asarray(d), jnp.asarray(p), jnp.asarray(i)],
                       descending=True)
    for o, r in zip(out, ref):
        np.testing.assert_array_equal(np.asarray(o), r[:, ::-1])


@pytest.mark.parametrize("f,blocks", [(4, 7), (16, 5), (64, 3)])
def test_merge_topf_accumulates_global_topf(f, blocks):
    """Feeding descending-sorted width-f chunks through merge_topf must
    equal the top-f of the concatenated stream under the same (d, pos)
    order."""
    rng = np.random.default_rng(f * 31 + blocks)
    all_d, all_p, all_i = [], [], []
    acc = [jnp.full((2, f), np.inf, jnp.float32),
           jnp.full((2, f), PAD_POS, jnp.int32),
           jnp.full((2, f), -1, jnp.int32)]
    for step in range(blocks):
        d = rng.integers(0, 4, (2, f)).astype(np.float32)
        p = (np.arange(f)[None, :] + step * f).astype(np.int32)
        p = np.broadcast_to(p, (2, f)).copy()
        i = rng.integers(0, 30, (2, f)).astype(np.int32)
        all_d.append(d), all_p.append(p), all_i.append(i)
        new = bitonic_sort([jnp.asarray(d), jnp.asarray(p), jnp.asarray(i)],
                           descending=True)
        acc = merge_topf(acc, new)
    ref = _lexsorted(np.concatenate(all_d, -1), np.concatenate(all_p, -1),
                     np.concatenate(all_i, -1))
    for o, r in zip(acc, ref):
        np.testing.assert_array_equal(np.asarray(o), r[:, :f])


def test_pow2_ceil():
    assert [pow2_ceil(n) for n in (1, 2, 3, 5, 8, 9, 64)] == \
        [1, 2, 4, 8, 8, 16, 64]


# ---------------------------------------------------------------------------
# satellite: pq_scan_paged_kernel tile-row invariant fails loudly
# ---------------------------------------------------------------------------

def test_paged_kernel_tile_row_invariant():
    from jax.experimental import checkify

    from repro.kernels.pq_scan import pq_scan_paged_kernel
    rng = np.random.default_rng(3)
    lut = jnp.asarray(rng.standard_normal((4, 4, 16)).astype(np.float32))
    codes = jnp.asarray(rng.integers(0, 16, (6, 8, 4)).astype(np.uint8))
    per_query = jnp.asarray(rng.integers(0, 6, (4, 3)).astype(np.int32))
    shared = jnp.repeat(per_query[::2], 2, axis=0)     # rows agree per tile

    # tile-shared rows: allowed, and row 0's list is really what's scored.
    # A 2-row and a 1-row contraction may round differently in the last
    # ulp, so they agree to the dot_general tolerance of the kernel tests
    out = pq_scan_paged_kernel(lut, codes, shared, query_tile=2,
                               interpret=True)
    ref = pq_scan_paged_kernel(lut, codes, shared, query_tile=1,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    # eager misuse raises instead of silently scoring the wrong blocks
    with pytest.raises(ValueError, match="tile rows"):
        pq_scan_paged_kernel(lut, codes, per_query, query_tile=2,
                             interpret=True)

    # traced misuse is checkable via debug=True + checkify
    def run(bi):
        return pq_scan_paged_kernel(lut, codes, bi, query_tile=2,
                                    interpret=True, debug=True)

    err, _ = jax.jit(checkify.checkify(run))(per_query)
    with pytest.raises(Exception, match="tile rows"):
        err.throw()
    err, _ = jax.jit(checkify.checkify(run))(shared)
    err.throw()                                        # no error when shared


# ---------------------------------------------------------------------------
# engine-level parity on adversarial synthetic plans
# ---------------------------------------------------------------------------

def _synth(seed, *, b=8, s=5, tb=12, blk=32, m=4, k=16, nlist=10, nid=200,
           tie_heavy=False):
    """A consistent (store, plan, lut, rank_of, sel, live) with duplicate
    ids, invalid items, misc co-assignments, and (optionally) integer
    luts so exact distance ties are everywhere."""
    rng = np.random.default_rng(seed)
    if tie_heavy:
        lut = rng.integers(0, 3, (b, m, k)).astype(np.float32)
    else:
        lut = rng.standard_normal((b, m, k)).astype(np.float32)
    codes = rng.integers(0, k, (tb, blk, m)).astype(np.uint8)
    ids = rng.integers(-1, nid, (tb, blk)).astype(np.int32)
    other = rng.integers(-1, nlist, (tb, blk)).astype(np.int32)
    # SEIL plans are per-query duplicate-free among valid slots
    blocks = np.stack([rng.choice(tb, s, replace=False)
                       for _ in range(b)]).astype(np.int32)
    ranks = np.sort(rng.integers(0, nlist, (b, s)), axis=1).astype(np.int32)
    valid = rng.random((b, s)) < 0.85
    rank_of = np.where(rng.random((b, nlist)) < 0.5,
                       rng.integers(0, nlist, (b, nlist)),
                       2 ** 30).astype(np.int32)
    sel = np.sort(rng.choice(nlist, (b, 3), replace=True), 1).astype(np.int32)
    live = jnp.asarray(rng.random(nid) < 0.8)
    store = BlockStore(jnp.asarray(codes), jnp.asarray(ids),
                       jnp.asarray(other))
    plan = QueryPlan(jnp.asarray(blocks), jnp.asarray(ranks),
                     jnp.asarray(valid), jnp.zeros(b, jnp.int32))
    return store, plan, jnp.asarray(lut), jnp.asarray(rank_of), \
        jnp.asarray(sel), live


def _unfused_reference(store, plan, lut, rank_of, sel, live, fetch,
                       exec_mode, use_kernel=False):
    """scan_blocks + live mask + stable preselect — the ground truth the
    fused stage must reproduce bitwise.  ``use_kernel`` must match the
    fused side so both streams carry the same ADC rounding (one-hot
    dot_general vs gather-sum differ in the last ulp)."""
    out = scan_blocks(store, plan, lut, rank_of, exec_mode=exec_mode,
                      sel=sel, use_kernel=use_kernel, query_tile=4)
    d = out.flat_d
    if live is not None:
        dead = (out.flat_i >= 0) & ~live[jnp.maximum(out.flat_i, 0)]
        d = jnp.where(dead, jnp.inf, d)
    ids = jnp.where(jnp.isfinite(d), out.flat_i, -1)
    cd, ci = preselect_candidates(d, ids, fetch=fetch)
    return cd, ci, out.approx_dco


@pytest.mark.parametrize("exec_mode", EXEC_MODES)
@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("with_live", [False, True])
def test_scan_blocks_topk_matches_preselect(exec_mode, use_kernel,
                                            with_live):
    store, plan, lut, rank_of, sel, live = _synth(
        17 + hash(exec_mode) % 100, tie_heavy=True)
    live = live if with_live else None
    fetch = 16
    ref_d, ref_i, ref_dco = _unfused_reference(
        store, plan, lut, rank_of, sel, live, fetch, exec_mode,
        use_kernel=use_kernel)
    out = scan_blocks_topk(store, plan, lut, rank_of, fetch=fetch,
                           exec_mode=exec_mode, use_kernel=use_kernel,
                           query_tile=4, sel=sel, live=live)
    np.testing.assert_array_equal(np.asarray(out.flat_d), np.asarray(ref_d))
    np.testing.assert_array_equal(np.asarray(out.flat_i), np.asarray(ref_i))
    np.testing.assert_array_equal(np.asarray(out.approx_dco),
                                  np.asarray(ref_dco))


def test_scan_blocks_topk_fetch_clamped_to_stream():
    """fetch beyond the unfused stream width degrades to a full stable
    sort of the stream — never an error, never a dropped candidate."""
    store, plan, lut, rank_of, sel, live = _synth(5, s=2, blk=8)
    wide = 999
    out = scan_blocks_topk(store, plan, lut, rank_of, fetch=wide,
                           exec_mode="paged", use_kernel=True, query_tile=1)
    s, blk = plan.blocks.shape[1], store.block_codes.shape[1]
    assert out.flat_d.shape == (plan.blocks.shape[0], s * blk)
    ref_d, ref_i, _ = _unfused_reference(store, plan, lut, rank_of, None,
                                         None, s * blk, "paged",
                                         use_kernel=True)
    np.testing.assert_array_equal(np.asarray(out.flat_d), np.asarray(ref_d))
    np.testing.assert_array_equal(np.asarray(out.flat_i), np.asarray(ref_i))


# satellite: hypothesis property — fused candidate order equals the
# stable preselect over the unfused stream for random plans, duplicate
# distances/ids, and tombstones, in both fused implementations.
@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 10 ** 6), exec_mode=st.sampled_from(EXEC_MODES),
       blk=st.sampled_from([8, 32]), s=st.integers(1, 6),
       fetch=st.sampled_from([1, 8, 24]), use_kernel=st.booleans(),
       with_live=st.booleans())
def test_property_fused_topk_order(seed, exec_mode, blk, s, fetch,
                                   use_kernel, with_live):
    store, plan, lut, rank_of, sel, live = _synth(
        seed, s=s, blk=blk, tie_heavy=True)
    live = live if with_live else None
    ref_d, ref_i, ref_dco = _unfused_reference(
        store, plan, lut, rank_of, sel, live,
        min(fetch, s * blk), exec_mode, use_kernel=use_kernel)
    out = scan_blocks_topk(store, plan, lut, rank_of, fetch=fetch,
                           exec_mode=exec_mode, use_kernel=use_kernel,
                           query_tile=4, sel=sel, live=live)
    np.testing.assert_array_equal(np.asarray(out.flat_d), np.asarray(ref_d))
    np.testing.assert_array_equal(np.asarray(out.flat_i), np.asarray(ref_i))
    np.testing.assert_array_equal(np.asarray(out.approx_dco),
                                  np.asarray(ref_dco))


# ---------------------------------------------------------------------------
# end-to-end: frozen / streaming / sharded pipelines, fused == unfused
# ---------------------------------------------------------------------------

def _assert_results_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))
    np.testing.assert_array_equal(np.asarray(a.approx_dco),
                                  np.asarray(b.approx_dco))
    np.testing.assert_array_equal(np.asarray(a.refine_dco),
                                  np.asarray(b.refine_dco))


@pytest.mark.parametrize("exec_mode", EXEC_MODES)
@pytest.mark.parametrize("use_kernel", [False, True])
def test_seil_search_fused_parity(rairs_index, unit_data, exec_mode,
                                  use_kernel):
    _, q, _ = unit_data
    idx = rairs_index
    kw = dict(nprobe=8, bigk=32, k=10, max_scan=idx.default_max_scan(8),
              dedup_results=idx.needs_result_dedup,
              oversample=idx.result_oversample, exec_mode=exec_mode,
              query_tile=4)
    base = seil_search(idx.arrays, idx.centroids, idx.codebook, idx.vectors,
                       q[:16], use_kernel=use_kernel, **kw)
    fused = seil_search(idx.arrays, idx.centroids, idx.codebook, idx.vectors,
                        q[:16], use_kernel=use_kernel, fused_topk=True, **kw)
    _assert_results_equal(fused, base)


@pytest.mark.parametrize("exec_mode", EXEC_MODES)
def test_streaming_fused_parity(rairs_index, unit_data, exec_mode):
    from repro.core.stream import StreamingIndex
    x, q, _ = unit_data
    rng = np.random.default_rng(11)
    st_idx = StreamingIndex(rairs_index)
    st_idx.insert(jnp.asarray(
        rng.standard_normal((37, x.shape[1])).astype(np.float32)))
    st_idx.delete(jnp.arange(0, 60, 5, dtype=jnp.int32))
    for uk in (False, True):
        base = st_idx.searcher(SearchParams(
            k=10, nprobe=8, exec_mode=exec_mode, query_tile=4,
            use_kernel=uk))(q[:16])
        fused = st_idx.searcher(SearchParams(
            k=10, nprobe=8, exec_mode=exec_mode, query_tile=4,
            use_kernel=uk, fused_topk=True))(q[:16])
        _assert_results_equal(fused, base)


def test_streaming_fused_parity_plan_reuse(rairs_index, unit_data):
    from repro.core.stream import StreamingIndex
    x, q, _ = unit_data
    rng = np.random.default_rng(13)
    st_idx = StreamingIndex(rairs_index)
    st_idx.insert(jnp.asarray(
        rng.standard_normal((21, x.shape[1])).astype(np.float32)))
    st_idx.delete(jnp.arange(0, 40, 7, dtype=jnp.int32))
    base = st_idx.searcher(SearchParams(
        k=10, nprobe=8, exec_mode="clustered", query_tile=4,
        plan_reuse=True, use_kernel=True))
    fused = st_idx.searcher(SearchParams(
        k=10, nprobe=8, exec_mode="clustered", query_tile=4,
        plan_reuse=True, use_kernel=True, fused_topk=True))
    for lo in (0, 8):                     # second batch hits the plan cache
        _assert_results_equal(fused(q[lo:lo + 8]), base(q[lo:lo + 8]))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_sharded_fused_parity(rairs_index, unit_data, use_kernel):
    """Mesh sessions now run the (interpret-mode) kernel path too: the
    fused per-device top-fetch replaces the preselect before the gather."""
    _, q, _ = unit_data
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    sh = rairs_index.shard(mesh)
    base = sh.searcher(SearchParams(k=10, nprobe=8, exec_mode="grouped",
                                    query_tile=4,
                                    use_kernel=use_kernel))(q[:16])
    fused = sh.searcher(SearchParams(k=10, nprobe=8, exec_mode="grouped",
                                     query_tile=4, use_kernel=use_kernel,
                                     fused_topk=True))(q[:16])
    _assert_results_equal(fused, base)
