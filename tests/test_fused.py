"""Scan stage -> top-k parity (engine/fused.py + kernels/topk.py).

The contract under test: ``scan_blocks_topk`` — oracle or Pallas kernel
— returns bitwise the stable ``preselect_candidates`` selection over
the full candidate stream (ties broken by flat plan position, masked
entries normalized to ``(+inf, -1)``), with logical DCO accounting
unchanged.  The stream is ``scan_blocks``' (the jnp scorer) or, for the
kernel, the kernel's own full-width stream (``scan_stream``) under
``scan_blocks``' masks, so both sides carry the same rounding.  Covered
across exec modes, tombstones, synthetic adversarial plans (duplicate
distances, duplicate ids, dead items), and end to end through
``seil_search`` against an in-test stage composition.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_stub import given, settings, st

from repro.core.engine import (BIG, BlockStore, QueryPlan, batch_union,
                               cluster_order, finalize_candidates, fit_tile,
                               plan_blocks, preselect_candidates, scan_blocks,
                               scan_blocks_topk, select_lists,
                               store_from_arrays, tables_from_arrays,
                               tile_unions, union_dims)
from repro.core.pq import pq_lut
from repro.core.search import SearchResult, finalize_fetch, seil_search
from repro.kernels.topk import PAD_POS, bitonic_sort, merge_topf, pow2_ceil
from repro.quant.nibbles import pack_nibbles
from scan_stream import kernel_stream

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
# engine-level parity on adversarial synthetic plans
# ---------------------------------------------------------------------------

def _synth(seed, *, b=8, s=5, tb=12, blk=32, m=4, k=16, nlist=10, nid=200,
           tie_heavy=False, ip=False):
    """A consistent (store, plan, lut, rank_of, sel, live) with duplicate
    ids, invalid items, misc co-assignments, and (optionally) integer
    luts so exact distance ties are everywhere, or inner-product tables
    (``pq_lut_ip``: negative entries, 2-dim subspaces)."""
    rng = np.random.default_rng(seed)
    if ip:
        from repro.core.pq import PQCodebook, pq_lut_ip
        books = rng.standard_normal((m, k, 2)).astype(np.float32)
        q = rng.standard_normal((b, 2 * m)).astype(np.float32)
        lut = np.asarray(pq_lut_ip(PQCodebook(jnp.asarray(books)),
                                   jnp.asarray(q)))
    elif tie_heavy:
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


def _kernel_dists(store, plan, lut, sel, exec_mode, query_tile, packed):
    """(B, S, BLK) plan-layout distances scored by the kernel, paged
    exactly as the fused stage pages them (engine/fused.py): every
    query its own list, the batch union per query tile, or per-tile
    unions in cluster order — so the rounding is the fused stage's."""
    b, s = plan.blocks.shape
    codes = store.block_codes
    if exec_mode == "paged":
        return kernel_stream(lut, codes, plan.blocks, packed=packed)
    if exec_mode == "grouped":
        qt = fit_tile(b, query_tile)
        union = batch_union(plan, codes.shape[0])
        tile_idx = jnp.broadcast_to(jnp.where(union < BIG, union, 0)[None],
                                    (b // qt, union.shape[0]))
        d_u = kernel_stream(lut, codes, tile_idx, query_tile=qt,
                            packed=packed)
        pos = jnp.searchsorted(union, plan.blocks.reshape(-1))
        pos = jnp.minimum(pos.reshape(b, s), union.shape[0] - 1)
        return jnp.take_along_axis(d_u, pos[:, :, None], axis=1)
    perm = cluster_order(sel)
    pb = plan.blocks[perm]
    t, w = union_dims(b, s, codes.shape[0], "clustered", query_tile)
    unions = tile_unions(pb, plan.valid[perm], t, w)
    qt = b // t
    d_u = kernel_stream(lut[perm], codes, jnp.where(unions < BIG, unions, 0),
                        query_tile=qt, packed=packed)
    pos = jax.vmap(jnp.searchsorted)(unions, pb.reshape(t, qt * s))
    pos = jnp.minimum(pos.reshape(b, s), w - 1)
    d_p = jnp.take_along_axis(d_u, pos[:, :, None], axis=1)
    return d_p[jnp.argsort(perm)]


def _unfused_reference(store, plan, lut, rank_of, sel, live, fetch,
                       exec_mode, use_kernel=False, query_tile=4,
                       packed=False):
    """Full candidate stream + live mask + stable preselect — the
    ground truth the scan stage must reproduce bitwise.  The stream is
    ``scan_blocks``' (masks and DCO always, distances without the
    kernel); with ``use_kernel`` its kept distances are the kernel's
    own (``_kernel_dists``), so both sides carry the same ADC rounding
    (one-hot dot_general vs gather-sum differ in the last ulp)."""
    out = scan_blocks(store, plan, lut, rank_of, exec_mode=exec_mode,
                      sel=sel, query_tile=query_tile, packed=packed)
    d = out.flat_d
    if use_kernel:
        kd = _kernel_dists(store, plan, lut, sel, exec_mode, query_tile,
                           packed).reshape(d.shape)
        d = jnp.where(jnp.isfinite(d), kd, jnp.inf)
    if live is not None:
        dead = (out.flat_i >= 0) & ~live[jnp.maximum(out.flat_i, 0)]
        d = jnp.where(dead, jnp.inf, d)
    ids = jnp.where(jnp.isfinite(d), out.flat_i, -1)
    cd, ci = preselect_candidates(d, ids, fetch=fetch)
    return cd, ci, out.approx_dco


@pytest.mark.parametrize("exec_mode", EXEC_MODES)
@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("with_live", [False, True])
@pytest.mark.parametrize("fetch,ip", [(16, False), (512, True), (1000, True)])
def test_scan_blocks_topk_matches_preselect(exec_mode, use_kernel,
                                            with_live, fetch, ip):
    """Tie-heavy tables at fetch 16 (F 16), and inner-product tables at
    fetch 512 and 1000 (F 512 and 1024: the t2i1m deployment's
    accumulator at k_factor 50 and 100) over a 40-block plan wider than
    the fetch."""
    wide = {} if fetch == 16 else {"s": 40, "tb": 48}
    store, plan, lut, rank_of, sel, live = _synth(
        17 + hash(exec_mode) % 100, tie_heavy=not ip, ip=ip, **wide)
    live = live if with_live else None
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
# distances/ids, and tombstones, in both fused implementations.  The
# kernels page G = 128 // BLK scan positions per grid step (one at BLK
# 128), so the (BLK, S) cases cover S not a multiple of G, down to one
# padded step: padding positions must neither add candidates nor count
# in approx_dco.
@pytest.mark.parametrize("blk,s", [(8, 5), (32, 1), (32, 5), (32, 7),
                                   (128, 3)])
@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 10 ** 6), exec_mode=st.sampled_from(EXEC_MODES),
       fetch=st.sampled_from([1, 8, 24]), use_kernel=st.booleans(),
       with_live=st.booleans(), packed=st.booleans(),
       query_tile=st.sampled_from([1, 4]))
def test_property_fused_topk_order(blk, s, seed, exec_mode, fetch,
                                   use_kernel, with_live, packed,
                                   query_tile):
    store, plan, lut, rank_of, sel, live = _synth(
        seed, s=s, blk=blk, tie_heavy=True)
    if packed:
        store = store._replace(block_codes=jnp.asarray(
            pack_nibbles(np.asarray(store.block_codes))))
    live = live if with_live else None
    ref_d, ref_i, ref_dco = _unfused_reference(
        store, plan, lut, rank_of, sel, live,
        min(fetch, s * blk), exec_mode, use_kernel=use_kernel,
        query_tile=query_tile, packed=packed)
    out = scan_blocks_topk(store, plan, lut, rank_of, fetch=fetch,
                           exec_mode=exec_mode, use_kernel=use_kernel,
                           query_tile=query_tile, sel=sel, live=live,
                           packed=packed)
    np.testing.assert_array_equal(np.asarray(out.flat_d), np.asarray(ref_d))
    np.testing.assert_array_equal(np.asarray(out.flat_i), np.asarray(ref_i))
    np.testing.assert_array_equal(np.asarray(out.approx_dco),
                                  np.asarray(ref_dco))


# ---------------------------------------------------------------------------
# end to end: seil_search == select -> plan -> reference stream -> finalize
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
    """The served program against the stages composed by hand around
    the reference stream: the same ids, distances and DCO, bitwise."""
    _, q, _ = unit_data
    idx = rairs_index
    assert idx.config.metric == "l2"
    kw = dict(bigk=32, k=10, metric="l2",
              dedup_results=idx.needs_result_dedup,
              oversample=idx.result_oversample)
    max_scan = idx.default_max_scan(8)
    served = seil_search(idx.arrays, idx.centroids, idx.codebook,
                         idx.vectors, q[:16], nprobe=8, max_scan=max_scan,
                         use_kernel=use_kernel, exec_mode=exec_mode,
                         query_tile=4, **kw)

    @jax.jit
    def composed(queries):
        selection = select_lists(queries, idx.centroids, nprobe=8)
        plan = plan_blocks(tables_from_arrays(idx.arrays), selection,
                           max_scan=max_scan)
        cd, ci, dco = _unfused_reference(
            store_from_arrays(idx.arrays), plan,
            pq_lut(idx.codebook, queries), selection.rank_of, selection.sel,
            None, finalize_fetch(kw["bigk"], kw["oversample"],
                                 kw["dedup_results"]),
            exec_mode, use_kernel=use_kernel)
        ids, dists, refine_dco = finalize_candidates(
            cd, ci, vectors=idx.vectors, queries=queries, **kw)
        return SearchResult(ids, dists, dco, refine_dco,
                            plan.valid.sum(1), plan.dropped)

    _assert_results_equal(served, composed(q[:16]))
