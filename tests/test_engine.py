"""Staged query engine: grouped (list-major batch-union) execution must
be exactly the paged execution — same ids, distances, and DCO counters —
and both search frontends (single-host, distributed) must compose the
same stages.  Plus stage-level unit tests for planning and the grouped
kernel path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import IndexConfig, build_index
from repro.core.distributed import distributed_search
from repro.core.engine import (BlockStore, batch_union, plan_blocks,
                               rank_table, scan_blocks_topk, select_lists,
                               store_from_arrays, tables_from_arrays)
from repro.core.engine.types import BIG
from repro.kernels.ref import pq_scan_paged_ref


def _assert_results_identical(ra, rb):
    for field in ra._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(ra, field)), np.asarray(getattr(rb, field)),
            err_msg=field)


@pytest.mark.parametrize("nprobe", [2, 8])
def test_grouped_equals_paged_bitwise(rairs_index, unit_data, nprobe):
    _, q, _ = unit_data
    qs = q[:48]
    rp = rairs_index.search(qs, k=10, nprobe=nprobe, max_scan=4096,
                            exec_mode="paged")
    rg = rairs_index.search(qs, k=10, nprobe=nprobe, max_scan=4096,
                            exec_mode="grouped")
    _assert_results_identical(rp, rg)


def test_grouped_equals_paged_duplicated_layout(unit_data, shared_trained):
    """The id-dedup tail for non-SEIL layouts must also be mode-invariant."""
    x, q, _ = unit_data
    cents, cb = shared_trained
    cfg = IndexConfig(nlist=64, strategy="srair", seil=False)
    idx = build_index(jax.random.PRNGKey(0), x, cfg, centroids=cents,
                      codebook=cb)
    rp = idx.search(q[:32], k=10, nprobe=8, exec_mode="paged")
    rg = idx.search(q[:32], k=10, nprobe=8, exec_mode="grouped")
    _assert_results_identical(rp, rg)


def test_grouped_equals_paged_under_budget_pressure(rairs_index, unit_data):
    """Equivalence must hold even when the plan drops blocks to the
    budget — both modes scan the same compacted plan."""
    _, q, _ = unit_data
    rp = rairs_index.search(q[:16], k=10, nprobe=8, max_scan=12,
                            exec_mode="paged")
    rg = rairs_index.search(q[:16], k=10, nprobe=8, max_scan=12,
                            exec_mode="grouped")
    assert np.asarray(rp.dropped_blocks).max() > 0  # budget actually binds
    _assert_results_identical(rp, rg)


def test_distributed_exec_modes_match(rairs_index, unit_data):
    _, q, gt = unit_data
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    qs = q[:16]
    rd_p = distributed_search(rairs_index, mesh, qs, nprobe=8, k=10,
                              max_scan_local=4096, exec_mode="paged")
    rd_g = distributed_search(rairs_index, mesh, qs, nprobe=8, k=10,
                              max_scan_local=4096, exec_mode="grouped")
    _assert_results_identical(rd_p, rd_g)
    # and the shard_map path still matches the single-host engine's DCO
    # (the unified SearchResult replaced DistSearchResult.local_dco)
    rl = rairs_index.search(qs, k=10, nprobe=8, max_scan=4096)
    np.testing.assert_array_equal(np.asarray(rd_g.approx_dco),
                                  np.asarray(rl.approx_dco))


def test_batch_union_covers_plan(rairs_index, unit_data):
    """The batch-union block list is sorted, duplicate-free, and contains
    every valid planned block (so grouped mode can never drop one)."""
    _, q, _ = unit_data
    arrays = rairs_index.arrays
    selection = select_lists(q[:32], rairs_index.centroids, nprobe=8,
                             metric="l2")
    plan = plan_blocks(tables_from_arrays(arrays), selection, max_scan=4096)
    union = np.asarray(batch_union(plan, arrays.block_codes.shape[0]))
    live = union[union < int(BIG)]
    assert (np.diff(live) > 0).all(), "sorted + unique"
    planned = np.unique(np.asarray(plan.blocks)[np.asarray(plan.valid)])
    assert np.isin(planned, live).all()
    assert len(live) == len(planned)


def test_plan_budget_and_dropped(rairs_index, unit_data):
    _, q, _ = unit_data
    selection = select_lists(q[:8], rairs_index.centroids, nprobe=8,
                             metric="l2")
    tables = tables_from_arrays(rairs_index.arrays)
    full = plan_blocks(tables, selection, max_scan=100000)
    tight = plan_blocks(tables, selection, max_scan=4)
    n_full = np.asarray(full.valid).sum(1)
    n_tight = np.asarray(tight.valid).sum(1)
    assert (n_tight <= 4).all()
    np.testing.assert_array_equal(
        np.asarray(tight.dropped), np.maximum(n_full - 4, 0))
    # compaction is stable: the tight plan is a prefix of the full plan
    fb, tb_ = np.asarray(full.blocks), np.asarray(tight.blocks)
    for i in range(len(tb_)):
        keep = int(n_tight[i])
        np.testing.assert_array_equal(tb_[i][:keep], fb[i][:keep])


def test_scan_grouped_kernel_matches_oracle():
    """The grouped exec mode's kernel path through the engine's scan
    stage, at a fetch that keeps every candidate, == jnp oracle on a
    synthetic store (the §5.3 kernel path, interpret mode on CPU)."""
    key = jax.random.PRNGKey(3)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    b, m, kk, tbn, blk, s = 8, 8, 16, 24, 32, 6
    lut = jax.random.normal(k1, (b, m, kk), jnp.float32)
    store = BlockStore(
        block_codes=jax.random.randint(k2, (tbn, blk, m), 0, kk
                                       ).astype(jnp.uint8),
        block_ids=jax.random.randint(k3, (tbn, blk), 0, 5000, jnp.int32),
        block_other=jnp.full((tbn, blk), -1, jnp.int32))
    nlist = 16
    sel = jax.random.randint(k4, (b, 4), 0, nlist, jnp.int32)
    rank_of = rank_table(sel, nlist)
    # a plan lists each block at most once per query (plan_blocks'
    # dedup), which the kernel path's slot maps rely on
    blocks = jax.vmap(lambda kq: jax.random.permutation(kq, tbn)[:s])(
        jax.random.split(jax.random.PRNGKey(5), b)).astype(jnp.int32)
    from repro.core.engine.types import QueryPlan
    plan = QueryPlan(blocks=blocks, ranks=jnp.zeros((b, s), jnp.int32),
                     valid=jnp.ones((b, s), bool),
                     dropped=jnp.zeros((b,), jnp.int32))
    out_k = scan_blocks_topk(store, plan, lut, rank_of, fetch=s * blk,
                             exec_mode="grouped", use_kernel=True,
                             query_tile=4, sel=sel)
    ref_d = np.asarray(pq_scan_paged_ref(lut, store.block_codes, blocks)
                       ).reshape(b, -1)
    ref_i = np.asarray(store.block_ids[blocks]).reshape(b, -1)
    out_d, out_i = np.asarray(out_k.flat_d), np.asarray(out_k.flat_i)
    # the stage returns the stream sorted by distance; compare it with
    # the oracle's stream ordered by (id, distance) on both sides
    for q in range(b):
        o, r = np.lexsort((out_d[q], out_i[q])), np.lexsort((ref_d[q],
                                                             ref_i[q]))
        np.testing.assert_array_equal(out_i[q][o], ref_i[q][r])
        np.testing.assert_allclose(out_d[q][o], ref_d[q][r],
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(out_k.approx_dco),
                                  np.full(b, s * blk))


def test_kernel_exec_modes_agree_end_to_end(rairs_index, unit_data):
    """Pallas paged vs grouped (interpret mode) on the real index: tiny
    workload, ids must match (distances agree to kernel tolerance)."""
    _, q, _ = unit_data
    qs = q[:8]
    rk_p = rairs_index.search(qs, k=10, nprobe=2, max_scan=24,
                              use_kernel=True, exec_mode="paged")
    rk_g = rairs_index.search(qs, k=10, nprobe=2, max_scan=24,
                              use_kernel=True, exec_mode="grouped")
    np.testing.assert_array_equal(np.asarray(rk_p.ids), np.asarray(rk_g.ids))
    np.testing.assert_allclose(np.asarray(rk_p.dists),
                               np.asarray(rk_g.dists), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(rk_p.approx_dco),
                                  np.asarray(rk_g.approx_dco))


def test_insert_batch_uses_cached_codes(rairs_index, unit_data, monkeypatch):
    """insert_batch must not re-encode the old corpus (codes are cached)."""
    import repro.core.index as index_mod
    x, _, _ = unit_data
    assert rairs_index.codes is not None
    calls = []
    real = index_mod.pq_encode

    def counting(cb, xs):
        calls.append(xs.shape[0])
        return real(cb, xs)

    monkeypatch.setattr(index_mod, "pq_encode", counting)
    idx2 = index_mod.insert_batch(rairs_index, x[:500])
    assert calls == [500], calls  # only the new batch was encoded
    assert idx2.codes.shape[0] == rairs_index.codes.shape[0] + 500
