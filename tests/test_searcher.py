"""Searcher sessions (compiled-plan search API), index persistence, and
the strategy registry — the PR-2 public-API surface.

Key invariants: a session is bitwise-identical to the legacy kwarg path
in both exec modes (even when the batch pads up to a bucket), repeated
batches hit cached executables with zero new compilations, and a
save/load round-trip returns an index whose results match in-memory."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (IndexConfig, SearchParams, Searcher, build_index,
                        insert_batch, load_index, register_strategy,
                        save_index, single_assign)
from repro.core.assign import STRATEGY_REGISTRY, available_strategies
from repro.core.io import INDEX_FORMAT_VERSION
from repro.core.search import seil_search


def _legacy_search(index, queries, *, k, nprobe, k_factor=10, max_scan=None,
                   exec_mode="paged", use_kernel=False, query_tile=8):
    """The pre-session kwarg path: a direct jit call at the exact batch
    shape (what RairsIndex.search compiled before searcher sessions)."""
    if max_scan is None:
        max_scan = index.default_max_scan(nprobe)
    return seil_search(
        index.arrays, index.centroids, index.codebook, index.vectors,
        queries, nprobe=nprobe, bigk=k * k_factor, k=k, max_scan=max_scan,
        metric=index.config.metric, dedup_results=index.needs_result_dedup,
        use_kernel=use_kernel, oversample=index.result_oversample,
        exec_mode=exec_mode, query_tile=query_tile)


def _assert_results_identical(ra, rb):
    for field in ra._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(ra, field)), np.asarray(getattr(rb, field)),
            err_msg=field)


# ---------------------------------------------------------------------------
# Searcher sessions
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("exec_mode", ["paged", "grouped"])
def test_searcher_bitwise_matches_legacy_path(rairs_index, unit_data,
                                              exec_mode):
    """B=48 pads to the 64 bucket — results must still be bitwise equal
    to the exact-shape legacy jit path (acceptance criterion)."""
    _, q, _ = unit_data
    qs = q[:48]
    searcher = rairs_index.searcher(
        SearchParams(k=10, nprobe=8, exec_mode=exec_mode))
    res = searcher(qs)
    legacy = _legacy_search(rairs_index, qs, k=10, nprobe=8,
                            exec_mode=exec_mode)
    _assert_results_identical(res, legacy)
    assert searcher.stats.padded_rows == 16


def test_searcher_zero_recompiles_after_warmup(rairs_index, unit_data):
    """Repeated batches of one shape never compile again (acceptance)."""
    _, q, _ = unit_data
    searcher = Searcher(rairs_index, SearchParams(k=10, nprobe=4))
    searcher(q[:32])
    compiles_after_warmup = searcher.stats.compiles
    assert compiles_after_warmup == 1
    for _ in range(3):
        searcher(q[:32])
    assert searcher.stats.compiles == compiles_after_warmup  # zero new
    assert searcher.stats.cache_hits == 3
    assert searcher.stats.calls == 4


def test_searcher_bucket_dispatch_shares_executables(rairs_index, unit_data):
    """Different batch sizes under one power-of-two bucket share one
    executable; a bigger batch adds exactly one more."""
    _, q, _ = unit_data
    searcher = Searcher(rairs_index, SearchParams(k=10, nprobe=4))
    for bs in (3, 5, 8, 7):                      # all fit the 8 bucket
        searcher(q[:bs])
    assert searcher.buckets == (4, 8)            # 3 -> 4, rest -> 8
    assert searcher.stats.compiles == 2
    searcher(q[:9])                              # new 16 bucket
    assert searcher.buckets == (4, 8, 16)
    assert searcher.stats.compiles == 3


def test_searcher_chunks_oversize_batches(rairs_index, unit_data):
    """Batches above the largest bucket are chunked and re-merged."""
    _, q, _ = unit_data
    searcher = rairs_index.searcher(
        SearchParams(k=10, nprobe=4, batch_buckets=(64,)))
    res = searcher(q[:150])                      # 64 + 64 + pad(22 -> 64)
    assert np.asarray(res.ids).shape == (150, 10)
    assert searcher.stats.compiles == 1
    assert searcher.stats.dispatches == 3
    legacy = _legacy_search(rairs_index, q[:150], k=10, nprobe=4)
    _assert_results_identical(res, legacy)


def test_searcher_counts_refined_rows_per_padded_dispatch(rairs_index,
                                                         unit_data):
    """``refined_rows`` is the host's count of candidate rows the exact
    re-rank scores: padded batch x bigk_eff per dispatch, chunks and
    bucket padding included."""
    from repro.core.params import RefineParams
    _, q, _ = unit_data
    searcher = rairs_index.searcher(
        SearchParams(k=10, nprobe=4, k_factor=4, batch_buckets=(4, 8)))
    searcher(q[:3])                              # one dispatch of 4
    assert searcher.stats.refined_rows == 4 * 40
    searcher(q[:13])                             # 8 + pad(5 -> 8)
    assert searcher.stats.refined_rows == (4 + 16) * 40
    assert searcher.compile_stats()["refined_rows"] == 20 * 40
    wide = rairs_index.searcher(SearchParams(
        k=10, nprobe=4, k_factor=4,
        refine=RefineParams(plane="full", refine_factor=3)))
    wide(q[:5])                                  # bucket 8, bigk_eff 120
    assert wide.stats.refined_rows == 8 * 120


def test_searcher_reports_fused_topk_width(rairs_index):
    """``compile_stats()["topk_width"]`` is the scan kernel's
    accumulator width F = pow2_ceil(fetch), the fetch capped by the scan
    width; None for a session that does not run the kernel."""
    from repro.kernels.topk import pow2_ceil
    blk = rairs_index.arrays.block_codes.shape[1]
    over = (rairs_index.result_oversample
            if rairs_index.needs_result_dedup else 1)
    assert rairs_index.searcher(SearchParams(
        k=10, nprobe=4)).compile_stats()["topk_width"] is None
    kernel = rairs_index.searcher(SearchParams(
        k=10, nprobe=4, use_kernel=True))
    assert kernel.compile_stats()["topk_width"] == pow2_ceil(
        min(100 * over, kernel.params.max_scan * blk))
    for k_factor, max_scan in ((10, None), (50, None), (100, 8)):
        sess = rairs_index.searcher(SearchParams(
            k=10, nprobe=4, k_factor=k_factor, max_scan=max_scan,
            use_kernel=True))
        fetch = min(10 * k_factor * over, sess.params.max_scan * blk)
        assert sess.compile_stats()["topk_width"] == pow2_ceil(fetch)
    assert sess.compile_stats()["topk_width"] == pow2_ceil(8 * blk)
    # the kernel pages four blocks of 32 per grid step: max_scan 8 needs
    # no padding, 6 pads two of eight positions
    assert blk == 32
    assert sess.compile_stats()["blocks_per_step"] == 4
    assert sess.compile_stats()["padded_positions_share"] == 0
    six = rairs_index.searcher(SearchParams(
        k=10, nprobe=4, max_scan=6, use_kernel=True)).compile_stats()
    assert (six["blocks_per_step"], six["padded_positions_share"]) == (4, 0.25)
    plain = rairs_index.searcher(SearchParams(k=10, nprobe=4))
    assert plain.compile_stats()["blocks_per_step"] is None


def test_index_search_wrapper_reuses_sessions(rairs_index, unit_data):
    """The kwarg path is a thin wrapper: identical kwargs -> one cached
    session, so repeat calls are compile-free."""
    _, q, _ = unit_data
    r1 = rairs_index.search(q[:16], k=10, nprobe=4)
    cache = rairs_index._searcher_cache
    key = SearchParams(k=10, nprobe=4)
    assert key in cache
    compiles = cache[key].stats.compiles
    r2 = rairs_index.search(q[:16], k=10, nprobe=4)
    assert cache[key].stats.compiles == compiles
    _assert_results_identical(r1, r2)


def test_searcher_rejects_bad_query_shapes(rairs_index, unit_data):
    _, q, _ = unit_data
    searcher = rairs_index.searcher(SearchParams(k=10, nprobe=4))
    with pytest.raises(ValueError, match="empty query batch"):
        searcher(q[:0])
    with pytest.raises(ValueError, match=r"\(B, D\)"):
        searcher(q[0])


def test_search_params_validation():
    with pytest.raises(ValueError):
        SearchParams(k=0)
    with pytest.raises(ValueError):
        SearchParams(nprobe=0)
    with pytest.raises(ValueError):
        SearchParams(exec_mode="vectorized")
    with pytest.raises(ValueError):
        SearchParams(max_scan=0)
    with pytest.raises(ValueError):
        SearchParams(batch_buckets=(8, 4))       # not ascending
    with pytest.raises(ValueError):
        SearchParams(query_tile=0)


def test_search_params_pin_the_scan_stage_selection():
    """The scan stage always selects the top ``fetch`` candidates:
    ``fused_topk`` defaults to True, the benchmark's configs may say so,
    and False is refused instead of silently ignored."""
    assert SearchParams().fused_topk is True
    assert SearchParams(fused_topk=True) == SearchParams()
    with pytest.raises(ValueError, match="top fetch candidates"):
        SearchParams(fused_topk=False)


def test_search_params_resolve_pins_max_scan(rairs_index):
    p = SearchParams(k=10, nprobe=8)
    r = p.resolve(rairs_index)
    assert r.max_scan == rairs_index.default_max_scan(8)
    assert SearchParams(k=10, nprobe=8, max_scan=7).resolve(rairs_index).max_scan == 7
    with pytest.raises(ValueError):
        SearchParams(nprobe=10_000).resolve(rairs_index)


# ---------------------------------------------------------------------------
# Persistence
# ---------------------------------------------------------------------------
def test_save_load_roundtrip_identical_results(rairs_index, unit_data,
                                               tmp_path):
    """load_index(save_index(x)) searches bitwise like the in-memory
    index (acceptance criterion)."""
    _, q, _ = unit_data
    path = os.path.join(tmp_path, "idx.npz")
    save_index(rairs_index, path)
    restored = load_index(path)
    assert restored.config == rairs_index.config
    assert restored.stats == rairs_index.stats
    np.testing.assert_array_equal(restored.assigns, rairs_index.assigns)
    for mode in ("paged", "grouped"):
        ra = rairs_index.search(q[:40], k=10, nprobe=8, exec_mode=mode)
        rb = restored.search(q[:40], k=10, nprobe=8, exec_mode=mode)
        _assert_results_identical(ra, rb)


def test_loaded_index_supports_insert(rairs_index, unit_data, tmp_path):
    """The bundle keeps assigns + cached codes, so append works post-load."""
    x, q, _ = unit_data
    path = os.path.join(tmp_path, "idx.npz")
    save_index(rairs_index, path)
    restored = load_index(path)
    grown = insert_batch(restored, x[:100])
    assert grown.vectors.shape[0] == rairs_index.vectors.shape[0] + 100
    r = grown.search(q[:8], k=10, nprobe=8)
    assert not np.isnan(np.asarray(r.dists)).any()


def test_load_rejects_wrong_version_and_garbage(rairs_index, tmp_path):
    import json
    path = os.path.join(tmp_path, "idx.npz")
    save_index(rairs_index, path)
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    meta = json.loads(bytes(arrays["meta_json"].tobytes()).decode())
    meta["format_version"] = INDEX_FORMAT_VERSION + 1
    arrays["meta_json"] = np.frombuffer(json.dumps(meta).encode(), np.uint8)
    bad = os.path.join(tmp_path, "bad.npz")
    with open(bad, "wb") as f:
        np.savez(f, **arrays)
    with pytest.raises(ValueError, match="format_version"):
        load_index(bad)

    not_index = os.path.join(tmp_path, "not_index.npz")
    with open(not_index, "wb") as f:
        np.savez(f, a=np.zeros(3))
    with pytest.raises(ValueError):
        load_index(not_index)


# ---------------------------------------------------------------------------
# Strategy registry + IndexConfig validation
# ---------------------------------------------------------------------------
def test_registry_has_paper_presets():
    assert available_strategies() == ("naive", "rair", "single", "soar",
                                      "srair")


def test_register_custom_strategy_builds_and_searches(unit_data,
                                                      shared_trained):
    """A user-registered strategy is a first-class IndexConfig citizen."""
    x, q, _ = unit_data
    cents, cb = shared_trained
    name = "test_reverse_single"

    @register_strategy(name)
    def _reverse(x_, centroids, cfg):
        a = np.asarray(single_assign(x_, centroids))
        return a[:, ::-1].copy() if a.shape[1] > 1 else a

    try:
        cfg = IndexConfig(nlist=64, strategy=name, seil=False)
        idx = build_index(jax.random.PRNGKey(0), x, cfg, centroids=cents,
                          codebook=cb)
        r = idx.search(q[:16], k=5, nprobe=8)
        assert np.asarray(r.ids).shape == (16, 5)
        with pytest.raises(ValueError, match="already registered"):
            register_strategy(name)(_reverse)
    finally:
        del STRATEGY_REGISTRY[name]


def test_index_config_validates_at_construction():
    with pytest.raises(ValueError, match="strategy"):
        IndexConfig(strategy="does_not_exist")
    with pytest.raises(ValueError, match="metric"):
        IndexConfig(metric="cosine")
    with pytest.raises(ValueError, match="nbits"):
        IndexConfig(nbits=9)
    with pytest.raises(ValueError, match="block"):
        IndexConfig(block=0)
    with pytest.raises(ValueError, match="multi_m"):
        IndexConfig(multi_m=1)
    with pytest.raises(ValueError, match="aggr"):
        IndexConfig(aggr="median")
    with pytest.raises(ValueError, match="nlist"):
        IndexConfig(nlist=0)
    # the old path only asserted inside build_index; now construction fails
    IndexConfig(strategy="rair", metric="ip", nbits=8)  # valid combos pass


def test_save_index_extra_meta_roundtrips(rairs_index, tmp_path):
    from repro.core import read_index_meta
    path = os.path.join(tmp_path, "idx.npz")
    save_index(rairs_index, path, extra={"dataset": "unit"})
    meta = read_index_meta(path)
    assert meta["extra"] == {"dataset": "unit"}
    assert meta["config"]["strategy"] == rairs_index.config.strategy


def test_distributed_rejects_unsupported_params(rairs_index, unit_data):
    """The shard_map path must refuse SearchParams fields it would
    otherwise silently drop, and still require nprobe/k without params.
    use_kernel is no longer one of them: the serve step routes the scan
    through the (interpret-mode on CPU) Pallas kernels since the fused
    top-k work, so it must serve rather than raise."""
    from repro.core.distributed import distributed_search
    _, q, _ = unit_data
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    base = distributed_search(rairs_index, mesh, q[:4],
                              params=SearchParams(k=10, nprobe=4))
    rk = distributed_search(rairs_index, mesh, q[:4],
                            params=SearchParams(k=10, nprobe=4,
                                                use_kernel=True))
    assert np.array_equal(np.asarray(rk.ids), np.asarray(base.ids))
    with pytest.raises(ValueError, match="max_scan"):
        distributed_search(rairs_index, mesh, q[:4],
                           params=SearchParams(k=10, nprobe=4, max_scan=64))
    with pytest.raises(TypeError, match="nprobe"):
        distributed_search(rairs_index, mesh, q[:4], k=10)


def test_insert_batch_does_not_reuse_stale_sessions(rairs_index, unit_data):
    """Sessions cache compiled executables over one index's arrays; a
    grown index must get fresh sessions, not stale ones."""
    x, q, _ = unit_data
    rairs_index.search(q[:8], k=10, nprobe=4)          # populate cache
    grown = insert_batch(rairs_index, x[:64])
    assert getattr(grown, "_searcher_cache", None) in (None, {})
    r = grown.search(q[:8], k=10, nprobe=4)
    assert np.asarray(r.ids).shape == (8, 10)


# ---------------------------------------------------------------------------
# inner product at a wide refine budget (the t2i1m deployment, tiny)
# ---------------------------------------------------------------------------
def test_ip_searcher_wide_refine_matches_exact_reference():
    """A SOAR+SEIL inner-product index over the benchmark's out-of-
    distribution generator (``modality_gap``), M = d/2, searched through
    the session with the fused kernel at F >= 512: returned distances
    are the exact inner products of the returned ids (within 1e-4 of
    each query's scale, the benchmark's ``dist_gap``), and 1,000
    re-ranked candidates recall more than 100 do on the same index."""
    from bench import reference
    from bench.corpus import make_corpus
    from repro.kernels.topk import pow2_ceil
    cfg = {"name": "tiny_t2i", "n": 4000, "d": 16, "n_queries": 48,
           "metric": "ip", "n_components": 16, "latent": 8,
           "modality_gap": True, "corpus_seed": 0}
    x, q = make_corpus(cfg, 7)
    index = build_index(jax.random.PRNGKey(0), x, IndexConfig(
        nlist=16, strategy="soar", seil=True, m_pq=8, metric="ip",
        kmeans_iters=4, pq_iters=4))
    gt = reference.exact_topk(x, q, 10, "ip")
    x_host, q_host = np.asarray(x), np.asarray(q)
    recall = {}
    for k_factor in (10, 100):
        sess = index.searcher(SearchParams(
            k=10, nprobe=4, k_factor=k_factor, use_kernel=True))
        res = sess(q)
        ids, dists = np.asarray(res.ids), np.asarray(res.dists)
        assert sess.compile_stats()["topk_width"] == pow2_ceil(
            min(10 * k_factor, sess.params.max_scan * 32))
        ref = reference.exact_dists(x_host, q_host, ids, "ip")
        scale = np.abs(ref).max(axis=1, keepdims=True)
        assert (ids >= 0).all()
        np.testing.assert_array_less(np.abs(dists - ref) / scale, 1e-4)
        recall[k_factor] = (ids[:, :, None] == gt[:, None, :]).any(1).mean()
    assert sess.compile_stats()["topk_width"] >= 512
    assert recall[100] > recall[10], recall
