"""Streaming mutable index (core/stream/, DESIGN.md §8).

Key invariants:
  * an unmutated StreamingIndex searches bitwise-identically to its
    wrapped RairsIndex (acceptance criterion);
  * appends go through the delta segment — never a full layout rebuild
    (build_seil call counting) — and inserted ids are retrievable;
  * deletes tombstone coherently across every view (the old layout-level
    seil.delete_ids path left assigns/vectors/stats/sessions stale);
  * mutations invalidate pinned sessions deterministically
    (StaleSessionError), and compaction bumps the epoch;
  * compact() reproduces a from-scratch build over the surviving corpus
    bitwise (same frozen centroids/codebook);
  * churn (interleaved insert/delete/compact) keeps recall vs a
    brute-force oracle within tolerance of a from-scratch rebuild, and
    compaction keeps the recall the index had under churn;
  * format-v2 bundles round-trip streaming state; v1 bundles still load.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_stub import given, settings, st

from repro.core import (IndexConfig, SearchParams, StaleSessionError,
                        StreamConfig, StreamingIndex, build_index,
                        build_seil_call_count, ground_truth, insert_batch,
                        load_index, recall_at_k, save_index)
from repro.core.seil import build_seil


def _assert_results_identical(ra, rb):
    for field in ra._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(ra, field)), np.asarray(getattr(rb, field)),
            err_msg=field)


@pytest.fixture()
def small_index(unit_data, shared_trained):
    """A fresh mutable-safe index over the first 5000 unit vectors (the
    session-scoped rairs_index must never be wrapped for mutation tests
    that could pollute its searcher cache semantics)."""
    x, _, _ = unit_data
    cents, cb = shared_trained
    cfg = IndexConfig(nlist=64, strategy="rair", seil=True)
    return build_index(jax.random.PRNGKey(0), x[:5000], cfg,
                       centroids=cents, codebook=cb)


# ---------------------------------------------------------------------------
# unmutated identity + insert path
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("exec_mode", ["paged", "grouped"])
def test_unmutated_stream_is_bitwise_identical(small_index, unit_data,
                                               exec_mode):
    """Wrapping alone changes nothing: same ids, distances, and DCO
    counters as the plain index (acceptance criterion).  (Uses the
    function-scoped index: delegation shares the base's searcher cache,
    which must not leak stats into the session-scoped fixture.)"""
    _, q, _ = unit_data
    stream = StreamingIndex(small_index)
    ra = small_index.search(q[:40], k=10, nprobe=8, exec_mode=exec_mode)
    rb = stream.search(q[:40], k=10, nprobe=8, exec_mode=exec_mode)
    _assert_results_identical(ra, rb)


def test_insert_goes_through_delta_not_layout_rebuild(small_index, unit_data):
    """Appends must not call build_seil (the O(n) rebuild the subsystem
    exists to avoid), and inserted vectors are immediately retrievable
    under their new ids."""
    x, _, _ = unit_data
    stream = StreamingIndex(small_index)
    before = build_seil_call_count()
    ids = stream.insert(x[5000:5400])
    assert build_seil_call_count() == before
    assert stream.base is small_index            # base epoch untouched
    np.testing.assert_array_equal(ids, np.arange(5000, 5400))
    assert stream.n_delta == 400 and stream.n_live == 5400
    probe = x[5007][None, :]
    r = stream.search(probe, k=1, nprobe=16)
    assert int(np.asarray(r.ids)[0, 0]) == 5007


def test_steady_state_churn_does_not_recompile(small_index, unit_data):
    """Within one capacity bucket, mutation-driven session turnover must
    reuse the stream-level executables: one compile total."""
    x, q, _ = unit_data
    stream = StreamingIndex(small_index, StreamConfig(delta_pad=512))
    params = SearchParams(k=10, nprobe=8)
    for step in range(4):
        stream.insert(x[5000 + step * 64:5000 + (step + 1) * 64])
        stream.delete([int(stream.live_ids()[step])])
        stream.searcher(params)(q[:16])
    stats = stream.searcher_stats()
    assert stats["compiles"] == 1, stats
    assert stats["invalidations"] == 3, stats


def test_delta_capacity_buckets_are_geometric(small_index, unit_data):
    x, _, _ = unit_data
    stream = StreamingIndex(small_index, StreamConfig(delta_pad=64))
    stream.insert(x[5000:5010])
    assert stream._delta.capacity == 64
    stream.insert(x[5010:5100])
    assert stream._delta.capacity == 128
    stream.insert(x[5100:5400])
    assert stream._delta.capacity == 512


# ---------------------------------------------------------------------------
# delete consistency (regression for the orphaned seil.delete_ids hole)
# ---------------------------------------------------------------------------
def test_delete_keeps_all_views_coherent(small_index, unit_data):
    """The old path (seil.delete_ids on the arrays) rewrote the layout
    only: assigns/vectors/stats stayed stale and cached sessions kept
    serving the deleted id.  StreamingIndex.delete must keep every view
    coherent and fail the stale session deterministically."""
    x, q, _ = unit_data
    stream = StreamingIndex(small_index)
    probe = x[42][None, :]
    assert int(np.asarray(stream.search(probe, k=1, nprobe=16).ids)[0, 0]) == 42

    stale = stream.searcher(SearchParams(k=1, nprobe=16))
    n = stream.delete([42, 42, 43])              # dupes are one tombstone
    assert n == 2
    # the session created pre-delete would have silently returned 42 on
    # the old path; now it is deterministically unusable
    with pytest.raises(StaleSessionError, match="version"):
        stale(probe)
    # fresh session: deleted id can never be returned
    r = stream.search(probe, k=10, nprobe=16)
    assert 42 not in np.asarray(r.ids)
    assert 43 not in np.asarray(r.ids)
    # id-aligned views stay coherent (n_total unchanged, liveness masked)
    assert stream.n_live == 4998
    assert stream.vectors.shape[0] == 5000
    assert stream.assigns.shape[0] == 5000
    assert not stream.live_mask()[42]
    # deleting again is a no-op; out-of-range raises
    assert stream.delete([42]) == 0
    with pytest.raises(ValueError, match="out of range"):
        stream.delete([stream.n_total])


def test_delete_of_delta_items(small_index, unit_data):
    x, _, _ = unit_data
    stream = StreamingIndex(small_index)
    ids = stream.insert(x[5000:5100])
    victim = int(ids[7])
    assert stream.delete([victim]) == 1
    r = stream.search(x[5007][None, :], k=5, nprobe=16)
    assert victim not in np.asarray(r.ids)
    assert stream.n_delta == 99


# ---------------------------------------------------------------------------
# session versioning / epochs
# ---------------------------------------------------------------------------
def test_mutations_invalidate_sessions_and_epochs_bump(small_index,
                                                       unit_data):
    x, q, _ = unit_data
    stream = StreamingIndex(small_index)
    params = SearchParams(k=10, nprobe=8)
    s0 = stream.searcher(params)
    assert s0.epoch == 0 and stream.version == 0
    s0(q[:8])                                    # usable while current

    stream.insert(x[5000:5064])
    with pytest.raises(StaleSessionError):
        s0(q[:8])
    s1 = stream.searcher(params)
    assert s1 is not s0 and s1.version == stream.version
    s1(q[:8])

    info = stream.compact()
    assert info["epoch"] == stream.epoch == 1
    with pytest.raises(StaleSessionError):
        s1(q[:8])
    s2 = stream.searcher(params)
    assert s2.epoch == 1
    assert np.asarray(s2(q[:8]).ids).shape == (8, 10)
    assert stream.stats.invalidations >= 1
    assert stream.searcher_stats()["epoch"] == 1


def test_searcher_cache_returns_same_session_while_current(small_index,
                                                           unit_data):
    _, q, _ = unit_data
    stream = StreamingIndex(small_index)
    a = stream.searcher(k=10, nprobe=8)
    b = stream.searcher(SearchParams(k=10, nprobe=8))
    assert a is b


# ---------------------------------------------------------------------------
# compaction equivalence
# ---------------------------------------------------------------------------
def test_compact_matches_from_scratch_rebuild(small_index, unit_data,
                                              shared_trained):
    """Churn equivalence (acceptance criterion): after inserts+deletes,
    compact() must equal build_index over the surviving corpus with the
    same frozen centroids/codebook — same layout arrays, same search
    ids, same distances."""
    x, q, _ = unit_data
    cents, cb = shared_trained
    stream = StreamingIndex(small_index)
    stream.insert(x[5000:5500])
    victims = np.array([1, 42, 4999, 5003, 5499])
    stream.delete(victims)
    info = stream.compact()
    assert info["n_live"] == 5495 and info["dropped"] == 5

    keep = np.ones(5500, bool)
    keep[victims] = False
    surv = np.asarray(x[:5500])[keep]
    ref = build_index(jax.random.PRNGKey(0), jnp.asarray(surv),
                      small_index.config, centroids=cents, codebook=cb)
    np.testing.assert_array_equal(np.asarray(stream.base.arrays.block_ids),
                                  np.asarray(ref.arrays.block_ids))
    np.testing.assert_array_equal(np.asarray(stream.base.arrays.block_codes),
                                  np.asarray(ref.arrays.block_codes))
    assert stream.base.stats == ref.stats
    for mode in ("paged", "grouped"):
        ra = stream.search(q[:48], k=10, nprobe=8, exec_mode=mode)
        rb = ref.search(q[:48], k=10, nprobe=8, exec_mode=mode)
        _assert_results_identical(ra, rb)
    # id remap: old id -> position in the surviving corpus
    remap = info["id_remap"]
    assert remap.shape == (5500,)
    assert (remap[victims] == -1).all()
    np.testing.assert_array_equal(remap[keep], np.arange(5495))


def test_auto_compaction_thresholds(small_index, unit_data):
    x, _, _ = unit_data
    stream = StreamingIndex(
        small_index, StreamConfig(delta_pad=64, compact_delta_frac=0.05))
    stream.insert(x[5000:5200])                  # 200 < 250 -> no compact
    assert stream.epoch == 0
    stream.insert(x[5200:5300])                  # 300 > 250 -> compact
    assert stream.epoch == 1 and stream.stats.auto_compactions == 1
    assert stream.n_delta == 0 and stream.n_live == 5300


def test_auto_compaction_returns_renumbered_ids(small_index, unit_data):
    """When an insert itself triggers compaction, the returned ids must
    be post-renumbering — stale pre-compaction ids would point a caller
    at the wrong vectors once tombstones shift the id space."""
    x, _, _ = unit_data
    stream = StreamingIndex(
        small_index, StreamConfig(delta_pad=64, compact_delta_frac=0.05))
    stream.delete(np.arange(10))                 # shift every later id down
    ids = stream.insert(x[5000:5300])            # crosses 250 -> auto-compact
    assert stream.epoch == 1
    np.testing.assert_array_equal(ids, np.arange(4990, 5290))
    probe = x[5007][None, :]
    r = stream.search(probe, k=1, nprobe=16)
    assert int(np.asarray(r.ids)[0, 0]) == int(ids[7])


def test_compaction_keeps_churn_recall(unit_data, shared_trained):
    """A fifth of the corpus appended in eight batches (no layout
    rebuild), a tenth of it deleted, then compacted: recall@10 against
    brute force over the live vectors after compaction is no more than
    0.02 below recall under churn."""
    x, q, _ = unit_data
    cents, cb = shared_trained
    n0 = 4800
    base = build_index(jax.random.PRNGKey(0), x[:n0],
                       IndexConfig(nlist=64, strategy="rair", seil=True),
                       centroids=cents, codebook=cb)
    stream = StreamingIndex(base)
    before = build_seil_call_count()
    for lo in range(n0, x.shape[0], 150):
        stream.insert(x[lo:lo + 150])
    assert build_seil_call_count() == before
    rng = np.random.default_rng(0)
    stream.delete(rng.choice(stream.n_total, stream.n_total // 10,
                             replace=False))

    def recall():
        gt = stream.live_ids()[ground_truth(stream.live_vectors(), q, 10)]
        return recall_at_k(np.asarray(stream.search(q, k=10, nprobe=16).ids),
                           gt)

    churn = recall()
    stream.compact()
    assert recall() >= churn - 0.02


def test_noop_delete_does_not_invalidate_sessions(small_index, unit_data):
    """Replaying a deletion log (idempotent retry) must not stale live
    sessions: a delete that changes nothing leaves the version alone."""
    _, q, _ = unit_data
    stream = StreamingIndex(small_index)
    stream.delete([42])
    sess = stream.searcher(SearchParams(k=10, nprobe=8))
    sess(q[:8])
    v = stream.version
    assert stream.delete([42]) == 0              # retry: already dead
    assert stream.version == v
    sess(q[:8])                                  # still current, no raise
    assert stream.searcher(SearchParams(k=10, nprobe=8)) is sess


# ---------------------------------------------------------------------------
# insert_batch compat wrapper
# ---------------------------------------------------------------------------
def test_insert_batch_is_a_streaming_wrapper(small_index, unit_data):
    """insert_batch returns a read-compatible StreamingIndex, appends in
    O(batch) (no layout rebuild), and compact() reproduces the legacy
    pooled rebuild bitwise (acceptance criterion)."""
    x, q, _ = unit_data
    before = build_seil_call_count()
    grown = insert_batch(small_index, x[5000:5300])
    assert isinstance(grown, StreamingIndex)
    assert build_seil_call_count() == before
    assert grown.vectors.shape[0] == 5300
    # repeat appends reuse the same stream
    grown2 = insert_batch(grown, x[5300:5400])
    assert grown2 is grown and grown.vectors.shape[0] == 5400

    # the legacy behaviour: pooled re-add rebuilding the full layout
    cfg = small_index.config
    legacy_arrays, legacy_stats = build_seil(
        grown.assigns, np.concatenate([small_index.codes,
                                       grown._delta.codes[:400]], axis=0),
        np.arange(5400, dtype=np.int32), cfg.nlist, block=cfg.block,
        shared=cfg.seil and cfg.multi_m == 2, code_bits=cfg.nbits)
    legacy = dataclasses.replace(
        small_index, arrays=legacy_arrays, stats=legacy_stats,
        assigns=grown.assigns, codes=None, vectors=grown.vectors)
    grown.compact()
    ra = grown.search(q[:32], k=10, nprobe=8)
    rb = legacy.search(q[:32], k=10, nprobe=8)
    _assert_results_identical(ra, rb)


# ---------------------------------------------------------------------------
# persistence (bundle v2)
# ---------------------------------------------------------------------------
def test_streaming_bundle_roundtrip(small_index, unit_data, tmp_path):
    x, q, _ = unit_data
    stream = StreamingIndex(small_index, StreamConfig(delta_pad=128))
    stream.insert(x[5000:5200])
    stream.delete([7, 5003])
    path = os.path.join(tmp_path, "stream.npz")
    save_index(stream, path, extra={"dataset": "unit"})
    restored = load_index(path)
    assert isinstance(restored, StreamingIndex)
    assert restored.epoch == stream.epoch
    assert restored.version == stream.version
    assert restored.n_live == stream.n_live
    assert restored.n_delta == stream.n_delta
    assert restored.stream_config == stream.stream_config
    ra = stream.search(q[:32], k=10, nprobe=8)
    rb = restored.search(q[:32], k=10, nprobe=8)
    _assert_results_identical(ra, rb)
    # a restored stream keeps mutating correctly
    restored.insert(x[5200:5250])
    assert restored.n_live == stream.n_live + 50


def test_v1_bundle_still_loads(small_index, unit_data, tmp_path):
    """Migration story: pre-streaming (v1) bundles are exactly v2 minus
    the streaming section — they must load as a plain RairsIndex."""
    _, q, _ = unit_data
    path = os.path.join(tmp_path, "v2.npz")
    save_index(small_index, path)
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    meta = json.loads(bytes(arrays["meta_json"].tobytes()).decode())
    assert meta["format_version"] == 5   # current writer (checksummed)
    meta["format_version"] = 1
    arrays["meta_json"] = np.frombuffer(json.dumps(meta).encode(), np.uint8)
    v1 = os.path.join(tmp_path, "v1.npz")
    with open(v1, "wb") as f:
        np.savez(f, **arrays)
    restored = load_index(v1)
    assert not isinstance(restored, StreamingIndex)
    ra = small_index.search(q[:16], k=10, nprobe=8)
    rb = restored.search(q[:16], k=10, nprobe=8)
    _assert_results_identical(ra, rb)


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------
def test_stream_config_and_inputs_validate(small_index):
    with pytest.raises(ValueError, match="delta_pad"):
        StreamConfig(delta_pad=0)
    with pytest.raises(ValueError, match="compact_delta_frac"):
        StreamConfig(compact_delta_frac=0.0)
    stream = StreamingIndex(small_index)
    with pytest.raises(TypeError, match="StreamingIndex"):
        StreamingIndex(stream)
    with pytest.raises(ValueError, match="insert batch"):
        stream.insert(np.zeros((4, 3), np.float32))
    assert stream.insert(np.zeros((0, 32), np.float32)).size == 0
    assert stream.delete([]) == 0
    assert stream.version == 0                   # no-ops don't bump


# ---------------------------------------------------------------------------
# property-style churn test (auto-skips without hypothesis)
# ---------------------------------------------------------------------------
@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 2 ** 16),
       n_ops=st.integers(2, 6),
       mid_compact=st.booleans())
def test_churn_recall_matches_scratch_rebuild(seed, n_ops, mid_compact):
    """Interleaved insert/delete(/compact) sequences: streaming recall vs
    a brute-force oracle over survivors must match a from-scratch
    rebuild's recall within tolerance, and the final compacted index
    must return exactly the rebuild's ids."""
    from repro.data import make_dataset
    x, q, _ = make_dataset("unit")
    x = np.asarray(x)
    q = jnp.asarray(q[:64])
    rng = np.random.default_rng(seed)
    cfg = IndexConfig(nlist=32, strategy="rair", seil=True,
                      kmeans_iters=4, pq_iters=4)
    n0 = 2000
    base = build_index(jax.random.PRNGKey(0), jnp.asarray(x[:n0]), cfg)
    stream = StreamingIndex(base, StreamConfig(delta_pad=64))

    pool = n0                                    # next unused corpus row
    rows = {i: i for i in range(n0)}             # live id -> corpus row
    for _ in range(n_ops):
        op = rng.integers(0, 3 if mid_compact else 2)
        if op == 0 and pool + 200 <= x.shape[0]:
            ids = stream.insert(x[pool:pool + 200])
            for j, i in enumerate(ids):
                rows[int(i)] = pool + j
            pool += 200
        elif op == 1 and len(rows) > 300:
            victims = rng.choice(stream.live_ids(), size=100, replace=False)
            stream.delete(victims)
            for v in victims:
                rows.pop(int(v), None)
        elif op == 2:
            remap = stream.compact()["id_remap"]
            rows = {int(remap[i]): r for i, r in rows.items()}

    surv_rows = np.array([rows[i] for i in sorted(rows)])
    oracle_corpus = jnp.asarray(x[surv_rows])
    gt = ground_truth(oracle_corpus, q, 10)

    rebuilt = build_index(jax.random.PRNGKey(0), oracle_corpus, cfg,
                          centroids=base.centroids, codebook=base.codebook)
    rec_rebuild = recall_at_k(np.asarray(rebuilt.search(q, k=10, nprobe=8).ids),
                              gt)
    live = stream.live_ids()
    pos_of = {int(i): p for p, i in enumerate(live)}
    r_stream = stream.search(q, k=10, nprobe=8)
    ids_as_pos = np.array([[pos_of.get(int(i), -1) for i in row]
                           for row in np.asarray(r_stream.ids)])
    rec_stream = recall_at_k(ids_as_pos, gt)
    assert rec_stream >= rec_rebuild - 0.05, (rec_stream, rec_rebuild)

    stream.compact()
    _assert_results_identical(stream.search(q, k=10, nprobe=8),
                              rebuilt.search(q, k=10, nprobe=8))
