"""Locality-aware query planning (engine/cluster.py, DESIGN.md §5).

Key invariants:
  * ``exec_mode="clustered"`` (query-tile clustering, per-tile block
    unions) is bitwise identical to ``"paged"`` on every search path —
    frozen, streaming (mutated), sharded (1-device mesh);
  * incremental plans (``SearchParams(plan_reuse=True)``) — the probe ->
    plan-cache merge -> scan split — are bitwise identical to fresh
    batch-wide plans, for grouped and clustered modes, and the cache
    invalidates with the session across mutations and epoch bumps;
  * every valid planned block lands inside its tile's union, unions are
    sorted/unique, and the cluster order is a stable permutation;
  * routed delta scans return exactly the exhaustive path's results
    whenever every delta item is reachable through the probed lists
    (nprobe = nlist), reduce DCO at serving nprobe, and keep inserted
    items retrievable;
  * the derived per-device ``max_scan_local`` (per-shard list occupancy)
    never truncates a plan — recall-neutral vs an un-truncating budget;
  * on a Zipf-skewed serving trace (and its uniform control) per-tile
    unions cut the batch union, the plan cache's hit/extend/miss counts
    partition its tiles, and every exec mode agrees on ids and DCO.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_stub import given, settings, st

from repro.core import (IndexConfig, SearchParams, Searcher,
                        StaleSessionError, StreamingIndex, build_index,
                        cluster_order, merge_unions_host, plan_blocks,
                        select_lists, tile_signatures, tile_unions,
                        union_dims, union_live)
from repro.core.engine import tables_from_arrays
from repro.core.engine.types import BIG


def _assert_results_identical(ra, rb, msg=""):
    for field in ra._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(ra, field)), np.asarray(getattr(rb, field)),
            err_msg=f"{msg}{field}")


# ---------------------------------------------------------------------------
# clustered exec mode == paged, everywhere
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("nprobe", [2, 8])
def test_clustered_equals_paged_bitwise(rairs_index, unit_data, nprobe):
    _, q, _ = unit_data
    qs = q[:48]
    rp = rairs_index.search(qs, k=10, nprobe=nprobe, max_scan=4096,
                            exec_mode="paged")
    rc = rairs_index.search(qs, k=10, nprobe=nprobe, max_scan=4096,
                            exec_mode="clustered")
    _assert_results_identical(rp, rc)


def test_clustered_equals_paged_under_budget_pressure(rairs_index,
                                                      unit_data):
    _, q, _ = unit_data
    rp = rairs_index.search(q[:16], k=10, nprobe=8, max_scan=12,
                            exec_mode="paged")
    rc = rairs_index.search(q[:16], k=10, nprobe=8, max_scan=12,
                            exec_mode="clustered")
    assert np.asarray(rp.dropped_blocks).max() > 0
    _assert_results_identical(rp, rc)


def test_clustered_kernel_path(rairs_index, unit_data):
    """The clustered exec mode's per-tile kernel paging through the
    engine (interpret mode): ids must match paged-kernel, distances to
    kernel tolerance."""
    _, q, _ = unit_data
    qs = q[:8]
    rk_p = rairs_index.search(qs, k=10, nprobe=2, max_scan=24,
                              use_kernel=True, exec_mode="paged")
    rk_c = rairs_index.search(qs, k=10, nprobe=2, max_scan=24,
                              use_kernel=True, exec_mode="clustered")
    np.testing.assert_array_equal(np.asarray(rk_p.ids), np.asarray(rk_c.ids))
    np.testing.assert_allclose(np.asarray(rk_p.dists),
                               np.asarray(rk_c.dists), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(rk_p.approx_dco),
                                  np.asarray(rk_c.approx_dco))


def test_clustered_streaming_mutated(small_stream, unit_data):
    stream, _ = small_stream
    _, q, _ = unit_data
    rp = stream.search(q[:32], k=10, nprobe=8, exec_mode="paged")
    rc = stream.search(q[:32], k=10, nprobe=8, exec_mode="clustered")
    _assert_results_identical(rp, rc)


def test_clustered_sharded_matches(rairs_index, unit_data):
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    _, q, _ = unit_data
    params = SearchParams(k=10, nprobe=8, exec_mode="clustered")
    res_l = rairs_index.searcher(params)(q[:32])
    res_s = rairs_index.shard(mesh).searcher(params)(q[:32])
    if len(jax.devices()) == 1:
        _assert_results_identical(res_l, res_s)
    else:
        np.testing.assert_allclose(
            np.sort(np.asarray(res_l.dists), 1),
            np.sort(np.asarray(res_s.dists), 1), rtol=0, atol=0)


# ---------------------------------------------------------------------------
# planner unit invariants
# ---------------------------------------------------------------------------
def test_cluster_order_is_stable_permutation(rairs_index, unit_data):
    _, q, _ = unit_data
    sel = select_lists(q[:64], rairs_index.centroids, nprobe=8,
                       metric="l2").sel
    perm = np.asarray(cluster_order(sel))
    assert sorted(perm.tolist()) == list(range(64))
    # grouped by the full signature prefix, stable within equal prefixes
    sig = np.asarray(sel)[:, :4]
    ordered = sig[perm]
    keys = [tuple(r) for r in ordered]
    assert keys == sorted(keys), "not in signature order"
    for a, b in zip(perm[:-1], perm[1:]):
        if tuple(sig[a]) == tuple(sig[b]):
            assert a < b, "stability violated on equal signatures"


def test_tile_unions_cover_plans(rairs_index, unit_data):
    _, q, _ = unit_data
    selection = select_lists(q[:32], rairs_index.centroids, nprobe=8,
                             metric="l2")
    plan = plan_blocks(tables_from_arrays(rairs_index.arrays), selection,
                       max_scan=4096)
    perm = np.asarray(cluster_order(selection.sel))
    t, w = union_dims(32, plan.blocks.shape[1],
                      rairs_index.arrays.block_codes.shape[0],
                      "clustered", 8)
    unions = np.asarray(tile_unions(jnp.asarray(np.asarray(plan.blocks)[perm]),
                                    jnp.asarray(np.asarray(plan.valid)[perm]),
                                    t, w))
    blocks = np.asarray(plan.blocks)[perm].reshape(t, -1, plan.blocks.shape[1])
    valid = np.asarray(plan.valid)[perm].reshape(blocks.shape)
    for i in range(t):
        live = unions[i][unions[i] < int(BIG)]
        assert (np.diff(live) > 0).all(), "sorted + unique"
        planned = np.unique(blocks[i][valid[i]])
        assert np.isin(planned, live).all()
        assert len(live) == len(planned)     # nothing beyond the tile's plans


def test_merge_unions_host_semantics():
    big = int(BIG)
    a = np.array([[1, 5, 9, big]], np.int64)
    # hit: subset reuses the cache unchanged
    used, hit, ext = merge_unions_host(a, np.array([[5, 9, big, big]],
                                                   np.int64))
    assert hit.all() and not ext.any()
    np.testing.assert_array_equal(used, a)
    # extend: merged fits the width
    used, hit, ext = merge_unions_host(a, np.array([[2, 5, big, big]],
                                                   np.int64))
    assert ext.all() and not hit.any()
    np.testing.assert_array_equal(used[0], [1, 2, 5, 9])
    # miss: merged would overflow -> own union wins (correctness first)
    own = np.array([[2, 3, 4, 6]], np.int64)
    used, hit, ext = merge_unions_host(a, own)
    assert not hit.any() and not ext.any()
    np.testing.assert_array_equal(used, own)
    # cold cache: own, counted as miss by the caller
    used, hit, ext = merge_unions_host(None, own)
    np.testing.assert_array_equal(used, own)
    assert not hit.any() and not ext.any()
    # signature-keyed alignment: a BIG-filled row for a first-seen tile
    # must classify as a miss (not an extend), and still scan own
    pad = np.full((1, 4), big, np.int64)
    own2 = np.array([[5, 7, big, big]], np.int64)
    used, hit, ext = merge_unions_host(
        np.concatenate([a, pad]), np.concatenate([own2, own2]),
        present=np.array([True, False]))
    assert ext[0] and not hit[0]                 # real cache row extends
    assert not hit[1] and not ext[1]             # absent row is a miss
    np.testing.assert_array_equal(used[1], own2[0])
    np.testing.assert_array_equal(used[0], [1, 5, 7, 9])


def test_tile_signatures_follow_working_set():
    """Tiles are named by lead list + run index; a boundary shift keeps
    the keys of the surviving groups identical across batches."""
    assert tile_signatures(np.array([4, 4, 9, 17])) == [
        (4, 0), (4, 1), (9, 0), (17, 0)]
    # popularity drift: list 4 loses a tile, 9 gains one — 9's first
    # tile and 17's tile keep their keys, so their cached unions survive
    assert tile_signatures(np.array([4, 9, 9, 17])) == [
        (4, 0), (9, 0), (9, 1), (17, 0)]


def test_tile_signatures_deep_split_same_lead():
    """At large nprobe the deep key separates tiles that share a hot
    lead list but probe different working sets: the probe prefix beyond
    the lead joins the key, so drift cannot reshuffle their cached
    unions into each other — while tiles with identical prefixes still
    coalesce under run indexing exactly as lead-only keys do."""
    rows = np.array([[4, 7, 2], [4, 11, 5], [4, 7, 2]])
    lead_only = tile_signatures(rows[:, 0])
    assert lead_only == [(4, 0), (4, 1), (4, 2)]       # positional only
    deep = tile_signatures(rows[:, 0], deep=rows)
    # distinct prefixes -> distinct identities; the repeat of (4,(7,2))
    # restarts its own run count instead of inheriting position 2
    assert deep == [(4, (7, 2), 0), (4, (11, 5), 0), (4, (7, 2), 0)]
    # identical consecutive working sets still coalesce by run index
    same = np.array([[4, 7, 2], [4, 7, 2]])
    assert tile_signatures(same[:, 0], deep=same) == [
        (4, (7, 2), 0), (4, (7, 2), 1)]


# ---------------------------------------------------------------------------
# incremental plans (plan_reuse)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("exec_mode", ["grouped", "clustered"])
def test_plan_reuse_bitwise_and_stats(rairs_index, unit_data, exec_mode):
    """Split-pipeline results == fresh monolithic plans; repeated batches
    hit the plan cache and stats surface next to compile stats."""
    _, q, _ = unit_data
    # max_scan pinned: keeps these sessions distinct from the default-
    # params sessions other test files assert fresh stats on
    rp = rairs_index.search(q[:48], k=10, nprobe=8, max_scan=4096,
                            exec_mode="paged")
    s = rairs_index.searcher(SearchParams(
        k=10, nprobe=8, max_scan=4096, exec_mode=exec_mode,
        plan_reuse=True))
    for _ in range(3):
        _assert_results_identical(rp, s(q[:48]), msg=f"{exec_mode} ")
    stats = s.compile_stats()["plan"]
    assert stats["batches"] == 3
    assert stats["hits"] > 0                      # steady state reuses
    assert stats["misses"] >= 1                   # cold cache
    assert stats["mean_union_live"] > 0
    # the dispatched width always covers the live union entries
    assert stats["mean_width"] >= stats["mean_union_live"]


def test_plan_reuse_rejects_paged():
    with pytest.raises(ValueError, match="plan_reuse"):
        SearchParams(exec_mode="paged", plan_reuse=True)


def test_plan_reuse_streaming_and_epoch_bump(small_stream, unit_data):
    """Plan cache lives on the session: mutations stale it with the
    session, and a fresh post-epoch session serves correct plans."""
    stream, x = small_stream
    _, q, _ = unit_data
    params = SearchParams(k=10, nprobe=8, exec_mode="clustered",
                          plan_reuse=True)
    s0 = stream.searcher(params)
    r0 = s0(q[:32])
    _assert_results_identical(
        stream.search(q[:32], k=10, nprobe=8, exec_mode="paged"), r0)
    assert s0.plan_stats.batches == 1

    stream.insert(x[5600:5650])                   # version bump
    with pytest.raises(StaleSessionError):
        s0(q[:32])
    s1 = stream.searcher(params)
    assert s1 is not s0 and s1.plan_stats.batches == 0   # fresh cache
    _assert_results_identical(
        stream.search(q[:32], k=10, nprobe=8, exec_mode="paged"),
        s1(q[:32]), msg="post-insert ")

    stream.compact()                              # epoch bump
    with pytest.raises(StaleSessionError):
        s1(q[:32])
    s2 = stream.searcher(params)
    assert s2.epoch == stream.epoch and s2.plan_stats.batches == 0
    _assert_results_identical(
        stream.search(q[:32], k=10, nprobe=8, exec_mode="paged"),
        s2(q[:32]), msg="post-epoch ")


def test_plan_reuse_probe_survives_capacity_jump(small_stream, unit_data):
    """The probe half consumes only base arrays: a delta capacity-bucket
    jump (which re-lowers the scan half) must reuse the compiled probe
    executable instead of paying a redundant compile."""
    stream, x = small_stream
    _, q, _ = unit_data
    params = SearchParams(k=10, nprobe=8, exec_mode="clustered",
                          plan_reuse=True)
    s0 = stream.searcher(params)
    s0(q[:32])
    before = dict(stream._probe_cache[s0.params])
    assert before                                 # probe compiled
    cap0 = stream._delta.capacity
    stream.insert(x[5500:6000])                   # 500 -> 1000 slots
    assert stream._delta.capacity > cap0          # bucket jump
    s1 = stream.searcher(params)
    _assert_results_identical(
        stream.search(q[:32], k=10, nprobe=8, exec_mode="paged"),
        s1(q[:32]), msg="post-jump ")
    after = stream._probe_cache[s1.params]
    for key, exe in before.items():
        assert after[key] is exe                  # shared, not recompiled
    assert 32 in s1.buckets                       # probe store reported


# ---------------------------------------------------------------------------
# routed delta scans
# ---------------------------------------------------------------------------
@pytest.fixture()
def routed_pair(unit_data, shared_trained):
    """Two streams over the same base corpus + churn: one exhaustive
    (huge threshold), one routed from the first insert."""
    x, _, _ = unit_data
    cents, cb = shared_trained
    streams = []
    for route_min in (10 ** 9, 0):
        cfg = IndexConfig(nlist=64, strategy="rair", seil=True,
                          kmeans_iters=8, pq_iters=6,
                          delta_route_min=route_min)
        base = build_index(jax.random.PRNGKey(0), x[:5000], cfg,
                           centroids=cents, codebook=cb)
        st = StreamingIndex(base)
        ids = st.insert(x[5000:5600])
        st.delete(ids[:64])
        st.delete(np.arange(32))
        streams.append(st)
    exhaustive, routed = streams
    assert not exhaustive.delta_routed and routed.delta_routed
    return exhaustive, routed


def test_routed_delta_bitwise_at_full_probe(routed_pair, unit_data):
    """With every list probed, routing reaches every live delta item:
    results identical to the exhaustive path (ids and distances)."""
    _, q, _ = unit_data
    exhaustive, routed = routed_pair
    re_ = exhaustive.search(q[:48], k=10, nprobe=64)
    rr = routed.search(q[:48], k=10, nprobe=64)
    np.testing.assert_array_equal(np.asarray(re_.ids), np.asarray(rr.ids))
    np.testing.assert_array_equal(np.asarray(re_.dists),
                                  np.asarray(rr.dists))
    # routing computes each reachable live slot exactly once -> identical
    # logical DCO at full probe depth
    np.testing.assert_array_equal(np.asarray(re_.approx_dco),
                                  np.asarray(rr.approx_dco))


def test_routed_delta_reduces_dco(routed_pair, unit_data):
    _, q, _ = unit_data
    exhaustive, routed = routed_pair
    de = np.asarray(exhaustive.search(q[:48], k=10, nprobe=8).approx_dco)
    dr = np.asarray(routed.search(q[:48], k=10, nprobe=8).approx_dco)
    assert dr.mean() < de.mean()


def test_routed_delta_items_retrievable(routed_pair, unit_data):
    x, _, _ = unit_data
    _, routed = routed_pair
    probe = x[5100][None, :]
    r = routed.search(probe, k=1, nprobe=16)
    assert int(np.asarray(r.ids)[0, 0]) == 5100


def test_routing_threshold_activates_on_capacity(unit_data, shared_trained):
    """Auto threshold: the delta routes only once its capacity bucket
    outgrows delta_route_min (static per-bucket property)."""
    x, _, _ = unit_data
    cents, cb = shared_trained
    cfg = IndexConfig(nlist=64, strategy="rair", seil=True,
                      kmeans_iters=8, pq_iters=6, delta_route_min=256)
    base = build_index(jax.random.PRNGKey(0), x[:5000], cfg,
                       centroids=cents, codebook=cb)
    st = StreamingIndex(base)
    assert st.delta_route_threshold == 256
    st.insert(x[5000:5100])          # capacity 256 == threshold -> exhaustive
    assert not st.delta_routed
    st.insert(x[5100:5400])          # capacity 512 > threshold -> routed
    assert st.delta_routed
    # default: nlist * block
    st2 = StreamingIndex(build_index(
        jax.random.PRNGKey(0), x[:5000],
        dataclasses.replace(cfg, delta_route_min=None),
        centroids=cents, codebook=cb))
    assert st2.delta_route_threshold == 64 * 32
    # explicit threshold is final: sessions route even at probe depths
    # where the padded gather would be dearer than the exhaustive scan
    assert st.routes_at(64)


def test_auto_routing_cost_guard(unit_data, shared_trained):
    """Auto threshold only: a hot-list-skewed delta grows the posting
    width until the routed gather (~nprobe x post_width rows/query)
    costs more than the exhaustive scan — the session then keeps the
    exhaustive fast path, and results stay correct."""
    x, _, _ = unit_data
    cents, cb = shared_trained
    cfg = IndexConfig(nlist=64, strategy="rair", seil=True,
                      kmeans_iters=8, pq_iters=6)          # auto threshold
    base = build_index(jax.random.PRNGKey(0), x[:5000], cfg,
                       centroids=cents, codebook=cb)
    st = StreamingIndex(base)
    rng = np.random.default_rng(7)
    hot = np.asarray(x[5000])[None, :] + rng.normal(
        0, 1e-3, (2200, x.shape[1])).astype(np.float32)    # one hot list
    st.insert(hot)
    assert st.delta_routed                  # capacity gate fires...
    assert st._delta.post_width * 8 > st._delta.capacity
    assert not st.routes_at(8)              # ...but routing would cost more
    r = st.search(np.asarray(x[5000])[None, :], k=1, nprobe=8)
    assert int(np.asarray(r.ids)[0, 0]) >= 5000    # delta item found


def test_routed_postings_follow_restore(unit_data, shared_trained,
                                        tmp_path):
    """Posting maps rebuild on bundle load: a restored routed stream
    searches identically to the in-memory one."""
    import os

    from repro.core import load_index, save_index
    x, q, _ = unit_data
    cents, cb = shared_trained
    cfg = IndexConfig(nlist=64, strategy="rair", seil=True,
                      kmeans_iters=8, pq_iters=6, delta_route_min=0)
    base = build_index(jax.random.PRNGKey(0), x[:5000], cfg,
                       centroids=cents, codebook=cb)
    st = StreamingIndex(base)
    st.insert(x[5000:5300])
    st.delete([5005, 17])
    path = os.path.join(tmp_path, "routed.npz")
    save_index(st, path)
    restored = load_index(path)
    assert restored.delta_routed
    _assert_results_identical(st.search(q[:24], k=10, nprobe=8),
                              restored.search(q[:24], k=10, nprobe=8))


# ---------------------------------------------------------------------------
# derived per-device budget (sharded)
# ---------------------------------------------------------------------------
def test_derived_max_scan_local_recall_neutral(rairs_index, unit_data):
    """The occupancy-derived per-device budget must never truncate: same
    results as an un-truncating explicit budget, with a tighter bound."""
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    _, q, _ = unit_data
    params = SearchParams(k=10, nprobe=8)
    derived = rairs_index.shard(mesh).searcher(params)
    wide = rairs_index.shard(mesh, max_scan_local=4096).searcher(params)
    _assert_results_identical(wide(q[:32]), derived(q[:32]))
    assert derived.max_scan_local <= derived.params.max_scan
    bound = rairs_index.shard(mesh).derived_max_scan_local(8)
    assert derived.max_scan_local == min(derived.params.max_scan, bound)


def test_sharded_rejects_plan_reuse(rairs_index):
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    with pytest.raises(ValueError, match="plan_reuse"):
        rairs_index.shard(mesh).searcher(
            SearchParams(exec_mode="clustered", plan_reuse=True))


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------
@pytest.fixture()
def small_stream(unit_data, shared_trained):
    x, _, _ = unit_data
    cents, cb = shared_trained
    cfg = IndexConfig(nlist=64, strategy="rair", seil=True,
                      kmeans_iters=8, pq_iters=6)
    base = build_index(jax.random.PRNGKey(0), x[:5000], cfg,
                       centroids=cents, codebook=cb)
    stream = StreamingIndex(base)
    ids = stream.insert(x[5000:5500])
    stream.delete(ids[:40])
    stream.delete(np.arange(20))
    return stream, x


# ---------------------------------------------------------------------------
# property test: plan equivalence across modes/paths (needs hypothesis)
# ---------------------------------------------------------------------------
@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 2 ** 16),
       nprobe=st.sampled_from([2, 4, 8, 16]),
       exec_mode=st.sampled_from(["grouped", "clustered"]),
       query_tile=st.sampled_from([1, 4, 8, 16]),
       mutate=st.booleans())
def test_plan_equivalence_property(seed, nprobe, exec_mode, query_tile,
                                   mutate):
    """Clustered + incremental plans == fresh batch-wide plans, bitwise,
    across exec modes and frozen/streaming/sharded paths — including
    across a mutation epoch bump (stale plan caches must die with their
    sessions)."""
    from repro.data import make_dataset
    x, q, _ = make_dataset("unit")
    rng = np.random.default_rng(seed)
    qs = jnp.asarray(np.asarray(q)[rng.choice(len(q), 32, replace=False)])
    cfg = IndexConfig(nlist=32, strategy="rair", seil=True,
                      kmeans_iters=4, pq_iters=4, delta_route_min=64)
    base = build_index(jax.random.PRNGKey(0), jnp.asarray(x[:2000]), cfg)
    params = SearchParams(k=10, nprobe=nprobe, exec_mode=exec_mode,
                          query_tile=query_tile, plan_reuse=True)
    paged = dataclasses.replace(params, exec_mode="paged",
                                plan_reuse=False)

    index = base.streaming() if mutate else base
    if mutate:
        ids = index.insert(x[2000:2000 + int(rng.integers(50, 300))])
        index.delete(ids[:10])
        index.delete(rng.choice(2000, 25, replace=False))

    ref = index.searcher(paged)(qs)
    sess = index.searcher(params)
    for _ in range(2):                       # second pass rides the cache
        _assert_results_identical(ref, sess(qs), msg="single-host ")

    if mutate:                               # epoch bump invalidates plans
        index.compact()
        with pytest.raises(StaleSessionError):
            sess(qs)
        ref2 = index.searcher(paged)(qs)
        _assert_results_identical(ref2, index.searcher(params)(qs),
                                  msg="post-compact ")
    else:                                    # frozen path rides a mesh too
        mesh = jax.make_mesh((len(jax.devices()),), ("data",))
        sharded = index.shard(mesh)
        rs = sharded.searcher(dataclasses.replace(params, plan_reuse=False)
                              )(qs)
        if sharded.ndev == 1:
            _assert_results_identical(ref, rs, msg="sharded ")


# ---------------------------------------------------------------------------
# a serving trace: Zipf-popular queries against a uniform control
# ---------------------------------------------------------------------------
SKEW_BATCH, SKEW_BATCHES, SKEW_TILE, SKEW_NPROBE = 256, 12, 16, 16


@pytest.fixture(scope="module")
def skewed_streams(unit_data):
    """Two traces of 12 batches of 256 queries over the unit query pool:
    ``uniform`` draws iid; ``zipf`` draws from 16 hot queries with
    Zipf(1.1) popularity (the head of a search-query distribution).
    Every draw gets Gaussian jitter at 2% of the pool's scale, so
    batches are near-duplicates, not exact repeats."""
    _, q, _ = unit_data
    rng = np.random.default_rng(0)
    pool = np.asarray(q)
    scale = float(pool.std()) * 0.02
    p = 1.0 / np.arange(1, 17) ** 1.1
    p /= p.sum()
    streams = {"uniform": [], "zipf": []}
    for _ in range(SKEW_BATCHES):
        for name, picks in (
                ("uniform", rng.integers(0, pool.shape[0], SKEW_BATCH)),
                ("zipf", rng.choice(16, SKEW_BATCH, p=p))):
            qb = pool[picks] + rng.normal(0.0, scale,
                                          (SKEW_BATCH, pool.shape[1]))
            streams[name].append(jnp.asarray(qb, jnp.float32))
    return streams


def _skew_session(index, exec_mode, plan_reuse=False):
    return Searcher(index, SearchParams(
        k=10, nprobe=SKEW_NPROBE, exec_mode=exec_mode, plan_reuse=plan_reuse,
        query_tile=SKEW_TILE, batch_buckets=(SKEW_BATCH,)))


@pytest.mark.parametrize("stream", ["uniform", "zipf"])
def test_skewed_stream_tile_unions_cut_the_batch_union(rairs_index,
                                                       skewed_streams,
                                                       stream):
    """Clustered execution's per-tile block unions are a strict cut of
    the batch-wide union the grouped mode scans, on both traces."""
    for qb in skewed_streams[stream][:4]:
        selection = select_lists(qb, rairs_index.centroids,
                                 nprobe=SKEW_NPROBE)
        plan = plan_blocks(tables_from_arrays(rairs_index.arrays), selection,
                           max_scan=rairs_index.default_max_scan(SKEW_NPROBE))
        blocks, valid = np.asarray(plan.blocks), np.asarray(plan.valid)
        batch_live = len(np.unique(blocks[valid]))
        perm = np.asarray(cluster_order(selection.sel))
        t = SKEW_BATCH // SKEW_TILE
        pb = blocks[perm].reshape(t, SKEW_TILE, -1)
        pv = valid[perm].reshape(t, SKEW_TILE, -1)
        tile_live = np.mean([len(np.unique(pb[i][pv[i]])) for i in range(t)])
        assert tile_live < batch_live, (stream, tile_live, batch_live)


@pytest.mark.parametrize("stream", ["uniform", "zipf"])
def test_skewed_stream_plan_cache_accounting(rairs_index, skewed_streams,
                                             stream):
    """Over a whole trace, hit / extend / miss partition the tiles the
    plan cache saw, and it reuses at least one; on the Zipf trace most
    tiles hit at steady state."""
    sess = _skew_session(rairs_index, "clustered", plan_reuse=True)
    for qb in skewed_streams[stream]:
        sess(qb)
    p = sess.compile_stats()["plan"]
    assert p["batches"] == SKEW_BATCHES
    assert p["tiles"] == SKEW_BATCHES * SKEW_BATCH // SKEW_TILE
    assert p["hits"] + p["extends"] + p["misses"] == p["tiles"], p
    assert p["hits"] + p["extends"] > 0, p
    if stream == "zipf":
        assert p["hit_rate"] > 0.5, p


@pytest.mark.parametrize("stream", ["uniform", "zipf"])
def test_skewed_stream_exec_modes_agree(rairs_index, skewed_streams, stream):
    """Paged, grouped and clustered-with-plan-reuse sessions return the
    same ids and count the same ADC distance computations on every
    batch of the trace."""
    sessions = [_skew_session(rairs_index, "paged"),
                _skew_session(rairs_index, "grouped"),
                _skew_session(rairs_index, "clustered", plan_reuse=True)]
    for qb in skewed_streams[stream]:
        ref, *rest = [s(qb) for s in sessions]
        for r in rest:
            np.testing.assert_array_equal(np.asarray(r.ids),
                                          np.asarray(ref.ids))
            np.testing.assert_array_equal(np.asarray(r.approx_dco),
                                          np.asarray(ref.approx_dco))
