"""Two-tier quantization ladder (DESIGN.md §12, repro/quant/).

The load-bearing invariants:

  * ``refine_factor=1`` (or ``plane='full'`` at rf=1, or no refine) is
    *bitwise* the single-tier path — the compiled program is literally
    today's, so turning the feature off can never change an answer;
  * the three exec modes agree bitwise under any plane, like they do
    single-tier;
  * pure widening (``plane='full'``, rf>1) re-ranks a superset of the
    single-tier candidate set with exact distances, so recall@k is
    monotone in the refine factor — the hypothesis property;
  * the ladder composes with every session type (frozen / streaming /
    sharded), the kernel + fused path, plan reuse, and compaction
    (codec carried, re-encode bitwise).
"""
import functools

import jax
import numpy as np
import pytest
from hypothesis_stub import given, settings, st

from repro.core import (IndexConfig, RefineParams, SearchParams,
                        StaleSessionError, build_index, recall_at_k)
from repro.quant import (PLANE_BACKENDS, compact_subdim, encode_plane,
                         pack_nibbles, packed_width, train_plane,
                         unpack_nibbles)

EXEC_MODES = ("paged", "grouped", "clustered")


def _ref(plane="binary", rf=4):
    return RefineParams(plane=plane, refine_factor=rf)


# ---------------------------------------------------------------------------
# params surface
# ---------------------------------------------------------------------------

def test_refine_params_validation():
    with pytest.raises(ValueError, match="plane"):
        RefineParams(plane="int8")
    with pytest.raises(ValueError, match="refine_factor"):
        RefineParams(plane="pq4", refine_factor=0)
    p = SearchParams(k=10, nprobe=8, refine=_ref("pq4", 4))
    assert p.bigk_eff == 4 * p.bigk
    assert p.active_plane == "pq4"
    # rf=1 and the 'full' plane run the exact single-tier program
    assert SearchParams(k=10, nprobe=8,
                        refine=_ref("pq4", 1)).active_plane is None
    assert SearchParams(k=10, nprobe=8,
                        refine=_ref("full", 4)).active_plane is None
    assert SearchParams(k=10, nprobe=8).bigk_eff == \
        SearchParams(k=10, nprobe=8).bigk


def test_plane_cache_and_validation(rairs_index):
    with pytest.raises(ValueError, match="backend"):
        rairs_index.plane("int8")
    p1 = rairs_index.plane("pq4")
    assert rairs_index.plane("pq4") is p1           # cached per backend
    # carried codec: identical codec object -> cache hit, not a rebuild
    assert rairs_index.plane("pq4", codec=p1.codec) is p1
    mc = compact_subdim(32)
    assert p1.m == 32 // mc and p1.ksub == 16
    assert p1.block_codes.shape[-1] == packed_width(p1.m)


# ---------------------------------------------------------------------------
# frozen sessions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("exec_mode", EXEC_MODES)
@pytest.mark.parametrize("plane", PLANE_BACKENDS + ("full",))
def test_rf1_bitwise_identical(rairs_index, unit_data, exec_mode, plane):
    """Acceptance: refine_factor=1 is bitwise the single-tier path."""
    _, q, _ = unit_data
    base = rairs_index.searcher(
        SearchParams(k=10, nprobe=16, exec_mode=exec_mode))(q[:48])
    rf1 = rairs_index.searcher(
        SearchParams(k=10, nprobe=16, exec_mode=exec_mode,
                     refine=_ref(plane, 1)))(q[:48])
    for f in base._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(base, f)), np.asarray(getattr(rf1, f)),
            err_msg=f)


@pytest.mark.parametrize("plane", PLANE_BACKENDS)
def test_two_tier_exec_modes_agree(rairs_index, unit_data, plane):
    _, q, _ = unit_data
    res = [rairs_index.searcher(
        SearchParams(k=10, nprobe=16, exec_mode=em, refine=_ref(plane, 4))
        )(q[:48]) for em in EXEC_MODES]
    for r in res[1:]:
        np.testing.assert_array_equal(np.asarray(res[0].ids),
                                      np.asarray(r.ids))
        np.testing.assert_array_equal(np.asarray(res[0].dists),
                                      np.asarray(r.dists))


def test_two_tier_recall_and_widening(rairs_index, unit_data):
    """binary tier-1 at rf=4 stays close to single-tier recall; pure
    widening (plane='full') can only improve it (superset re-rank)."""
    _, q, gt = unit_data
    p0 = SearchParams(k=10, nprobe=16)
    r0 = recall_at_k(np.asarray(rairs_index.searcher(p0)(q).ids), gt)
    r_bin = recall_at_k(np.asarray(rairs_index.searcher(
        SearchParams(k=10, nprobe=16, refine=_ref("binary", 4)))(q).ids), gt)
    r_full = recall_at_k(np.asarray(rairs_index.searcher(
        SearchParams(k=10, nprobe=16, refine=_ref("full", 4)))(q).ids), gt)
    assert r_bin >= r0 - 0.02, (r_bin, r0)
    assert r_full >= r0, (r_full, r0)


def test_two_tier_kernel_fused_parity(rairs_index, unit_data):
    """The Pallas scan->top-k path scans the packed plane in VMEM and
    returns the same ids as the jnp reference (exact tier-2 absorbs
    tier-1 rounding differences)."""
    _, q, _ = unit_data
    ref = _ref("pq4", 4)
    rj = rairs_index.searcher(
        SearchParams(k=10, nprobe=16, exec_mode="clustered",
                     refine=ref))(q[:32])
    rk = rairs_index.searcher(
        SearchParams(k=10, nprobe=16, exec_mode="clustered", use_kernel=True,
                     refine=ref))(q[:32])
    np.testing.assert_array_equal(np.asarray(rj.ids), np.asarray(rk.ids))


def test_two_tier_plan_reuse_parity(rairs_index, unit_data):
    """Incremental plans compose with the ladder; the deep-signature
    split counter (satellite: smarter plan signatures) is reported."""
    _, q, _ = unit_data
    ref = _ref("binary", 4)
    pp = SearchParams(k=10, nprobe=16, exec_mode="clustered",
                      plan_reuse=True, refine=ref)
    sess = rairs_index.searcher(pp)
    rp = sess(q[:48])
    rm = rairs_index.searcher(
        SearchParams(k=10, nprobe=16, exec_mode="clustered", refine=ref)
        )(q[:48])
    np.testing.assert_array_equal(np.asarray(rp.ids), np.asarray(rm.ids))
    np.testing.assert_array_equal(np.asarray(rp.dists), np.asarray(rm.dists))
    plan = sess.compile_stats()["plan"]
    assert plan["sig_deep_split"] >= 0


def test_two_tier_dco_split(rairs_index, unit_data):
    """Tier-1 scans the same candidate count; tier-2 rescoring widens
    with the refine factor — the split the traffic model reports."""
    from repro.obs.stats import session_traffic_model
    _, q, _ = unit_data
    s0 = rairs_index.searcher(SearchParams(k=10, nprobe=16))
    s2 = rairs_index.searcher(
        SearchParams(k=10, nprobe=16, refine=_ref("pq4", 4)))
    r0, r2 = s0(q[:32]), s2(q[:32])
    assert np.asarray(r2.approx_dco).sum() == np.asarray(r0.approx_dco).sum()
    assert np.asarray(r2.refine_dco).sum() > np.asarray(r0.refine_dco).sum()
    model = session_traffic_model(s2)["refine"]
    assert model["plane"] == "pq4" and model["bigk_eff"] == 4 * model["bigk"]
    assert model["m_compact"] < model["m_full"]
    assert model["total_ops"] < model["single_tier_ops"]
    assert "refine" not in session_traffic_model(s0)


# ---------------------------------------------------------------------------
# streaming sessions
# ---------------------------------------------------------------------------

@pytest.fixture()
def fresh_stream(unit_data):
    x, q, _ = unit_data
    cfg = IndexConfig(nlist=64, strategy="rair", seil=True,
                      kmeans_iters=8, pq_iters=6)
    base = build_index(jax.random.PRNGKey(0), x[:5600], cfg)
    return base.streaming(), x, q


def test_streaming_two_tier(fresh_stream):
    stream, x, q = fresh_stream
    ref = _ref("binary", 4)
    p_two = SearchParams(k=10, nprobe=16, refine=ref)
    p_one = SearchParams(k=10, nprobe=16)
    # pristine epoch: rf=1 delegates to the base session bitwise
    r0 = stream.searcher(p_one)(q)
    r1 = stream.searcher(SearchParams(k=10, nprobe=16,
                                      refine=_ref("binary", 1)))(q)
    np.testing.assert_array_equal(np.asarray(r0.ids), np.asarray(r1.ids))
    stream.searcher(p_two)(q)
    codec0 = stream._plane_codecs["binary"]

    # mutations: new items reachable through the plane path, dead masked
    ids = stream.insert(x[5600:5800])
    stream.delete(np.arange(60))
    got = np.asarray(stream.searcher(p_two)(q).ids)
    assert not (set(got[got >= 0].tolist()) & set(range(60)))
    assert set(got[got >= 0].tolist()) & set(ids.tolist()), \
        "inserted items never surfaced through the two-tier path"
    r0 = stream.searcher(p_one)(q)
    r1 = stream.searcher(SearchParams(k=10, nprobe=16,
                                      refine=_ref("binary", 1)))(q)
    np.testing.assert_array_equal(np.asarray(r0.ids), np.asarray(r1.ids))
    np.testing.assert_array_equal(np.asarray(r0.dists), np.asarray(r1.dists))

    # compaction carries the codec: the rebuilt epoch re-encodes with
    # the pinned codec instead of retraining (bitwise plane continuity)
    stream.compact()
    stream.searcher(p_two)(q)
    assert stream._plane_codecs["binary"] is codec0
    assert stream.base.plane("binary").codec is codec0

    # sessions pin versions exactly like single-tier ones
    sess = stream.searcher(p_two)
    stream.insert(x[:4])
    with pytest.raises(StaleSessionError):
        sess(q[:8])
    stream.searcher(p_two)(q[:8])


# ---------------------------------------------------------------------------
# sharded sessions
# ---------------------------------------------------------------------------

def test_sharded_two_tier(rairs_index, unit_data):
    """1-device mesh: the serve-step ladder is bitwise the local one."""
    _, q, _ = unit_data
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    sharded = rairs_index.shard(mesh)
    for ref in (_ref("pq4", 1), _ref("binary", 4)):
        p = SearchParams(k=10, nprobe=16, refine=ref)
        r_l = rairs_index.searcher(p)(q[:32])
        r_s = sharded.searcher(p)(q[:32])
        if sharded.ndev == 1:
            np.testing.assert_array_equal(np.asarray(r_l.ids),
                                          np.asarray(r_s.ids))
            np.testing.assert_array_equal(np.asarray(r_l.dists),
                                          np.asarray(r_s.dists))
        else:
            np.testing.assert_array_equal(
                np.sort(np.asarray(r_l.dists), 1),
                np.sort(np.asarray(r_s.dists), 1))


# ---------------------------------------------------------------------------
# nibble layout + backends
# ---------------------------------------------------------------------------

def test_nibble_roundtrip_exhaustive():
    rng = np.random.default_rng(0)
    for m in (1, 2, 3, 4, 7, 8, 16):
        codes = rng.integers(0, 16, size=(5, 9, m), dtype=np.uint8)
        packed = pack_nibbles(codes)
        assert packed.shape[-1] == packed_width(m) == (m + 1) // 2
        np.testing.assert_array_equal(
            np.asarray(unpack_nibbles(packed, m)), codes)


def test_binary_backend_is_sign_code():
    """Nearest-corner encoding of the virtual codebook is exactly the
    per-dimension sign bit against the corpus mean."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(512, 16)).astype(np.float32) * \
        rng.uniform(0.5, 2.0, size=16).astype(np.float32)
    codec = train_plane("binary", jax.random.PRNGKey(0), x)
    codes = encode_plane(codec, x)
    bits = (x > x.mean(axis=0)).astype(np.uint8).reshape(512, 4, 4)
    expect = (bits << np.arange(4)[None, None, :]).sum(-1).astype(np.uint8)
    np.testing.assert_array_equal(codes, expect)


@functools.lru_cache(maxsize=None)
def _tiny_index(seed: int):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(8, 16)).astype(np.float32) * 3.0
    x = (centers[rng.integers(0, 8, 1200)]
         + rng.normal(size=(1200, 16)).astype(np.float32) * 0.5)
    q = (centers[rng.integers(0, 8, 24)]
         + rng.normal(size=(24, 16)).astype(np.float32) * 0.5)
    cfg = IndexConfig(nlist=16, block=16, strategy="rair", seil=True,
                      kmeans_iters=4, pq_iters=4)
    idx = build_index(jax.random.PRNGKey(seed), x, cfg)
    from repro.core import ground_truth
    gt = np.asarray(ground_truth(idx.vectors, q, 10))
    return idx, q, gt


# satellite: hypothesis property — two-tier recall@k with a pure
# widening plane is >= single-tier recall at equal k (the widened
# survivor set is a superset and tier-2 re-ranks it exactly).
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 3), nprobe=st.sampled_from([2, 4, 8]),
       rf=st.sampled_from([2, 4, 8]),
       exec_mode=st.sampled_from(list(EXEC_MODES)))
def test_property_widening_recall_monotone(seed, nprobe, rf, exec_mode):
    idx, q, gt = _tiny_index(seed)
    base = recall_at_k(np.asarray(idx.searcher(
        SearchParams(k=10, nprobe=nprobe, exec_mode=exec_mode))(q).ids), gt)
    wide = recall_at_k(np.asarray(idx.searcher(
        SearchParams(k=10, nprobe=nprobe, exec_mode=exec_mode,
                     refine=_ref("full", rf)))(q).ids), gt)
    assert wide >= base, (wide, base, seed, nprobe, rf, exec_mode)


def test_widening_recall_monotone_deterministic():
    """The property above at fixed points (runs without hypothesis)."""
    for seed in (0, 1):
        idx, q, gt = _tiny_index(seed)
        for nprobe in (2, 8):
            base = recall_at_k(np.asarray(idx.searcher(
                SearchParams(k=10, nprobe=nprobe))(q).ids), gt)
            for rf in (2, 4):
                wide = recall_at_k(np.asarray(idx.searcher(
                    SearchParams(k=10, nprobe=nprobe,
                                 refine=_ref("full", rf)))(q).ids), gt)
                assert wide >= base, (seed, nprobe, rf, wide, base)
