"""Pallas kernel validation: shape/dtype sweep vs the pure-jnp oracle
(interpret mode executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_stub import given, settings, st

from jax.experimental import pallas as pl
from jax.extend import core as jcore

from repro.kernels.ops import (_align, pq_scan_grouped, pq_scan_paged,
                               pq_scan_tiled)
from repro.kernels.pq_scan import (_kernel_lut, _score_block,
                                   blocks_per_step, pq_scan_tiled_kernel,
                                   pq_scan_topk_kernel)
from repro.kernels.ref import onehot_lut_ref, pq_scan_paged_ref
from repro.quant.nibbles import pack_nibbles


@pytest.mark.parametrize("b,m,k,tb,blk,s", [
    (1, 4, 16, 3, 32, 2),
    (4, 8, 16, 10, 32, 6),
    (8, 64, 16, 32, 32, 5),
    (2, 16, 16, 7, 128, 3),
    (2, 32, 8, 5, 64, 4),     # 3-bit-table variant
    (16, 2, 16, 4, 32, 1),
    (3, 8, 16, 9, 32, 7),     # 7 positions at 4 blocks per grid step
    (2, 8, 16, 6, 8, 5),      # 16 blocks of 8 per step, 11 padding
])
def test_pq_scan_paged_matches_ref(b, m, k, tb, blk, s):
    key = jax.random.PRNGKey(b * 131 + m)
    k1, k2, k3 = jax.random.split(key, 3)
    lut = jax.random.normal(k1, (b, m, k), jnp.float32)
    codes = jax.random.randint(k2, (tb, blk, m), 0, k).astype(jnp.uint8)
    idx = jax.random.randint(k3, (b, s), 0, tb, jnp.int32)
    out = pq_scan_paged(lut, codes, idx)
    ref = pq_scan_paged_ref(lut, codes, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pq_scan_dtypes(dtype):
    key = jax.random.PRNGKey(7)
    k1, k2, k3 = jax.random.split(key, 3)
    lut = jax.random.normal(k1, (4, 8, 16), jnp.float32).astype(dtype)
    codes = jax.random.randint(k2, (6, 32, 8), 0, 16).astype(jnp.uint8)
    idx = jax.random.randint(k3, (4, 3), 0, 6, jnp.int32)
    out = pq_scan_paged(lut.astype(jnp.float32), codes, idx)
    ref = pq_scan_paged_ref(lut.astype(jnp.float32), codes, idx)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)


def test_grouped_mode_query_tiles():
    key = jax.random.PRNGKey(9)
    k1, k2, k3 = jax.random.split(key, 3)
    b, m, kk, tb, blk, s = 8, 16, 16, 12, 32, 7
    lut = jax.random.normal(k1, (b, m, kk), jnp.float32)
    codes = jax.random.randint(k2, (tb, blk, m), 0, kk).astype(jnp.uint8)
    sidx = jax.random.randint(k3, (s,), 0, tb, jnp.int32)
    for qt in (1, 2, 4, 8):
        out = pq_scan_grouped(lut, codes, sidx, query_tile=qt)
        ref = pq_scan_paged_ref(lut, codes,
                                jnp.broadcast_to(sidx[None], (b, s)))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_tiled_mode_per_tile_lists():
    """pq_scan_tiled: each query tile pages its own (tile-padded) scan
    list through the scalar-prefetched index_map — the clustered exec
    mode's kernel path, validated in interpret mode on CPU against the
    per-query oracle fed the tile-broadcast lists."""
    key = jax.random.PRNGKey(13)
    k1, k2, k3 = jax.random.split(key, 3)
    b, m, kk, tb, blk, w = 16, 8, 16, 20, 32, 5
    lut = jax.random.normal(k1, (b, m, kk), jnp.float32)
    codes = jax.random.randint(k2, (tb, blk, m), 0, kk).astype(jnp.uint8)
    for qt in (1, 2, 4, 8, 16):
        tiles = b // qt
        tile_idx = jax.random.randint(k3, (tiles, w), 0, tb, jnp.int32)
        out = pq_scan_tiled(lut, codes, tile_idx, query_tile=qt)
        full = jnp.repeat(tile_idx, qt, axis=0)          # (B, W) broadcast
        ref = pq_scan_paged_ref(lut, codes, full)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("blk,g", [(8, 16), (32, 4), (64, 2), (128, 1),
                                   (256, 1)])
def test_blocks_per_step(blk, g):
    """A grid step of either scan kernel pages the blocks that fill the
    128 lanes: four at the deployments' block 32, one from 128 up."""
    assert blocks_per_step(blk) == g


def _score_one_block(lut, codes, packed):
    """``_score_block`` over one code block, interpreted: lut (QT, M, K),
    codes (BLK, MB) -> (QT, BLK), fed the flat LUT the kernels read."""
    qt, blk = lut.shape[0], codes.shape[0]

    def body(lut_ref, codes_ref, out_ref):
        out_ref[...] = _score_block(lut_ref, (codes_ref,), packed)

    return pl.pallas_call(
        body, out_shape=jax.ShapeDtypeStruct((qt, blk), jnp.float32),
        interpret=True)(_kernel_lut(lut, packed), codes[None])


@pytest.mark.parametrize("qt", [1, 4, 8])
@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("m,m_kernel", [(16, 16), (64, 64), (64, 128)])
@pytest.mark.parametrize("blk", [32, 128])
def test_onehot_identity_vs_gather(blk, m, m_kernel, packed, qt):
    """The kernel's one-hot contraction (codes spread to lanes, flat LUT)
    is exactly the LUT gather, and the one-hot oracle.  ``m_kernel`` 128
    is the chip's width: 64 subspaces zero-padded by ``ops._align``."""
    key = jax.random.PRNGKey(11 + blk + m_kernel + qt)
    k1, k2 = jax.random.split(key)
    lut = jax.random.normal(k1, (qt, m, 16), jnp.float32)
    codes = jax.random.randint(k2, (blk, m), 0, 16, jnp.int32)
    plane = (jnp.asarray(pack_nibbles(np.asarray(codes, np.uint8)))
             if packed else codes.astype(jnp.uint8))
    klut, kcodes = _align(lut, plane[None], packed,
                          on_tpu=m_kernel > m)
    assert klut.shape[1] == m_kernel, klut.shape
    out = np.asarray(_score_one_block(klut, kcodes[0], packed))
    gather = lut[:, jnp.arange(m)[None, :], codes].sum(-1)     # (QT, BLK)
    for q in range(qt):
        np.testing.assert_allclose(
            out[q], np.asarray(onehot_lut_ref(lut[q], codes)),
            rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out, np.asarray(gather), rtol=1e-5,
                               atol=1e-5)


def _kernel_jaxprs(closed):
    """Every jaxpr nested in a pallas_call of ``closed``."""
    found = []

    def walk(jaxpr, inside):
        if inside:
            found.append(jaxpr)
        for eqn in jaxpr.eqns:
            for sub in eqn.params.values():
                if isinstance(sub, (jcore.Jaxpr, jcore.ClosedJaxpr)):
                    walk(getattr(sub, "jaxpr", sub),
                         inside or eqn.primitive.name == "pallas_call")

    walk(closed.jaxpr, False)
    return found


@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("fused", [False, True])
def test_kernel_bodies_merge_no_lanes(fused, packed):
    """No reshape inside either kernel body changes the minor (lane)
    dimension: a lane-merging reshape of the one-hot or the LUT lowers
    on TPU as sublane rotates and shuffles on every grid step."""
    b, m, kk, tb, blk, s, nlist = 8, 16, 16, 4, 32, 3, 8
    mb = m // 2 if packed else m
    lut = jnp.zeros((b, m, kk), jnp.float32)
    codes = jnp.zeros((tb, blk, mb), jnp.uint8)
    idx = jnp.zeros((b, s), jnp.int32)
    if fused:
        plane = jnp.zeros((tb, blk), jnp.int32)
        closed = jax.make_jaxpr(lambda *a: pq_scan_topk_kernel(
            *a, query_tile=1, fetch=16, interpret=True, packed=packed))(
            lut, codes, plane, plane, idx, jnp.zeros((b, nlist), jnp.int32),
            idx, idx)
    else:
        closed = jax.make_jaxpr(lambda *a: pq_scan_tiled_kernel(
            *a, query_tile=1, interpret=True, packed=packed))(lut, codes, idx)
    bodies = _kernel_jaxprs(closed)
    assert bodies
    for jaxpr in bodies:
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "reshape":
                (src,), (dst,) = eqn.invars, eqn.outvars
                assert src.aval.shape[-1] == dst.aval.shape[-1], eqn


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10 ** 6), m=st.sampled_from([2, 4, 8, 16]),
       blk=st.sampled_from([8, 32]), s=st.integers(1, 6),
       b=st.sampled_from([1, 2, 4]))
def test_property_pq_scan(seed, m, blk, s, b):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    tb = 8
    lut = jax.random.normal(k1, (b, m, 16), jnp.float32)
    codes = jax.random.randint(k2, (tb, blk, m), 0, 16).astype(jnp.uint8)
    idx = jax.random.randint(k3, (b, s), 0, tb, jnp.int32)
    np.testing.assert_allclose(
        np.asarray(pq_scan_paged(lut, codes, idx)),
        np.asarray(pq_scan_paged_ref(lut, codes, idx)), rtol=1e-5, atol=1e-5)
