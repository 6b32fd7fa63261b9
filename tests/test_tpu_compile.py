"""Ahead-of-time compiles of the scan kernel for a TPU v5e.

Interpret mode (the CPU test path) accepts block shapes, reshapes and
gathers that the TPU lowering refuses, so these tests compile the
Pallas kernel for a *described* v5e chip — no chip needed — at the
widths the chip runs: M=64 at 4 bits padded to 128, K=16, fetch=128,
nlist=4096, blocks 32 and 128, unpacked and nibble-packed, with and
without the tombstone plane, query tiles 1 to 8, a 1024-query batch
whose scan lists need the SMEM chunking, and the kernel at the
exact shapes of the SIFT1M and Text-to-Image 1M paged deployments,
where a grid step pages four blocks of 32 (SIFT1M's 557 scan positions
padded to 560).  Shapes only; nothing
runs.  The topology is described inside a module fixture (never at
import), and the persistent compilation cache is off around the
compiles: an entry written for a described chip cannot be read back.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.pq_scan import pq_scan_topk_kernel

M, K, TB, NLIST, S, FETCH = 128, 16, 4096, 4096, 256, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text          # a Mosaic kernel, compiled


@pytest.mark.parametrize("blk,packed,dead,qt,b", [
    (32, False, False, 1, 64),
    (32, True, True, 8, 64),
    (128, False, True, 4, 64),
    (128, True, False, 2, 64),
    (32, False, True, 8, 1024),  # SMEM-chunked batch
    (32, False, False, 8, 64),
    (128, True, False, 1, 64),
    (32, False, False, 1, 1024),  # per-query paging, SMEM-chunked batch
])
def test_topk_kernel_compiles(one_chip, blk, packed, dead, qt, b):
    mb = M // 2 if packed else M
    args = [_spec(one_chip, (b, M, K), jnp.float32),
            _spec(one_chip, (TB, blk, mb), jnp.uint8),
            _spec(one_chip, (TB, blk), jnp.int32),
            _spec(one_chip, (TB, blk), jnp.int32),
            _spec(one_chip, (b // qt, S), jnp.int32),
            _spec(one_chip, (b, NLIST), jnp.int32),
            _spec(one_chip, (b, S), jnp.int32),
            _spec(one_chip, (b, S), jnp.int32)]
    if dead:
        args.append(_spec(one_chip, (TB, blk), jnp.uint8))
    _compiled_kernel(
        lambda *a: pq_scan_topk_kernel(*a, query_tile=qt, fetch=FETCH,
                                       packed=packed),
        *args)


@pytest.mark.parametrize("packed,dead", [(False, False), (True, False),
                                         (False, True)],
                         ids=["False", "True", "dead"])
def test_topk_kernel_compiles_at_sift1m_paged_shape(one_chip, packed, dead):
    """The kernel as the SIFT1M paged deployment runs it: a flat
    (B, 1, M*K) LUT from the wrapper, query tile 1, block 32 (four
    blocks per grid step), M 64 padded to 128, nlist 4096, 557 scan
    positions (padded to 560), fetch 100, the index's 54,140 blocks and
    a 1024-query batch; packed or not, and with the streaming index's
    tombstone plane."""
    b, blk, tb, s, fetch = 1024, 32, 54140, 557, 100
    mb = M // 2 if packed else M
    args = [_spec(one_chip, (b, M, K), jnp.float32),
            _spec(one_chip, (tb, blk, mb), jnp.uint8),
            _spec(one_chip, (tb, blk), jnp.int32),
            _spec(one_chip, (tb, blk), jnp.int32),
            _spec(one_chip, (b, s), jnp.int32),
            _spec(one_chip, (b, NLIST), jnp.int32),
            _spec(one_chip, (b, s), jnp.int32),
            _spec(one_chip, (b, s), jnp.int32)]
    if dead:
        args.append(_spec(one_chip, (tb, blk), jnp.uint8))
    _compiled_kernel(
        lambda *a: pq_scan_topk_kernel(*a, query_tile=1, fetch=fetch,
                                       packed=packed),
        *args)


@pytest.mark.parametrize("fetch", [500, 1000])
def test_topk_kernel_compiles_at_t2i1m_paged_width(one_chip, fetch):
    """The kernel at the Text-to-Image deployment's shapes: fetch
    500 (its k_factor 50, a 512-lane accumulator) and 1,000 (k_factor
    100, 1,024 lanes, ten merge stages per grid step), M 100 padded to
    128, 604 scan positions, the index's 58,706 blocks, a 1024-query
    batch."""
    b, blk, tb, s = 1024, 32, 58706, 604
    _compiled_kernel(
        lambda *a: pq_scan_topk_kernel(*a, query_tile=1, fetch=fetch),
        _spec(one_chip, (b, M, K), jnp.float32),
        _spec(one_chip, (tb, blk, M), jnp.uint8),
        _spec(one_chip, (tb, blk), jnp.int32),
        _spec(one_chip, (tb, blk), jnp.int32),
        _spec(one_chip, (b, s), jnp.int32),
        _spec(one_chip, (b, NLIST), jnp.int32),
        _spec(one_chip, (b, s), jnp.int32),
        _spec(one_chip, (b, s), jnp.int32))
