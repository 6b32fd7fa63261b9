#!/usr/bin/env python3
"""On-chip smoke run of the IVF-PQ search path at SIFT1M scale.

    python chip_smoke.py                # one TPU chip: every phase below
    python chip_smoke.py --four-chips   # the sharded path on 4 chips only

One chip, one process:

  1. corpus     1,000,000 x 128 f32 SIFT1M-shaped vectors + 1,000 queries
                (``sift1m_full``), generated on the chip from ``--seed``;
  2. build      ``build_index`` with nlist=4096, RAIR + SEIL (block 32);
  3. reference  exact top-10 by brute force at full f32 precision;
  4. search     ``index.searcher(...)`` with the Pallas kernel compiled
                (checked in the executable's HLO) in the paged and
                clustered exec modes; ids must agree with the jnp path on >= 99% of
                (query, rank) entries; recall@10 against the reference;
  5. gateway    a few hundred single-query requests through ``Gateway``
                + ``run_open_loop`` on the kernel path: no errors,
                every request answered;
  6. fidelity   the 100k ``sift1m`` stand-in at nlist=256 / nprobe=16:
                recall must lie within 0.01 of the same code's recall on
                CPU.

``--four-chips`` builds the same corpus, shards it over a 4-device mesh
(``index.shard``) and compares the kernel path with the one-chip
searcher in the same process: block arrays on all four devices, ids
equal up to tie order, recall within 0.005.  It runs no other phase.

Without a TPU the script exits non-zero before any phase.  The last
line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

# recall@10 of the 100k fidelity configuration (sift1m, seed 0, nlist=256,
# nprobe=16, jnp paged path, all 2,000 queries) computed by this code on
# CPU (JAX_PLATFORMS=cpu, jax 0.9.0)
CPU_FIDELITY_RECALL = 0.97195
FIDELITY_TOL = 0.01
MIN_ID_AGREEMENT = 0.99
FOUR_CHIP_RECALL_TOL = 0.005
# dispatch bucket of the jnp scan path: it materializes (B, S, BLK, M)
# gathered codes — 31 GB of program memory at a 1024-query bucket over
# the 1M index — so it serves in 16-query chunks (results are
# row-identical across buckets)
JNP_BUCKETS = (16,)

_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:8.1f}s] {msg}", flush=True)


@contextlib.contextmanager
def phase(name: str, times: dict):
    t0 = time.perf_counter()
    log(f"{name} ...")
    yield
    times[name] = time.perf_counter() - t0
    log(f"{name} done in {times[name]:.2f}s")


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    log(f"check {'PASS' if ok else 'FAIL'}: {what}")
    if not ok:
        raise CheckFailed(what)


def device_memory(dev) -> str:
    stats = dev.memory_stats() or {}
    return (f"bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def build_corpus_index(seed: int, times: dict):
    """SIFT1M-shaped corpus + the nlist=4096 RAIR+SEIL index over it."""
    import jax
    from repro.core import IndexConfig, build_index
    from repro.data import make_dataset
    with phase("corpus", times):
        x, q, _ = make_dataset("sift1m_full", seed=seed)
        jax.block_until_ready((x, q))
    log(f"corpus: base {tuple(x.shape)} {x.dtype}, queries {tuple(q.shape)}")
    with phase("build", times):
        index = build_index(jax.random.PRNGKey(seed), x,
                            IndexConfig(nlist=4096, strategy="rair",
                                        seil=True))
        jax.block_until_ready(index.vectors)
    log("build phases (s): " + json.dumps(
        {k: round(v, 3) for k, v in index.build_seconds.items()}))
    log(f"layout: blocks={index.stats.n_blocks} "
        f"block={index.config.block} items={index.stats.n_items_stored}")
    return index, q


def kernel_in_hlo(searcher, batch: int) -> bool:
    """The session's compiled executable holds a Mosaic custom call —
    the kernel ran compiled, not interpreted.  Read from the session's
    executable cache directly, so the check adds no cache hit."""
    exe = searcher._compiled[searcher.params.bucket_for(batch)]
    return "tpu_custom_call" in exe.as_text()


def id_agreement(a, b) -> float:
    import numpy as np
    return float(np.mean(np.asarray(a) == np.asarray(b)))


def search_phase(index, q, gt, times: dict) -> None:
    """Kernel paths against the jnp path.  Every exec mode returns the
    same ids by construction (tests/test_engine.py), so one paged jnp
    run is the reference for all of them."""
    import jax
    import numpy as np
    from repro.core import SearchParams, recall_at_k
    ref_p = SearchParams(k=10, nprobe=32, batch_buckets=JNP_BUCKETS)
    with phase("search_jnp", times):
        ref = index.searcher(ref_p)(q)
        jax.block_until_ready(ref.ids)
    log(f"jnp: recall@10={recall_at_k(np.asarray(ref.ids), gt):.4f}")
    for mode in ("paged", "clustered"):
        name = f"{mode}_kernel"
        sess = index.searcher(SearchParams(
            k=10, nprobe=32, exec_mode=mode, use_kernel=True))
        with phase(f"search_{name}", times):
            res = sess(q)
            jax.block_until_ready(res.ids)
        rec = recall_at_k(np.asarray(res.ids), gt)
        agree = id_agreement(res.ids, ref.ids)
        log(f"{name}: recall@10={rec:.4f} id agreement with jnp="
            f"{agree:.4f}")
        check(kernel_in_hlo(sess, q.shape[0]),
              f"{name}: Pallas kernel compiled (tpu_custom_call in HLO)")
        check(agree >= MIN_ID_AGREEMENT,
              f"{name}: id agreement {agree:.4f} >= {MIN_ID_AGREEMENT}")
        check(bool(np.isfinite(np.asarray(res.dists)).all()),
              f"{name}: finite distances")


def gateway_phase(index, q, times: dict) -> None:
    import numpy as np
    from repro.core import SearchParams
    from repro.gateway import Gateway, GatewayConfig, run_open_loop
    params = SearchParams(k=10, nprobe=32, exec_mode="paged",
                          use_kernel=True)
    n_requests = 300
    with phase("gateway", times):
        with Gateway(index, params,
                     config=GatewayConfig(max_batch=16,
                                          max_delay_ms=2.0)) as gw:
            out = run_open_loop(gw, np.asarray(q), offered_qps=200.0,
                                n_requests=n_requests, seed=0,
                                timeout_s=300.0)
    log(f"gateway: n_ok={out['n_ok']}/{out['n_requests']} "
        f"errors={out['errors']} shed={out['shed']} "
        f"achieved_qps={out['achieved_qps']} p50_ms={out['p50_ms']} "
        f"p99_ms={out['p99_ms']} mean_batch={out['mean_batch']}")
    check(out["errors"] == 0, "gateway: 0 errors")
    check(out["n_ok"] == n_requests,
          f"gateway: {out['n_ok']} of {n_requests} requests answered")


def fidelity_recall(seed: int = 0) -> float:
    """recall@10 of the 100k ``sift1m`` stand-in at nlist=256."""
    import jax
    import numpy as np
    from repro.core import (IndexConfig, SearchParams, build_index,
                            ground_truth, recall_at_k)
    from repro.data import make_dataset
    x, q, _ = make_dataset("sift1m", seed=seed)
    index = build_index(jax.random.PRNGKey(seed), x, IndexConfig(nlist=256))
    gt = ground_truth(index.vectors, q, 10)
    res = index.searcher(SearchParams(k=10, nprobe=16,
                                      batch_buckets=JNP_BUCKETS))(q)
    return recall_at_k(np.asarray(res.ids), gt)


def one_chip(args, dev) -> None:
    from repro.core import ground_truth
    times: dict = {}
    index, q = build_corpus_index(args.seed, times)
    log(f"device memory after build: {device_memory(dev)}")
    with phase("exact_reference", times):
        gt = ground_truth(index.vectors, q, 10)
    search_phase(index, q, gt, times)
    gateway_phase(index, q, times)
    st = index.searcher_stats()
    # a session compiles exactly on an executable-cache miss
    log(f"session stats: sessions={st['sessions']} compiles={st['compiles']} "
        f"cache_hits={st['cache_hits']} cache_misses={st['compiles']}")
    del index
    with phase("fidelity_100k", times):
        rec = fidelity_recall(args.seed)
    log(f"fidelity: chip recall@10={rec:.4f} cpu recall@10="
        f"{CPU_FIDELITY_RECALL}")
    check(abs(rec - CPU_FIDELITY_RECALL) <= FIDELITY_TOL,
          f"fidelity: |{rec:.4f} - {CPU_FIDELITY_RECALL}| <= {FIDELITY_TOL}")
    log(f"device memory at end: {device_memory(dev)}")
    log("phase times (s): " + json.dumps(
        {k: round(v, 3) for k, v in times.items()}))


def four_chips(args, devices) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import SearchParams, ground_truth, recall_at_k
    check(len(devices) >= 4, f"{len(devices)} devices >= 4")
    times: dict = {}
    index, q = build_corpus_index(args.seed, times)
    with phase("exact_reference", times):
        gt = ground_truth(index.vectors, q, 10)
    params = SearchParams(k=10, nprobe=32, exec_mode="paged",
                          use_kernel=True)
    with phase("search_one_chip", times):
        one = index.searcher(params)(q)
        jax.block_until_ready(one.ids)
    sharded = index.shard(Mesh(np.asarray(devices[:4]), ("data",)))
    sess = sharded.searcher(params)
    with phase("search_four_chips", times):
        four = sess(q)
        jax.block_until_ready(four.ids)
    check(kernel_in_hlo(sess, q.shape[0]),
          "four chips: Pallas kernel compiled (tpu_custom_call in HLO)")
    base = sharded._ensure_state().base
    for name in ("block_codes", "block_ids", "block_other"):
        arr = getattr(base, name)
        on = {s.device for s in arr.addressable_shards}
        check(len(arr.sharding.device_set) == 4 and len(on) == 4,
              f"{name} {tuple(arr.shape)} sharded over 4 devices "
              f"({sorted(d.id for d in on)})")
    for d in devices[:4]:
        log(f"device {d.id} memory: {device_memory(d)}")
    ids1, ids4 = np.asarray(one.ids), np.asarray(four.ids)
    d1, d4 = np.asarray(one.dists), np.asarray(four.dists)
    same_sets = float(np.mean(np.all(np.sort(ids1, 1) == np.sort(ids4, 1),
                                     axis=1)))
    same_dists = bool(np.allclose(d1, d4, rtol=1e-6, atol=1e-6))
    r1, r4 = recall_at_k(ids1, gt), recall_at_k(ids4, gt)
    log(f"four chips: recall@10 one={r1:.4f} four={r4:.4f} "
        f"same id set per query={same_sets:.4f} "
        f"sorted dists equal={same_dists} exact id order="
        f"{id_agreement(ids1, ids4):.4f}")
    check(same_dists and same_sets >= MIN_ID_AGREEMENT,
          "four chips: ids equal to one chip up to tie order")
    check(abs(r1 - r4) <= FOUR_CHIP_RECALL_TOL,
          f"four chips: |recall diff| {abs(r1 - r4):.4f} <= "
          f"{FOUR_CHIP_RECALL_TOL}")
    log(f"compile stats: {sess.compile_stats()}")
    log("phase times (s): " + json.dumps(
        {k: round(v, 3) for k, v in times.items()}))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded path and its "
                         "one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no accelerator: {e}", file=sys.stderr)
        return 2
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r} "
              f"devices only — nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is missing next to this "
              f"script: {e}", file=sys.stderr)
        return 2
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} compile cache={compile_cache.enable()}")
    try:
        if args.four_chips:
            four_chips(args, devices)
        else:
            one_chip(args, dev)
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
