"""Async serving gateway (src/repro/gateway/, DESIGN.md §10).

Key invariants:
  * a request served through the gateway returns exactly what a direct
    session call returns — coalescing changes latency, never results;
  * a concurrent burst coalesces (batch_fill > 1) while an isolated
    request still flushes on its deadline with batch == 1;
  * the queue drains whole signature lanes oldest-first (locality
    grouping can reorder only within one flush window) and honors
    per-request deadlines;
  * telemetry counters are monotone, percentile estimates never
    understate, and periodic sink records arrive in order;
  * streaming gateways return *stable external ids*: mutations round
    trip (insert -> search -> delete -> resolve) and survive an epoch
    handover;
  * zero-downtime handover: concurrent client threads see zero errors
    and zero result gaps while ``compact_async`` folds and installs a
    new epoch under live traffic (satellite: no StaleSessionError
    escapes, every returned id still resolves afterwards).
"""
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import (IndexConfig, SearchParams, StreamConfig,
                        StreamingIndex, build_index)
from repro.gateway import (Gateway, GatewayClosed, GatewayConfig,
                           LatencyHistogram, MemorySink, PendingRequest,
                           RequestQueue, run_open_loop)


@pytest.fixture()
def stream_index(unit_data, shared_trained):
    """Fresh mutable index per test (never wrap the session-scoped
    rairs_index for mutation tests)."""
    x, _, _ = unit_data
    cents, cb = shared_trained
    cfg = IndexConfig(nlist=64, strategy="rair", seil=True)
    base = build_index(jax.random.PRNGKey(0), x[:4000], cfg,
                       centroids=cents, codebook=cb)
    return StreamingIndex(base, StreamConfig(delta_pad=512))


# ---------------------------------------------------------------------------
# config validation + lifecycle
# ---------------------------------------------------------------------------

def test_config_validation(rairs_index):
    with pytest.raises(ValueError):
        GatewayConfig(max_delay_ms=-1.0)
    with pytest.raises(ValueError):
        GatewayConfig(max_batch=0)
    with pytest.raises(ValueError):
        GatewayConfig(admission="lifo")
    with pytest.raises(ValueError):
        GatewayConfig(compact_delta_frac=0.0)
    # compaction thresholds need something to compact
    with pytest.raises(ValueError):
        Gateway(rairs_index, k=10, nprobe=8,
                config=GatewayConfig(compact_delta_frac=0.5))


def test_submit_validates_and_close_rejects(rairs_index, unit_data):
    _, q, _ = unit_data
    with Gateway(rairs_index, k=10, nprobe=8,
                 config=GatewayConfig(max_batch=4)) as gw:
        with pytest.raises(ValueError):
            gw.submit(q[0][:8])                  # wrong dimensionality
        with pytest.raises(ValueError):
            gw.submit(q[:2])                     # a batch is not a query
        r = gw.search(q[0])
        assert r.ids.shape == (10,)
        # mutations need a streaming index
        with pytest.raises(TypeError):
            gw.insert(q[:1])
        with pytest.raises(TypeError):
            gw.compact_async()
    assert gw.stats()["closed"]
    # typed close error — and still a RuntimeError for legacy callers
    with pytest.raises(GatewayClosed):
        gw.submit(q[0])
    with pytest.raises(RuntimeError):
        gw.submit(q[0])


# ---------------------------------------------------------------------------
# results: gateway == direct session, coalescing happens
# ---------------------------------------------------------------------------

def test_gateway_matches_direct_session(rairs_index, unit_data):
    _, q, _ = unit_data
    params = SearchParams(k=10, nprobe=8)
    direct = rairs_index.searcher(params)
    with Gateway(rairs_index, params,
                 config=GatewayConfig(max_batch=8, max_delay_ms=5.0)) as gw:
        pending = [gw.submit(q[i]) for i in range(16)]
        results = [p.result(30.0) for p in pending]
    for i, r in enumerate(results):
        ref = direct(q[i:i + 1])
        np.testing.assert_array_equal(np.asarray(r.ids),
                                      np.asarray(ref.ids)[0].astype(np.int64))
        np.testing.assert_allclose(np.asarray(r.dists),
                                   np.asarray(ref.dists)[0], rtol=1e-5)


def test_burst_coalesces_and_deadline_flushes(rairs_index, unit_data):
    _, q, _ = unit_data
    with Gateway(rairs_index, k=10, nprobe=8,
                 config=GatewayConfig(max_batch=16, max_delay_ms=50.0)) as gw:
        pending = [gw.submit(q[i]) for i in range(32)]
        results = [p.result(30.0) for p in pending]
        assert max(r.batch for r in results) > 1
        snap = gw.telemetry.snapshot()
        assert snap["batch_fill"] > 1.0
        assert snap["counters"]["responses"] == 32
        # an isolated request flushes on its own deadline, alone
        t0 = time.perf_counter()
        lone = gw.search(q[0], timeout=30.0)
        assert lone.batch == 1
        assert time.perf_counter() - t0 < 5.0


def test_open_loop_generator(rairs_index, unit_data):
    _, q, _ = unit_data
    with Gateway(rairs_index, k=10, nprobe=8,
                 config=GatewayConfig(max_batch=8, max_delay_ms=2.0)) as gw:
        out = run_open_loop(gw, q[:32], offered_qps=2000.0, n_requests=64,
                            timeout_s=60.0)
    assert out["errors"] == 0 and out["n_ok"] == 64
    assert out["p50_ms"] > 0 and out["p99_ms"] >= out["p50_ms"]
    assert out["mean_batch"] >= 1.0


# ---------------------------------------------------------------------------
# queue semantics (no gateway, no compiles)
# ---------------------------------------------------------------------------

def _req(sig, deadline=None):
    return PendingRequest(np.zeros(4, np.float32), sig, deadline=deadline)


def test_queue_drains_whole_lanes_oldest_first():
    qu = RequestQueue(grouped=True)
    a0, b0, a1 = _req(7), _req(3), _req(7)
    for r in (a0, b0, a1):
        qu.put(r)
    batch = qu.take_batch(16)
    # lane 7 is oldest (a0) so drains whole before lane 3
    assert batch == [a0, a1, b0]
    assert qu.depth == 0 and qu.take_batch(4) == []


def test_queue_respects_max_batch_and_fifo_within_lane():
    qu = RequestQueue(grouped=False)
    reqs = [_req(i) for i in range(5)]
    for r in reqs:
        qu.put(r)
    assert qu.take_batch(3) == reqs[:3]
    assert qu.take_batch(3) == reqs[3:]


def test_queue_deadline_tightens_flush():
    qu = RequestQueue(grouped=True)
    now = time.perf_counter()
    qu.put(_req(1, deadline=now + 0.001))
    due = qu.oldest_flush_at(max_delay=10.0)
    assert due is not None and due - now < 0.1   # deadline, not max_delay
    qu.take_batch(8)
    assert qu.oldest_flush_at(10.0) is None      # empty -> no flush time


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_latency_histogram_percentiles_never_understate():
    h = LatencyHistogram()
    vals = [1e-4, 5e-4, 1e-3, 5e-3, 1e-2]
    for v in vals:
        h.record(v)
    assert h.percentile(50) >= 5e-4
    assert h.percentile(99) >= h.percentile(50) >= h.percentile(10)
    snap = h.snapshot()
    assert snap["count"] == 5 and snap["max_ms"] == pytest.approx(10.0)
    # locked snapshot schema: sinks derive rates from count and
    # cross-interval means from sum_ms without re-binning
    assert set(snap) == {"count", "sum_ms", "mean_ms", "p50_ms", "p95_ms",
                         "p99_ms", "max_ms"}
    assert snap["sum_ms"] == pytest.approx(sum(vals) * 1e3)
    assert snap["mean_ms"] == pytest.approx(snap["sum_ms"] / snap["count"])
    empty = LatencyHistogram().snapshot()
    assert empty["count"] == 0 and empty["mean_ms"] == 0.0


def test_telemetry_add_rejects_negative_deltas():
    from repro.gateway.telemetry import Telemetry
    tm = Telemetry()
    tm.add("approx_dco", 16.0)
    with pytest.raises(ValueError, match="monotone"):
        tm.add("approx_dco", -1.0)
    assert tm.snapshot()["counters"] == {}      # counters untouched
    # signed sums (ip-metric top-1 scores are negated inner products)
    # go through the documented escape hatch
    tm.add_signed("top1_dist", -3.5)
    tm.add_signed("top1_dist", 1.0)
    tm.inc("responses")
    assert tm.snapshot()["mean_top1_dist"] == pytest.approx(-2.5)


def test_periodic_sink_and_monotone_counters(rairs_index, unit_data):
    _, q, _ = unit_data
    sink = MemorySink()
    with Gateway(rairs_index, k=10, nprobe=8, sinks=(sink,),
                 config=GatewayConfig(max_batch=4, max_delay_ms=1.0,
                                      telemetry_interval_s=0.02)) as gw:
        for i in range(12):
            gw.search(q[i])
        time.sleep(0.08)                      # let at least one period pass
        stats = gw.stats()
    assert stats["telemetry"]["counters"]["responses"] == 12
    assert stats["session"]["compiles"] >= 1
    kinds = [r["kind"] for r in sink.records]
    assert kinds[-1] == "gateway_final"       # close() emits a final record
    assert "gateway_stats" in kinds
    # counters only ever grow across successive records
    for name in ("requests", "responses", "batches"):
        seq = [r["counters"].get(name, 0) for r in sink.records]
        assert seq == sorted(seq)
    assert all(r["counters"].get("errors", 0) == 0 for r in sink.records)


def test_warmup_ladder_precompiles_every_bucket(rairs_index, unit_data):
    _, q, _ = unit_data
    # distinct params -> a session no other test has warmed
    with Gateway(rairs_index, k=10, nprobe=5,
                 config=GatewayConfig(max_batch=4, max_delay_ms=1.0)) as gw:
        compiles_after_warmup = gw.stats()["session"]["compiles"]
        assert compiles_after_warmup >= 3     # buckets 1, 2, 4
        for i in range(6):                    # lands in buckets 1 and 2
            gw.search(q[i])
        assert gw.stats()["session"]["compiles"] == compiles_after_warmup


# ---------------------------------------------------------------------------
# streaming: stable external ids + zero-downtime handover
# ---------------------------------------------------------------------------

def test_mutations_roundtrip_external_ids(stream_index, unit_data):
    x, q, _ = unit_data
    new = x[4000:4032]
    with Gateway(stream_index, k=10, nprobe=16,
                 config=GatewayConfig(max_batch=4, max_delay_ms=1.0)) as gw:
        ext = gw.insert(new)
        assert ext.shape == (32,)
        # an inserted vector is its own nearest neighbor, by external id
        r = gw.search(new[0])
        assert int(np.asarray(r.ids)[0]) == int(ext[0])
        assert gw.delete(ext[:8]) == 8
        h = gw.compact_async("test")
        info = h.wait(120.0)
        assert h.state == "installed" and info["n_live"] > 0
        # handles survive the epoch swap: deleted -> -1, live -> resolvable
        resolved = gw.resolve_ids(ext)
        assert (resolved[:8] == -1).all() and (resolved[8:] >= 0).all()
        r2 = gw.search(new[9])
        assert int(np.asarray(r2.ids)[0]) == int(ext[9])
        st = gw.stats()
        assert st["stream"]["epoch"] == 1
        assert st["telemetry"]["counters"]["handovers"] == 1
        assert st["handover"]["state"] == "idle"
        assert st["handover"]["last"]["reason"] == "test"


def test_handover_under_live_traffic(stream_index, unit_data):
    """Satellite: clients keep searching while compaction folds and the
    new epoch installs — zero errors, zero StaleSessionError escapes,
    and every id any client ever received still resolves afterwards."""
    x, q, _ = unit_data
    cfg = GatewayConfig(max_batch=8, max_delay_ms=1.0)
    with Gateway(stream_index, k=10, nprobe=16, config=cfg) as gw:
        gw.insert(x[4000:4128])               # give the fold real work
        failures, results = [], []
        res_lock = threading.Lock()

        def client(seed):
            rng = np.random.default_rng(seed)
            for _ in range(25):
                try:
                    r = gw.search(q[int(rng.integers(len(q)))], timeout=60.0)
                    with res_lock:
                        results.append(r)
                except Exception as e:        # noqa: BLE001 — recorded
                    failures.append(e)

        threads = [threading.Thread(target=client, args=(s,))
                   for s in range(3)]
        for t in threads:
            t.start()
        h = gw.compact_async("churn")
        h.wait(120.0)
        for t in threads:
            t.join()

        assert not failures
        st = gw.stats()
        assert st["telemetry"]["counters"].get("errors", 0) == 0
        assert st["telemetry"]["counters"].get("stale_retries", 0) == 0
        assert st["stream"]["epoch"] == 1
        epochs = {r.epoch for r in results}
        assert 0 in epochs                    # old epoch kept serving
        # every id any client received resolves against the live corpus
        all_ids = np.unique(np.concatenate(
            [np.asarray(r.ids) for r in results]))
        all_ids = all_ids[all_ids >= 0]
        assert (gw.resolve_ids(all_ids) >= 0).all()


def test_telemetry_observe_atomic_under_threads():
    """The batched ``observe`` path keeps cross-metric invariants exact
    in *every* snapshot: a dispatch records responses and its latency
    samples under one lock acquisition, so a concurrent reader can
    never see the counter move without the histogram (or half a
    multi-sum update)."""
    from repro.gateway.telemetry import Telemetry
    tm = Telemetry()
    stop = threading.Event()

    def writer():
        while not stop.is_set():
            tm.observe(counters={"responses": 2, "batches": 1},
                       sums={"result_slots": 20.0, "result_filled": 18.0},
                       latencies=[(tm.latency, 1e-3), (tm.latency, 2e-3)])

    threads = [threading.Thread(target=writer) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        bad = []
        for _ in range(300):
            snap = tm.snapshot()
            c, s = snap["counters"], snap["latency"]
            if c.get("responses", 0) != s["count"]:
                bad.append((c.get("responses", 0), s["count"]))
            if c.get("responses", 0) != 2 * c.get("batches", 0):
                bad.append(("responses/batches", c))
            # multi-sum atomicity: slots and filled move together
            slots = snap["counters"].get("responses", 0) * 10.0
            if abs(slots * 0.9 -
                   (snap["result_fill_rate"] * slots)) > 1e-6:
                bad.append(("fill_rate", snap["result_fill_rate"]))
        assert not bad, bad[:5]
    finally:
        stop.set()
        for t in threads:
            t.join(10.0)
    # a torn observe (negative sum) must reject before mutating anything
    before = tm.snapshot()
    with pytest.raises(ValueError):
        tm.observe(counters={"responses": 1}, sums={"approx_dco": -1.0})
    after = tm.snapshot()
    assert after["counters"] == before["counters"]


@pytest.mark.parametrize("field,delta,faults", [
    (None, 0, 0),            # healthy point: nothing to report
    ("errors", 1, 2),        # an untyped error also breaks the identity
    ("shed", 1, 0),          # typed shedding is accounted, not a fault
    ("n_ok", -1, 1),         # a lost request breaks the identity
])
def test_serve_cli_load_point_faults(field, delta, faults):
    """The serve CLI exits non-zero on untyped errors or a broken
    accounting identity (ok+shed+deadline+closed+errors == submitted)."""
    from repro.launch.serve import load_point_faults
    out = {"n_requests": 10, "n_ok": 10, "shed": 0, "deadline_failed": 0,
           "closed": 0, "errors": 0}
    if field is not None:
        out[field] += delta
    if field == "shed":
        out["n_ok"] -= 1
    assert len(load_point_faults(100.0, out)) == faults
