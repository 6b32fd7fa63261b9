"""Chaos suite: deterministic fault injection across the serving stack
(DESIGN.md §13).

Every test here drives *production* code paths under an installed
``FaultPlan`` and asserts the overload/failure contract:

  * no deadlock and no silently dropped request — every ``submit``
    resolves with a result or a typed ``RairsError``;
  * a compaction-worker crash retries with backoff, then rolls back to
    the pinned old epoch and surfaces ``HandoverFailed`` — serving
    continues, and the external-id remap chain is NOT consumed by the
    failed attempt (a retried compaction resolves ids exactly once);
  * requests past their deadline fail typed at dequeue, never dispatch;
  * close() honors the drain grace window, then fails leftovers typed;
  * corrupted / truncated bundles are rejected naming the bad member.

CI (chaos-smoke) runs this file under two values of ``RAIRS_CHAOS_SEED``
— determinism means a failure reproduces from the seed alone.
"""
import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import (IndexConfig, SearchParams, StreamConfig,
                        StreamingIndex, build_index, load_index, save_index)
from repro.errors import (CorruptBundleError, DeadlineExceeded,
                          FaultInjected, GatewayClosed, HandoverFailed,
                          Overloaded, RairsError)
from repro.faults import FaultPlan, FaultSpec
from repro.gateway import (Gateway, GatewayConfig, degrade_ladder,
                           run_open_loop)

CHAOS_SEED = int(os.environ.get("RAIRS_CHAOS_SEED", "0"))


@pytest.fixture()
def stream_index(unit_data, shared_trained):
    x, _, _ = unit_data
    cents, cb = shared_trained
    cfg = IndexConfig(nlist=64, strategy="rair", seil=True)
    base = build_index(jax.random.PRNGKey(0), x[:2000], cfg,
                       centroids=cents, codebook=cb)
    return StreamingIndex(base, StreamConfig(delta_pad=512))


# ---------------------------------------------------------------------------
# the plan itself: deterministic schedules
# ---------------------------------------------------------------------------

def test_fault_plan_is_deterministic():
    specs = (FaultSpec("a", prob=0.3), FaultSpec("b", prob=0.7),)

    def schedule(seed):
        plan = FaultPlan(seed, specs)
        return [(plan.visit("a") is not None, plan.visit("b") is not None)
                for _ in range(64)]

    s1, s2 = schedule(CHAOS_SEED), schedule(CHAOS_SEED)
    assert s1 == s2                      # same seed -> same schedule
    assert schedule(CHAOS_SEED + 1) != s1   # seeds actually matter
    fires_a = sum(a for a, _ in s1)
    assert 0 < fires_a < 64              # prob is neither 0 nor 1


def test_fault_spec_validates_and_explicit_schedule():
    with pytest.raises(ValueError):
        FaultSpec("x", kind="explode")
    plan = FaultPlan(CHAOS_SEED, (FaultSpec("s", at=(1, 3)),))
    fired = [plan.visit("s") is not None for _ in range(5)]
    assert fired == [False, True, False, True, False]
    assert plan.visits("s") == 5 and plan.fired() == 2


def test_max_hits_caps_a_probabilistic_spec():
    plan = FaultPlan(CHAOS_SEED, (FaultSpec("s", prob=1.0, max_hits=2),))
    fired = [plan.visit("s") is not None for _ in range(6)]
    assert sum(fired) == 2 and fired[:2] == [True, True]


# ---------------------------------------------------------------------------
# dispatch faults: typed failure, no dropped request, service recovers
# ---------------------------------------------------------------------------

def test_dispatch_fault_fails_typed_and_recovers(rairs_index, unit_data):
    _, q, _ = unit_data
    plan = FaultPlan(CHAOS_SEED, (
        FaultSpec("gateway.dispatch", kind="raise", at=(0,)),))
    with plan.installed():
        with Gateway(rairs_index, k=10, nprobe=8,
                     config=GatewayConfig(max_batch=4, max_delay_ms=1.0,
                                          warmup=False)) as gw:
            bad = gw.submit(q[0])
            with pytest.raises(FaultInjected):
                bad.result(30.0)
            # the fault consumed visit 0; the service keeps serving
            good = gw.search(q[1], timeout=30.0)
            assert good.ids.shape == (10,)
            snap = gw.telemetry.snapshot()
            assert snap["counters"]["errors"] >= 1
            assert snap["counters"]["responses"] >= 1


def test_overload_chaos_every_request_resolves(rairs_index, unit_data):
    """2x-saturating offered load + injected dispatch latency + a
    bounded queue: every submitted request must resolve — result,
    ``Overloaded``, or ``DeadlineExceeded`` — none may hang."""
    _, q, _ = unit_data
    plan = FaultPlan(CHAOS_SEED, (
        FaultSpec("gateway.dispatch", kind="delay", prob=0.5,
                  delay_s=0.02),))
    n, results = 120, []
    with plan.installed():
        with Gateway(rairs_index, k=10, nprobe=8,
                     config=GatewayConfig(max_batch=8, max_delay_ms=1.0,
                                          max_queue=16, overload="reject",
                                          warmup=False)) as gw:
            pending = []
            for i in range(n):
                pending.append(gw.submit(q[i % len(q)]))
                time.sleep(0.0005)       # ~2000 qps offered, far past sat.
            for p in pending:
                try:
                    results.append(p.result(60.0))
                except RairsError as e:
                    results.append(e)
            snap = gw.telemetry.snapshot()
    assert len(results) == n             # nothing hung, nothing dropped
    ok = sum(1 for r in results if not isinstance(r, Exception))
    shed = sum(1 for r in results if isinstance(r, Overloaded))
    assert ok + shed == n
    assert ok > 0 and shed > 0           # overload actually bit
    c = snap["counters"]
    assert c["requests"] == n
    assert c["responses"] == ok and c["shed"] == shed


@pytest.mark.parametrize("mode", ["unbounded", "shed", "degrade"])
def test_open_loop_overload_resolves_typed(rairs_index, unit_data, mode):
    """An open-loop Poisson stream far past saturation, with injected
    dispatch latency, through each admission mode — no bound, a bounded
    queue that rejects, and the bounded queue with the quality ladder:
    every request is answered or fails typed (ok + shed + deadline +
    closed == submitted, no untyped error), and the unbounded gateway
    never sheds."""
    _, q, _ = unit_data
    params = SearchParams(k=10, nprobe=8, max_scan=16)
    base = dict(max_batch=8, max_delay_ms=1.0, warmup=False)
    bounded = dict(base, max_queue=16, overload="reject")
    cfg = {"unbounded": GatewayConfig(**base),
           "shed": GatewayConfig(**bounded),
           "degrade": GatewayConfig(
               **bounded, degrade=degrade_ladder(params, levels=2)[1:],
               degrade_hold=2)}[mode]
    n = 128
    plan = FaultPlan(CHAOS_SEED, (
        FaultSpec("gateway.dispatch", kind="delay", prob=0.5,
                  delay_s=0.02),))
    with plan.installed():
        with Gateway(rairs_index, params, config=cfg) as gw:
            pt = run_open_loop(gw, np.asarray(q), 2000.0, n,
                               seed=CHAOS_SEED)
    assert (pt["n_ok"] + pt["shed"] + pt["deadline_failed"] + pt["closed"]
            + pt["errors"]) == n, pt
    assert pt["errors"] == 0, pt
    if mode == "unbounded":
        assert pt["shed"] == 0, pt


def test_block_policy_applies_backpressure(rairs_index, unit_data):
    """overload="block" parks producers instead of shedding: every
    request completes, and the queue never exceeds its bound."""
    _, q, _ = unit_data
    plan = FaultPlan(CHAOS_SEED, (
        FaultSpec("gateway.dispatch", kind="delay", prob=1.0,
                  delay_s=0.005),))
    n = 48
    with plan.installed():
        with Gateway(rairs_index, k=10, nprobe=8,
                     config=GatewayConfig(max_batch=4, max_delay_ms=0.5,
                                          max_queue=8, overload="block",
                                          warmup=False)) as gw:
            depths, pending = [], []

            def producer():
                for i in range(n):
                    pending.append(gw.submit(q[i % len(q)]))
                    depths.append(gw.queue.depth)

            t = threading.Thread(target=producer)
            t.start()
            t.join(60.0)
            assert not t.is_alive()      # backpressure, not deadlock
            results = [p.result(60.0) for p in pending]
    assert len(results) == n
    assert max(depths) <= 8


# ---------------------------------------------------------------------------
# deadlines and drain
# ---------------------------------------------------------------------------

def test_expired_request_fails_at_dequeue_never_dispatched(rairs_index,
                                                           unit_data):
    _, q, _ = unit_data
    with Gateway(rairs_index, k=10, nprobe=8,
                 config=GatewayConfig(max_batch=4, warmup=False)) as gw:
        before = gw.telemetry.counter("responses")
        r = gw.submit(q[0], deadline_s=-0.001)   # already expired
        with pytest.raises(DeadlineExceeded):
            r.result(30.0)
        assert gw.telemetry.counter("deadline_failures") == 1
        # it was never dispatched: responses did not move for it
        assert gw.telemetry.counter("responses") == before
        # a healthy request with a generous deadline still completes
        assert gw.submit(q[1], deadline_s=30.0).result(30.0).ids.shape \
            == (10,)


def test_close_drain_window_fails_leftovers_typed(rairs_index, unit_data):
    """drain_s=0: close() fails queued work immediately — with the
    typed ``GatewayClosed``, not a bare RuntimeError."""
    _, q, _ = unit_data
    plan = FaultPlan(CHAOS_SEED, (
        FaultSpec("gateway.dispatch", kind="delay", prob=1.0,
                  delay_s=0.05),))
    with plan.installed():
        gw = Gateway(rairs_index, k=10, nprobe=8,
                     config=GatewayConfig(max_batch=2, max_delay_ms=0.5,
                                          drain_s=0.0, warmup=False))
        pending = [gw.submit(q[i % len(q)]) for i in range(32)]
        gw.close()
    outcomes = []
    for p in pending:
        try:
            outcomes.append(p.result(10.0))
        except GatewayClosed as e:
            outcomes.append(e)
    assert len(outcomes) == 32
    dropped = [o for o in outcomes if isinstance(o, GatewayClosed)]
    assert dropped                       # the zero-grace window cut some
    assert all(isinstance(o, GatewayClosed) or o.ids.shape == (10,)
               for o in outcomes)


def test_close_default_drains_everything(rairs_index, unit_data):
    _, q, _ = unit_data
    gw = Gateway(rairs_index, k=10, nprobe=8,
                 config=GatewayConfig(max_batch=8, warmup=False))
    pending = [gw.submit(q[i % len(q)]) for i in range(24)]
    gw.close()                            # drain_s=None: drain until empty
    assert all(p.result(10.0).ids.shape == (10,) for p in pending)


# ---------------------------------------------------------------------------
# degradation ladder
# ---------------------------------------------------------------------------

def test_degradation_ladder_steps_down_and_recovers(rairs_index, unit_data):
    _, q, _ = unit_data
    params = SearchParams(k=10, nprobe=8)
    ladder = degrade_ladder(params, levels=2)
    assert [p.nprobe for p in ladder] == [8, 4, 2]
    plan = FaultPlan(CHAOS_SEED, (
        FaultSpec("gateway.dispatch", kind="delay", prob=1.0,
                  delay_s=0.01, max_hits=30),))
    with plan.installed():
        with Gateway(rairs_index, params,
                     config=GatewayConfig(
                         max_batch=4, max_delay_ms=0.5, max_queue=8,
                         overload="block", degrade=ladder[1:],
                         degrade_hold=1, warmup=False)) as gw:
            pending = [gw.submit(q[i % len(q)]) for i in range(64)]
            results = [p.result(60.0) for p in pending]
            levels = {r.level for r in results}
            assert levels - {0}          # pressure pushed the ladder down
            snap = gw.telemetry.snapshot()
            assert snap["counters"]["degrade_steps_down"] >= 1
            # pressure gone (faults exhausted): the ladder steps back up
            deadline = time.time() + 30.0
            while time.time() < deadline:
                if gw.search(q[0], timeout=30.0).level == 0:
                    break
                time.sleep(0.01)
            assert gw.stats()["quality"]["level"] == 0
            assert gw.search(q[0], timeout=30.0).level == 0
            assert gw.telemetry.counter("degrade_steps_up") >= 1


# ---------------------------------------------------------------------------
# compaction crash: retry -> rollback -> typed surface, old epoch serves
# ---------------------------------------------------------------------------

def test_fold_crash_retries_then_succeeds(stream_index, unit_data):
    _, q, _ = unit_data
    plan = FaultPlan(CHAOS_SEED, (
        FaultSpec("gateway.fold", kind="raise", at=(0,)),))
    with plan.installed():
        with Gateway(stream_index, k=10, nprobe=8,
                     config=GatewayConfig(max_batch=8, warmup=False,
                                          handover_retries=2,
                                          handover_backoff_s=0.01)) as gw:
            gw.insert(np.asarray(unit_data[0][2000:2032]))
            epoch0 = stream_index.epoch
            h = gw.compact_async("chaos")
            info = h.wait(60.0)
            assert h.state == "installed" and info["epoch"] == epoch0 + 1
            assert gw.telemetry.counter("handover_retries") == 1
            assert gw.search(q[0], timeout=30.0).epoch == epoch0 + 1


def test_fold_crash_exhausts_retries_rolls_back(stream_index, unit_data):
    x, q, _ = unit_data
    plan = FaultPlan(CHAOS_SEED, (
        FaultSpec("gateway.fold", kind="raise", prob=1.0),))
    with Gateway(stream_index, k=10, nprobe=8,
                 config=GatewayConfig(max_batch=8, warmup=False,
                                      handover_retries=1,
                                      handover_backoff_s=0.01)) as gw:
        ext = gw.insert(np.asarray(x[2000:2064]))
        gw.delete(ext[:8])
        epoch0, version0 = stream_index.epoch, stream_index.version
        resolved0 = gw.resolve_ids(ext)
        with plan.installed():
            h = gw.compact_async("chaos")
            with pytest.raises(HandoverFailed) as ei:
                h.wait(60.0)
            assert isinstance(ei.value.__cause__, FaultInjected)
        # rolled back: same epoch, pinned session still serves
        assert stream_index.epoch == epoch0
        assert gw.telemetry.counter("handover_failures") == 1
        r = gw.search(q[0], timeout=30.0)
        assert r.epoch == epoch0 and r.ids.shape == (10,)
        # the failed attempt consumed NO remap link: handles unchanged
        np.testing.assert_array_equal(gw.resolve_ids(ext), resolved0)
        assert stream_index.version == version0
        # a clean retry compacts and the same handles still resolve
        h2 = gw.compact_async("retry")
        assert h2.wait(60.0)["epoch"] == epoch0 + 1
        resolved1 = gw.resolve_ids(ext)
        assert (resolved1[:8] == -1).all()       # deletes stayed deleted
        assert (resolved1[8:] >= 0).all()        # survivors still resolve
        # exactly one remap was consumed, by the successful install
        res = gw.search(np.asarray(x[2010]), timeout=30.0)
        assert (np.asarray(res.ids) >= 0).any()


def test_failed_then_retried_compaction_remap_chain(stream_index, unit_data):
    """Satellite: the streaming-level contract behind the gateway test
    above — ``abort()`` must not consume a remap link, so resolve_ids
    chains exactly one remap per *successful* install."""
    x, _, _ = unit_data
    stream = stream_index
    ids = stream.insert(np.asarray(x[2000:2040]))
    ext = stream.external_ids(ids)
    stream.delete(ids[:5])
    before = stream.resolve_ids(ext)
    # attempt 1: folds fine, then rolls back (simulating install crash)
    p1 = stream.begin_compact("will-abort")
    p1.fold()
    p1.abort()
    np.testing.assert_array_equal(stream.resolve_ids(ext), before)
    assert stream._pending_compact is None   # rollback released the slot
    # attempt 2: retried compaction lands; the chain advances once
    p2 = stream.begin_compact("retry")
    p2.fold()
    info = p2.install()
    assert info["epoch"] == stream.epoch
    after = stream.resolve_ids(ext)
    assert (after[:5] == -1).all() and (after[5:] >= 0).all()
    # remapped internal ids still point at the same vectors
    live_ext = ext[5:]
    ints = stream.resolve_ids(live_ext)
    got = np.asarray(stream.base.vectors)[ints[ints < stream.n_base]]
    want = np.asarray(x[2000:2040])[5:][ints < stream.n_base]
    np.testing.assert_allclose(got, want, rtol=1e-5)


# ---------------------------------------------------------------------------
# storage faults: truncation / bit-flips reject typed, old bundle survives
# ---------------------------------------------------------------------------

def test_bitflip_fault_rejected_naming_member(rairs_index, tmp_path):
    path = os.path.join(tmp_path, "idx.npz")
    save_index(rairs_index, path)
    plan = FaultPlan(CHAOS_SEED, (
        FaultSpec("io.read_array", kind="bitflip", at=(0,)),))
    with plan.installed():
        with pytest.raises(CorruptBundleError, match="crc32 mismatch"):
            load_index(path)
    # uninstalled plan: the same bundle loads clean
    assert load_index(path) is not None


def test_truncation_fault_rejected(rairs_index, tmp_path):
    path = os.path.join(tmp_path, "sharded")
    save_index(rairs_index, path, shards=2)
    plan = FaultPlan(CHAOS_SEED, (
        FaultSpec("io.read_array", kind="truncate", at=(1,)),))
    with plan.installed():
        with pytest.raises(CorruptBundleError):
            load_index(path)
    assert load_index(path) is not None


def test_interrupted_save_previous_bundle_loadable(stream_index, unit_data,
                                                   tmp_path):
    """Crash-safe commit protocol: kill the sharded save before the
    manifest lands — the previous bundle must still load byte-clean."""
    x, _, _ = unit_data
    path = os.path.join(tmp_path, "bundle")
    save_index(stream_index, path, shards=2)
    first = load_index(path)
    stream_index.insert(np.asarray(x[2000:2016]))
    # a torn second save: member files appear, the manifest commit never
    # happens (simulated by the writer dying mid-way)
    with open(os.path.join(path, "shard_0000-00000000.npz"), "wb") as fh:
        fh.write(b"\x00" * 100)           # torn write from the dead saver
    again = load_index(path)
    assert again.n_total == first.n_total     # still the committed state
    # the next successful save commits atomically and sweeps the orphan
    save_index(stream_index, path, shards=2)
    assert "shard_0000-00000000.npz" not in os.listdir(path)
    assert load_index(path).n_total == stream_index.n_total
