"""Open-loop arithmetic on a fake gateway: latency from the due time,
generator lateness, the fixed arrival set, and the nearest-rank
percentile."""
import threading
import time

import numpy as np

from bench import traffic


class _Result:
    def __init__(self, latency_s, queued_s):
        self.ids = np.arange(3)
        self.dists = np.zeros(3, np.float32)
        self.latency_s = latency_s
        self.queued_s = queued_s
        self.batch = 1


class _Handle:
    def __init__(self, service_s, queued_s, fail=False):
        self.t_enqueue = time.perf_counter()
        self._r = _Result(service_s, queued_s)
        self._fail = fail

    def result(self, timeout=None):
        if self._fail:
            raise TimeoutError("never answered")
        return self._r


class FakeGateway:
    """Answers each request ``service_s`` after it was queued; ``stall_s``
    makes every ``stall_every``-th submit block the generator."""

    def __init__(self, service_s=0.004, stall_every=0, stall_s=0.0,
                 drop_every=0):
        self.service_s = service_s
        self.stall_every = stall_every
        self.stall_s = stall_s
        self.drop_every = drop_every
        self.n = 0
        self.lock = threading.Lock()

    def submit(self, q):
        with self.lock:
            self.n += 1
            n = self.n
        if self.stall_every and n % self.stall_every == 0:
            time.sleep(self.stall_s)
        return _Handle(self.service_s, self.service_s / 2,
                       fail=bool(self.drop_every and n % self.drop_every == 0))


POOL = np.zeros((7, 4), np.float32)


def test_arrival_schedule_is_fixed_by_the_mix():
    mix = {"rate_qps": 500.0}
    a = traffic.arrival_times(mix, 2.0)
    assert len(a) == 1000 and (np.diff(a) >= 0).all()
    assert 0.0 <= a[0] and a[-1] < 2.0
    np.testing.assert_array_equal(a, traffic.arrival_times(mix, 2.0))
    other = traffic.arrival_times(dict(mix, schedule_seed=1), 2.0)
    assert len(other) == 1000 and not np.allclose(a, other)


def test_latency_runs_from_due_time():
    gw = FakeGateway(service_s=0.004)
    win = traffic.run_open_loop(gw.submit, POOL, {"rate_qps": 200.0}, 0.5)
    assert win.attempted == 100 and win.failed == 0
    assert len(win.qidx) == 100
    # latency = lateness + the gateway's own enqueue -> answer time
    np.testing.assert_allclose(win.latency_s, win.lateness_s + 0.004,
                               atol=1e-9)
    assert (win.lateness_s > -1e-3).all()
    np.testing.assert_allclose(win.queued_s, 0.002)
    np.testing.assert_allclose(win.service_s, 0.002)
    np.testing.assert_array_equal(win.qidx, np.arange(100) % len(POOL))


def test_stalled_generator_shows_as_latency_and_lateness():
    calm = traffic.run_open_loop(FakeGateway().submit, POOL,
                                 {"rate_qps": 200.0}, 0.5)
    stalled = traffic.run_open_loop(
        FakeGateway(stall_every=20, stall_s=0.05).submit, POOL,
        {"rate_qps": 200.0}, 0.5)
    # a stall delays every request due behind it; their latency counts it
    assert stalled.lateness_s.max() >= 0.04
    assert traffic.percentile(stalled.latency_s, 99) >= 0.04
    assert traffic.percentile(calm.latency_s, 99) < 0.04


def test_unanswered_requests_count_as_failed_and_fill_the_tail():
    win = traffic.run_open_loop(FakeGateway(drop_every=10).submit, POOL,
                                {"rate_qps": 200.0}, 0.5, grace_s=0.0)
    assert win.failed == 10 and len(win.qidx) == 90
    assert np.isinf(win.latency_s).sum() == 10
    assert traffic.percentile(win.latency_s, 95) == np.inf


def test_nearest_rank_percentile():
    v = np.arange(1, 101, dtype=float)
    assert traffic.percentile(v, 50) == 50
    assert traffic.percentile(v, 99) == 99
    assert traffic.percentile(v, 100) == 100
    assert traffic.percentile(np.array([3.0]), 99) == 3.0
