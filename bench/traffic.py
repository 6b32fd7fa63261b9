"""The one general traffic generator: a mix is a data file that it reads.

``bench/traffic/<mix>.json`` holds ``{"kind": ..., ...parameters}``:

* ``"batch"``: a closed loop with one client.  Batches of ``batch``
  queries are drawn in order from the pool (wrapping round), each sent
  through the session and its answer copied back to the host before
  the next is sent.  The window runs batches until ``seconds`` have
  passed; the batch in flight at the close finishes and counts, and the
  rate is taken over all the work and all the time.
* ``"open_loop"``: single-query requests through the gateway, due on a
  fixed schedule of ``rate_qps`` x ``seconds`` arrivals, whatever the
  server does.  ``gateway`` holds the gateway's settings.  The schedule
  is drawn once from the mix's ``schedule_seed`` (a Poisson process
  with that many arrivals in the window), the same for every ``--seed``,
  so every run offers the same load; the seed orders the query pool
  they draw from (``corpus.py``).  Latency runs from
  the instant a request was due to the instant its answer came back, so
  a generator that falls behind shows as latency and as lateness.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Callable, List, Optional

import numpy as np

GRACE_S = 60.0           # how long past the close an answer may still come


def span(name: str, on: bool):
    """A host span in the profiler's trace, or nothing when not tracing."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Window:
    """What one measured window produced."""
    seconds: float                   # wall time of the window, all of it
    qidx: np.ndarray                 # (A,) pool row of every answer
    ids: np.ndarray                  # (A, k) returned ids
    dists: np.ndarray                # (A, k) returned distances
    attempted: int
    failed: int
    latency_s: Optional[np.ndarray] = None   # (attempted,) due -> answer, inf if failed
    queued_s: Optional[np.ndarray] = None    # (A,) program's enqueue -> taken
    service_s: Optional[np.ndarray] = None   # (A,) program's taken -> answered
    lateness_s: Optional[np.ndarray] = None  # (attempted,) due -> submitted
    flush_sizes: Optional[np.ndarray] = None  # (A,) requests in each answer's flush
    batches: List[np.ndarray] = dataclasses.field(default_factory=list)


def batch_rows(i: int, batch: int, n_pool: int) -> np.ndarray:
    return (np.arange(batch) + i * batch) % n_pool


def run_batch(search: Callable, pool: np.ndarray, mix: dict, seconds: float,
              trace: bool = False) -> Window:
    """Closed loop.  ``search(q (B, d) host f32) -> (ids, dists)``."""
    batch = int(mix["batch"])
    qidx, ids, dists, batches = [], [], [], []
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < seconds:
        rows = batch_rows(i, batch, pool.shape[0])
        with span("bench.session_call", trace):
            res = search(pool[rows])
        with span("bench.block", trace):
            ids.append(np.asarray(res[0]))
            dists.append(np.asarray(res[1]))
        qidx.append(rows)
        batches.append(rows)
        i += 1
    elapsed = time.perf_counter() - t0
    n = i * batch
    return Window(seconds=elapsed, qidx=np.concatenate(qidx),
                  ids=np.concatenate(ids), dists=np.concatenate(dists),
                  attempted=n, failed=0, batches=batches)


def arrival_times(mix: dict, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of ``rate_qps * seconds``
    requests, drawn from the mix's ``schedule_seed``."""
    n = max(1, int(round(float(mix["rate_qps"]) * seconds)))
    rng = np.random.default_rng(int(mix.get("schedule_seed", 0)))
    return np.sort(rng.uniform(0.0, seconds, size=n))


def run_open_loop(submit: Callable, pool: np.ndarray, mix: dict,
                  seconds: float, trace: bool = False,
                  grace_s: Optional[float] = None) -> Window:
    """Open loop.  ``submit(q (d,)) -> handle`` with ``t_enqueue`` (the
    ``time.perf_counter`` instant it was queued) and ``result(timeout)``
    returning an object with ``ids``, ``dists``, ``latency_s`` (enqueue
    -> answer), ``queued_s`` (enqueue -> taken into a flush) and
    ``batch`` (the requests in that flush)."""
    grace_s = GRACE_S if grace_s is None else grace_s
    due = arrival_times(mix, seconds)
    n = len(due)
    n_pool = pool.shape[0]
    handles = []
    t0 = time.perf_counter() + 0.001
    for i in range(n):
        at = t0 + due[i]
        wait = at - time.perf_counter()
        if wait > 0:
            with span("bench.wait", trace):
                time.sleep(wait)
        with span("bench.submit", trace):
            handles.append(submit(pool[i % n_pool]))
    close = t0 + seconds
    latency = np.full(n, np.inf)
    lateness = np.zeros(n)
    qidx, ids, dists, queued, service, flush = [], [], [], [], [], []
    failed = 0
    last = close
    with span("bench.wait", trace):
        for i, h in enumerate(handles):
            lateness[i] = h.t_enqueue - (t0 + due[i])
            try:
                r = h.result(max(0.0, close + grace_s - time.perf_counter()))
            except Exception:           # shed, failed or never answered
                failed += 1
                continue
            done = h.t_enqueue + r.latency_s
            last = max(last, done)
            latency[i] = done - (t0 + due[i])
            qidx.append(i % n_pool)
            ids.append(np.asarray(r.ids))
            dists.append(np.asarray(r.dists))
            queued.append(r.queued_s)
            service.append(r.latency_s - r.queued_s)
            flush.append(r.batch)
    k = ids[0].shape[0] if ids else 0
    return Window(
        seconds=last - t0,
        qidx=np.asarray(qidx, np.int64),
        ids=np.stack(ids) if ids else np.zeros((0, k), np.int64),
        dists=np.stack(dists) if dists else np.zeros((0, k), np.float32),
        attempted=n, failed=failed, latency_s=latency,
        queued_s=np.asarray(queued), service_s=np.asarray(service),
        lateness_s=lateness, flush_sizes=np.asarray(flush, np.int64))


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the sample at or below it (inf where failures fill the tail)."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return float("nan")
    i = min(max(int(math.ceil(q / 100.0 * len(v))) - 1, 0), len(v) - 1)
    return float(v[i])
