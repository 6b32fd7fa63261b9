"""Span-based tracer for the serving stack (DESIGN.md §11).

One ``Tracer`` records *complete* spans: name, category, thread, start
time, duration, nesting depth, and a free-form counter dict.  Spans are
opened with the module-level ``span(...)`` context manager, which
guarantees well-nesting per thread (a span closes before anything that
opened earlier on the same thread) — asserted in tests/test_obs.py
under concurrent gateway traffic.

Zero overhead when disabled is a hard contract: ``span()`` returns a
shared no-op singleton and ``fence()`` returns its argument untouched —
no lock, no allocation that grows, no device synchronization — so the
instrumented dispatch path is the production path.  The module keeps a
global work counter (``work_count()``) bumped on every recorded span,
raw event, and fence; tests assert it does not move while tracing is
off (a counter-based assertion, deliberately not a timing one).

``fence(x)`` is how device work becomes attributable: with a tracer
active it blocks until ``x``'s buffers are ready, so the enclosing
span's duration covers the device time of its stage instead of just the
dispatch cost of an async call.  Fencing changes *when* the host
observes values, never the values — traced results are bitwise
identical to untraced ones (asserted).

``Tracer.event`` records cross-thread exemplar events (e.g. one span
per sampled gateway request, spanning enqueue→fulfill) on virtual
request tracks; these carry no nesting contract.

Profiler mode (``start(profiler=True)``) is the mode for measuring
speed.  Each span also opens a ``jax.profiler.TraceAnnotation`` of the
same name, so a JAX profiler capture taken meanwhile holds the spans on
its own clock, on the thread that ran them, beside the device's ops.
``fence()`` does nothing and sessions keep their production
executables: the traced program is the served one.  While a
profiler-mode tracer is active a ``gc.callbacks`` hook records every
garbage collection as a ``python.gc`` span.
"""
from __future__ import annotations

import gc
import threading
import time
from typing import Any, Dict, List, Optional

import jax

# virtual-track tids for cross-thread exemplar events (Tracer.event):
# requests overlap in time, so they rotate over a small pool of tracks
# instead of stacking on the recording thread's (well-nested) track.
_REQ_TID_BASE = 1_000_000
_REQ_TRACKS = 8

# module-global tracer work counter: spans + events + fences ever
# recorded.  The zero-overhead-when-disabled test pins this.
_WORK = 0
_ACTIVE: Optional["Tracer"] = None
_ACTIVE_LOCK = threading.Lock()


class _NoopSpan:
    """Shared do-nothing span returned by ``span()`` while disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def add(self, **counters):
        return self


_NOOP = _NoopSpan()


class _Span:
    """A live span; created by ``Tracer.span`` and recorded on exit."""

    __slots__ = ("_tracer", "name", "cat", "args", "t0", "depth", "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.t0 = 0.0
        self.depth = 0
        self._ann = None

    def add(self, **counters) -> "_Span":
        """Attach counters to the span (merged into its args)."""
        self.args.update(counters)
        return self

    def __enter__(self) -> "_Span":
        stack = self._tracer._stack()
        self.depth = len(stack)
        stack.append(self)
        if self._tracer.profiler:
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._tracer._stack().pop()
        self._tracer._record(self.name, self.cat, threading.get_ident(),
                             self.t0, t1 - self.t0, self.depth, self.args,
                             kind="span")
        return False


class Tracer:
    """Thread-safe span/event recorder.

    ``sample`` thins exemplar events (``sampled()`` is true once every
    ``sample`` calls); ``max_events`` bounds memory — past it, records
    are counted in ``dropped`` instead of stored.  ``profiler`` selects
    profiler mode (module docstring): spans also go to the JAX
    profiler's trace, and nothing is fenced.
    """

    def __init__(self, sample: int = 1, max_events: int = 200_000,
                 profiler: bool = False):
        if sample < 1:
            raise ValueError(f"sample must be >= 1, got {sample}")
        self.t0 = time.perf_counter()
        self.sample = sample
        self.max_events = max_events
        self.profiler = profiler
        self.records: List[Dict[str, Any]] = []
        self.fences = 0
        self.dropped = 0
        # re-entrant: a collection may start, and record its span, on a
        # thread that is inside a locked section
        self._lock = threading.RLock()
        self._tls = threading.local()
        self._sample_ctr = 0
        self._req_slot = 0
        self._gc_open: Dict[int, Any] = {}

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        """``gc.callbacks`` hook (profiler mode): one ``python.gc`` span
        per collection, on the thread whose allocation triggered it."""
        tid = threading.get_ident()
        if phase == "start":
            ann = jax.profiler.TraceAnnotation("python.gc")
            ann.__enter__()
            self._gc_open[tid] = (ann, time.perf_counter())
            return
        opened = self._gc_open.pop(tid, None)
        if opened is None:
            return
        ann, t0 = opened
        t1 = time.perf_counter()
        ann.__exit__(None, None, None)
        self._record("python.gc", "gc", tid, t0, t1 - t0,
                     len(self._stack()),
                     {"generation": info.get("generation"),
                      "collected": info.get("collected")}, kind="span")

    # -- recording ------------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _record(self, name, cat, tid, t0, dur, depth, args, kind) -> None:
        global _WORK
        rec = {"name": name, "cat": cat, "tid": tid,
               "ts": t0 - self.t0, "dur": dur, "depth": depth,
               "kind": kind, "args": args}
        with self._lock:
            _WORK += 1
            if len(self.records) >= self.max_events:
                self.dropped += 1
            else:
                self.records.append(rec)

    def span(self, name: str, cat: str = "host", **args) -> _Span:
        return _Span(self, name, cat, args)

    def event(self, name: str, t0: float, dur: float, cat: str = "request",
              tid: Optional[int] = None, **args) -> None:
        """Record a cross-thread complete event (no nesting contract).
        ``t0`` is an absolute ``time.perf_counter()`` timestamp.  Without
        an explicit ``tid`` the event lands on a rotating virtual
        request track so overlapping requests render side by side."""
        if tid is None:
            with self._lock:
                slot = self._req_slot
                self._req_slot = (slot + 1) % _REQ_TRACKS
            tid = _REQ_TID_BASE + slot
        self._record(name, cat, tid, t0, dur, 0, args, kind="event")

    def sampled(self) -> bool:
        """True once every ``sample`` calls (always true at sample=1)."""
        with self._lock:
            n = self._sample_ctr
            self._sample_ctr += 1
        return n % self.sample == 0

    # -- aggregation ----------------------------------------------------
    def stage_summary(self) -> Dict[str, Dict[str, Any]]:
        """Per-span-name aggregate: count, total/mean seconds, and the
        sum of every numeric counter the spans carried."""
        with self._lock:
            recs = list(self.records)
        out: Dict[str, Dict[str, Any]] = {}
        for r in recs:
            if r["kind"] != "span":
                continue
            agg = out.setdefault(r["name"], {"count": 0, "total_s": 0.0,
                                             "counters": {}})
            agg["count"] += 1
            agg["total_s"] += r["dur"]
            for k, v in r["args"].items():
                if isinstance(v, bool) or not isinstance(v, (int, float)):
                    continue
                agg["counters"][k] = agg["counters"].get(k, 0) + v
        for agg in out.values():
            agg["mean_ms"] = agg["total_s"] / agg["count"] * 1e3
        return out


# ---------------------------------------------------------------------------
# module-level API — the only names instrumentation sites use
# ---------------------------------------------------------------------------

def enabled() -> bool:
    """True while a tracer is active (``start()`` .. ``stop()``)."""
    return _ACTIVE is not None


def tracer() -> Optional[Tracer]:
    """The active tracer, or None."""
    return _ACTIVE


def work_count() -> int:
    """Total tracer work ever done in this process (spans + events +
    fences recorded).  Pinned by the zero-overhead-when-disabled test."""
    return _WORK


def span(name: str, cat: str = "host", **args):
    """Open a span on the active tracer, or a shared no-op when none."""
    t = _ACTIVE
    if t is None:
        return _NOOP
    return t.span(name, cat, **args)


def fencing() -> bool:
    """True while a fenced (not profiler-mode) tracer is active: sessions
    then dispatch their stage-fenced programs."""
    t = _ACTIVE
    return t is not None and not t.profiler


def fence(x):
    """Block until ``x``'s device buffers are ready — only while a fenced
    tracer is active (the production path and profiler mode never
    synchronize).  Returns ``x``."""
    t = _ACTIVE
    if t is not None and not t.profiler:
        global _WORK
        jax.block_until_ready(x)
        with t._lock:
            t.fences += 1
            _WORK += 1
    return x


def start(sample: int = 1, max_events: int = 200_000,
          profiler: bool = False) -> Tracer:
    """Install a fresh active tracer (errors if one is already active).
    ``profiler=True`` selects profiler mode (module docstring)."""
    global _ACTIVE
    with _ACTIVE_LOCK:
        if _ACTIVE is not None:
            raise RuntimeError("a tracer is already active; stop() it first")
        _ACTIVE = Tracer(sample=sample, max_events=max_events,
                         profiler=profiler)
        if profiler:
            gc.callbacks.append(_ACTIVE._on_gc)
        return _ACTIVE


def stop() -> Tracer:
    """Deactivate and return the active tracer (errors if none)."""
    global _ACTIVE
    with _ACTIVE_LOCK:
        if _ACTIVE is None:
            raise RuntimeError("no active tracer")
        t = _ACTIVE
        _ACTIVE = None
        if t.profiler:
            gc.callbacks.remove(t._on_gc)
        return t


class trace:
    """``with obs.trace() as tr: ...`` — start/stop scoped to a block."""

    def __init__(self, sample: int = 1, max_events: int = 200_000,
                 profiler: bool = False):
        self._kw = {"sample": sample, "max_events": max_events,
                    "profiler": profiler}

    def __enter__(self) -> Tracer:
        self._t = start(**self._kw)
        return self._t

    def __exit__(self, *exc) -> bool:
        stop()
        return False
