"""Typed error taxonomy for the serving stack (DESIGN.md §13).

Every failure a caller can observe from the gateway, the streaming
handover machinery, or the persistence layer is a subclass of
``RairsError`` — so ``except RairsError`` catches "the system told me
no" while letting genuine bugs (TypeError, KeyError, ...) propagate.

Several leaves *also* subclass the stdlib exception callers
historically saw at that site (``GatewayClosed`` is a RuntimeError,
``DeadlineExceeded`` a TimeoutError, ``CorruptBundleError`` a
ValueError), so pre-taxonomy ``except`` clauses keep working — the
taxonomy tightens what is raised, never what is caught.

This module is dependency-free on purpose: anything (core/io.py, the
gateway, the fault injector) may import it without pulling in jax.
"""
from __future__ import annotations

__all__ = [
    "RairsError",
    "Overloaded",
    "DeadlineExceeded",
    "GatewayClosed",
    "HandoverFailed",
    "CorruptBundleError",
    "FaultInjected",
]


class RairsError(Exception):
    """Root of every deliberate, typed failure this system raises."""


class Overloaded(RairsError):
    """Admission control shed the request: the gateway queue was at
    ``max_queue`` under the ``reject`` overload policy.  The request
    was never enqueued; retrying after backoff is safe."""


class DeadlineExceeded(RairsError, TimeoutError):
    """The request's deadline passed before dispatch.  Raised at
    dequeue time — a request that has already blown its budget is
    failed, never scanned.  Subclasses TimeoutError so generic
    timeout handling still applies."""


class GatewayClosed(RairsError, RuntimeError):
    """The gateway is shut down (or closed while this request was
    queued past the drain window).  Subclasses RuntimeError: callers
    that caught the old ``RuntimeError("gateway is closed")`` still
    do."""


class HandoverFailed(RairsError, RuntimeError):
    """Async compaction failed after exhausting its retry budget; the
    gateway rolled back to the pinned old epoch and keeps serving.
    ``__cause__`` carries the final underlying exception."""


class CorruptBundleError(RairsError, ValueError):
    """A persisted index bundle failed integrity verification
    (truncated file, bad magic, or a per-array crc32 mismatch).  The
    message names the offending member, e.g.
    ``shard_0003-1a2b3c4d.npz:block_codes``."""


class FaultInjected(RairsError):
    """Raised by an installed ``FaultPlan`` at a ``raise``-kind fault
    site.  Only ever seen in chaos tests — production code paths treat
    it like any other dispatch/worker failure."""
