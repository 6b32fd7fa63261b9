"""Observability: engine-deep tracing, unified stats, trace export.

The tracer (``repro/obs/tracer.py``) is a span recorder threaded through
the whole serving stack — ``Gateway`` dispatcher (wait, flush, stack,
fetch, fulfill) → ``Searcher`` dispatch (pad, execute, slice) → engine
stages → sharded lowering.  It is **off by default**: every
instrumentation point goes through the module-level ``span()`` /
``fence()`` helpers, which are no-ops (shared singleton span, no device
sync, no recorded work) until ``start()`` installs an active tracer.

An active tracer runs in one of two modes:

  * fenced (the default, ``launch/serve.py --trace``): device work is
    timed by fencing (``jax.block_until_ready``) at stage boundaries and
    staged pipelines (``seil_search_traced`` et al.) replace the
    monolithic executables — bitwise-identical by construction and
    asserted in tests/test_obs.py.  Host-clock stage times, for reading
    a dispatch's structure, not its speed;
  * profiler (``start(profiler=True)``): each span is also a
    ``jax.profiler.TraceAnnotation``, so a profiler capture holds the
    spans on its own clock beside the device's ops; nothing is fenced,
    no staged program is built, and the served executables run as in
    production, where ``jax.named_scope`` names each engine stage
    (``select_lists``, ``plan_blocks``, ``scan``, ``finalize``) in the
    ops' metadata.  Collections show as ``python.gc`` spans.

Export paths (DESIGN.md §11):
  * ``write_trace`` — Chrome/Perfetto trace-event JSON (``--trace`` on
    launch/serve.py); ``validate_trace`` is the schema gate CI runs.
  * ``to_prometheus`` — text exposition of any nested stats dict.
  * ``snapshot_all`` — the one documented stats schema unifying session
    compile stats, plan-cache stats, per-stage DCO from span counters,
    gateway telemetry, and the modeled HBM traffic of the scan stage.
"""
from .export import (to_prometheus, to_trace_events, validate_trace,
                     write_trace)
from .stats import scan_traffic_model, session_traffic_model, snapshot_all
from .tracer import (Tracer, enabled, fence, fencing, span, start, stop,
                     trace, tracer, work_count)

__all__ = [
    "Tracer", "enabled", "fence", "fencing", "span", "start", "stop", "trace",
    "tracer", "work_count",
    "to_trace_events", "write_trace", "validate_trace", "to_prometheus",
    "snapshot_all", "scan_traffic_model", "session_traffic_model",
]
