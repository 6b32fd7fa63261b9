"""Engine-deep tracing & unified stats (src/repro/obs/, DESIGN.md §11).

Key invariants:
  * tracing disabled is the production path: the module-global work
    counter does not move across a full search dispatch (a counter
    assertion, deliberately not a timing one), ``span()`` hands back a
    shared no-op singleton, and ``fence()`` returns its argument
    untouched;
  * tracing on changes *when* the host observes device values, never
    the values — every dispatch path (monolithic, fused top-k,
    plan-reuse, sharded, streaming delta) returns bitwise-identical
    ids/dists traced vs untraced;
  * spans are well-nested per thread even under concurrent gateway
    submits (request exemplars live on separate virtual tracks);
  * profiler mode runs the production executables (no compile, no staged
    program, no fence, bitwise-equal results) and its spans land in the
    JAX profiler's ``.xplane.pb`` on the thread that ran them;
  * the exported document is schema-valid Chrome/Perfetto trace-event
    JSON, and ``snapshot_all``/``to_prometheus`` carry the documented
    layout.
"""
import itertools
import json
import threading

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import obs
from repro.core import (IndexConfig, SearchParams, StreamConfig,
                        StreamingIndex, build_index)
from repro.gateway import Gateway, GatewayConfig
from repro.obs.tracer import _REQ_TID_BASE


@pytest.fixture(autouse=True)
def clean_tracer():
    """No tracer leaks into or out of any test, even on failure."""
    if obs.enabled():
        obs.stop()
    yield
    if obs.enabled():
        obs.stop()


def _run(searcher, q, n=32):
    res = searcher(q[:n])
    return jax.tree.map(np.asarray, res)


# ---------------------------------------------------------------------------
# zero overhead while disabled
# ---------------------------------------------------------------------------

def test_disabled_tracing_does_no_work(rairs_index, unit_data):
    _, q, _ = unit_data
    searcher = rairs_index.searcher(SearchParams(k=10, nprobe=8))
    _run(searcher, q)                       # compile outside the window
    assert not obs.enabled() and obs.tracer() is None
    w0 = obs.work_count()
    _run(searcher, q)
    assert obs.work_count() == w0           # no span, event, or fence
    # span() is a shared no-op singleton; fence() is identity
    assert obs.span("a", cat="device") is obs.span("b")
    x = np.arange(3)
    assert obs.fence(x) is x
    assert obs.work_count() == w0


# ---------------------------------------------------------------------------
# traced == untraced, bitwise, on every dispatch path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("label,params,expect_spans", [
    ("paged", SearchParams(k=10, nprobe=8),
     {"stage.select_lists", "stage.plan_blocks", "stage.scan_blocks_topk",
      "stage.finalize"}),
    ("fused", SearchParams(k=10, nprobe=8, use_kernel=True),
     {"stage.scan_blocks_topk"}),
    ("plan_reuse", SearchParams(k=10, nprobe=8, exec_mode="clustered",
                                plan_reuse=True),
     {"stage.probe_plan", "stage.merge_unions_host",
      "stage.scan_finalize"}),
])
def test_traced_results_bitwise_identical(rairs_index, unit_data, label,
                                          params, expect_spans):
    _, q, _ = unit_data
    searcher = rairs_index.searcher(params)
    ref = _run(searcher, q)
    with obs.trace():
        _run(searcher, q)                   # compile the traced stages
    with obs.trace() as tr:
        res = _run(searcher, q)
    np.testing.assert_array_equal(ref.ids, res.ids)
    np.testing.assert_array_equal(ref.dists, res.dists)
    np.testing.assert_array_equal(ref.approx_dco, res.approx_dco)
    summary = tr.stage_summary()
    assert expect_spans <= set(summary), summary.keys()
    assert "searcher.dispatch" in summary
    assert tr.fences > 0                    # device work was fenced


def test_traced_sharded_dispatch_bitwise_identical(rairs_index, unit_data):
    _, q, _ = unit_data
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    searcher = rairs_index.shard(mesh).searcher(SearchParams(k=10, nprobe=8))
    ref = _run(searcher, q)
    with obs.trace():
        _run(searcher, q)
    with obs.trace() as tr:
        res = _run(searcher, q)
    np.testing.assert_array_equal(ref.ids, res.ids)
    np.testing.assert_array_equal(ref.dists, res.dists)
    summary = tr.stage_summary()
    assert {"stage.shard_scan", "stage.gather_finalize"} <= set(summary)
    # the per-stage DCO split lands on the right stages
    assert summary["stage.shard_scan"]["counters"]["approx_dco"] > 0
    assert summary["stage.gather_finalize"]["counters"]["refine_dco"] > 0


def test_traced_streaming_delta_scan(unit_data, shared_trained):
    x, q, _ = unit_data
    cents, cb = shared_trained
    cfg = IndexConfig(nlist=64, strategy="rair", seil=True)
    base = build_index(jax.random.PRNGKey(0), x[:4000], cfg,
                       centroids=cents, codebook=cb)
    stream = StreamingIndex(base, StreamConfig(delta_pad=512))
    stream.insert(x[4000:4256])
    searcher = stream.searcher(SearchParams(k=10, nprobe=8))
    ref = _run(searcher, q)
    with obs.trace():
        _run(searcher, q)
    with obs.trace() as tr:
        res = _run(searcher, q)
    np.testing.assert_array_equal(ref.ids, res.ids)
    np.testing.assert_array_equal(ref.dists, res.dists)
    summary = tr.stage_summary()
    assert "stage.delta_scan" in summary
    assert summary["stage.delta_scan"]["counters"]["delta_dco"] > 0


# ---------------------------------------------------------------------------
# well-nesting under concurrent gateway traffic
# ---------------------------------------------------------------------------

def _assert_well_nested(records):
    by_tid = {}
    for r in records:
        if r["kind"] == "span":
            by_tid.setdefault(r["tid"], []).append(
                (r["ts"], r["ts"] + r["dur"]))
    assert by_tid
    for tid, iv in by_tid.items():
        for (s1, e1), (s2, e2) in itertools.combinations(sorted(iv), 2):
            disjoint = e1 <= s2 or e2 <= s1
            nested = (s1 <= s2 and e2 <= e1) or (s2 <= s1 and e1 <= e2)
            assert disjoint or nested, \
                f"tid {tid}: spans ({s1},{e1}) and ({s2},{e2}) interleave"


def test_spans_well_nested_under_concurrent_submits(rairs_index, unit_data):
    _, q, _ = unit_data
    errors = []

    def client(seed, gw):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(8):
                gw.search(q[int(rng.integers(0, q.shape[0]))], timeout=60.0)
        except Exception as e:                         # pragma: no cover
            errors.append(e)

    with obs.trace() as tr:
        with Gateway(rairs_index, k=10, nprobe=8,
                     config=GatewayConfig(max_batch=8,
                                          max_delay_ms=2.0)) as gw:
            threads = [threading.Thread(target=client, args=(i, gw))
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
    assert not errors
    names = {r["name"] for r in tr.records}
    assert {"gateway.submit", "gateway.flush", "searcher.dispatch"} <= names
    _assert_well_nested(tr.records)
    # request exemplars are events on virtual tracks, outside the
    # nesting contract
    reqs = [r for r in tr.records if r["name"] == "gateway.request"]
    assert reqs and all(r["kind"] == "event" and r["tid"] >= _REQ_TID_BASE
                        for r in reqs)


# ---------------------------------------------------------------------------
# tracer contracts
# ---------------------------------------------------------------------------

def test_start_stop_contracts():
    with pytest.raises(RuntimeError):
        obs.stop()                          # nothing active
    t = obs.start()
    try:
        with pytest.raises(RuntimeError):
            obs.start()                     # no nested tracers
    finally:
        assert obs.stop() is t
    with pytest.raises(ValueError):
        obs.Tracer(sample=0)


def test_max_events_bounds_memory_and_counts_drops():
    with obs.trace(max_events=2) as tr:
        for i in range(5):
            with obs.span(f"s{i}"):
                pass
    assert len(tr.records) == 2 and tr.dropped == 3


def test_event_sampling_and_virtual_tracks():
    with obs.trace(sample=3) as tr:
        hits = [tr.sampled() for _ in range(9)]
        tr.event("gateway.request", tr.t0, 1e-3, queued_ms=0.5)
    assert hits == [True, False, False] * 3
    (ev,) = tr.records
    assert ev["kind"] == "event" and ev["tid"] >= _REQ_TID_BASE


# ---------------------------------------------------------------------------
# export: trace-event JSON + Prometheus text
# ---------------------------------------------------------------------------

def test_trace_event_export_roundtrip(tmp_path):
    with obs.trace() as tr:
        with obs.span("stage.demo", cat="device", approx_dco=3):
            with obs.span("inner"):
                pass
        tr.event("gateway.request", tr.t0, 1e-3, queued_ms=0.1)
    path = tmp_path / "trace.json"
    doc = obs.write_trace(tr, str(path))
    assert json.loads(path.read_text()) == doc
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"stage.demo", "inner",
                                       "gateway.request"}
    demo = next(e for e in xs if e["name"] == "stage.demo")
    inner = next(e for e in xs if e["name"] == "inner")
    assert demo["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= demo["ts"] + demo["dur"] + 1e-6
    assert demo["args"]["approx_dco"] == 3
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert any(m["name"] == "process_name" for m in metas)
    tracks = {m["args"]["name"] for m in metas if m["name"] == "thread_name"}
    assert any(n.startswith("thread-") for n in tracks)
    assert any(n.startswith("requests-") for n in tracks)
    assert doc["otherData"]["fences"] == tr.fences


def test_validate_trace_rejects_malformed():
    ok = {"traceEvents": [{"name": "a", "ph": "X", "pid": 1, "tid": 0,
                           "ts": 0.0, "dur": 1.0}]}
    assert obs.validate_trace(ok) is ok
    for bad in (
        [],                                             # not an object
        {"traceEvents": []},                            # empty
        {"traceEvents": [{"name": "a", "ph": "B",       # unsupported ph
                          "pid": 1, "tid": 0}]},
        {"traceEvents": [{"ph": "X", "pid": 1, "tid": 0,
                          "ts": 0.0, "dur": 1.0}]},     # nameless
        {"traceEvents": [{"name": "a", "ph": "X", "pid": 1, "tid": 0,
                          "ts": -1.0, "dur": 1.0}]},    # negative ts
        {"traceEvents": [{"name": "a", "ph": "X", "pid": 1, "tid": 0,
                          "ts": 0.0, "dur": 1.0, "args": 7}]},
    ):
        with pytest.raises(ValueError):
            obs.validate_trace(bad)


def test_prometheus_exposition():
    text = obs.to_prometheus({"a": {"b": 1.5, "on": True}, "c": 2,
                              "drop": ["x"], "strs": "no",
                              "name.with-dots": 7})
    lines = text.splitlines()
    assert text.endswith("\n") and lines == sorted(lines)
    assert "rairs_a_b 1.5" in lines
    assert "rairs_a_on 1" in lines
    assert "rairs_c 2" in lines
    assert "rairs_name_with_dots 7" in lines
    assert not any("drop" in ln or "strs" in ln for ln in lines)


# ---------------------------------------------------------------------------
# snapshot_all: the unified stats schema
# ---------------------------------------------------------------------------

def test_snapshot_all_schema(rairs_index, unit_data):
    _, q, _ = unit_data
    searcher = rairs_index.searcher(SearchParams(k=10, nprobe=8))
    with obs.trace():
        _run(searcher, q)                   # compile traced stages
    with obs.trace() as tr:
        _run(searcher, q)
    snap = obs.snapshot_all(searcher=searcher, tracer=tr)
    assert snap["schema_version"] == 1
    assert set(snap) == {"schema_version", "session", "hbm_model", "trace"}
    assert snap["session"]["compiles"] >= 1
    model = snap["hbm_model"]
    assert model["scan_width"] >= model["fetch"] > 0
    assert set(model["bytes_per_query"]) == {
        "unfused_scan_write", "fused_scan_write", "write_reduction_x",
        "unfused_roundtrip", "fused_roundtrip", "roundtrip_reduction_x"}
    trace = snap["trace"]
    assert 0.0 < trace["stage_attribution"] <= 1.0
    assert trace["fences"] > 0 and trace["dropped"] == 0
    assert trace["dco"]["stage.scan_blocks_topk.approx_dco"] > 0
    assert trace["dco"]["stage.finalize.refine_dco"] > 0
    # the trace section renders to prometheus lines end-to-end
    assert "rairs_trace_stage_attribution" in obs.to_prometheus(snap)


def _assert_boundary_traffic_cut(model):
    """The scan stage's top-``fetch`` selection cuts the modeled bytes
    per query crossing the scan/finalize boundary at least 4x, written
    and round trip, against writing the full candidate stream."""
    assert model["fused_scan_write"] < model["unfused_scan_write"], model
    assert model["write_reduction_x"] >= 4.0, model
    assert model["roundtrip_reduction_x"] >= 4.0, model


@pytest.mark.parametrize("max_scan,k_factor", [(557, 10), (604, 50)],
                         ids=["sift1m", "t2i1m"])
def test_scan_traffic_model_at_the_deployments(max_scan, k_factor):
    """At the benchmark deployments' widths: block 32, k 10, SEIL
    layouts (no result dedup, so fetch = bigK)."""
    from repro.core.search import finalize_fetch
    from repro.obs.stats import scan_traffic_model
    _assert_boundary_traffic_cut(scan_traffic_model(
        scan_width=max_scan * 32,
        fetch=finalize_fetch(10 * k_factor, 2, False)))


def test_session_traffic_model_cuts_boundary_traffic(rairs_index):
    """At a live session's own operating point (the unit index at
    nprobe 16, its default scan budget)."""
    from repro.obs.stats import session_traffic_model
    model = session_traffic_model(
        rairs_index.searcher(SearchParams(k=10, nprobe=16)))
    assert model["scan_width"] > model["fetch"] > 0
    _assert_boundary_traffic_cut(model["bytes_per_query"])


def test_snapshot_all_with_gateway(rairs_index, unit_data):
    _, q, _ = unit_data
    with Gateway(rairs_index, k=10, nprobe=8,
                 config=GatewayConfig(max_batch=8, max_delay_ms=2.0)) as gw:
        for i in range(8):
            gw.search(q[i])
        snap = obs.snapshot_all(gateway=gw)
    assert {"schema_version", "gateway", "session", "hbm_model"} <= set(snap)
    assert snap["gateway"]["telemetry"]["counters"]["responses"] == 8
    assert "trace" not in snap              # no tracer supplied


# ---------------------------------------------------------------------------
# profiler mode: spans on the profiler's clock, production executables
# ---------------------------------------------------------------------------

def test_disabled_tracing_does_no_work_through_the_gateway(rairs_index,
                                                            unit_data):
    """With tracing off, the dispatcher's spans (wait, flush, stack,
    fetch, fulfill) and the session's (pad, execute, slice) do no work."""
    _, q, _ = unit_data
    with Gateway(rairs_index, k=10, nprobe=8,
                 config=GatewayConfig(max_batch=8, max_delay_ms=2.0)) as gw:
        gw.search(q[0], timeout=60.0)       # compile outside the window
        w0 = obs.work_count()
        for i in range(4):
            gw.search(q[i], timeout=60.0)   # one row, padded to a bucket
        assert obs.work_count() == w0
    searcher = rairs_index.searcher(SearchParams(k=10, nprobe=8))
    _run(searcher, q, n=3)
    w0 = obs.work_count()
    _run(searcher, q, n=3)                  # padded: pad, execute, slice
    assert obs.work_count() == w0


def _stage_cache_sizes():
    from repro.core import search as search_mod
    from repro.core.stream import search as stream_search
    return [f._cache_size() for f in (
        search_mod._stage_select, search_mod._stage_plan,
        search_mod._stage_scan, search_mod._stage_finalize,
        stream_search._stage_delta, stream_search._stage_finalize_stream)]


def _session(label, rairs_index, unit_data, shared_trained):
    p = SearchParams(k=10, nprobe=8)
    if label == "sharded":
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
        return rairs_index.shard(mesh).searcher(p)
    if label == "streaming":
        x, _, _ = unit_data
        cents, cb = shared_trained
        base = build_index(jax.random.PRNGKey(0), x[:4000],
                           IndexConfig(nlist=64, strategy="rair", seil=True),
                           centroids=cents, codebook=cb)
        stream = StreamingIndex(base, StreamConfig(delta_pad=512))
        stream.insert(x[4000:4256])
        return stream.searcher(p)
    return rairs_index.searcher({
        "paged": p,
        "fused": SearchParams(k=10, nprobe=8, use_kernel=True),
        "plan_reuse": SearchParams(k=10, nprobe=8, exec_mode="clustered",
                                   plan_reuse=True)}[label])


@pytest.mark.parametrize("label", ["paged", "fused", "plan_reuse",
                                   "sharded", "streaming"])
def test_profiler_mode_runs_the_production_executables(
        rairs_index, unit_data, shared_trained, label):
    _, q, _ = unit_data
    searcher = _session(label, rairs_index, unit_data, shared_trained)
    ref = _run(searcher, q, n=5)            # compiles its bucket
    compiles = searcher.stats.compiles
    stages = _stage_cache_sizes()
    with obs.trace(profiler=True) as tr:
        res = _run(searcher, q, n=5)
    assert searcher.stats.compiles == compiles
    assert _stage_cache_sizes() == stages   # no _stage_* program built
    assert tr.fences == 0
    np.testing.assert_array_equal(ref.ids, res.ids)
    np.testing.assert_array_equal(ref.dists, res.dists)
    names = {r["name"] for r in tr.records}
    assert {"searcher.dispatch", "searcher.pad", "searcher.execute",
            "searcher.slice"} <= names
    if label != "plan_reuse":               # its stages are its programs
        assert not any(n.startswith("stage.") for n in names)


def _xplane_spans(path):
    """{line index: [(start, end, name)]} of the host lines' events."""
    pd = jax.profiler.ProfileData.from_file(path)
    out = {}
    i = 0
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out[i] = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in line.events]
            i += 1
    return out


def _contains(outer, inner):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_profiler_mode_spans_land_in_the_xplane(rairs_index, unit_data,
                                                tmp_path):
    import glob
    import os
    _, q, _ = unit_data
    with Gateway(rairs_index, k=10, nprobe=8,
                 config=GatewayConfig(max_batch=8, max_delay_ms=2.0)) as gw:
        gw.search(q[0], timeout=60.0)       # compile outside the capture
        jax.profiler.start_trace(str(tmp_path))
        try:
            with obs.trace(profiler=True) as tr:
                for i in range(6):
                    gw.search(q[i], timeout=60.0)
        finally:
            jax.profiler.stop_trace()
    assert tr.fences == 0
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    lines = _xplane_spans(path)
    flushes = {i: [e for e in evs if e[2] == "gateway.flush"]
               for i, evs in lines.items()}
    (disp,) = [i for i, f in flushes.items() if f]   # one dispatcher line
    evs = lines[disp]
    subs = [i for i, e in lines.items()
            if any(x[2] == "gateway.submit" for x in e)]
    assert subs and disp not in subs         # clients submit elsewhere
    for name in ("gateway.wait", "gateway.stack", "gateway.fetch",
                 "gateway.fulfill"):
        assert any(e[2] == name for e in evs), name
    # gateway.flush > searcher.dispatch > searcher.execute, on this thread
    nested = 0
    for f in flushes[disp]:
        for d in (e for e in evs if e[2] == "searcher.dispatch"
                  and _contains(f, e)):
            nested += any(e[2] == "searcher.execute" and _contains(d, e)
                          for e in evs)
    assert nested >= len(flushes[disp]) >= 1
    # the in-memory record holds the same spans
    assert {"gateway.flush", "searcher.execute", "gateway.wait"} <= {
        r["name"] for r in tr.records}


def test_profiler_mode_records_collections_as_spans():
    import gc
    with obs.trace(profiler=True) as tr:
        hook = tr._on_gc
        assert hook in gc.callbacks
        gc.collect()
    assert hook not in gc.callbacks          # removed with the tracer
    recs = [r for r in tr.records if r["name"] == "python.gc"]
    assert all(r["kind"] == "span" for r in recs)
    assert any(r["args"]["generation"] == 2 for r in recs)
    with obs.trace() as fenced:              # the default mode: no hook
        assert fenced._on_gc not in gc.callbacks
        gc.collect()
    assert not fenced.records
