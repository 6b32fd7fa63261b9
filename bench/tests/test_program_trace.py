"""The reduction of the program's own spans and engine scopes: interval
partitions, the HLO scope map, and scopes and idle gaps on small traces
recorded here on the CPU (where the XLA CPU client's op events, which
carry ``hlo_module``/``hlo_op`` stats, stand in for a device's)."""
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from bench import program_trace as pt
from bench import trace


def test_innermost_partitions_nested_spans():
    spans = [(0.0, 10.0, "flush"), (1.0, 6.0, "dispatch"),
             (2.0, 5.0, "execute"), (7.0, 8.0, "fulfill"),
             (12.0, 13.0, "wait")]
    segs = pt.innermost(spans)
    assert segs == [(0.0, 1.0, "flush"), (1.0, 2.0, "dispatch"),
                    (2.0, 5.0, "execute"), (5.0, 6.0, "dispatch"),
                    (6.0, 7.0, "flush"), (7.0, 8.0, "fulfill"),
                    (8.0, 10.0, "flush"), (12.0, 13.0, "wait")]
    assert trace.total((a, b) for a, b, _ in segs) == 11.0
    # overlapping (not nested) intervals still partition their union
    assert pt.innermost([(0.0, 2.0, "a"), (1.0, 3.0, "b")]) == [
        (0.0, 1.0, "a"), (1.0, 3.0, "b")]


def test_split_puts_gaps_under_segments_first_layer_first():
    gaps = [(0.0, 4.0), (6.0, 9.0)]
    segs = [(1.0, 3.0, "flush"), (6.0, 7.0, "wait")]
    gc = [(2.0, 2.5, "python.gc")]
    out = pt.split(gaps, segs, gc)
    assert out == pytest.approx({"python.gc": 0.5, "flush": 1.5,
                                 "wait": 1.0, "none": 4.0})
    assert sum(out.values()) == pytest.approx(7.0)
    assert pt.split(gaps, []) == {"none": 7.0}


def test_stage_and_kernel_names():
    assert pt.stage_of("jit(seil_search)/scan/jit(_pad)/pad") == "scan"
    assert pt.stage_of("jit(f)/shard_map/plan_blocks/gather") == "plan_blocks"
    assert pt.stage_of("arrays.block_codes") is None
    assert pt.kernel_of('%pq_scan_topk.1 = custom-call(...)') == "pq_scan_topk"
    assert pt.kernel_of('%pq_scan.3 = custom-call(...)') == "pq_scan"
    assert pt.kernel_of("%fusion.2 = fusion(...)") is None


# compiled HLO as a v5e names it (shortened): a fusion whose own
# metadata is missing takes its body's stage, the Mosaic call its name
HLO = '''HloModule jit_seil_search, is_scheduled=true

%fused_computation.2 (param_0: s32[64]) -> s32[64] {
  %param_0 = s32[64]{0} parameter(0)
  ROOT %gather.1 = s32[64]{0} gather(%param_0), metadata={op_name="jit(seil_search)/plan_blocks/gather"}
}

ENTRY %main.33 (queries.1: f32[8,32]) -> s32[8,100] {
  %queries.1 = f32[8,32]{1,0} parameter(0), metadata={op_name="queries"}
  %fusion.2 = s32[64]{0} fusion(%x), kind=kCustom, calls=%fused_computation.2
  %pad.2 = u8[268,32,128]{2,1,0} pad(%y, %c), padding=0_0x0_0x0_112, metadata={op_name="jit(seil_search)/scan/jit(_pad)/pad"}
  %pq_scan_topk.1 = (f32[8,1,128]) custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(seil_search)/scan/jit(pq_scan_topk_kernel)/pq_scan_topk/pallas_call"}
  ROOT %sort.4 = s32[8,100]{1,0} sort(%z), metadata={op_name="jit(seil_search)/finalize/sort"}
}
'''


def test_scope_map_reads_compiled_hlo():
    m = pt.scope_map([HLO])
    assert m[("jit_seil_search", "fusion.2")] == ("plan_blocks", None)
    assert m[("jit_seil_search", "pad.2")] == ("scan", None)
    assert m[("jit_seil_search", "pq_scan_topk.1")] == ("scan", "pq_scan_topk")
    assert m[("jit_seil_search", "sort.4")] == ("finalize", None)
    assert m[("jit_seil_search", "queries.1")] == (None, None)


class _Event:
    def __init__(self, name, start_ns, stats=()):
        self.name, self.start_ns, self.stats = name, start_ns, stats


def test_op_labels_as_a_v5e_trace_gives_them():
    """A v5e op event names only its HLO instruction (no ``op_name``, no
    ``hlo_module``): the module comes from the ``XLA Modules`` line."""
    smap = pt.scope_map([HLO])
    modules = ([1.0, 5.0], [4.0, 6.0],
               ["jit_seil_search", "jit_concatenate"])
    memo = {}
    fusion = _Event("%fusion.2 = s32[64]{0} fusion(s32[64]{0} %x), "
                    "kind=kCustom, calls=%fused_computation.2", 2e9)
    kern = _Event('%pq_scan_topk.1 = (f32[8,1,128]) custom-call(%a), '
                  'custom_call_target="tpu_custom_call"', 3e9)
    eager = _Event("%fusion.2 = f32[2,128]{1,0} fusion(%p, %q)", 5.5e9)
    assert pt._op_label(fusion, modules, smap, memo) == (
        "plan_blocks", "fusion.2", "fusion.2")
    assert pt._op_label(kern, modules, smap, memo) == (
        "scan", "pq_scan_topk.1", "pq_scan_topk")
    # the same instruction name in an eager program belongs to no stage
    assert pt._op_label(eager, modules, smap, memo)[0] == pt.UNSCOPED
    # with its op_name in the text, the event needs no map
    named = _Event('%sort.9 = s32[8] sort(%z), metadata={op_name='
                   '"jit(seil_search)/finalize/sort"}', 9e9)
    assert pt._op_label(named, None, {}, memo)[0] == "finalize"


def _cpu_ops(plane, line):
    return plane == "/host:CPU" and line.startswith("tf_XLA")


@jax.jit
def _two_stages(x):
    with jax.named_scope("select_lists"):
        y = jnp.tanh(x @ x)
    with jax.named_scope("finalize"):
        z = jnp.sort(y, axis=1)
    return z.sum()


def test_scopes_of_a_recorded_trace_through_the_hlo_map():
    x = jnp.ones((384, 384), jnp.float32)
    _two_stages(x).block_until_ready()
    text = _two_stages.lower(x).compile().as_text()
    with trace.capture() as cap:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                _two_stages(x).block_until_ready()
                time.sleep(0.01)
    try:
        red = pt.reduce_program(cap["path"], [text], device_lines=_cpu_ops)
        base = trace.reduce(cap["path"], device_lines=_cpu_ops)
        bare = pt.reduce_program(cap["path"], device_lines=_cpu_ops)
    finally:
        trace.discard(cap)
    scopes = red["scopes"]
    assert scopes["select_lists"] > 0 and scopes["finalize"] > 0
    # the scopes partition the busy union
    assert sum(scopes.values()) == pytest.approx(base["busy_s"], rel=1e-9)
    top = dict(red["scope_ops"]["finalize"])
    assert 0 < sum(top.values()) <= scopes["finalize"] + 1e-12
    # without the map nothing on the CPU names its stage
    assert set(bare["scopes"]) == {pt.UNSCOPED}
    # no program span in this trace
    assert red["spans"] == {} and red["program_gaps"] == {}


def test_program_spans_split_the_idle_time():
    """The program's profiler-mode spans, from the thread that drives the
    device, take the idle time: a wait on that thread is the longest."""
    from repro import obs
    f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()

    def dispatcher():
        for _ in range(3):
            with obs.span("gateway.wait"):
                time.sleep(0.03)
            with obs.span("gateway.flush"):
                with obs.span("searcher.dispatch"):
                    with obs.span("searcher.execute"):
                        r = f(x)
                with obs.span("gateway.fetch"):
                    r.block_until_ready()
                with obs.span("gateway.fulfill"):
                    time.sleep(0.005)

    with trace.capture() as cap:
        with pt.program_tracer() as tr, \
                jax.profiler.TraceAnnotation("bench.window"):
            th = threading.Thread(target=dispatcher)
            th.start()
            with obs.span("gateway.submit"):      # another thread's span
                time.sleep(0.05)
            th.join()
    try:
        red = pt.reduce_program(cap["path"], device_lines=_cpu_ops)
        base = trace.reduce(cap["path"], device_lines=_cpu_ops)
    finally:
        trace.discard(cap)
    assert tr is not None and tr.fences == 0
    gaps = red["program_gaps"]
    idle = base["window_s"] - base["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    assert max(gaps, key=gaps.get) == "gateway.wait"
    assert gaps["gateway.wait"] >= 0.08
    assert "gateway.submit" not in gaps         # not the driving thread
    assert gaps.get("gateway.fulfill", 0) >= 0.01
    within = red["idle_within"]
    assert within["gateway.flush"] >= gaps.get("gateway.fulfill", 0)
    assert within["gateway.flush"] <= idle
    assert red["spans"]["gateway.flush"][0] == 3
    assert red["spans"]["gateway.submit"][0] == 1


def test_program_tracer_without_a_profiler_mode(monkeypatch):
    from repro import obs

    def old_start(sample=1, max_events=200_000):
        raise AssertionError("not reached")
    monkeypatch.setattr(obs, "start", old_start)
    with pt.program_tracer() as tr:
        assert tr is None
    assert not obs.enabled()
