"""The trace reduction: interval arithmetic, and busy union, kernel
events and gaps by span on a small trace recorded here on the CPU (where
the XLA CPU client's op events stand in for a device's)."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import trace


def test_union_complement_and_clip():
    busy = trace.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.7)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert trace.total(busy) == 3.0
    assert trace.complement(busy, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0),
                                                 (4.0, 5.0)]
    assert trace.clip((0.0, 2.0), 1.0, 3.0) == (1.0, 2.0)
    assert trace.clip((0.0, 1.0), 1.0, 3.0) is None


def test_gap_label_is_the_most_overlapping_span():
    host = [(0.0, 1.0, "bench.submit"), (1.0, 3.0, "bench.wait"),
            (3.0, 3.2, "bench.block")]
    ends = [b for _, b, _ in host]
    assert trace._label(host, ends, (0.5, 2.5)) == "bench.wait"
    assert trace._label(host, ends, (2.95, 3.2)) == "bench.block"
    assert trace._label(host, ends, (5.0, 6.0)) == "none"


def _cpu_ops(plane, line):
    return plane == "/host:CPU" and line.startswith("tf_XLAPjRtCpuClient")


def _is_matmul(event):
    return event.name.startswith("dot")


@pytest.fixture(scope="module")
def recorded():
    f = jax.jit(lambda a: (a @ a).sum())
    g = jax.jit(lambda a: jnp.tanh(a).sum())
    x = jnp.ones((384, 384), jnp.float32)
    f(x).block_until_ready()
    g(x).block_until_ready()
    with trace.capture() as cap:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(4):
                with jax.profiler.TraceAnnotation("bench.session_call"):
                    a = f(x)
                    b = g(x)
                with jax.profiler.TraceAnnotation("bench.block"):
                    a.block_until_ready()
                    b.block_until_ready()
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(0.02)
    yield cap
    trace.discard(cap)


def test_reduction_of_a_recorded_trace(recorded):
    red = trace.reduce(recorded["path"], device_lines=_cpu_ops,
                       kernel=_is_matmul)
    assert red["devices"] == 1
    assert 0.08 <= red["window_s"] < 5.0
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["kernel_events"] >= 4
    assert 0 < red["kernel_busy_s"] <= red["kernel_s"] <= red["busy_s"]
    assert sum(s for _, s in red["ops"]) >= red["busy_s"] - 1e-9
    idle = sum(s for _, s in red["gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    labels = dict(red["gaps"])
    assert set(labels) <= {"bench.session_call", "bench.block", "bench.wait",
                           "none"}
    # the sleeps are where the device sat idle longest
    assert red["gaps"][0][0] == "bench.wait"
    assert labels["bench.wait"] >= 0.06


def test_reduction_needs_the_window_span(tmp_path):
    with trace.capture() as cap:
        jax.jit(lambda a: a + 1)(jnp.ones(8)).block_until_ready()
    try:
        with pytest.raises(ValueError, match="bench.window"):
            trace.reduce(cap["path"], device_lines=_cpu_ops)
    finally:
        trace.discard(cap)


def test_default_reading_finds_no_device_on_cpu(recorded):
    red = trace.reduce(recorded["path"])
    assert red["devices"] == 0 and red["busy_s"] == 0
    assert red["kernel_events"] == 0


class _Event:
    def __init__(self, name):
        self.name = name


# op events as a v5e trace names them (shortened HLO text)
KERNEL_OP = ('%closed_call.13 = (f32[64,1,128]{2,1,0}) custom-call(s32[64,557]'
             '{1,0} %dynamic-slice_bitcast_fusion.6), custom_call_target='
             '"tpu_custom_call", operand_layout_constraints={s32[64,557]{1,0}}')
TOPK_OP = ('%custom-call = (f32[1024,32]{1,0}) custom-call(f32[1024,4096]{1,0} '
           '%fusion.20), custom_call_target="TopK"')
FUSION_OP = ('%fusion.9 = s32[589824]{0} fusion(s32[1024,4096]{1,0} '
             '%custom-call.34, s32[589824]{0} %reshape.152), kind=kCustom')


def test_only_mosaic_calls_are_the_kernel():
    assert trace.is_kernel(_Event(KERNEL_OP))
    assert not trace.is_kernel(_Event(TOPK_OP))
    assert not trace.is_kernel(_Event(FUSION_OP))
    assert trace.op_name(KERNEL_OP) == "closed_call.13 tpu_custom_call"
    assert trace.op_name(FUSION_OP) == "fusion.9"
    assert trace.op_name("dot_general.1") == "dot_general.1"
