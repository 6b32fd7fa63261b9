"""Profiler capture and its reduction to busy time, kernel time and
idle gaps.

A traced run records the window with ``jax.profiler`` (no Python
tracer) inside a ``bench.window`` span, and the harness marks what the
host is doing with ``bench.*`` spans (``bench.session_call``,
``bench.block``, ``bench.submit``, ``bench.wait``).  ``reduce`` reads the
``.xplane.pb`` with ``jax.profiler.ProfileData`` and returns:

* ``window_s``  the ``bench.window`` span's length;
* ``busy_s``    the union of the device's op intervals inside it,
  averaged over the devices;
* ``kernel_s``  the summed durations of the scan kernel's events (the
  Mosaic custom calls), and ``kernel_busy_s`` their union;
* ``ops``       device time per op (its HLO name), largest first; ops
  nest (a loop holds the kernel calls it makes), so these overlap;
* ``gaps``      idle time between device ops, each gap put down to the
  ``bench.*`` span that overlaps it most (``none`` where none does),
  summed by span name, largest first.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import shutil
import tempfile
from typing import Callable, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]

# the device planes and the line of their per-op events on a TPU; an
# op event is named by its HLO text, and a Pallas kernel's names its
# Mosaic target
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


@contextlib.contextmanager
def capture():
    """Trace the block; yields a dict that holds the ``.xplane.pb`` path
    once the block has ended.  The files live in a temporary directory
    under ``$TMPDIR``; ``discard`` removes it."""
    import jax
    d = tempfile.mkdtemp(prefix="bench_trace_")
    out = {"dir": d, "path": None}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        out["path"] = found[0] if found else None


def discard(captured: dict) -> None:
    shutil.rmtree(captured["dir"], ignore_errors=True)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(iv: Interval, lo: float, hi: float) -> Optional[Interval]:
    a, b = max(iv[0], lo), min(iv[1], hi)
    return (a, b) if b > a else None


def complement(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def is_kernel(event) -> bool:
    return KERNEL_TARGET in event.name


def op_name(name: str) -> str:
    """``%fusion.9 = s32[...] fusion(...), ...`` -> ``fusion.9``; a
    Mosaic kernel's call gets ``tpu_custom_call`` after its name."""
    short = name.split(" = ", 1)[0].lstrip("%")
    return short + " tpu_custom_call" if KERNEL_TARGET in name else short


def _label(host, ends, gap: Interval) -> str:
    """Name of the host span that overlaps ``gap`` most, or ``none``."""
    label, best = "none", 0.0
    i = bisect.bisect_right(ends, gap[0])
    while i < len(host) and host[i][0] < gap[1]:
        a, b, n = host[i]
        ov = min(b, gap[1]) - max(a, gap[0])
        if ov > best:
            label, best = n, ov
        i += 1
    return label


def default_device_lines(plane_name: str, line_name: str) -> bool:
    return plane_name.startswith(DEVICE_PLANE_PREFIX) and line_name == OPS_LINE


def reduce(path: str, *,
           device_lines: Callable[[str, str], bool] = default_device_lines,
           kernel: Callable = is_kernel, span_prefix: str = "bench.",
           window_span: str = "bench.window") -> dict:
    """Reduce one trace file (see the module docstring).  Times are in
    seconds.  ``device_lines(plane, line)`` picks the lines whose events
    are device ops; ``kernel(event)`` picks the scan kernel's events."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices = {}
    spans = []
    for plane in pd.planes:
        for line in plane.lines:
            if device_lines(plane.name, line.name):
                devices.setdefault(plane.name, []).extend(
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                     e.name, kernel(e)) for e in line.events)
            elif not plane.name.startswith(DEVICE_PLANE_PREFIX):
                spans.extend(
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                     e.name) for e in line.events
                    if e.name.startswith(span_prefix))
    windows = [(a, b) for a, b, n in spans if n == window_span]
    if not windows:
        raise ValueError(f"no {window_span!r} span in the trace")
    lo, hi = min(a for a, _ in windows), max(b for _, b in windows)
    # the harness's spans come from one thread and do not overlap
    host = sorted((a, b, n) for a, b, n in spans if n != window_span)
    ends = [b for _, b, _ in host]
    busy_s, kernel_s, kernel_busy_s = [], 0.0, []
    ops: dict = {}
    gaps: dict = {}
    n_kernel = 0
    for events in devices.values():
        clipped = []
        kern = []
        for a, b, name, is_k in events:
            iv = clip((a, b), lo, hi)
            if iv is None:
                continue
            clipped.append(iv)
            short = op_name(name)
            ops[short] = ops.get(short, 0.0) + (iv[1] - iv[0])
            if is_k:
                kern.append(iv)
                kernel_s += iv[1] - iv[0]
                n_kernel += 1
        busy = union(clipped)
        busy_s.append(total(busy))
        kernel_busy_s.append(total(union(kern)))
        for g in complement(busy, lo, hi):
            label = _label(host, ends, g)
            gaps[label] = gaps.get(label, 0.0) + (g[1] - g[0])
    n_dev = max(len(devices), 1)
    return {
        "window_s": hi - lo,
        "devices": len(devices),
        "busy_s": sum(busy_s) / n_dev,
        "kernel_s": kernel_s / n_dev,
        "kernel_busy_s": sum(kernel_busy_s) / n_dev,
        "kernel_events": n_kernel,
        "ops": sorted(ops.items(), key=lambda kv: -kv[1]),
        "gaps": sorted(gaps.items(), key=lambda kv: -kv[1]),
    }
