"""The program's own spans and engine scopes in a profiler trace.

``trace.reduce`` sees device ops and the harness's ``bench.*`` spans.
With the program's tracer in profiler mode (``program_tracer()``) the
same capture also holds the program's spans (``gateway.*``,
``searcher.*``, ``stage.*``, ``python.gc``: ``repro/obs``) on the
thread that ran them, and every op of the served executables names its
engine stage in its ``op_name`` metadata (``jax.named_scope``:
``select_lists``, ``plan_blocks``, ``scan``, ``finalize``).
``reduce_program`` reads both, inside ``bench.window``, and returns:

* ``scopes``        the device's busy union split by engine scope, plus
  ``unscoped`` (ops of no engine scope: the session's eager pad and
  slice programs, transfers, input layout copies); each instant goes to
  the innermost op running then, so the scopes add up to the busy union;
* ``scope_kernel``  the part of each scope's time that is a Mosaic kernel;
* ``scope_ops``     each scope's largest ops (HLO name, seconds), five at most;
* ``kernels``       summed device durations of the kernels' events by
  kernel name (``pq_scan``, ``pq_scan_topk``);
* ``program_gaps``  device idle time split by the innermost program
  span open at each instant on the thread that drives the device (the
  one that runs ``searcher.dispatch``), a ``python.gc`` span on any
  thread first; ``none`` where no span is open;
* ``idle_within``   device idle time inside each program span name's
  intervals on that thread (nested spans count for every level);
* ``spans``         ``[count, seconds]`` per program span name, over
  every thread, clipped to the window.

Times are seconds, averaged over devices like ``trace.reduce``'s.  A
trace that holds no program span (the program has no profiler mode, or
none was started) gives ``program_gaps``, ``idle_within`` and
``spans`` empty.  An op's scope comes from its ``op_name`` where the
event carries it (in its name or stats), else from ``hlo_texts``: the
compiled executables' ``as_text()``, keyed by (HLO module, instruction),
with the module from the event's ``hlo_module`` stat or the device's
``XLA Modules`` line.  A v5e trace needs the map: its op events name
their HLO instruction only, with no ``op_name`` and no module stat, and
its modules are named ``jit_seil_search(<fingerprint>)``.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from .trace import (DEVICE_PLANE_PREFIX, clip, complement,
                    default_device_lines, is_kernel, union)

STAGES = ("select_lists", "plan_blocks", "scan", "finalize")
UNSCOPED = "unscoped"
KERNELS = ("pq_scan_topk", "pq_scan")          # longest first
PROGRAM_PREFIXES = ("gateway.", "searcher.", "stage.", "python.")
DRIVER_SPAN = "searcher.dispatch"
GC_SPAN = "python.gc"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
TOP_OPS = 5

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s')
_CALLS = re.compile(r'(?:calls|to_apply|body|condition)=%?([\w.\-]+)')
_COMPUTATION = re.compile(r'^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$')
_MODULE = re.compile(r'^HloModule\s+([\w.\-]+)')

Segment = Tuple[float, float, str]


@contextlib.contextmanager
def program_tracer():
    """The program's tracer in profiler mode for the block, or nothing
    when the program has no such mode (yields the tracer or None)."""
    try:
        from repro import obs
        tracer = obs.start(profiler=True)
    except (ImportError, TypeError):
        yield None
        return
    try:
        yield tracer
    finally:
        obs.stop()


def stage_of(op_name: str) -> Optional[str]:
    """The engine stage named in an ``op_name`` path, or None:
    ``jit(seil_search)/scan/jit(_pad)/pad`` -> ``scan``."""
    for part in op_name.split("/"):
        if part in STAGES:
            return part
    return None


def kernel_of(text: str) -> Optional[str]:
    for name in KERNELS:
        if name in text:
            return name
    return None


def _module_key(name: str) -> str:
    """``jit_seil_search(1234)`` -> ``jit_seil_search``."""
    return name.split("(", 1)[0].strip()


def scope_map(hlo_texts: Iterable[str]) -> Dict[Tuple[str, str], tuple]:
    """(module, instruction) -> (stage or None, kernel name or None) from
    compiled HLO text.  An instruction without an ``op_name`` takes the
    most common stage of the computations it calls (a fusion's body).
    Where executables of one module name disagree, the most common
    reading wins."""
    votes: Dict[Tuple[str, str], collections.Counter] = {}
    for text in hlo_texts:
        m = _MODULE.match(text)
        module = m.group(1) if m else ""
        comp_stages: Dict[str, collections.Counter] = {}
        instrs = []                  # (name, own stage, callees, kernel)
        comp = None
        for line in text.splitlines():
            cm = _COMPUTATION.match(line)
            if cm and " = " not in line:
                comp = cm.group(1)
                continue
            im = _INSTR.match(line)
            if not im:
                continue
            om = _OP_NAME.search(line)
            stage = stage_of(om.group(1)) if om else None
            if comp is not None and stage is not None:
                comp_stages.setdefault(comp, collections.Counter())[stage] += 1
            kernel = (kernel_of(line) if 'custom_call_target="tpu_custom_call"'
                      in line else None)
            instrs.append((im.group(1), stage, _CALLS.findall(line), kernel))
        for name, stage, callees, kernel in instrs:
            if stage is None:
                c = collections.Counter()
                for callee in callees:
                    c.update(comp_stages.get(callee, {}))
                stage = c.most_common(1)[0][0] if c else None
            votes.setdefault((module, name), collections.Counter())[
                (stage, kernel)] += 1
    return {k: c.most_common(1)[0][0] for k, c in votes.items()}


def innermost(spans: Iterable[Segment]) -> List[Segment]:
    """Partition the time under ``spans`` (nested intervals) into
    segments, each labelled with the innermost span open there."""
    out: List[Segment] = []
    stack: List[Segment] = []
    t = None

    def emit(a, b, label):
        if b > a:
            out.append((a, b, label))

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][1] <= a:
            top = stack.pop()
            emit(t, top[1], top[2])
            t = max(t, top[1])
        if stack:
            emit(t, a, stack[-1][2])
        stack.append((a, b, name))
        t = a
    while stack:
        top = stack.pop()
        emit(t, top[1], top[2])
        t = max(t, top[1])
    return out


def split(gaps: List[Tuple[float, float]], segments: List[Segment],
          first: List[Segment] = ()) -> Dict[str, float]:
    """Seconds of ``gaps`` under each label: ``first``'s segments take
    precedence, then ``segments``'; the rest is ``none``."""
    out: Dict[str, float] = collections.defaultdict(float)
    for layer in (list(first), list(segments)):
        layer.sort()
        starts = [a for a, _, _ in layer]
        rest = []
        for ga, gb in gaps:
            i = max(bisect.bisect_right(starts, ga) - 1, 0)
            t = ga
            while i < len(layer) and layer[i][0] < gb:
                a, b, label = layer[i]
                a, b = max(a, ga), min(b, gb)
                if b > a:
                    out[label] += b - a
                    if a > t:
                        rest.append((t, a))
                    t = max(t, b)
                i += 1
            if gb > t:
                rest.append((t, gb))
        gaps = rest
    out["none"] += sum(b - a for a, b in gaps)
    return dict(out)


def _op_label(event, modules, smap, memo) -> tuple:
    """(stage or UNSCOPED, op name, kernel name) of one device op event.
    Labels read from the event's own text are kept in ``memo`` by it."""
    text = event.name
    if text in memo:
        return memo[text]
    om = _OP_NAME.search(text)
    stage = stage_of(om.group(1)) if om else None
    short = text.split(" = ", 1)[0].lstrip("%")
    if stage is not None:
        memo[text] = (stage, short, kernel_of(short) or short)
        return memo[text]
    stats = dict(event.stats)
    for v in stats.values():
        if stage is None and isinstance(v, str) and "/" in v:
            stage = stage_of(v)
    short = str(stats.get("hlo_op") or short)
    module = stats.get("hlo_module")
    if module is None and modules:
        i = bisect.bisect_right(modules[0], event.start_ns * 1e-9) - 1
        if i >= 0 and modules[1][i] >= event.start_ns * 1e-9:
            module = modules[2][i]
    mapped = smap.get((_module_key(str(module or "")), short), (None, None))
    return (stage or mapped[0] or UNSCOPED, short,
            kernel_of(short) or mapped[1] or short)


def reduce_program(path: str, hlo_texts: Iterable[str] = (), *,
                   device_lines: Callable[[str, str], bool] =
                   default_device_lines) -> dict:
    """Reduce one trace file (see the module docstring)."""
    import jax
    smap = scope_map(hlo_texts)
    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    memo: Dict[str, tuple] = {}
    threads: List[List[Segment]] = []
    windows = []
    for plane in pd.planes:
        lines = list(plane.lines)
        modules = None
        for line in lines:
            if line.name == MODULES_LINE:
                evs = sorted((e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9,
                              _module_key(e.name)) for e in line.events)
                modules = ([a for a, _, _ in evs], [b for _, b, _ in evs],
                           [n for _, _, n in evs])
        for line in lines:
            if device_lines(plane.name, line.name):
                devices.setdefault(plane.name, []).extend(
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                     *_op_label(e, modules, smap, memo), is_kernel(e))
                    for e in line.events)
            elif not plane.name.startswith(DEVICE_PLANE_PREFIX):
                spans = []
                for e in line.events:
                    iv = (e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9, e.name)
                    if e.name == WINDOW_SPAN:
                        windows.append(iv[:2])
                    elif e.name.startswith(PROGRAM_PREFIXES):
                        spans.append(iv)
                if spans:
                    threads.append(spans)
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = min(a for a, _ in windows), max(b for _, b in windows)

    spans_out: Dict[str, list] = {}
    gc_spans: List[Segment] = []
    for spans in threads:
        for a, b, name in spans:
            iv = clip((a, b), lo, hi)
            if iv is None:
                continue
            agg = spans_out.setdefault(name, [0, 0.0])
            agg[0] += 1
            agg[1] += iv[1] - iv[0]
            if name == GC_SPAN:
                gc_spans.append((iv[0], iv[1], name))
    driving = [s for s in threads if any(n == DRIVER_SPAN for _, _, n in s)]
    driver = max(driving, default=[],
                 key=lambda s: sum(b - a for a, b, n in s if n == DRIVER_SPAN))
    driver = [(a, b, n) for a, b, n in driver if clip((a, b), lo, hi)]
    segments = innermost(driver)
    by_name: Dict[str, list] = {}
    for a, b, n in driver + gc_spans:
        by_name.setdefault(n, []).append((a, b))

    n_dev = max(len(devices), 1)
    scopes: Dict[str, float] = collections.defaultdict(float)
    scope_kernel: Dict[str, float] = collections.defaultdict(float)
    scope_ops: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float))
    kernels: Dict[str, float] = collections.defaultdict(float)
    gaps_out: Dict[str, float] = collections.defaultdict(float)
    within: Dict[str, float] = collections.defaultdict(float)
    for events in devices.values():
        ops = []
        for a, b, stage, op, kname, is_kern in events:
            iv = clip((a, b), lo, hi)
            if iv is None:
                continue
            ops.append((iv[0], iv[1], (stage, op, is_kern)))
            if is_kern:
                kernels[kname] += (iv[1] - iv[0]) / n_dev
        for a, b, (stage, op, is_kern) in innermost(ops):
            scopes[stage] += (b - a) / n_dev
            scope_ops[stage][op] += (b - a) / n_dev
            if is_kern:
                scope_kernel[stage] += (b - a) / n_dev
        gaps = complement(union((a, b) for a, b, _ in ops), lo, hi)
        if not threads:
            continue
        for label, s in split(gaps, segments, gc_spans).items():
            gaps_out[label] += s / n_dev
        for name, ivs in by_name.items():
            for label, s in split(gaps, [(a, b, name)
                                         for a, b in union(ivs)]).items():
                if label == name:
                    within[name] += s / n_dev
    return {
        "scopes": dict(scopes),
        "scope_kernel": dict(scope_kernel),
        "scope_ops": {k: sorted(v.items(), key=lambda kv: -kv[1])[:TOP_OPS]
                      for k, v in scope_ops.items()},
        "kernels": dict(kernels),
        "program_gaps": dict(gaps_out),
        "idle_within": dict(within),
        "spans": spans_out,
    }
