"""One run of one cell: set-up, the measured window, the check, the
result line.  Driven by ``BENCHMARK.json`` and the files it names:

* ``bench/configs/<config>.json``   the deployment (corpus, index,
  search parameters, limits of the check);
* ``bench/traffic/<traffic>.json``  the traffic mix (``traffic.py``);
* ``bench/metrics/<metric>.py``     one reader per per-layer metric,
  ``read(ctx) -> float | None`` (``None``: nothing to read in this run).

A new configuration, mix or per-layer metric is a new file and a new
entry in ``BENCHMARK.json``; nothing here names one.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Optional

import numpy as np

from . import reference, roofline, trace as tracemod, traffic
from .corpus import make_corpus
from .system import SYSTEMS

BENCH_DIR = Path(__file__).resolve().parent
WARM_S = 1.0            # open-loop traffic before the window, not measured


class HarnessError(RuntimeError):
    """The benchmark's own files are inconsistent, or the device is not
    one it knows: no result is printed."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_benchmark(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise HarnessError(f"no workload {name!r} in BENCHMARK.json; cells: "
                       f"{[w['name'] for w in bench['workloads']]}")


def load_config(root: Path, bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((Path(root) / c["file"]).read_text())
    raise HarnessError(f"no configuration {name!r} in BENCHMARK.json")


def load_mix(root: Path, name: str) -> dict:
    path = Path(root) / "bench" / "traffic" / f"{name}.json"
    if not path.is_file():
        raise HarnessError(f"no traffic mix file {path}")
    return json.loads(path.read_text())


def load_reader(root: Path, metric: str):
    path = Path(root) / "bench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise HarnessError(f"no reader {path} for per-layer metric {metric!r}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(device_kind: str) -> dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise HarnessError(
            f"device kind {device_kind!r} is not in bench/peaks.json "
            f"({sorted(table['devices'])}); add its published peaks")
    return table["devices"][device_kind]


def require_chip(chips: int):
    """(devices, peaks) when JAX finds at least ``chips`` TPU chips of a
    kind the peaks table knows; HarnessError otherwise.  The benchmark
    never falls back to the CPU."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise HarnessError(f"no accelerator: {e}") from e
    if devices[0].platform != "tpu":
        raise HarnessError(f"needs a TPU; JAX found {devices[0].platform!r} "
                           f"devices only")
    if len(devices) < chips:
        raise HarnessError(f"the cell needs {chips} chips, JAX found "
                           f"{len(devices)}")
    return devices, load_peaks(devices[0].device_kind)


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (``$JAX_COMPILATION_
    CACHE_DIR``, else ``<checkout>/.jax_cache``), keeping every program
    however quick to compile, so that a second run compiles nothing."""
    import jax
    try:
        from repro import compile_cache
    except ImportError as e:
        raise HarnessError(f"the program (src/repro) is missing: {e}") from e
    cache_dir = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


class ProgramsBuilt:
    """Counts the programs JAX lowers (each new shape of a jitted call or
    an eager op, compiled or loaded from the persistent cache) while the
    ``with`` block runs: the window should build none."""
    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __enter__(self):
        from jax._src import monitoring
        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.n += 1

    def __exit__(self, *exc):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._on)


def metrics_for(bench: dict, cell: str, trace: bool) -> list:
    """The cell's end-to-end metrics (trace 0) or per-layer ones (trace 1)."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def _e2e_values(setup_s: float, win: traffic.Window,
                checks: dict) -> dict:
    vals = {"setup_s": setup_s,
            "recall_at_10": checks["recall_at_10"]["value"]}
    if win.latency_s is None:
        vals["qps"] = len(win.qidx) / win.seconds
    else:
        vals["p50_ms"] = traffic.percentile(win.latency_s, 50) * 1e3
        vals["p99_ms"] = traffic.percentile(win.latency_s, 99) * 1e3
    return vals


def run_cell(root: Path, cell_name: str, seed: int, seconds: float,
             trace: bool = False, system: str = "program",
             t_start: Optional[float] = None, bench: Optional[dict] = None,
             peaks: Optional[dict] = None) -> dict:
    """Run one cell once in this process and return the result object
    (the result line's keys, ``checks`` last).  ``t_start`` is when the
    process started (set-up is counted from it)."""
    import jax
    t_start = time.perf_counter() if t_start is None else t_start
    root = Path(root)
    bench = bench or load_benchmark(root)
    cell = find_cell(bench, cell_name)
    cfg = load_config(root, bench, cell["config"])
    mix = load_mix(root, cell["traffic"])
    wanted = metrics_for(bench, cell_name, trace)
    readers = ({m["name"]: load_reader(root, m["name"]) for m in wanted}
               if trace else {})
    dev = jax.devices()[0]

    # -- set-up: corpus, build, warm-up --------------------------------
    t = time.perf_counter()
    x, pool_dev = make_corpus(cfg, seed)
    jax.block_until_ready((x, pool_dev))
    pool = np.asarray(pool_dev)
    corpus_s = time.perf_counter() - t
    sys_ = SYSTEMS[system](cfg, x)
    t = time.perf_counter()
    if mix["kind"] == "batch":
        search = sys_.batch_search()
        np.asarray(search(pool[traffic.batch_rows(0, int(mix["batch"]),
                                                  len(pool))])[0])
    elif mix["kind"] == "open_loop":
        gw = sys_.open_gateway(mix.get("gateway", {}))
        sys_.warm_flushes(pool, int(mix.get("warm_flush_sizes", 1 << 30)))
        traffic.run_open_loop(gw.submit, pool, mix, WARM_S)
    else:
        raise HarnessError(f"unknown traffic kind {mix['kind']!r}")
    # set-up leaves some hundred thousand objects behind (programs,
    # executables, the index); a full collection over them stalls the
    # process, generator and gateway alike, for about 0.2 s.  Freeze
    # them, so that the window's collections scan only its own objects.
    gc.collect()
    gc.freeze()
    warm_s = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f}s: corpus {corpus_s:.3f}s, build "
        f"{sys_.build_s:.3f}s {json.dumps(sys_.build_phases)}, warm "
        f"{warm_s:.3f}s; layout {sys_.layout}")

    # -- the measured window -------------------------------------------
    before = sys_.counters()
    profile = tracemod.capture() if trace else contextlib.nullcontext()
    with profile as captured, ProgramsBuilt() as built, \
            traffic.span("bench.window", trace):
        if mix["kind"] == "batch":
            win = traffic.run_batch(search, pool, mix, seconds, trace)
        else:
            win = traffic.run_open_loop(gw.submit, pool, mix, seconds, trace)
    gc.unfreeze()
    after = sys_.counters()
    delta = {k: after[k] - before.get(k, 0) for k in after}
    stats = dev.memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use")
    tables = sys_.tables() if trace and mix["kind"] == "batch" else {}
    sys_.close()
    del sys_
    log(f"window {win.seconds:.3f}s: {len(win.qidx)} answered of "
        f"{win.attempted}, failed {win.failed}; counters {delta}; "
        f"programs built in the window {built.n}")
    if win.lateness_s is not None:
        log(f"latency from due: p50 "
            f"{traffic.percentile(win.latency_s, 50) * 1e3:.3f} ms, p99 "
            f"{traffic.percentile(win.latency_s, 99) * 1e3:.3f} ms")
        log(f"generator lateness: p50 "
            f"{traffic.percentile(win.lateness_s, 50) * 1e3:.3f} ms, max "
            f"{float(win.lateness_s.max(initial=0.0)) * 1e3:.3f} ms; "
            f"flush size p50 {traffic.percentile(win.flush_sizes, 50):.0f}, "
            f"max {int(win.flush_sizes.max(initial=0))}")

    # -- the check against the reference --------------------------------
    t = time.perf_counter()
    k = cfg["search"]["k"]
    rows = np.unique(win.qidx)          # only the pool rows answered
    gt = np.full((len(pool), k), -1, np.int32)
    if len(rows):
        gt[rows] = reference.exact_topk(x, pool_dev[rows], k, cfg["metric"])
    x_host = np.asarray(x)
    del x
    checks = reference.compare(
        win.qidx, win.ids, win.dists, gt, x_host, pool, cfg["metric"],
        win.failed, cfg["correct"])
    log(f"reference and check {time.perf_counter() - t:.3f}s")

    result = {
        "correct": all(c["ok"] for c in checks.values()),
        "attempted": int(win.attempted),
        "failed": int(win.failed),
        "metrics": {},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": peak_bytes},
    }
    if not trace:
        vals = _e2e_values(setup_s, win, checks)
        for m in wanted:
            if m["name"] not in vals:
                raise HarnessError(f"end-to-end metric {m['name']!r} has "
                                   f"no value in a {mix['kind']} cell")
            result["metrics"][m["name"]] = {"value": vals[m["name"]],
                                            "unit": m["unit"]}
    else:
        red = tracemod.reduce(captured["path"])
        tracemod.discard(captured)
        ctx = {
            "config": cfg, "mix": mix, "window": win, "trace": red,
            "counters": delta, "peaks": peaks or load_peaks(dev.device_kind),
            "scan_work": functools.lru_cache(None)(
                lambda: _scan_work(cfg, pool, win, tables)),
        }
        for m in wanted:
            v = readers[m["name"]](ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": float(v),
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in red["ops"][:10]],
            "idle_gaps": [[n, s] for n, s in red["gaps"][:10]],
        }
        log(f"trace: busy {red['busy_s']:.6f}s of {red['window_s']:.6f}s, "
            f"kernel {red['kernel_s']:.6f}s in {red['kernel_events']} events")
    result["checks"] = checks
    return result


def _scan_work(cfg: dict, pool: np.ndarray, win: traffic.Window,
               tables: dict) -> Optional[dict]:
    """Summed ops and bytes of the scans of the window's batches."""
    if not tables or not win.batches:
        return None
    idx, srch = cfg["index"], cfg["search"]
    work = {"ops": 0.0, "bytes": 0.0}
    for rows in win.batches:
        w = roofline.scan_work(
            pool[rows], tables, nprobe=srch["nprobe"],
            max_scan=tables["max_scan"], metric=cfg["metric"],
            m=idx["m_pq"], nbits=idx["nbits"],
            bigk=srch["k"] * srch["k_factor"])
        work["ops"] += w["ops"]
        work["bytes"] += w["bytes"]
    return work


def print_result(result: dict) -> None:
    """The compared numbers as the last lines of standard error, then the
    result as the last line of standard output."""
    for name, c in result["checks"].items():
        rel = ">=" if name == "recall_at_10" else "<="
        log(f"check {name} = {c['value']!r} (limit {rel} {c['limit']!r}) "
            f"{'ok' if c['ok'] else 'FAILED'}")
    print(json.dumps(result), flush=True)
