"""The system under test, as the harness drives it.

``ProgramSystem`` builds the program's index over the benchmark's corpus
and serves it, as ``build_index`` returns it, through the program's own
entry points: the ``Searcher`` session (``RairsIndex.searcher(params)``)
for batch traffic, and ``Gateway`` for open-loop traffic.  Nothing is
cached across runs: the build is set-up that every deployment pays.  Its
key comes from the configuration's ``corpus_seed``, as the corpus does,
so every run builds the same layout and runs the same compiled programs.

``ControlSystem`` puts the reference, one precision lower, in the
program's place (``reference.LowPrecisionSearch``).
"""
from __future__ import annotations

import time
from typing import NamedTuple

import numpy as np


class ProgramSystem:
    name = "program"

    def __init__(self, cfg: dict, x):
        import jax
        from repro.core import IndexConfig, SearchParams, build_index
        from .corpus import seed32
        self.cfg = cfg
        t0 = time.perf_counter()
        key = seed32(cfg.get("corpus_seed", 0), "build/" + cfg["name"])
        self.index = build_index(jax.random.PRNGKey(key), x,
                                 IndexConfig(**cfg["index"]))
        jax.block_until_ready(self.index.arrays)
        self.build_s = time.perf_counter() - t0
        self.build_phases = dict(self.index.build_seconds)
        a = self.index.arrays
        self.layout = {"blocks": a.block_ids.shape[0],
                       "owned": a.owned.shape[1], "refs": a.refs.shape[1],
                       "misc": a.misc.shape[1]}
        self.params = SearchParams(**cfg["search"])
        self._sessions = []
        self._gateway = None

    # -- batch traffic -------------------------------------------------
    def batch_search(self):
        sess = self.index.searcher(self.params)
        self._sessions.append(sess)

        def search(q):
            r = sess(q)
            return r.ids, r.dists
        return search

    # -- open-loop traffic ---------------------------------------------
    def open_gateway(self, gateway_cfg: dict):
        from repro.gateway import Gateway, GatewayConfig
        gw = Gateway(self.index, self.params,
                     config=GatewayConfig(**gateway_cfg))
        self._gateway = gw
        sess = self.index.searcher(gw.params)
        self._sessions.append(sess)
        return gw

    def warm_flushes(self, pool: np.ndarray, sizes: int) -> None:
        """Call the gateway's session once with a flush of each size
        1..``sizes`` and of each dispatch bucket up to ``max_batch``,
        through its own entry point, so that the window runs no
        executable for the first time and builds none of the small
        programs the session runs around a flush of a new size."""
        gw = self._gateway
        sess = self.index.searcher(gw.params)
        top = gw.config.max_batch
        buckets = {gw.params.bucket_for(n) for n in range(1, top + 1)}
        for n in sorted(set(range(1, min(sizes, top) + 1)) | buckets):
            np.asarray(sess(pool[:n]).ids)

    # -- counters and state --------------------------------------------
    def counters(self) -> dict:
        out = {"padded_rows": 0, "dispatches": 0, "calls": 0, "compiles": 0}
        for s in {id(s): s for s in self._sessions}.values():
            for key in out:
                out[key] += getattr(s.stats, key)
        return out

    def tables(self) -> dict:
        """Host copies of what the index holds, for the scan's work count."""
        a = self.index.arrays
        return {"centroids": np.asarray(self.index.centroids),
                "owned": np.asarray(a.owned), "refs": np.asarray(a.refs),
                "refs_other": np.asarray(a.refs_other),
                "misc": np.asarray(a.misc),
                "block_ids": np.asarray(a.block_ids),
                "block_other": np.asarray(a.block_other),
                "max_scan": int(self.index.searcher(self.params)
                                .params.max_scan)}

    def close(self) -> None:
        if self._gateway is not None:
            self._gateway.close()
        self._gateway = None
        self._sessions = []
        self.index = None


class _Answer(NamedTuple):
    ids: np.ndarray
    dists: np.ndarray
    latency_s: float
    queued_s: float
    batch: int


class _Done:
    """An already-answered request, shaped like the gateway's handle."""

    def __init__(self, t_enqueue, ids, dists):
        self.t_enqueue = t_enqueue
        self._r = _Answer(ids, dists, time.perf_counter() - t_enqueue, 0.0,
                          1)

    def result(self, timeout=None):
        return self._r


class ControlSystem:
    """The reference at bfloat16 in the program's place."""
    name = "control"

    def __init__(self, cfg: dict, x):
        from .reference import LowPrecisionSearch
        self.cfg = cfg
        self.low = LowPrecisionSearch(x, cfg["search"]["k"], cfg["metric"])
        self.build_s = 0.0
        self.build_phases = {}
        self.layout = {}

    def batch_search(self):
        return self.low.search

    def open_gateway(self, gateway_cfg: dict):
        return self

    def submit(self, q):
        t = time.perf_counter()
        ids, d = self.low.search(np.asarray(q)[None, :])
        return _Done(t, np.asarray(ids)[0], np.asarray(d)[0])

    def warm_flushes(self, pool: np.ndarray, sizes: int) -> None:
        np.asarray(self.low.search(pool[:1])[0])

    def counters(self) -> dict:
        return {}

    def tables(self) -> dict:
        return {}

    def close(self) -> None:
        self.low = None


SYSTEMS = {"program": ProgramSystem, "control": ControlSystem}
