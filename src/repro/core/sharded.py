"""ShardedIndex — one index, any topology (DESIGN.md §4).

``index.shard(mesh, axes=...)`` wraps a ``RairsIndex`` *or* a
``StreamingIndex`` as a mesh-resident view that serves through the
exact same session protocol as the single-host path::

    sharded  = index.shard(mesh)                  # deployment detail
    searcher = sharded.searcher(SearchParams(k=10, nprobe=16))
    result   = searcher(queries)                  # pad-and-dispatch buckets
    searcher.compile_stats()                      # same counters

``ShardedSearcher`` reuses all of ``Searcher``'s machinery (batch-size
buckets, chunking, compile/cache stats, (epoch, version) pinning) and
only swaps the ``_lower`` / ``_call_inputs`` hooks: lowering produces a
``shard_map`` executable of the serve step built by
``core/distributed.py::build_serve_step`` instead of a single-device
``seil_search`` program.

Data placement happens once per index state, not per call: block
arrays/refine vectors are padded to the device count and committed with
a block-id/vector-id range ``NamedSharding``; centroids, the SEIL list
tables, PQ codebooks, the delta segment, and the tombstone mask are
committed replicated.  Placement is two-tier: the base layout (block
store + tables) is placed once per *epoch*, the mutable pieces
(vectors incl. delta rows, delta buffers, tombstone mask) once per
*version* — so insert/delete never re-transfer the block store, only
compaction does.  A mutated ``StreamingIndex`` base invalidates the
per-version state and every open session exactly like the single-host
``StreamingSearcher`` (``StaleSessionError``); compiled executables are
shared through a shape-keyed cache, so steady-state churn on the mesh
never recompiles.

On a 1-device mesh the whole pipeline — plan window, local scan,
stable top-fetch preselect, identity collectives, owner refinement —
is bitwise identical to the plain ``Searcher`` (asserted in
tests/test_sharded.py for both exec modes, frozen and streaming).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs
from ..dist import shard_map
from .distributed import build_serve_step
from .params import SearchParams
from .search import SearchResult
from .searcher import Searcher
from .stream import StaleSessionError, StreamingIndex


@dataclasses.dataclass
class _BasePlacement:
    """Mesh-resident arrays of one *epoch* of the base layout.

    The block arrays are row shards (block-id range); the list tables,
    centroids, and codebooks replicate.  Nothing here changes on
    insert/delete — only compaction (a new epoch) invalidates it — so
    the expensive block-store transfer happens once per epoch, not once
    per mutation.
    """
    block_codes: jnp.ndarray        # (TBp, BLK, M) sharded
    block_ids: jnp.ndarray          # (TBp, BLK)    sharded
    block_other: jnp.ndarray        # (TBp, BLK)    sharded
    block_lo: jnp.ndarray           # (ndev,)       sharded, per-device scalar
    dev_rank: jnp.ndarray           # (ndev,)       sharded, per-device scalar
    owned: jnp.ndarray              # replicated list tables …
    owned_other: jnp.ndarray
    refs: jnp.ndarray
    refs_other: jnp.ndarray
    misc: jnp.ndarray
    centroids: jnp.ndarray
    codebooks: jnp.ndarray


@dataclasses.dataclass
class _PlacedState:
    """Full per-*version* state: the epoch base plus the mutable pieces
    (refine vectors incl. delta rows, delta buffers, tombstone mask).

    ``signature`` keys the compiled-executable cache: two states with
    equal shapes can share every executable because arrays are runtime
    arguments, never baked into the program.
    """
    base: _BasePlacement
    vectors: jnp.ndarray            # (Np, D)   sharded by vector-id range
    vec_lo: jnp.ndarray             # (ndev,)   sharded, per-device scalar
    delta_codes: jnp.ndarray        # (cap, M)  replicated ((0, M) frozen)
    delta_ids: jnp.ndarray          # (cap,)    replicated
    live: jnp.ndarray               # (n_total,) replicated ((0,) frozen)
    signature: Tuple

    def serve_args(self) -> tuple:
        b = self.base
        return (b.block_codes, b.block_ids, b.block_other,
                b.owned, b.owned_other, b.refs, b.refs_other, b.misc,
                b.centroids, b.codebooks, self.vectors, self.vec_lo,
                b.block_lo, b.dev_rank,
                self.delta_codes, self.delta_ids, self.live)


class _Placement:
    """Placed arrays + executable cache shared by every ShardedIndex of
    one (index, mesh, axes) — views differing only in ``max_scan_local``
    must not place the index twice."""

    def __init__(self):
        self.state: Optional[_PlacedState] = None
        self.version = None
        self.base: Optional[_BasePlacement] = None
        self.base_epoch = None
        self.exec_cache: Dict[tuple, dict] = {}
        self.budget_cache: Dict[tuple, int] = {}   # derived max_scan_local
        # compact-plane placements (DESIGN.md §12): per-epoch sharded
        # packed block codes + replicated codec books, and per-version
        # replicated delta plane codes — placed lazily on first refine
        # session, dropped with the epoch exactly like the base
        self.plane_base: Dict[str, tuple] = {}
        self.plane_delta: Dict[str, tuple] = {}


def shard_index(index, mesh, axes=("data",),
                max_scan_local: Optional[int] = None) -> "ShardedIndex":
    """Cached ``ShardedIndex`` factory — the implementation behind
    ``RairsIndex.shard`` / ``StreamingIndex.shard``.  Cached per
    (mesh, axes, max_scan_local) on the index (``Mesh`` is hashable, so
    equal meshes hit the same entry); views differing only in
    ``max_scan_local`` additionally share one placement + executable
    cache through ``_Placement``, so no configuration ever places the
    arrays twice."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    key = (mesh, axes, max_scan_local)
    cache = getattr(index, "_shard_cache", None)
    if cache is None:
        cache = {}
        index._shard_cache = cache
    if key not in cache:
        cache[key] = ShardedIndex(index, mesh, axes=axes,
                                  max_scan_local=max_scan_local)
    return cache[key]


def _pad_rows(x: np.ndarray, mult: int, fill) -> np.ndarray:
    pad = (-x.shape[0]) % mult
    if pad == 0:
        return x
    widths = ((0, pad),) + ((0, 0),) * (x.ndim - 1)
    return np.pad(x, widths, constant_values=fill)


class ShardedIndex:
    """A mesh deployment of an index, serving through ``Searcher`` sessions.

    Duck-type compatible with the read side of ``RairsIndex`` /
    ``StreamingIndex`` (config / centroids / codebook / vectors /
    searcher / search / searcher_stats), and — over a streaming base —
    with the mutation side too (insert / delete / compact), so call
    sites written against the single-host API run unchanged on a mesh.
    """

    def __init__(self, index, mesh, axes=("data",),
                 max_scan_local: Optional[int] = None):
        if isinstance(index, ShardedIndex):
            raise TypeError("index is already a ShardedIndex")
        self.index = index
        self.mesh = mesh
        self.axes = (axes,) if isinstance(axes, str) else tuple(axes)
        for a in self.axes:
            if a not in mesh.shape:
                raise ValueError(
                    f"mesh has no axis {a!r} (axes: {tuple(mesh.shape)})")
        ndev = 1
        for a in self.axes:
            ndev *= mesh.shape[a]
        self.ndev = ndev
        self.max_scan_local = max_scan_local
        self.streaming = isinstance(index, StreamingIndex)
        pcache = getattr(index, "_placement_cache", None)
        if pcache is None:
            pcache = {}
            index._placement_cache = pcache
        self._placement: _Placement = pcache.setdefault(
            (mesh, self.axes), _Placement())
        self._sessions: Dict[SearchParams, "ShardedSearcher"] = {}
        self._retired: Dict[str, int] = {}
        self._n_invalidations = 0

    # ------------------------------------------------------------------
    # read-side duck typing
    # ------------------------------------------------------------------
    @property
    def config(self):
        return self.index.config

    @property
    def centroids(self):
        return self.index.centroids

    @property
    def codebook(self):
        return self.index.codebook

    @property
    def vectors(self):
        return self.index.vectors

    @property
    def needs_result_dedup(self) -> bool:
        return self.index.needs_result_dedup

    @property
    def result_oversample(self) -> int:
        return self.index.result_oversample

    def default_max_scan(self, nprobe: int, slack: float = 1.3) -> int:
        return self.index.default_max_scan(nprobe, slack)

    @property
    def epoch(self) -> int:
        return getattr(self.index, "epoch", 0)

    @property
    def version(self) -> int:
        return getattr(self.index, "version", 0)

    # mutation passthrough (streaming base only) ------------------------
    def _stream(self) -> StreamingIndex:
        if not self.streaming:
            raise TypeError(
                "mutations need a streaming base: shard a StreamingIndex "
                "(index.streaming().shard(mesh)) instead of a frozen "
                "RairsIndex")
        return self.index

    def insert(self, x) -> np.ndarray:
        """Append through the base's delta path; placed state and open
        sessions refresh lazily on the next ``searcher()`` fetch."""
        return self._stream().insert(x)

    def delete(self, ids) -> int:
        return self._stream().delete(ids)

    def compact(self, reason: str = "manual") -> dict:
        """Fold delta + tombstones on the base; the fresh epoch's block
        arrays are re-sharded over the mesh on the next session fetch."""
        return self._stream().compact(reason=reason)

    def live_ids(self) -> np.ndarray:
        return self._stream().live_ids()

    def live_vectors(self):
        return self._stream().live_vectors()

    # ------------------------------------------------------------------
    # mesh placement
    # ------------------------------------------------------------------
    def _put(self, x, spec) -> jnp.ndarray:
        return jax.device_put(jnp.asarray(x), NamedSharding(self.mesh, spec))

    def _place_base(self, base) -> _BasePlacement:
        """Place one epoch's immutable base layout (the expensive part:
        the full block store crosses host->device once per epoch)."""
        nd = self.ndev
        sh, rep = P(self.axes), P()
        arrays = base.arrays
        owned_np = np.asarray(arrays.owned)
        bo_np = np.asarray(arrays.block_other)
        owned_other = np.where(owned_np >= 0,
                               bo_np[np.maximum(owned_np, 0), 0], -1
                               ).astype(np.int32)
        codes = _pad_rows(np.asarray(arrays.block_codes), nd, 0)
        bids = _pad_rows(np.asarray(arrays.block_ids), nd, -1)
        both = _pad_rows(np.asarray(arrays.block_other), nd, -1)
        lanes = np.arange(nd, dtype=np.int32)
        tb_l = codes.shape[0] // nd
        return _BasePlacement(
            block_codes=self._put(codes, sh),
            block_ids=self._put(bids, sh),
            block_other=self._put(both, sh),
            block_lo=self._put(lanes * tb_l, sh),
            dev_rank=self._put(lanes, sh),
            owned=self._put(arrays.owned, rep),
            owned_other=self._put(owned_other, rep),
            refs=self._put(arrays.refs, rep),
            refs_other=self._put(arrays.refs_other, rep),
            misc=self._put(arrays.misc, rep),
            centroids=self._put(base.centroids, rep),
            codebooks=self._put(base.codebook.codebooks, rep))

    def _build_state(self) -> _PlacedState:
        idx = self.index
        nd = self.ndev
        pl = self._placement
        sh, rep = P(self.axes), P()
        if self.streaming:
            dev = idx._device_state()      # id-aligned base+delta mirrors
            base = idx.base
            vectors_full = np.asarray(dev.vectors_full)
            delta_codes = self._put(dev.delta_codes, rep)
            delta_ids = self._put(dev.delta_ids, rep)
            live = self._put(dev.live_full, rep)
            cap = dev.capacity
        else:
            base = idx
            vectors_full = np.asarray(idx.vectors)
            delta_codes = self._put(
                np.zeros((0, base.codebook.m), np.uint8), rep)
            delta_ids = self._put(np.zeros((0,), np.int32), rep)
            live = self._put(np.zeros((0,), bool), rep)
            cap = 0
        if pl.base is None or pl.base_epoch != self.epoch:
            pl.base = self._place_base(base)
            pl.base_epoch = self.epoch
            pl.plane_base.clear()
            pl.plane_delta.clear()
        vecs = _pad_rows(vectors_full, nd, 0.0)
        n_l = vecs.shape[0] // nd
        lanes = np.arange(nd, dtype=np.int32)
        return _PlacedState(
            base=pl.base,
            vectors=self._put(vecs, sh),
            vec_lo=self._put(lanes * n_l, sh),
            delta_codes=delta_codes, delta_ids=delta_ids, live=live,
            signature=(pl.base.block_ids.shape[0], vecs.shape[0], cap, nd))

    def plane(self, backend: str, codec=None):
        """Forwarded plane accessor (``Searcher.__init__`` resolves the
        session's plane through it): the wrapped index owns the codec
        and the host-side layout; the mesh placement happens separately
        in ``_plane_args``."""
        return self.index.plane(backend, codec=codec)

    def _plane_args(self, plane) -> tuple:
        """Mesh placements of one compact plane: packed block codes
        padded and row-sharded exactly like the base block store (same
        padded TB, so per-device block-id windows line up), codec books
        and the delta's plane codes replicated.  Cached per epoch /
        version on the shared placement like their full-width twins."""
        pl = self._placement
        sh, rep = P(self.axes), P()
        hit = pl.plane_base.get(plane.backend)
        if hit is None:
            codes = _pad_rows(np.asarray(plane.block_codes), self.ndev, 0)
            hit = (self._put(codes, sh),
                   self._put(plane.codec.codebooks, rep))
            pl.plane_base[plane.backend] = hit
        key = (plane.backend, self.version)
        dhit = pl.plane_delta.get(plane.backend)
        if dhit is None or dhit[0] != key:
            if self.streaming:
                dcodes = self.index._plane_delta_codes(plane.backend)
            else:
                dcodes = np.zeros(
                    (0, int(plane.codec.codebooks.shape[0])), np.uint8)
            dhit = (key, self._put(dcodes, rep))
            pl.plane_delta[plane.backend] = dhit
        return hit[0], hit[1], dhit[1]

    def _ensure_state(self) -> _PlacedState:
        pl = self._placement
        v = self.version
        if pl.state is None or pl.version != v:
            pl.state = self._build_state()
            pl.version = v
        return pl.state

    def derived_max_scan_local(self, nprobe: int) -> int:
        """Per-device plan budget from per-shard list occupancy.

        For each device, every list contributes only the table entries
        (owned/refs/misc) whose block falls inside that device's
        block-id range; the worst query can select at most the
        ``nprobe`` fullest such lists, so the sum of their local counts
        is a safe upper bound on any local plan size — by construction
        the derived budget never truncates a plan, hence is
        recall-neutral (tests/test_plan.py).  Sessions use
        ``min(params.max_scan, derived)`` when ``max_scan_local`` is
        unset: strictly tighter padded scan bounds than replicating the
        full per-query budget on every shard, and on one device
        bitwise-identical to the plain Searcher in both regimes (either
        the old budget applies, or nothing truncates anywhere).
        Cached per (epoch, nprobe, ndev) on the shared placement."""
        pl = self._placement
        key = (self.epoch, nprobe, self.ndev)
        if key not in pl.budget_cache:
            base = self.index.base if self.streaming else self.index
            arrays = base.arrays
            nd = self.ndev
            tb = np.asarray(arrays.block_codes).shape[0]
            tb_l = (tb + (-tb) % nd) // nd        # padded rows per device
            nlist = base.config.nlist
            counts = np.zeros((nlist, nd), np.int64)
            for tbl in (arrays.owned, arrays.refs, arrays.misc):
                t = np.asarray(tbl)
                rows = np.repeat(np.arange(t.shape[0]), t.shape[1])
                blocks = t.ravel()
                ok = blocks >= 0
                np.add.at(counts, (rows[ok], blocks[ok] // tb_l), 1)
            top = np.sort(counts, axis=0)[::-1][:nprobe]
            pl.budget_cache[key] = max(int(top.sum(axis=0).max()), 1)
        return pl.budget_cache[key]

    # ------------------------------------------------------------------
    # sessions
    # ------------------------------------------------------------------
    def searcher(self, params: Optional[SearchParams] = None,
                 **kwargs) -> "ShardedSearcher":
        """Create (or fetch) a compiled mesh session for `params`.

        Same contract as the single-host ``searcher()``: sessions are
        cached per params object; over a streaming base a cached session
        is returned only while the index has not mutated past it —
        stale sessions are dropped (stats folded) and replaced, with
        executables shared through a shape-keyed cache.
        """
        if params is None:
            params = SearchParams(**kwargs)
        elif kwargs:
            params = dataclasses.replace(params, **kwargs)
        if params.plan_reuse:
            raise ValueError(
                "plan_reuse is a single-host session feature (the plan "
                "cache merges host-side between dispatches); mesh "
                "sessions support exec_mode='clustered' for per-device "
                "tile unions instead")
        sess = self._sessions.get(params)
        if sess is not None and sess.version == self.version:
            return sess
        if sess is not None:
            self._fold_session(sess)
            self._n_invalidations += 1
        sess = ShardedSearcher(self, params)
        self._sessions[params] = sess
        return sess

    def search(self, queries: jnp.ndarray, k: int, nprobe: int,
               k_factor: int = 10, max_scan: Optional[int] = None,
               exec_mode: str = "paged", query_tile: int = 8
               ) -> SearchResult:
        """Convenience kwarg path mirroring ``RairsIndex.search``."""
        return self.searcher(SearchParams(
            k=k, nprobe=nprobe, k_factor=k_factor, max_scan=max_scan,
            exec_mode=exec_mode, query_tile=query_tile))(queries)

    def _fold_session(self, sess: "Searcher"):
        for key, v in sess.stats.as_dict().items():
            self._retired[key] = self._retired.get(key, 0) + v

    def searcher_stats(self) -> dict:
        live = list(self._sessions.values())
        out = {
            "sessions": len(live) + self._n_invalidations,
            "invalidations": self._n_invalidations,
            "ndev": self.ndev,
            "epoch": self.epoch,
            "version": self.version,
        }
        for key in ("compiles", "cache_hits"):
            out[key] = (self._retired.get(key, 0)
                        + sum(getattr(s.stats, key) for s in live))
        return out


class ShardedSearcher(Searcher):
    """A compiled shard_map session over one ``ShardedIndex``.

    Identical outer machinery to ``Searcher`` (create via
    ``sharded.searcher(params)``): pad-and-dispatch batch buckets,
    chunking, compile/cache stats, and — over a streaming base —
    (epoch, version) pinning with deterministic ``StaleSessionError``.
    Only the two lowering hooks differ: ``_lower`` jits the
    ``build_serve_step`` shard_map program over the mesh, and
    ``_call_inputs`` feeds the placed shard arrays.
    """

    def __init__(self, sharded: ShardedIndex, params: SearchParams):
        self.sharded = sharded
        self.version = sharded.version
        state = sharded._ensure_state()
        super().__init__(sharded.index, params)
        self.epoch = sharded.epoch
        self._state = state
        # per-device plan budget: explicit max_scan_local, or derived
        # from per-shard list occupancy (never truncates, so tighter
        # padded bounds stay recall-neutral) capped by the per-query one
        self.max_scan_local = (
            sharded.max_scan_local if sharded.max_scan_local is not None
            else min(self.params.max_scan,
                     sharded.derived_max_scan_local(self.params.nprobe)))
        # executables depend on (params, per-device budget, shapes) only
        # — arrays are runtime args — so sibling views and later epochs
        # with equal shapes share them (the resolved budget keys the
        # cache: a new epoch may derive a different bound)
        self._compiled = sharded._placement.exec_cache.setdefault(
            (self.params, self.max_scan_local, state.signature), {})

    def _check_current(self) -> None:
        sh = self.sharded
        if self.version != sh.version:
            raise StaleSessionError(
                f"sharded session pinned (epoch {self.epoch}, version "
                f"{self.version}) but the index is at (epoch {sh.epoch}, "
                f"version {sh.version}); mutations invalidate sessions — "
                f"re-fetch via sharded.searcher(params)")

    def _serve_args(self) -> tuple:
        """Runtime serve-step arguments, with the compact-plane
        substitution applied when a refine tier is active: sharded
        packed block codes for the block store, the plane codec's books
        for the LUT source, the plane's delta codes for the delta scan.
        Everything else — vectors, tables, tombstones — is untouched;
        tier-2 owner refinement runs over the exact shard vectors."""
        args = self._state.serve_args()
        if self._plane is None:
            return args
        bc, cb, dc = self.sharded._plane_args(self._plane)
        args = list(args)
        args[0], args[9], args[14] = bc, cb, dc
        return tuple(args)

    def _build_step(self, stage: str):
        sh = self.sharded
        p = self.params
        idx = sh.index
        return build_serve_step(
            nprobe=p.nprobe, bigk=p.bigk_eff, k=p.k,
            max_scan_local=self.max_scan_local,
            metric=idx.config.metric,
            dedup_results=idx.needs_result_dedup,
            oversample=idx.result_oversample,
            exec_mode=p.exec_mode, query_tile=p.query_tile,
            axes=sh.axes, ndev=sh.ndev, streaming=sh.streaming,
            use_kernel=p.use_kernel, stage=stage,
            packed_codes=self._plane is not None)

    def _lower(self, bucket: int):
        sh = self.sharded
        serve = self._build_step("all")
        s, r = P(sh.axes), P()
        fn = jax.jit(shard_map(
            serve, mesh=sh.mesh,
            in_specs=(s, s, s,                 # block shard
                      r, r, r, r, r,           # list tables
                      r, r,                    # centroids, codebooks
                      s, s, s, s,              # vectors, vec_lo/block_lo/rank
                      r, r, r,                 # delta + tombstones
                      r),                      # queries
            out_specs=SearchResult(ids=r, dists=r, approx_dco=r,
                                   refine_dco=r, scanned_blocks=r,
                                   dropped_blocks=r)))
        q_spec = jax.ShapeDtypeStruct(
            (bucket, sh.index.vectors.shape[1]), jnp.float32)
        return fn.lower(*self._serve_args(), q_spec)

    def _call_inputs(self) -> tuple:
        return self._serve_args()

    # -- traced two-program split (DESIGN.md §11) ----------------------
    def _lower_stage_scan(self, bucket: int):
        """Lower the per-shard scan half: same in_specs as the fused
        program; the per-device candidate streams come out sharded on
        their fetch axis (global width fetch*ndev)."""
        sh = self.sharded
        s, r = P(sh.axes), P()
        cand = P(None, sh.axes)
        fn = jax.jit(shard_map(
            self._build_step("scan"), mesh=sh.mesh,
            in_specs=(s, s, s, r, r, r, r, r, r, r, s, s, s, s, r, r, r, r),
            out_specs=(cand, cand, r, r, r)))
        q_spec = jax.ShapeDtypeStruct(
            (bucket, sh.index.vectors.shape[1]), jnp.float32)
        return fn.lower(*self._serve_args(), q_spec)

    def _lower_stage_tail(self, bucket: int, l_d, l_ids):
        """Lower the gather/finalize tail against the scan half's
        candidate-stream shapes: each device slices its own fetch
        columns back out, all_gathers, and refines owner-scored exact
        distances — identical collectives to the fused program."""
        sh = self.sharded
        st = self._state
        s, r = P(sh.axes), P()
        cand = P(None, sh.axes)
        fn = jax.jit(shard_map(
            self._build_step("tail"), mesh=sh.mesh,
            in_specs=(s, s, r, cand, cand),
            out_specs=(r, r, r)))
        q_spec = jax.ShapeDtypeStruct(
            (bucket, sh.index.vectors.shape[1]), jnp.float32)
        spec = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), (l_d, l_ids))
        return fn.lower(st.vectors, st.vec_lo, q_spec, *spec)

    def _dispatch_traced(self, bucket: int, qc):
        """Stage-fenced mesh dispatch: the shard_map program split at
        the preselect/all_gather boundary into two AOT executables, so
        a trace separates per-shard scan time from the gather/merge
        tail — the two halves the multi-device regression hides in."""
        sh = self.sharded
        st = self._state
        scan_exe = self._get_exe(("tscan", bucket),
                                 lambda: self._lower_stage_scan(bucket))
        with obs.span("stage.shard_scan", cat="device", bucket=bucket,
                      ndev=sh.ndev) as sp:
            l_d, l_ids, approx_dco, scanned, dropped = obs.fence(
                scan_exe(*self._call_inputs(), qc))
            sp.add(approx_dco=int(np.sum(np.asarray(approx_dco))),
                   scanned_blocks=int(np.sum(np.asarray(scanned))))
        tail_exe = self._get_exe(
            ("ttail", bucket),
            lambda: self._lower_stage_tail(bucket, l_d, l_ids))
        with obs.span("stage.gather_finalize", cat="device", bucket=bucket,
                      ndev=sh.ndev) as sp:
            out_ids, out_d, refine_dco = obs.fence(
                tail_exe(st.vectors, st.vec_lo, qc, l_d, l_ids))
            sp.add(refine_dco=int(np.sum(np.asarray(refine_dco))))
        return SearchResult(
            ids=out_ids, dists=out_d, approx_dco=approx_dco,
            refine_dco=refine_dco, scanned_blocks=scanned,
            dropped_blocks=dropped)
