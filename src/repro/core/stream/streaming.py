"""StreamingIndex — a mutable, epoch-versioned view over a frozen base.

The paper builds SEIL once over a static corpus; production corpora
churn.  ``StreamingIndex`` makes insert/delete first-class (DESIGN.md
§8) without giving up the static-shape query engine:

  * the **base** is an ordinary immutable ``RairsIndex`` (one *epoch*);
  * inserts go to an append-only **delta segment** (stream/delta.py):
    assigned through the strategy registry and PQ-encoded exactly like
    the base, then scanned through a padded flat buffer that merges into
    the shared finalize stage (stream/search.py) — no layout rebuild.
    Small deltas scan exhaustively; once capacity outgrows
    ``IndexConfig.delta_route_min`` (default ``nlist * block``) the scan
    is *routed* through the probed lists via the per-list posting map
    maintained on append (stream/search.py docstring);
  * deletes flip bits in a **tombstone mask** over the whole id space;
    dead items are masked at query time, never rewritten out;
  * **compaction** folds survivors (base minus tombstones, plus live
    delta) into a fresh ``build_seil`` base, renumbers ids densely
    (``last_remap`` maps old -> new, -1 = deleted) and bumps ``epoch``.
    ``begin_compact`` is the zero-downtime variant (DESIGN.md §10): it
    snapshots the epoch so the O(n) fold can run on a worker thread
    while the stream keeps serving and mutating, and ``install`` swaps
    the new epoch in atomically, replaying whatever mutations arrived
    after the snapshot;
  * **external ids** are stable handles: the id first issued for an
    item never changes even though compaction renumbers the internal
    id space — ``resolve_ids`` / ``external_ids`` translate through a
    composed map that chains every ``last_remap``, so gateway clients
    holding result ids survive epoch handovers;
  * **sessions** (``StreamingSearcher``) pin the (epoch, version) they
    compiled against: any mutation bumps ``version``, and a stale
    session raises ``StaleSessionError`` instead of silently serving
    pre-mutation state — the failure mode of the old layout-level
    ``seil.delete_ids`` path.  Fresh sessions share compiled executables
    through a stream-level cache keyed by (params, delta capacity), so
    steady-state churn never recompiles.

Mutation costs: insert is O(batch) (assign + encode + buffer patch),
delete is O(batch) (scatter into the mask), compaction is the one O(n)
operation — amortized by thresholds (``StreamConfig``) or triggered
explicitly.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

# module (not symbol) imports: the insert path must observe monkeypatched
# index_mod.pq_encode / compute_assignments exactly like build_index does
from .. import index as index_mod
from ..params import SearchParams
from ..search import SearchResult
from ..searcher import Searcher
from ..seil import build_seil
from ...errors import RairsError
from .delta import DeltaSegment
from .search import (scan_finalize_stream, streaming_search,
                     streaming_search_traced)


class StaleSessionError(RairsError, RuntimeError):
    """A searcher session outlived the index state it compiled against."""


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Streaming-side knobs (query knobs stay in ``SearchParams``).

    delta_pad           delta capacity bucket quantum: buffers are padded
                        to ``delta_pad * 2**j`` slots so compiled shapes
                        stay bounded under churn
    compact_delta_frac  auto-compact when the delta segment exceeds this
                        fraction of the base size (None = manual only)
    compact_dead_frac   auto-compact when tombstoned items exceed this
                        fraction of the id space (None = manual only)
    """
    delta_pad: int = 256
    compact_delta_frac: Optional[float] = None
    compact_dead_frac: Optional[float] = None

    def __post_init__(self):
        if self.delta_pad < 1:
            raise ValueError(f"delta_pad must be >= 1, got {self.delta_pad}")
        for name in ("compact_delta_frac", "compact_dead_frac"):
            v = getattr(self, name)
            if v is not None and not v > 0:
                raise ValueError(f"{name} must be > 0 or None, got {v!r}")


@dataclasses.dataclass
class StreamStats:
    """Mutation / session accounting for one StreamingIndex."""
    inserts: int = 0           # vectors appended
    deletes: int = 0           # items newly tombstoned
    compactions: int = 0
    auto_compactions: int = 0  # subset of compactions (threshold-triggered)
    sessions: int = 0          # StreamingSearcher objects created
    invalidations: int = 0     # cached sessions dropped as stale

    def as_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class _DeviceState:
    """Device mirrors of the mutable state, patched in O(batch) between
    capacity-bucket / posting-width jumps (which rebuild them wholesale)."""
    vectors_full: jnp.ndarray   # (n_base + cap, D) id-aligned refine store
    delta_codes: jnp.ndarray    # (cap, M) uint8
    delta_ids: jnp.ndarray      # (cap,) int32 global ids, -1 dead/unused
    delta_post: jnp.ndarray     # (nlist, L) int32 per-list slot postings
    delta_assigns: jnp.ndarray  # (cap, m) int32 assigned lists per slot
    live_full: jnp.ndarray      # (n_base + cap,) bool
    capacity: int


def _fold_epoch(base, base_live: np.ndarray, d_vectors: np.ndarray,
                d_codes: np.ndarray, d_assigns: np.ndarray,
                d_live: np.ndarray):
    """Pure epoch fold: survivors of (base, delta) -> a fresh
    ``RairsIndex`` plus the dense old->new remap over the snapshot id
    space (-1 = deleted).  Touches only its arguments and the immutable
    base, so it is safe to run off-thread against snapshot copies while
    the owning ``StreamingIndex`` keeps serving (``begin_compact``)."""
    cfg = base.config
    codes_base = base.codes
    if codes_base is None:     # pre-cache bundle: encode once
        codes_base = np.asarray(
            index_mod.pq_encode(base.codebook, base.vectors))
    vec = np.concatenate(
        [np.asarray(base.vectors)[base_live], d_vectors[d_live]], axis=0)
    codes = np.concatenate(
        [np.asarray(codes_base)[base_live], d_codes[d_live]], axis=0)
    assigns = np.concatenate(
        [np.asarray(base.assigns)[base_live], d_assigns[d_live]], axis=0)
    n = vec.shape[0]
    shared = cfg.seil and cfg.multi_m == 2
    t1 = time.perf_counter()
    arrays, seil_stats = build_seil(
        assigns, codes, np.arange(n, dtype=np.int32), cfg.nlist,
        block=cfg.block, shared=shared, code_bits=cfg.nbits)
    t_layout = time.perf_counter() - t1
    alive_full = np.concatenate([base_live, d_live])
    remap = np.full(alive_full.shape[0], -1, np.int64)
    remap[np.nonzero(alive_full)[0]] = np.arange(n)
    new_base = index_mod.RairsIndex(
        config=cfg, centroids=base.centroids, codebook=base.codebook,
        arrays=arrays, vectors=jnp.asarray(vec), stats=seil_stats,
        assigns=assigns, codes=codes,
        build_seconds={"layout": t_layout})
    return new_base, remap, t_layout


class PendingCompaction:
    """A two-phase zero-downtime compaction (``begin_compact``).

    ``fold()`` builds the next epoch from a snapshot taken at
    ``begin_compact`` time; it reads only the snapshot copies and the
    immutable base, so a worker thread can run it while the stream keeps
    answering queries and absorbing mutations.  ``install()`` then swaps
    the folded epoch in atomically and *replays* everything that arrived
    after the snapshot: tail inserts re-append with their already-
    computed codes/assignments (no re-encoding), post-snapshot deletes
    re-tombstone through the remap.  The combined remap over the full
    pre-install id space lands in ``stream.last_remap`` and chains into
    the external-id map exactly like a synchronous ``compact()``.

    Thread contract: ``fold()`` may run on any thread; ``install()``
    mutates the stream and must be serialized against every other use of
    the index — the gateway's dispatcher thread calls it between
    dispatched batches (DESIGN.md §10 handover state machine).
    """

    def __init__(self, stream: "StreamingIndex", reason: str):
        self.stream = stream
        self.reason = reason
        self.state = "folding"
        d = stream._delta
        self._epoch0 = stream.epoch
        self._n_base0 = stream.n_base
        self._count0 = d.count
        self._base_live0 = stream._base_live.copy()
        self._d_vectors0 = d.vectors[:d.count].copy()
        self._d_codes0 = d.codes[:d.count].copy()
        self._d_assigns0 = d.assigns[:d.count].copy()
        self._d_live0 = d.live[:d.count].copy()
        self._folded = None
        self._fold_seconds = 0.0

    def fold(self) -> "PendingCompaction":
        """The O(n) rebuild — run this off-thread; chainable."""
        if self.state != "folding":
            raise RuntimeError(f"fold() on a {self.state} compaction")
        t0 = time.perf_counter()
        self._folded = _fold_epoch(
            self.stream.base, self._base_live0, self._d_vectors0,
            self._d_codes0, self._d_assigns0, self._d_live0)
        self._fold_seconds = time.perf_counter() - t0
        self.state = "ready"
        return self

    def abort(self) -> None:
        """Drop the pending fold; the stream stays on its current epoch."""
        self.state = "aborted"
        if self.stream._pending_compact is self:
            self.stream._pending_compact = None

    def install(self) -> dict:
        """Atomically swap the folded epoch in and replay the mutation
        tail.  Must not race any other use of the stream (see class
        docstring); sessions become stale exactly as under ``compact``."""
        st = self.stream
        if self.state != "ready":
            raise RuntimeError(
                f"install() on a {self.state} compaction (fold() first)")
        if st.epoch != self._epoch0:
            self.abort()
            raise RuntimeError(
                "a competing compaction landed while this one folded; "
                "the snapshot is stale")
        t0 = time.perf_counter()
        new_base, remap0, t_layout = self._folded
        d = st._delta
        # mutations that arrived after the snapshot
        tail_vec = d.vectors[self._count0:d.count].copy()
        tail_codes = d.codes[self._count0:d.count].copy()
        tail_assigns = d.assigns[self._count0:d.count].copy()
        tail_live = d.live[self._count0:d.count].copy()
        dead_base = self._base_live0 & ~st._base_live
        dead_delta = self._d_live0 & ~d.live[:self._count0]
        n_total_old = self._n_base0 + d.count
        # swap epochs (sessions stale from here on)
        st.base = new_base
        st.epoch += 1
        st.version += 1
        st.stats.compactions += 1
        st._retire_sessions()
        st._reset_epoch_state()
        # remap over the full pre-install id space: snapshot ids fold
        # through remap0, live tail inserts re-append under fresh ids
        remap = np.full(n_total_old, -1, np.int64)
        remap[:remap0.size] = remap0
        if tail_live.any():
            lv = np.nonzero(tail_live)[0]
            slots, _ = st._delta.append(
                tail_vec[lv], tail_codes[lv], tail_assigns[lv])
            remap[self._n_base0 + self._count0 + lv] = st.n_base + slots
        # post-snapshot deletes: their victims folded in as live (the
        # snapshot predates them) — re-tombstone through the remap.
        # Stats/version stay put: these mutations were already counted
        # when the caller issued them.
        dead_old = np.concatenate(
            [np.nonzero(dead_base)[0],
             self._n_base0 + np.nonzero(dead_delta)[0]])
        if dead_old.size:
            st._apply_tombstones(remap[dead_old])
        st._apply_remap(remap)
        st._pending_compact = None
        self.state = "installed"
        return {"epoch": st.epoch, "reason": self.reason,
                "n_live": st.n_live,
                "dropped": int((remap < 0).sum()),
                "seconds": self._fold_seconds + time.perf_counter() - t0,
                "layout_seconds": t_layout,
                "replayed_inserts": int(tail_live.sum()),
                "replayed_deletes": int(dead_old.size),
                "id_remap": remap}


class StreamingIndex:
    """Mutable index: an immutable ``RairsIndex`` base epoch plus delta
    segment, tombstone mask, and versioned searcher sessions.

    Duck-type compatible with the read side of ``RairsIndex`` (config /
    centroids / codebook / vectors / stats / searcher / search), so
    existing call sites — including the ``insert_batch`` compat wrapper —
    keep working unchanged.
    """

    def __init__(self, base, config: Optional[StreamConfig] = None):
        if isinstance(base, StreamingIndex):
            raise TypeError("base must be an immutable RairsIndex, not a "
                            "StreamingIndex (nest epochs via compact())")
        self.base = base
        self.stream_config = config or StreamConfig()
        self.epoch = 0
        self.version = 0            # bumps on every insert/delete/compact
        self.stats = StreamStats()
        self.last_remap = None      # old id -> new id after last compact
        self._retired: Dict[str, int] = {}   # folded stats of dead sessions
        self._pending_compact: Optional[PendingCompaction] = None
        # stable external ids: the handle first issued for an item never
        # changes; _ext_to_int chains every compaction remap (-1 = dead)
        # and _int_to_ext is its inverse over the current id space
        self._ext_to_int = np.arange(self.n_base, dtype=np.int64)
        self._int_to_ext = np.arange(self.n_base, dtype=np.int64)
        # compact-plane codecs outlive epochs: train once, re-encode
        # every rebuilt base with the carried codec (quant/plane.py)
        self._plane_codecs: Dict[str, object] = {}
        self._reset_epoch_state()

    def _reset_epoch_state(self):
        base = self.base
        self._delta = DeltaSegment(
            dim=int(base.vectors.shape[1]), m_pq=int(base.codebook.m),
            m_assign=int(base.assigns.shape[1]),
            pad=self.stream_config.delta_pad,
            nlist=int(base.config.nlist))
        self._base_live = np.ones(self.n_base, bool)
        self._dead_base = 0
        self._dev: Optional[_DeviceState] = None
        self._sessions: Dict[SearchParams, "StreamingSearcher"] = {}
        self._exec_cache: Dict[tuple, dict] = {}
        # plan_reuse probe-half executables: they consume only the base
        # arrays, so they survive delta capacity/posting bucket jumps
        # (keyed per params; dropped with the epoch like everything here)
        self._probe_cache: Dict[SearchParams, dict] = {}
        # per-backend device mirrors of the delta's compact-plane codes,
        # keyed by (version, capacity) — dropped with the epoch
        self._plane_delta: Dict[str, tuple] = {}

    # ------------------------------------------------------------------
    # sizes / views
    # ------------------------------------------------------------------
    @property
    def n_base(self) -> int:
        return int(self.base.vectors.shape[0])

    @property
    def n_total(self) -> int:
        """Size of the id space (base + every delta slot ever used)."""
        return self.n_base + self._delta.count

    @property
    def n_delta(self) -> int:
        """Live items in the delta segment."""
        return self._delta.n_live

    @property
    def n_dead(self) -> int:
        return self._dead_base + self._delta.n_dead

    @property
    def n_live(self) -> int:
        return self.n_total - self.n_dead

    @property
    def has_mutations(self) -> bool:
        """Any insert/delete since the current epoch's base was built."""
        return self._delta.count > 0 or self._dead_base > 0

    @property
    def delta_route_threshold(self) -> int:
        """Delta capacity above which the scan routes through the probed
        lists (``IndexConfig.delta_route_min``; default ``nlist *
        block`` — the point where the exhaustive delta costs as much per
        query as scanning every list's worth of one block)."""
        cfg = self.base.config
        if cfg.delta_route_min is not None:
            return cfg.delta_route_min
        return cfg.nlist * cfg.block

    @property
    def delta_routed(self) -> bool:
        """Whether the current delta capacity bucket scans routed.
        Keyed on *capacity* (not live count) so the choice is a static
        property of the compiled shapes."""
        return self._delta.capacity > self.delta_route_threshold

    def routes_at(self, nprobe: int) -> bool:
        """Session-level routing decision.  An explicit
        ``delta_route_min`` is the caller's final word; under the auto
        threshold the routed path must also be cheaper than the scan it
        replaces: the padded routed gather costs ~``nprobe x
        post_width`` ADC rows per query, and a hot-list-skewed delta
        can grow the posting width until that exceeds the exhaustive
        ``capacity`` — then the exhaustive fast path stays in force."""
        if not self.delta_routed:
            return False
        if self.base.config.delta_route_min is not None:
            return True
        return nprobe * self._delta.post_width < self._delta.capacity

    # read-side duck typing with RairsIndex --------------------------------
    @property
    def config(self):
        return self.base.config

    @property
    def centroids(self):
        return self.base.centroids

    @property
    def codebook(self):
        return self.base.codebook

    @property
    def arrays(self):
        return self.base.arrays

    @property
    def seil_stats(self):
        return self.base.stats

    # RairsIndex exposes `.stats` as SeilStats; StreamingIndex.stats is the
    # mutation counter, so the layout stats keep their own accessor above.

    @property
    def needs_result_dedup(self) -> bool:
        return self.base.needs_result_dedup

    @property
    def result_oversample(self) -> int:
        return self.base.result_oversample

    def default_max_scan(self, nprobe: int, slack: float = 1.3) -> int:
        return self.base.default_max_scan(nprobe, slack)

    def plane(self, backend: str, codec=None):
        """The stream-level compact plane (DESIGN.md §12): delegates to
        the current base epoch but *pins the codec* across compactions —
        the first epoch trains it, every rebuilt base re-encodes its
        surviving corpus with the carried codec (deterministic, so the
        folded plane is bitwise what a reload would derive).  An
        explicit ``codec=`` (bundle restore) takes precedence."""
        if codec is None:
            codec = self._plane_codecs.get(backend)
        pp = self.base.plane(backend, codec=codec)
        self._plane_codecs[backend] = pp.codec
        return pp

    def _plane_delta_codes(self, backend: str) -> jnp.ndarray:
        """(capacity, Mc) uint8 compact codes over the delta buffer.

        Deliberately *unpacked*: the delta scan is a per-slot gather-ADC
        (stream/search.py), which composes with the plane codec's LUT
        as-is — nibble packing only pays inside the blocked base scan.
        Recomputed lazily per (version, capacity); the delta is small by
        construction, so the O(capacity) encode rides the mutation
        budget, never the steady-state query path."""
        key = (self.version, self._delta.capacity)
        hit = self._plane_delta.get(backend)
        if hit is not None and hit[0] == key:
            return hit[1]
        from ...quant import encode_plane
        codes = jnp.asarray(encode_plane(self.plane(backend).codec,
                                         self._delta.vectors))
        self._plane_delta[backend] = (key, codes)
        return codes

    @property
    def vectors(self) -> jnp.ndarray:
        """(n_total, D) id-aligned vector view (tombstoned rows included)."""
        d = self._delta
        if d.count == 0:
            return self.base.vectors
        return jnp.concatenate(
            [self.base.vectors, jnp.asarray(d.vectors[:d.count])], axis=0)

    @property
    def assigns(self) -> np.ndarray:
        """(n_total, m) id-aligned assignment view (analysis benches)."""
        d = self._delta
        if d.count == 0:
            return self.base.assigns
        return np.concatenate(
            [np.asarray(self.base.assigns), d.assigns[:d.count]], axis=0)

    @property
    def codes(self) -> Optional[np.ndarray]:
        """(n_total, M) id-aligned cached-PQ-code view (None only for a
        pre-code-cache base that was never mutated)."""
        d = self._delta
        base_codes = self.base.codes
        if d.count == 0:
            return base_codes
        if base_codes is None:   # pre-cache bundle: encode once, like compact
            base_codes = np.asarray(
                index_mod.pq_encode(self.base.codebook, self.base.vectors))
        return np.concatenate(
            [np.asarray(base_codes), d.codes[:d.count]], axis=0)

    def live_mask(self) -> np.ndarray:
        """(n_total,) host bool: True where the id is still live."""
        return np.concatenate(
            [self._base_live, self._delta.live[:self._delta.count]])

    def live_ids(self) -> np.ndarray:
        return np.nonzero(self.live_mask())[0].astype(np.int64)

    def live_vectors(self) -> jnp.ndarray:
        """(n_live, D) surviving vectors in id order (oracle / recall)."""
        d = self._delta
        host = np.concatenate(
            [np.asarray(self.base.vectors), d.vectors[:d.count]], axis=0)
        return jnp.asarray(host[self.live_mask()])

    # ------------------------------------------------------------------
    # device mirrors
    # ------------------------------------------------------------------
    def _device_state(self) -> _DeviceState:
        if self._dev is None:
            d = self._delta
            nb = self.n_base
            vec = np.concatenate(
                [np.asarray(self.base.vectors), d.vectors], axis=0)
            ids = np.full(d.capacity, -1, np.int32)
            used = np.arange(d.count)
            live_used = used[d.live[:d.count]]
            ids[live_used] = nb + live_used
            live_full = np.concatenate([self._base_live, d.live])
            self._dev = _DeviceState(
                vectors_full=jnp.asarray(vec),
                delta_codes=jnp.asarray(d.codes),
                delta_ids=jnp.asarray(ids),
                delta_post=jnp.asarray(d.post),
                delta_assigns=jnp.asarray(d.assigns),
                live_full=jnp.asarray(live_full),
                capacity=d.capacity)
        return self._dev

    # ------------------------------------------------------------------
    # mutations
    # ------------------------------------------------------------------
    def insert(self, x) -> np.ndarray:
        """Append vectors through the delta path; returns their global ids.

        O(batch): strategy-registry assignment + PQ encoding of the new
        rows and buffer patches — never a layout rebuild (asserted via
        ``seil.build_seil_call_count`` in tests/test_stream.py).
        """
        x = np.asarray(x, np.float32)
        if x.ndim != 2 or x.shape[1] != self.base.vectors.shape[1]:
            raise ValueError(
                f"insert batch must be (B, {self.base.vectors.shape[1]}), "
                f"got {x.shape}")
        if x.shape[0] == 0:
            return np.zeros(0, np.int64)
        base = self.base
        xj = jnp.asarray(x)
        assigns = np.asarray(index_mod.compute_assignments(
            xj, base.centroids, base.config), np.int32)
        codes = np.asarray(index_mod.pq_encode(base.codebook, xj))
        nb = self.n_base
        slots, grew = self._delta.append(x, codes, assigns)
        ids = nb + slots
        # issue permanent external handles (identical to the internal id
        # at insert time; compaction remaps chain through _apply_remap)
        ext = np.arange(self._ext_to_int.size,
                        self._ext_to_int.size + ids.size, dtype=np.int64)
        self._ext_to_int = np.concatenate([self._ext_to_int, ids])
        self._int_to_ext = np.concatenate([self._int_to_ext, ext])
        if self._dev is not None and not grew:
            dv = self._dev
            s0 = int(slots[0])
            dv.vectors_full = jax.lax.dynamic_update_slice(
                dv.vectors_full, xj, (jnp.int32(nb + s0), jnp.int32(0)))
            dv.delta_codes = jax.lax.dynamic_update_slice(
                dv.delta_codes, jnp.asarray(codes),
                (jnp.int32(s0), jnp.int32(0)))
            dv.delta_ids = jax.lax.dynamic_update_slice(
                dv.delta_ids, jnp.asarray(ids, jnp.int32), (jnp.int32(s0),))
            dv.delta_assigns = jax.lax.dynamic_update_slice(
                dv.delta_assigns, jnp.asarray(assigns),
                (jnp.int32(s0), jnp.int32(0)))
            pl, pc, ps = self._delta.last_post_update
            if len(pl):
                dv.delta_post = dv.delta_post.at[
                    jnp.asarray(pl), jnp.asarray(pc)].set(
                    jnp.asarray(ps, jnp.int32))
            dv.live_full = jax.lax.dynamic_update_slice(
                dv.live_full, jnp.ones(len(slots), bool),
                (jnp.int32(nb + s0),))
        else:
            self._dev = None   # capacity/posting bucket jump: rebuild lazily
        self.version += 1
        self.stats.inserts += x.shape[0]
        epoch_before = self.epoch
        self._maybe_auto_compact()
        if self.epoch != epoch_before:
            # compaction renumbered the id space; the fresh inserts are
            # alive by construction, so the remap covers all of them
            ids = self.last_remap[ids]
        return ids

    def delete(self, ids) -> int:
        """Tombstone `ids` (base and/or delta); returns how many were
        live until now.  Dead/duplicate ids are a no-op; out-of-range
        ids raise.  O(batch): bitmap scatter, no layout rewrite."""
        ids = np.unique(np.asarray(ids, np.int64).ravel())
        if ids.size == 0:
            return 0
        if ids[0] < 0 or ids[-1] >= self.n_total:
            raise ValueError(
                f"delete ids out of range [0, {self.n_total})")
        nb = self.n_base
        bids = ids[ids < nb]
        dslots = ids[ids >= nb] - nb
        newly_base = int(self._base_live[bids].sum())
        newly = newly_base + int(self._delta.live[dslots].sum())
        if newly == 0:
            return 0        # idempotent retry: nothing changed, nothing stales
        self._base_live[bids] = False
        self._dead_base += newly_base
        self._delta.mark_dead(dslots)
        if self._dev is not None:
            dv = self._dev
            dv.live_full = dv.live_full.at[jnp.asarray(ids)].set(False)
            if dslots.size:
                dv.delta_ids = dv.delta_ids.at[jnp.asarray(dslots)].set(-1)
        self.version += 1
        self.stats.deletes += newly
        self._maybe_auto_compact()
        return newly

    def compact(self, reason: str = "manual") -> dict:
        """Fold delta + tombstones into a fresh base epoch.

        Survivors keep their relative (id) order — base first, then delta
        — and are renumbered densely, so the new base is exactly what
        ``build_index`` would produce over the surviving corpus with the
        same frozen centroids/codebook (asserted in tests/test_stream.py).
        ``last_remap[old_id] -> new_id`` (-1 = deleted) records the
        renumbering; every open session becomes stale.
        """
        if self._pending_compact is not None:
            raise RuntimeError(
                "a background compaction is pending (begin_compact); "
                "install() or abort() it before compacting synchronously")
        t0 = time.perf_counter()
        d = self._delta
        new_base, remap, t_layout = _fold_epoch(
            self.base, self._base_live, d.vectors[:d.count],
            d.codes[:d.count], d.assigns[:d.count], d.live[:d.count])
        n = int((remap >= 0).sum())
        self.base = new_base
        self.epoch += 1
        self.version += 1
        self.stats.compactions += 1
        self._retire_sessions()
        self._reset_epoch_state()
        self._apply_remap(remap)
        return {"epoch": self.epoch, "reason": reason, "n_live": n,
                "dropped": int(remap.size - n),
                "seconds": time.perf_counter() - t0,
                "layout_seconds": t_layout, "id_remap": remap}

    def begin_compact(self, reason: str = "background") -> PendingCompaction:
        """Start a zero-downtime compaction: snapshot this epoch and
        return a ``PendingCompaction`` whose ``fold()`` can run on a
        worker thread while searches and mutations keep flowing, and
        whose ``install()`` swaps the new epoch in atomically (replaying
        the post-snapshot mutation tail).  Only one may be pending;
        threshold auto-compaction stands down while it is."""
        if self._pending_compact is not None:
            raise RuntimeError("a background compaction is already pending")
        p = PendingCompaction(self, reason)
        self._pending_compact = p
        return p

    # ------------------------------------------------------------------
    # stable external ids (survive compaction renumbering)
    # ------------------------------------------------------------------
    def _apply_remap(self, remap: np.ndarray) -> None:
        """Record a compaction renumbering and chain it into the
        composed external-id map (external handles never change)."""
        self.last_remap = remap
        e2i = self._ext_to_int
        valid = e2i >= 0
        nxt = np.full(e2i.shape, -1, np.int64)
        nxt[valid] = remap[e2i[valid]]
        self._ext_to_int = nxt
        i2e = np.full(self.n_total, -1, np.int64)
        ext = np.nonzero(nxt >= 0)[0]
        i2e[nxt[ext]] = ext
        self._int_to_ext = i2e

    def resolve_ids(self, external_ids) -> np.ndarray:
        """Map stable external handles (gateway responses,
        ``external_ids``) to current internal ids; -1 for handles that
        were deleted or never issued.  Handles survive any number of
        compactions — the map chains every ``last_remap``."""
        e = np.asarray(external_ids, np.int64)
        flat = e.ravel()
        out = np.full(flat.shape, -1, np.int64)
        ok = (flat >= 0) & (flat < self._ext_to_int.size)
        ints = self._ext_to_int[flat[ok]]
        live = self.live_mask()
        out[ok] = np.where(
            (ints >= 0) & live[np.clip(ints, 0, live.size - 1)], ints, -1)
        return out.reshape(e.shape)

    def external_ids(self, internal_ids) -> np.ndarray:
        """Map current internal ids (e.g. ``SearchResult.ids``) to their
        stable external handles; -1 pads pass through."""
        i = np.asarray(internal_ids, np.int64)
        flat = i.ravel()
        out = np.full(flat.shape, -1, np.int64)
        ok = (flat >= 0) & (flat < self._int_to_ext.size)
        out[ok] = self._int_to_ext[flat[ok]]
        return out.reshape(i.shape)

    def _apply_tombstones(self, ids: np.ndarray) -> None:
        """Install-time tombstone scatter: no version bump, stats, or
        auto-compaction — the replayed deletes were already counted when
        the caller issued them (``PendingCompaction.install``)."""
        ids = np.asarray(ids, np.int64).ravel()
        ids = np.unique(ids[ids >= 0])
        if ids.size == 0:
            return
        nb = self.n_base
        bids = ids[ids < nb]
        dslots = ids[ids >= nb] - nb
        self._dead_base += int(self._base_live[bids].sum())
        self._base_live[bids] = False
        self._delta.mark_dead(dslots)
        if self._dev is not None:
            dv = self._dev
            dv.live_full = dv.live_full.at[jnp.asarray(ids)].set(False)
            if dslots.size:
                dv.delta_ids = dv.delta_ids.at[jnp.asarray(dslots)].set(-1)

    def restore_state(self, *, epoch: int, version: int,
                      base_live: np.ndarray, delta_vectors: np.ndarray,
                      delta_codes: np.ndarray, delta_assigns: np.ndarray,
                      delta_live: np.ndarray) -> None:
        """Rehydrate persisted epoch state (bundle v2 load, core/io.py)
        into a freshly wrapped base — exact codes/assigns/liveness are
        restored, nothing is recomputed.  Only valid before any
        mutation."""
        if self.version != 0 or self._delta.count != 0:
            raise RuntimeError("restore_state requires a pristine "
                               "StreamingIndex")
        if delta_vectors.shape[0]:
            self._delta.append(delta_vectors, delta_codes, delta_assigns)
            self._delta.mark_dead(np.nonzero(~delta_live)[0])
        if base_live.shape[0] != self.n_base:
            raise ValueError(
                f"base_live has {base_live.shape[0]} bits for a base of "
                f"{self.n_base} vectors")
        self._base_live[:] = base_live
        self._dead_base = int((~base_live).sum())
        self._dev = None
        self.epoch = int(epoch)
        self.version = int(version)
        # external-id state is not persisted (v2 bundles predate it): a
        # restored stream re-issues identity handles over its id space
        self._ext_to_int = np.arange(self.n_total, dtype=np.int64)
        self._int_to_ext = np.arange(self.n_total, dtype=np.int64)

    def _maybe_auto_compact(self):
        if self._pending_compact is not None:
            return      # the background fold owns this epoch's compaction
        sc = self.stream_config
        if (sc.compact_delta_frac is not None
                and self._delta.count > sc.compact_delta_frac
                * max(1, self.n_base)):
            self.stats.auto_compactions += 1
            self.compact(reason="delta_threshold")
        elif (sc.compact_dead_frac is not None
                and self.n_dead > sc.compact_dead_frac
                * max(1, self.n_total)):
            self.stats.auto_compactions += 1
            self.compact(reason="dead_threshold")

    def shard(self, mesh, axes=("data",), max_scan_local=None):
        """Deploy this mutable index over `mesh` as a ``ShardedIndex``
        (core/sharded.py): the base epoch shards by block/vector range,
        the delta segment and tombstone mask replicate (the delta is
        tiny by construction), and compaction re-shards the fresh base
        lazily.  Mutations keep flowing through this StreamingIndex
        (the sharded view forwards insert/delete/compact); sessions on
        the mesh pin (epoch, version) exactly like single-host ones.
        Cached per (mesh, axes, max_scan_local)."""
        from ..sharded import shard_index
        return shard_index(self, mesh, axes=axes,
                           max_scan_local=max_scan_local)

    # ------------------------------------------------------------------
    # sessions
    # ------------------------------------------------------------------
    def searcher(self, params: Optional[SearchParams] = None,
                 **kwargs) -> "StreamingSearcher":
        """Create (or fetch) a session pinned to the current version.

        A cached session is returned only while the index has not
        mutated past it; otherwise its stats are folded into the
        aggregate, it is dropped as stale, and a fresh session — sharing
        this stream's compiled-executable cache — replaces it.
        """
        if params is None:
            params = SearchParams(**kwargs)
        elif kwargs:
            params = dataclasses.replace(params, **kwargs)
        sess = self._sessions.get(params)
        if sess is not None and sess.version == self.version:
            return sess
        if sess is not None:
            self._fold_session(sess)
            self.stats.invalidations += 1
        sess = StreamingSearcher(self, params)
        self._sessions[params] = sess
        self.stats.sessions += 1
        return sess

    def search(self, queries: jnp.ndarray, k: int, nprobe: int,
               k_factor: int = 10, max_scan: Optional[int] = None,
               use_kernel: bool = False, exec_mode: str = "paged",
               query_tile: int = 8) -> SearchResult:
        """Convenience kwarg path mirroring ``RairsIndex.search`` —
        always dispatches through a current (never stale) session."""
        return self.searcher(SearchParams(
            k=k, nprobe=nprobe, k_factor=k_factor, max_scan=max_scan,
            use_kernel=use_kernel, exec_mode=exec_mode,
            query_tile=query_tile))(queries)

    def _fold_session(self, sess: "Searcher"):
        for key, v in sess.stats.as_dict().items():
            self._retired[key] = self._retired.get(key, 0) + v

    def _retire_sessions(self):
        for sess in self._sessions.values():
            self._fold_session(sess)
        self._sessions.clear()

    def searcher_stats(self) -> dict:
        """Aggregate compile-cache stats over live + retired sessions,
        extending the RairsIndex accessor with mutation/epoch fields."""
        live = list(self._sessions.values())
        out = {
            "sessions": self.stats.sessions,
            "invalidations": self.stats.invalidations,
            "epoch": self.epoch,
            "version": self.version,
        }
        for key in ("compiles", "cache_hits"):
            out[key] = (self._retired.get(key, 0)
                        + sum(getattr(s.stats, key) for s in live))
        out["base"] = self.base.searcher_stats()
        return out


class StreamingSearcher(Searcher):
    """An (epoch, version)-pinned session over a ``StreamingIndex``.

    A pristine epoch (no mutations yet) delegates to the wrapped base
    index's own session, so an unmutated ``StreamingIndex`` searches
    bitwise-identically to its ``RairsIndex``.  Once mutated, the
    session dispatches ``streaming_search`` (base stages + exhaustive
    delta scan + tombstone mask) per batch bucket; executables live in a
    stream-level cache keyed by (params, delta capacity), so the session
    churn caused by version pinning never recompiles.
    """

    def __init__(self, stream: StreamingIndex, params: SearchParams):
        self.stream = stream
        self.version = stream.version
        ap = params.active_plane
        if ap is not None:
            # pin the carried codec on the base's plane cache *before*
            # Searcher.__init__ resolves it, so a post-compaction epoch
            # re-encodes with the stream's codec instead of retraining
            stream.plane(ap)
        super().__init__(stream.base, params)
        self.epoch = stream.epoch
        # pinned at session creation: a mutation that changes the answer
        # also bumps the version, which stales the session anyway
        self._route_delta = stream.routes_at(self.params.nprobe)
        if stream.has_mutations:
            self._delegate = None
            # executables depend on (params, delta shapes) only.  The
            # posting width joins the key only once this session routes
            # (the routed gather width is a compiled shape); on the
            # exhaustive path the posting map is replaced by a
            # zero-width placeholder, so steady-state appends growing
            # the postings never recompile the exhaustive executables.
            post_w = stream._delta.post_width if self._route_delta else 0
            self._compiled = stream._exec_cache.setdefault(
                (self.params, stream._delta.capacity, post_w), {})
        else:
            self._delegate = stream.base.searcher(params)

    def _probe_exe_store(self) -> dict:
        """Probe-half executables consume only base arrays — share them
        across delta capacity/posting bucket jumps (same epoch)."""
        return self.stream._probe_cache.setdefault(self.params, {})

    def _check_current(self):
        st = self.stream
        if self.version != st.version:
            raise StaleSessionError(
                f"searcher session pinned (epoch {self.epoch}, version "
                f"{self.version}) but the StreamingIndex is at (epoch "
                f"{st.epoch}, version {st.version}); mutations invalidate "
                f"sessions — re-fetch via stream.searcher(params)")

    def _stream_state(self) -> tuple:
        """Streaming analogue of ``Searcher._scan_state``: when a refine
        tier is active, substitute the plane-packed base block codes,
        the plane codec (LUT source), and the plane's *unpacked* delta
        codes — the delta gather-ADC and the blocked base scan then both
        score tier-1 distances against the same codec, and tier-2 stays
        the shared exact finalize over ``vectors_full``."""
        idx = self.stream.base
        dev = self.stream._device_state()
        if self._plane is None:
            return idx.arrays, idx.codebook, dev.delta_codes, False
        return (dataclasses.replace(idx.arrays,
                                    block_codes=self._plane.block_codes),
                self._plane.codec,
                self.stream._plane_delta_codes(self._plane.backend),
                True)

    def _lower(self, bucket: int):
        p = self.params
        idx = self.stream.base
        dev = self.stream._device_state()
        arrays, codebook, delta_codes, packed = self._stream_state()
        q_spec = jax.ShapeDtypeStruct(
            (bucket, idx.vectors.shape[1]), jnp.float32)
        return streaming_search.lower(
            arrays, idx.centroids, codebook, dev.vectors_full,
            delta_codes, dev.delta_ids, self._post_arg(dev),
            dev.delta_assigns, dev.live_full, q_spec,
            nprobe=p.nprobe, bigk=p.bigk_eff, k=p.k, max_scan=p.max_scan,
            metric=idx.config.metric,
            dedup_results=idx.needs_result_dedup,
            use_kernel=p.use_kernel, oversample=idx.result_oversample,
            exec_mode=p.exec_mode, query_tile=p.query_tile,
            route_delta=self._route_delta, packed_codes=packed)

    def _dispatch_traced(self, bucket: int, qc):
        """Stage-fenced streaming dispatch (repro/obs/): the base stage
        programs plus a separate delta-scan span, so a trace shows the
        delta-vs-base DCO split directly.  A pristine session never
        reaches this — ``__call__`` delegates to the base session."""
        p = self.params
        idx = self.stream.base
        dev = self.stream._device_state()
        arrays, codebook, delta_codes, packed = self._stream_state()
        return streaming_search_traced(
            arrays, idx.centroids, codebook, dev.vectors_full,
            delta_codes, dev.delta_ids, self._post_arg(dev),
            dev.delta_assigns, dev.live_full, qc,
            nprobe=p.nprobe, bigk=p.bigk_eff, k=p.k, max_scan=p.max_scan,
            metric=idx.config.metric,
            dedup_results=idx.needs_result_dedup,
            use_kernel=p.use_kernel, oversample=idx.result_oversample,
            exec_mode=p.exec_mode, query_tile=p.query_tile,
            route_delta=self._route_delta, packed_codes=packed)

    def _post_arg(self, dev) -> jnp.ndarray:
        """The posting-map argument: real directory when routed, a
        zero-width placeholder otherwise (keeps exhaustive-path
        executable signatures independent of posting growth)."""
        if self._route_delta:
            return dev.delta_post
        return jnp.zeros((self.stream.base.config.nlist, 0), jnp.int32)

    def _call_inputs(self) -> tuple:
        idx = self.stream.base
        dev = self.stream._device_state()
        arrays, codebook, delta_codes, _ = self._stream_state()
        return (arrays, idx.centroids, codebook, dev.vectors_full,
                delta_codes, dev.delta_ids, self._post_arg(dev),
                dev.delta_assigns, dev.live_full)

    # -- incremental-plan hooks: the probe half is the base index's own
    # (inherited — self.index IS stream.base), only the scan half swaps
    # in the streaming tail (delta merge + tombstones) ------------------
    def _lower_scan(self, bucket: int, probe_spec, unions_spec):
        p = self.params
        idx = self.stream.base
        dev = self.stream._device_state()
        arrays, _, delta_codes, packed = self._stream_state()
        q_spec = jax.ShapeDtypeStruct(
            (bucket, idx.vectors.shape[1]), jnp.float32)
        return scan_finalize_stream.lower(
            arrays, dev.vectors_full, delta_codes, dev.delta_ids,
            self._post_arg(dev), dev.delta_assigns, dev.live_full, q_spec,
            probe_spec, unions_spec,
            bigk=p.bigk_eff, k=p.k, metric=idx.config.metric,
            dedup_results=idx.needs_result_dedup,
            use_kernel=p.use_kernel, oversample=idx.result_oversample,
            exec_mode=p.exec_mode, query_tile=p.query_tile,
            route_delta=self._route_delta, packed_codes=packed)

    def _scan_inputs(self) -> tuple:
        idx = self.stream.base
        dev = self.stream._device_state()
        arrays, _, delta_codes, _ = self._stream_state()
        return (arrays, dev.vectors_full, delta_codes,
                dev.delta_ids, self._post_arg(dev), dev.delta_assigns,
                dev.live_full)

    def __call__(self, queries: jnp.ndarray) -> SearchResult:
        if self._delegate is not None:
            self._check_current()
            return self._delegate(queries)
        return super().__call__(queries)

    search = __call__
