"""Unified Index API: one composable search surface over every backend.

The paper's pitch is ONE indexer with tunable accuracy/cost knobs; this
module is that surface (DESIGN.md §5).  An ``IndexSpec`` describes how an
index is built, ``SearchParams`` describes one query's knobs, and every
registered backend (rpf, rpf+int8, lsh-cascade, bruteforce) answers the same
``search(queries, params)`` call — all candidate-based backends rerank
through the fused single-pass pipeline (``core.pipeline``).

Lifecycle (DESIGN.md §8 — segmented, LSM-style):
  * ``build_index(key, db, spec)``   — registry-dispatched constructor,
  * ``index.search(queries, params)``— (dists (B, k), ids (B, k)); reads a
    published immutable ``IndexView`` — NO writer lock on the read path,
  * ``index.add(x)`` / ``index.upsert(id, x)`` / ``index.delete(ids)`` —
    paper §5 incremental updates: adds land in a small delta buffer
    (immediately queryable), the delta is sealed into an immutable segment
    once it outgrows ``spec.delta_cap``, and deletes/upserts tombstone the
    old row via a per-segment validity bitmap that the fused rerank masks,
  * ``index.snapshot()``            — the current ``IndexView``: a frozen,
    independently searchable point-in-time state (copy-on-write; later
    mutations never leak into it),
  * ``index.compact(block=...)``    — rebuild the live point set into one
    fresh segment.  The rebuild runs OFF the writer lock (readers and
    writers proceed concurrently) and the segment list is swapped in
    atomically, folding in any deletes that raced the rebuild,
  * ``index.tuned_params``          — the recall-targeted operating point
    found by ``repro.index.tune`` (DESIGN.md §9); when set it becomes the
    default for ``search()`` calls that pass no params, and it rides the
    manifest so a loaded index remembers how it was tuned,
  * ``index.shard_params`` / ``index.serving_plan`` — the serving-runtime
    metadata (DESIGN.md §12): per-shard tuned operating points from
    ``tune_sharded`` and the capacity planner's traffic model + fleet plan
    (plain dict here — the index layer never imports the serve layer),
  * ``index.save(path)`` / ``load_index(path)`` — versioned multi-segment
    manifest (format 5: format 4's segment state + tuned/per-shard
    operating points + serving plan, plus the per-row metadata columns
    and their schema/vocab) via the elastic checkpointer; format-4/3/2/1
    checkpoints written by older code load through read shims,
  * ``build_index(..., metadata={col: values})`` — columnar per-row
    attributes (int/categorical/timestamp) enabling
    ``SearchParams.filter`` predicates (DESIGN.md §13): evaluated into
    per-segment bitmaps that ride the same fused-kernel validity path as
    tombstones, with selectivity-aware candidate widening.

Thread safety: mutations serialize on a per-index lock and publish a fresh
immutable view; searches read the latest view with a single attribute load
(the serving layer calls them from batcher threads while writers mutate).
"""
from __future__ import annotations

import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer, _flatten_with_names
from repro.filter.metadata import MetaBlock, MetadataStore
from repro.index.params import IndexSpec, SearchParams
from repro.index.segments import DELTA_SID, DeltaBuffer, IndexView, SealedSegment
from repro.tracing import INDEX_SEARCH, span

_BACKENDS: dict[str, type["Index"]] = {}
_BUILTINS_LOADED = False


def register_backend(name: str):
    """Class decorator: register an Index subclass under ``name``."""

    def deco(cls: type["Index"]) -> type["Index"]:
        cls.backend = name
        _BACKENDS[name] = cls
        return cls

    return deco


def _ensure_backends_loaded() -> None:
    # flag, not `if not _BACKENDS`: a user-registered backend must not
    # suppress the built-in registrations
    global _BUILTINS_LOADED
    if not _BUILTINS_LOADED:
        _BUILTINS_LOADED = True
        import repro.index.backends  # noqa: F401  (registers on import)


def get_backend(name: str) -> type["Index"]:
    _ensure_backends_loaded()
    if name not in _BACKENDS:
        raise KeyError(f"unknown index backend {name!r}; "
                       f"registered: {sorted(_BACKENDS)}")
    return _BACKENDS[name]


def available_backends() -> list[str]:
    _ensure_backends_loaded()
    return sorted(_BACKENDS)


def build_index(key: jax.Array | None, db: np.ndarray,
                spec: IndexSpec | None = None, metadata: dict | None = None,
                meta_schema: dict | None = None, **spec_kw) -> "Index":
    """Build an index per ``spec`` (or ``IndexSpec(**spec_kw)``).

    ``key`` seeds the randomized builds (rpf forests); None falls back to
    ``jax.random.key(spec.seed)``.

    ``metadata`` (optional) attaches columnar per-row attributes — a dict
    of column name -> length-N values — enabling ``SearchParams.filter``
    predicates.  Column kinds (int/categorical/timestamp) are inferred
    from dtypes or pinned by ``meta_schema`` ({name: kind}); see
    ``repro.filter``.
    """
    spec = spec if spec is not None else IndexSpec(**spec_kw)
    return get_backend(spec.backend).build(key, db, spec, metadata=metadata,
                                           meta_schema=meta_schema)


def load_index(path: str) -> "Index":
    """Restore an index saved with ``Index.save`` (backend from manifest)."""
    manifest = _read_manifest(path)
    spec = IndexSpec.from_dict(manifest["extra"]["spec"])
    return get_backend(spec.backend)._load(path, spec, manifest)


def _ckpt_dir(path: str, step: int) -> str:
    return os.path.join(path, f"step_{step:010d}")


def _read_manifest(path: str) -> dict:
    step = Checkpointer(path).latest_step()
    if step is None:
        raise FileNotFoundError(f"no index checkpoint under {path}")
    with open(os.path.join(_ckpt_dir(path, step), "manifest.json")) as f:
        manifest = json.load(f)
    return manifest


class Index:
    """Base class: the segmented mutable lifecycle; backends plug in engines.

    Subclass contract (see index/backends.py):
      * ``engine_cls``            — the per-segment search engine: built as
        ``engine_cls(spec, key, rows)``, exposing
        ``search(q, params, valid=None) -> (dists, local_ids)``, host
        ``db`` rows, ``state_tree()`` / ``state_skeleton(spec)`` /
        ``from_state(spec, state)`` for checkpointing,
      * ``_v1_skeleton(spec)``    — pytree shape of the legacy single-
        segment checkpoint format (the format-1 read shim),
      * ``_extra_stats()``        — backend-specific ``stats()`` keys.
    """

    backend: str = ""
    engine_cls: type | None = None

    def __init__(self, key: jax.Array | None, db: np.ndarray,
                 spec: IndexSpec, metadata: dict | None = None,
                 meta_schema: dict | None = None):
        self.spec = spec
        self._lock = threading.Lock()
        if key is None:
            key = jax.random.key(spec.seed)
        self.key = key
        db = np.ascontiguousarray(np.asarray(db, np.float32))
        self._d = int(db.shape[1])
        meta_block = None
        meta_store = None
        if metadata is not None:
            meta_store, meta_block = MetadataStore.from_arrays(
                metadata, db.shape[0], schema=meta_schema)
        engine = self.engine_cls(spec, key, db)
        seg = SealedSegment(sid=0, engine=engine,
                            gids=np.arange(db.shape[0], dtype=np.int32),
                            meta=meta_block)
        self._init_runtime([seg], next_gid=db.shape[0], next_sid=1,
                           meta_store=meta_store)

    def _init_runtime(self, segments: list[SealedSegment], next_gid: int,
                      next_sid: int, meta_store: MetadataStore | None = None
                      ) -> None:
        """Shared tail of __init__ and the checkpoint loaders."""
        self._tuned_params: SearchParams | None = None
        self._shard_params: tuple[SearchParams, ...] | None = None
        self._serving_plan: dict | None = None
        # what the last tune() saw (sample queries + kwargs + live-row
        # count), session-local: compact() retunes from it when the live
        # set has drifted past the staleness threshold (DESIGN.md §14)
        self._tune_ctx: dict | None = None
        self._tuned_n_live = 0
        self._n_retunes = 0
        self._meta_store = meta_store
        self._segments = list(segments)
        self._delta = DeltaBuffer(self._d, meta_store=meta_store)
        self._next_gid = int(next_gid)
        self._next_sid = int(next_sid)
        self._compacting = False
        self._n_seals = 0
        self._n_compactions = 0
        self._n_deleted_total = 0
        # live-row directory: global id -> (segment sid | DELTA_SID, row)
        self._loc: dict[int, tuple[int, int]] = {}
        for seg in self._segments:
            rows = np.flatnonzero(seg.live)
            self._loc.update(zip(seg.gids[rows].tolist(),
                                 ((seg.sid, int(r)) for r in rows)))
        self._publish_locked()

    # ------------------------------------------------------------ lifecycle
    @classmethod
    def build(cls, key: jax.Array | None, db: np.ndarray, spec: IndexSpec,
              metadata: dict | None = None,
              meta_schema: dict | None = None) -> "Index":
        return cls(key, db, spec, metadata=metadata, meta_schema=meta_schema)

    def _publish_locked(self) -> None:
        """Swap in a fresh immutable view (caller holds the writer lock)."""
        self._view = IndexView(tuple(self._segments), self._delta.view(),
                               store=self._meta_store)

    def snapshot(self) -> IndexView:
        """The current immutable view: searchable, frozen, lock-free."""
        return self._view

    @property
    def n_rows(self) -> int:
        """Number of LIVE points (tombstoned rows excluded)."""
        return self._view.n_live

    @property
    def db(self) -> np.ndarray:
        """All sealed rows, segment order (compat; includes tombstoned rows
        still physically present until the next ``compact()``)."""
        segments = self._view.segments
        if len(segments) == 1:
            return segments[0].rows
        if not segments:
            return np.zeros((0, self._d), np.float32)
        return np.concatenate([s.rows for s in segments])

    def live_points(self) -> tuple[np.ndarray, np.ndarray]:
        """Canonical (gids, rows) of the live point set (segment order) —
        the ordering ``compact()`` rebuilds with."""
        return self._view.live_points()

    @property
    def _primary_engine(self):
        return self._view.segments[0].engine

    @property
    def meta_store(self) -> MetadataStore | None:
        """The metadata schema + categorical vocab (None = no metadata)."""
        return self._meta_store

    def stats(self) -> dict:
        """Consistent counter snapshot (taken under the writer lock)."""
        with self._lock:
            segments = list(self._segments)
            n_static = sum(s.n_rows for s in segments)
            n_dead = sum(s.n_dead for s in segments)
            n_delta = self._delta.n_live
            return {
                "backend": self.backend,
                "n_static": n_static,
                "n_overflow": n_delta,
                "n_delta": n_delta,
                "n_live": n_static - n_dead + n_delta,
                "n_tombstones": n_dead + (self._delta.count
                                          - self._delta.n_live),
                "n_deleted_total": self._n_deleted_total,
                "n_segments": len(segments),
                "n_seals": self._n_seals,
                "n_compactions": self._n_compactions,
                "n_retunes": self._n_retunes,
                "compaction_in_progress": self._compacting,
                "metadata_columns": (sorted(self._meta_store.columns)
                                     if self._meta_store is not None else []),
                **self._extra_stats(),
            }

    def _extra_stats(self) -> dict:
        return {}

    # --------------------------------------------------------------- search
    @property
    def tuned_params(self) -> SearchParams | None:
        """The tuned operating point (``repro.index.tune``), or None.

        When set, a bare ``search(queries)`` — no params, no kwargs — uses
        it instead of ``SearchParams()``; explicit params always win.
        Persisted in the manifest (format 3), so it survives save/load.
        """
        return self._tuned_params

    @tuned_params.setter
    def tuned_params(self, params: SearchParams | None) -> None:
        if params is not None and not isinstance(params, SearchParams):
            raise TypeError(f"tuned_params must be SearchParams or None, "
                            f"got {type(params).__name__}")
        self._tuned_params = params

    @property
    def shard_params(self) -> tuple[SearchParams, ...] | None:
        """Per-shard tuned operating points (``tune_sharded``), or None.

        One ``SearchParams`` per DB shard of the mesh partitioning the
        tuning measured on; the serving runtime projects them onto the
        sharded query path (``serve.runtime.uniform_shard_params`` for the
        SPMD hot loop).  Persisted in the manifest (format 4).
        """
        return self._shard_params

    @shard_params.setter
    def shard_params(self, params) -> None:
        if params is not None:
            params = tuple(params)
            if not params or not all(isinstance(p, SearchParams)
                                     for p in params):
                raise TypeError("shard_params must be a non-empty sequence "
                                "of SearchParams, or None")
        self._shard_params = params

    @property
    def serving_plan(self) -> dict | None:
        """Capacity-planner output riding the manifest (format 4): a plain
        ``{"plan": ..., "traffic_model": ...}`` dict (see
        ``repro.serve.planner`` for the typed views — the index layer
        stays below the serve layer and never imports it)."""
        return self._serving_plan

    @serving_plan.setter
    def serving_plan(self, plan: dict | None) -> None:
        if plan is not None and not isinstance(plan, dict):
            raise TypeError(f"serving_plan must be a JSON-ready dict or "
                            f"None, got {type(plan).__name__}")
        self._serving_plan = plan

    def search(self, queries: np.ndarray, params: SearchParams | None = None,
               **params_kw) -> tuple[jax.Array, jax.Array]:
        """queries (B, d) or (d,) -> (dists (B, k), ids (B, k)).

        ``params`` (or loose ``**params_kw``, e.g. ``search(q, k=5)``)
        selects the operating point; with neither, the index's persisted
        ``tuned_params`` apply when present, else ``SearchParams()``.

        Invalid slots: dist +inf, id -1.  Fans out over the sealed segments
        and the incremental-add delta, with tombstones masked inside the
        fused rerank; reads the published view — never the writer lock.
        The call returns before the device finishes: its ``index.search``
        span (``repro.tracing``) is the host's dispatch time.
        """
        if params is None and not params_kw and self._tuned_params is not None:
            params = self._tuned_params
        params = params if params is not None else SearchParams(**params_kw)
        view = self._view
        with span(INDEX_SEARCH,
                  segments=len(view.segments) + (view.delta is not None),
                  levels=view.descent_levels(params)):
            return view.search(queries, params)

    # ------------------------------------------------------------ mutations
    def _encode_meta_locked(self, metadata: dict | None) -> dict | None:
        """Point metadata -> column codes (the add/upsert front door).

        Metadata-carrying indexes require every column on every add (the
        predicates are total); metadata on a metadata-less index is an
        error rather than a silent drop."""
        if self._meta_store is None:
            if metadata:
                raise ValueError("this index carries no metadata — build "
                                 "with build_index(..., metadata=...) first")
            return None
        return self._meta_store.encode_point(metadata)

    def add(self, x: np.ndarray, metadata: dict | None = None) -> int:
        """Paper §5 incremental update. Returns the new point's id.

        The point lands in the delta buffer (immediately queryable); once
        the delta outgrows the seal threshold it is sealed into an
        immutable segment with its own engine — no full rebuild (that is
        ``compact()``'s job, explicitly or in the background).
        ``metadata`` must cover the index's metadata schema exactly when
        one exists ({column: value}).
        """
        x = np.asarray(x, np.float32).reshape(-1)
        with self._lock:
            codes = self._encode_meta_locked(metadata)
            gid = self._next_gid
            self._next_gid += 1
            row = self._delta.append(x, gid, meta=codes)
            self._loc[gid] = (DELTA_SID, row)
            self._maybe_seal_locked()
            self._publish_locked()
            return gid

    def delete(self, ids) -> int:
        """Tombstone one id or an iterable of ids. Returns the count.

        Raises KeyError (before any mutation) if any id is unknown or
        already deleted; deleted rows stop appearing in search results
        immediately and are physically dropped at the next seal/compact.
        """
        id_list = [int(ids)] if np.isscalar(ids) else [int(g) for g in ids]
        with self._lock:
            locs, seen = [], set()
            for gid in id_list:
                loc = self._loc.get(gid)
                if loc is None or gid in seen:
                    raise KeyError(f"id {gid} is not a live point")
                seen.add(gid)
                locs.append(loc)
            # apply: one bitmap copy per touched segment, not per id
            by_sid: dict[int, list[int]] = {}
            for gid, (sid, row) in zip(id_list, locs):
                del self._loc[gid]
                by_sid.setdefault(sid, []).append(row)
            for sid, rows in by_sid.items():
                if sid == DELTA_SID:
                    for row in rows:
                        self._delta.kill(row)
                else:
                    i = self._segment_pos_locked(sid)
                    self._segments[i] = self._segments[i].with_tombstones(
                        np.asarray(rows))
            self._n_deleted_total += len(id_list)
            self._publish_locked()
        return len(id_list)

    def upsert(self, gid: int, x: np.ndarray,
               metadata: dict | None = None) -> int:
        """Insert-or-replace the vector for ``gid`` (id is preserved).

        The old row (if any) is tombstoned and the new vector appended to
        the delta under the same global id — searches see exactly one live
        row per id at all times.  On a metadata-carrying index the new
        row's ``metadata`` replaces the old row's (all columns required,
        like :meth:`add` — rows are immutable, attributes ride the row).
        """
        gid = int(gid)
        x = np.asarray(x, np.float32).reshape(-1)
        with self._lock:
            codes = self._encode_meta_locked(metadata)
            old = self._loc.get(gid)
            if old is not None:
                self._kill_locked(old)
            row = self._delta.append(x, gid, meta=codes)
            self._loc[gid] = (DELTA_SID, row)
            if gid >= self._next_gid:
                self._next_gid = gid + 1
            self._maybe_seal_locked()
            self._publish_locked()
        return gid

    def _segment_pos_locked(self, sid: int) -> int:
        for i, seg in enumerate(self._segments):
            if seg.sid == sid:
                return i
        raise AssertionError(f"directory references unknown segment {sid}")

    def _kill_locked(self, loc: tuple[int, int]) -> None:
        sid, row = loc
        if sid == DELTA_SID:
            self._delta.kill(row)
            return
        i = self._segment_pos_locked(sid)
        self._segments[i] = self._segments[i].with_tombstones(
            np.asarray([row]))

    # ----------------------------------------------------------- seal/flush
    def _seal_threshold(self) -> float:
        if self.spec.delta_cap > 0:
            return float(self.spec.delta_cap)
        n_static = sum(s.n_rows for s in self._segments)
        return max(1.0, self.spec.rebuild_frac * n_static)

    def _maybe_seal_locked(self) -> None:
        if self._delta.count >= self._seal_threshold():
            self._seal_delta_locked()

    def _seal_delta_locked(self) -> None:
        """Freeze the delta's live rows into a new immutable segment."""
        rows, gids, meta_cols = self._delta.live_rows()
        if rows.shape[0] == 0:
            self._delta = DeltaBuffer(self._d, meta_store=self._meta_store)
            return
        sid = self._next_sid
        # build the engine BEFORE retiring the delta: a failed build (OOM,
        # interrupt) must not lose the pending adds or corrupt the directory
        engine = self.engine_cls(self.spec, jax.random.fold_in(self.key, sid),
                                 rows)
        self._next_sid += 1
        self._delta = DeltaBuffer(self._d, meta_store=self._meta_store)
        meta = MetaBlock(meta_cols) if meta_cols is not None else None
        self._segments.append(SealedSegment(sid=sid, engine=engine,
                                            gids=gids, meta=meta))
        self._loc.update(zip(gids.tolist(),
                             ((sid, j) for j in range(gids.shape[0]))))
        self._n_seals += 1

    def flush(self) -> None:
        """Seal any pending delta rows into an immutable segment."""
        with self._lock:
            self._seal_delta_locked()
            self._publish_locked()

    # ------------------------------------------------------------ compaction
    def compact(self, block: bool = True):
        """Rebuild the live point set into one fresh segment.

        The expensive rebuild runs OFF the writer lock: concurrent
        searches keep reading the old view and concurrent mutations keep
        landing (deletes that race the rebuild are re-applied to the new
        segment at swap time; adds sealed during the rebuild survive as
        their own segments).  ``block=False`` runs the rebuild on a
        daemon thread and returns it; ``block=True`` returns a stats dict.

        The rebuild uses the index's original key over the live rows in
        canonical (segment) order, so a compacted index answers bitwise
        identically to a fresh ``build_index(key, live_rows, spec)``.
        The rebuild itself rides the batched cross-tree forest builder
        (DESIGN.md §10), so compaction cost scales like one fast build,
        not L tree builds.

        Tuner-aware: when the index was tuned and the live-row count has
        since drifted past the staleness threshold, the swap is followed
        by a retune from the recorded tuning context, so the compacted
        index never keeps serving a pre-churn operating point
        (:meth:`_maybe_retune`, counted in ``stats()['n_retunes']``).
        """
        with self._lock:
            if self._compacting:
                raise RuntimeError("compaction already in progress")
            self._compacting = True
            try:
                self._seal_delta_locked()
                snap = list(self._segments)
                parts = []
                for seg in snap:
                    live_idx = np.flatnonzero(seg.live)
                    parts.append((seg.sid, live_idx, seg.rows[live_idx],
                                  seg.gids[live_idx],
                                  seg.meta.take(live_idx)
                                  if seg.meta is not None else None))
                self._publish_locked()
            except BaseException:
                self._compacting = False
                raise

        def _rebuild() -> dict:
            try:
                sources = [(sid, int(r)) for sid, live_idx, _, _, _ in parts
                           for r in live_idx]
                gids = (np.concatenate([p[3] for p in parts])
                        if parts else np.zeros(0, np.int32))
                rows = (np.concatenate([p[2] for p in parts])
                        if parts else np.zeros((0, self._d), np.float32))
                meta = (MetaBlock.concat([p[4] for p in parts])
                        if self._meta_store is not None else None)
                engine = (self.engine_cls(self.spec, self.key, rows)
                          if rows.shape[0] else None)
                with self._lock:
                    snap_sids = {seg.sid for seg in snap}
                    newer = [s for s in self._segments
                             if s.sid not in snap_sids]
                    if engine is not None:
                        # fold in deletes/upserts that raced the rebuild:
                        # a source row is still live iff the directory
                        # still points at its pre-compaction location
                        live = np.fromiter(
                            (self._loc.get(int(g)) == src
                             for g, src in zip(gids, sources)),
                            bool, count=gids.shape[0])
                        sid = self._next_sid
                        self._next_sid += 1
                        seg = SealedSegment(sid=sid, engine=engine,
                                            gids=gids, live=live, meta=meta)
                        for j, (g, alive) in enumerate(zip(gids.tolist(),
                                                           live)):
                            if alive:
                                self._loc[g] = (sid, j)
                        self._segments = [seg] + newer
                    else:
                        self._segments = newer
                    self._n_compactions += 1
                    self._publish_locked()
                    stats = {"n_rows": int(rows.shape[0]),
                             "n_segments_in": len(snap),
                             "n_segments_out": len(self._segments)}
            finally:
                self._compacting = False
            # retune (if stale) only after the swap is published and the
            # compaction flag dropped: the tuner searches the index, and a
            # concurrent compact() must not be blocked by it
            self._maybe_retune()
            return stats

        if block:
            return _rebuild()
        t = threading.Thread(target=_rebuild, daemon=True)
        t.start()
        return t

    # staleness threshold: retune when the live-row count has drifted by
    # more than this fraction since the operating point was tuned
    _RETUNE_STALENESS = 0.25

    def _maybe_retune(self) -> None:
        """Close the stale-tune gap: after compaction, refresh the tuned
        operating point when the live set no longer resembles the one the
        last ``tune()`` measured.

        A tuned probe budget is a statement about a specific corpus; heavy
        churn (deletes halving the index, bulk adds doubling it) silently
        invalidates it, and before this hook ``compact()`` kept serving the
        pre-churn ``tuned_params``.  Requires a recorded tuning context
        (``tune()`` ran in this session — the context is session-local, it
        does not ride the manifest); retunes with the same sample queries
        and kwargs, so the refreshed point answers the same recall target.
        """
        ctx, tuned_n = self._tune_ctx, self._tuned_n_live
        if ctx is None or tuned_n <= 0:
            return
        if abs(self.n_rows - tuned_n) / tuned_n < self._RETUNE_STALENESS:
            return
        from repro.index.tune import tune_report   # deferred: avoids a cycle
        tune_report(self, ctx["queries"], **ctx["kwargs"])
        self._n_retunes += 1

    # -------------------------------------------------------------- save/load
    def save(self, path: str) -> str:
        """Checkpoint the index under ``path`` (multi-segment manifest v5).

        Pending delta rows are sealed first (cheap — a per-delta engine
        build, NOT a full rebuild), then every segment's engine state,
        global-id column, tombstone bitmap and metadata columns land
        through the elastic checkpointer, along with the tuned operating
        point (``tuned_params``), the per-shard operating points
        (``shard_params``), the capacity plan (``serving_plan``) and the
        metadata schema + categorical vocab (``meta_schema``) when set.
        A save→load roundtrip is bitwise: the restored index answers
        every query — filtered or not — identically to the saved one,
        with the same default params — and a serving runtime stood up on
        it resolves the same fleet plan.
        """
        with self._lock:
            self._seal_delta_locked()
            self._publish_locked()
            tree: dict = {"key_data": jax.random.key_data(self.key),
                          "segments": {}}
            seg_meta = []
            for i, seg in enumerate(self._segments):
                seg_tree = {
                    "engine": seg.engine.state_tree(),
                    "gids": seg.gids,
                    "live": seg.live,
                }
                if self._meta_store is not None:
                    seg_tree["meta"] = dict(seg.meta.cols)
                tree["segments"][f"{i:03d}"] = seg_tree
                seg_meta.append({"sid": seg.sid, "n_rows": seg.n_rows})
            ckpt = Checkpointer(path, keep=1)
            return ckpt.save(0, tree,
                             extra={"spec": self.spec.to_dict(),
                                    "backend": self.backend,
                                    "format": 5,
                                    "dim": self._d,
                                    "segments": seg_meta,
                                    "next_gid": self._next_gid,
                                    "next_sid": self._next_sid,
                                    "tuned_params": (
                                        self._tuned_params.to_dict()
                                        if self._tuned_params is not None
                                        else None),
                                    "shard_params": (
                                        [p.to_dict()
                                         for p in self._shard_params]
                                        if self._shard_params is not None
                                        else None),
                                    "serving_plan": self._serving_plan,
                                    "meta_schema": (
                                        self._meta_store.to_json()
                                        if self._meta_store is not None
                                        else None)})

    @classmethod
    def load(cls, path: str) -> "Index":
        manifest = _read_manifest(path)
        return cls._load(path, IndexSpec.from_dict(manifest["extra"]["spec"]),
                         manifest)

    @classmethod
    def _restore_tree(cls, path: str, manifest: dict, skeleton) -> dict:
        """Restore a checkpoint into the SHAPE of ``skeleton`` (leaf values
        ignored; shapes/dtypes come from the manifest)."""
        shapes = {leaf["name"]: (leaf["shape"], leaf["dtype"])
                  for leaf in manifest["leaves"]}
        named = _flatten_with_names(skeleton)
        leaves = []
        for name, _ in named:
            shape, dtype = shapes[name]
            leaves.append(np.zeros(shape, dtype))
        template = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(skeleton), leaves)
        state, _ = Checkpointer(path).restore(template,
                                              step=manifest["step"])
        return state

    @classmethod
    def _load(cls, path: str, spec: IndexSpec, manifest: dict) -> "Index":
        if manifest["extra"].get("format", 1) >= 2:
            return cls._load_v2(path, spec, manifest)
        return cls._load_v1(path, spec, manifest)

    @classmethod
    def _load_v2(cls, path: str, spec: IndexSpec, manifest: dict) -> "Index":
        """Loader for segmented manifests (formats 2 through 5).

        Each format only ADDS optional extras on top of format 2's segment
        state — format 3 the tuned operating point, format 4 the per-shard
        params and serving plan, format 5 the metadata schema + per-segment
        metadata columns — so the older-format read shims are this same
        path with the newer extras absent (None).
        """
        extra = manifest["extra"]
        n_seg = len(extra["segments"])
        meta_schema = extra.get("meta_schema")
        store = (MetadataStore.from_json(meta_schema)
                 if meta_schema is not None else None)
        # meta leaves exist on disk only when the writer carried a store;
        # keying the skeleton off meta_schema (not the leaf list) means a
        # v4-and-earlier manifest — or a v5 one with the schema stripped —
        # skips them, and surplus on-disk leaves are simply ignored.
        meta_cols = sorted(store.columns) if store is not None else []
        skeleton = {"key_data": 0,
                    "segments": {f"{i:03d}": {
                        "engine": cls.engine_cls.state_skeleton(spec),
                        "gids": 0, "live": 0,
                        **({"meta": {c: 0 for c in meta_cols}}
                           if store is not None else {})}
                        for i in range(n_seg)}}
        state = cls._restore_tree(path, manifest, skeleton)
        obj = cls.__new__(cls)
        obj.spec = spec
        obj._lock = threading.Lock()
        obj.key = jax.random.wrap_key_data(
            jnp.asarray(state["key_data"], jnp.uint32))
        obj._d = int(extra["dim"])
        segments = []
        for i, meta in enumerate(extra["segments"]):
            st = state["segments"][f"{i:03d}"]
            seg_meta = None
            if store is not None:
                seg_meta = MetaBlock({c: np.asarray(st["meta"][c],
                                                    store.dtype(c))
                                      for c in meta_cols})
            segments.append(SealedSegment(
                sid=int(meta["sid"]),
                engine=cls.engine_cls.from_state(spec, st["engine"]),
                gids=np.asarray(st["gids"], np.int32),
                live=np.asarray(st["live"], bool),
                meta=seg_meta))
        obj._init_runtime(segments, next_gid=extra["next_gid"],
                          next_sid=extra["next_sid"], meta_store=store)
        tuned = extra.get("tuned_params")
        if tuned is not None:
            obj._tuned_params = SearchParams.from_dict(tuned)
        shard = extra.get("shard_params")
        if shard:
            obj._shard_params = tuple(SearchParams.from_dict(p)
                                      for p in shard)
        obj._serving_plan = extra.get("serving_plan") or None
        return obj

    @classmethod
    def _load_v1(cls, path: str, spec: IndexSpec, manifest: dict) -> "Index":
        """Read shim for the legacy single-segment checkpoint format."""
        state = cls._restore_tree(path, manifest, cls._v1_skeleton(spec))
        obj = cls.__new__(cls)
        obj.spec = spec
        obj._lock = threading.Lock()
        obj.key = jax.random.wrap_key_data(
            jnp.asarray(state["key_data"], jnp.uint32))
        engine = cls.engine_cls.from_state(spec, state)
        obj._d = int(engine.db.shape[1])
        n = engine.db.shape[0]
        seg = SealedSegment(sid=0, engine=engine,
                            gids=np.arange(n, dtype=np.int32))
        obj._init_runtime([seg], next_gid=n, next_sid=1)
        return obj

    # ------------------------------------------------------ subclass hooks
    @classmethod
    def _v1_skeleton(cls, spec: IndexSpec) -> dict:
        raise NotImplementedError
