"""Segmented mutable-index state: sealed segments, the delta buffer, views.

The mutation half of the unified Index API (DESIGN.md §8) is LSM-shaped:

  * ``SealedSegment`` — an immutable block of rows with a backend-built
    search state ("engine"), a global-id column, and a tombstone bitmap.
    Sealed segments are never edited in place: a delete produces a new
    ``SealedSegment`` object sharing the engine/rows/ids and carrying a
    copy-on-write ``live`` bitmap, so published views stay frozen.
  * ``DeltaBuffer`` — the one mutable piece: a small growable host buffer
    of freshly added rows, brute-force searched through the same fused
    rerank kernel as every sealed backend.  The stacked device copy is
    cached and re-uploaded only when new rows landed since the last search
    (never re-stacked per query).  Sealing a delta builds a fresh engine
    over its rows — for forest backends that is one batched cross-tree
    build (DESIGN.md §10), which is what keeps the seal path cheap.
  * ``IndexView`` — an immutable snapshot of (sealed segments, delta
    prefix, tombstones).  ``Index.search`` grabs the current view with a
    single attribute read — readers never take the writer lock — and
    ``Index.snapshot()`` hands the view out directly for repeatable reads.

Engines are duck-typed (see ``index/backends.py``): anything exposing
``search(q, params, valid=None) -> (dists, local_ids)`` plus the host
``db`` rows works.  All distance math — sealed, delta, and brute-force —
funnels through ``core.pipeline.rerank_fused``'s fused gather+distance+
top-k path, so a row's distance is bitwise-identical no matter which
segment it currently lives in (the property the mutation tests pin).

Filtered search (DESIGN.md §13) rides the same machinery: a sealed
segment optionally carries an immutable ``MetaBlock`` of per-row metadata
columns; ``SearchParams.filter`` predicates compile per segment into a
match bitmap (cached on the block), AND with ``live``, and replace the
tombstone mask on the engine's ``valid=`` path — the kernels never learn
about predicates.  ``IndexView.search`` estimates the filter's
selectivity from those bitmaps and either widens the candidate budget
(``repro.filter.predicate.widen_params``) or, below the brute-force
threshold, exact-scans only the matching rows.
"""
from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.search import merge_topk_pairs
from repro.index.params import SearchParams

# location tag for rows living in the (unsealed) delta buffer
DELTA_SID = -1

_DELTA_MIN_CAP = 64


@jax.jit
def _remap_gids(local_ids: jax.Array, gids_dev: jax.Array) -> jax.Array:
    """Segment-local result ids -> global ids (-1 slots pass through)."""
    safe = jnp.maximum(local_ids, 0)
    return jnp.where(local_ids >= 0, gids_dev[safe], -1)


def brute_force_topk(q: jax.Array, rows_dev: jax.Array, params: SearchParams,
                     valid: jax.Array | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """Exact scan via the fused rerank path: (B, k) dists + LOCAL row ids.

    Used by the bruteforce backend and the delta overlay.  Routing the scan
    through ``rerank_fused`` (ids = arange, mask = validity) keeps the
    distance arithmetic identical to every candidate-based backend, which
    is what makes mutated-index results bitwise-comparable to fresh builds.
    The id matrix is padded to >= k columns so the top-k is well-defined
    on segments smaller than k.
    """
    from repro.core.pipeline import rerank_fused
    b = q.shape[0]
    n = rows_dev.shape[0]
    m = max(n, params.k)
    ids = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (b, n))
    if valid is None:
        mask = jnp.ones((b, n), bool)
    else:
        mask = jnp.broadcast_to(valid[None, :], (b, n))
    if m > n:
        ids = jnp.pad(ids, ((0, 0), (0, m - n)), constant_values=-1)
        mask = jnp.pad(mask, ((0, 0), (0, m - n)))
    return rerank_fused(q, ids, mask, rows_dev, params.k,
                        metric=params.metric, mode=params.mode, dedup=False,
                        chunk=params.chunk)


class SealedSegment:
    """Immutable sealed segment: engine + global ids + tombstone bitmap.

    ``live`` is copy-on-write: ``with_tombstones`` returns a new segment
    sharing the engine/gids (and their cached device copies) with a fresh
    bitmap, so views published before a delete keep the old liveness.
    """

    __slots__ = ("sid", "engine", "gids", "live", "n_dead", "identity_gids",
                 "meta", "_gids_dev_cell", "_live_dev", "_filter_dev")

    def __init__(self, sid: int, engine, gids: np.ndarray,
                 live: np.ndarray | None = None,
                 identity_gids: bool | None = None,
                 meta=None,
                 _gids_dev_cell: list | None = None):
        self.sid = sid
        self.engine = engine
        self.gids = np.ascontiguousarray(np.asarray(gids, np.int32))
        if live is None:
            live = np.ones(self.gids.shape[0], bool)
        self.live = live
        self.n_dead = int(live.size - np.count_nonzero(live))
        if identity_gids is None:
            identity_gids = bool(np.array_equal(
                self.gids, np.arange(self.gids.shape[0], dtype=np.int32)))
        self.identity_gids = identity_gids
        # immutable per-row metadata columns (repro.filter.MetaBlock);
        # SHARED across with_tombstones copies — metadata never changes
        # after seal, so its predicate-bitmap cache warms once per segment
        self.meta = meta
        # one-element cell shared across with_tombstones copies
        self._gids_dev_cell = (_gids_dev_cell if _gids_dev_cell is not None
                               else [None])
        self._live_dev = None
        # per-OBJECT cache: predicate -> (n_match_live, device valid mask);
        # not shared across copies because it folds in THIS object's live
        self._filter_dev: dict = {}

    @property
    def n_rows(self) -> int:
        return self.gids.shape[0]

    @property
    def n_live(self) -> int:
        return self.n_rows - self.n_dead

    @property
    def rows(self) -> np.ndarray:
        return self.engine.db

    @property
    def gids_dev(self) -> jax.Array:
        if self._gids_dev_cell[0] is None:
            self._gids_dev_cell[0] = jnp.asarray(self.gids)
        return self._gids_dev_cell[0]

    @property
    def live_dev(self) -> jax.Array:
        if self._live_dev is None:
            self._live_dev = jnp.asarray(self.live)
        return self._live_dev

    def with_tombstones(self, rows: np.ndarray) -> "SealedSegment":
        """New segment object with ``rows`` (local indices) marked dead."""
        live = self.live.copy()
        live[rows] = False
        return SealedSegment(self.sid, self.engine, self.gids, live=live,
                             identity_gids=self.identity_gids,
                             meta=self.meta,
                             _gids_dev_cell=self._gids_dev_cell)

    def filter_valid(self, predicate, store) -> tuple[int, jax.Array | None]:
        """(live match count, device validity mask) for ``predicate``.

        The mask is ``match & live`` — the filter and the tombstones fused
        into ONE bitmap for the kernels' existing ``valid=`` path.  The
        host match bitmap caches on the (shared) MetaBlock; the combined
        device mask caches per segment object, so repeated filtered
        queries on an unmutated view upload nothing.
        """
        cached = self._filter_dev.get(predicate)
        if cached is None:
            combined = self.meta.match(predicate, store) & self.live
            n = int(np.count_nonzero(combined))
            cached = (n, jnp.asarray(combined) if n else None)
            self._filter_dev[predicate] = cached
        return cached

    def search(self, q: jax.Array, params: SearchParams,
               valid: jax.Array | None = None
               ) -> tuple[jax.Array, jax.Array]:
        """(dists, GLOBAL ids) over this segment's live rows.

        ``valid`` optionally overrides the validity mask (the filtered
        path passes its combined filter+tombstone bitmap); by default the
        tombstone bitmap applies when any row is dead.
        """
        if valid is None:
            valid = self.live_dev if self.n_dead else None
        d, li = self.engine.search(q, params, valid=valid)
        return d, _remap_gids(li, self.gids_dev)


class DeltaBuffer:
    """Growable host buffer of freshly added rows (the LSM memtable).

    Appends go to a capacity-doubling numpy buffer; rows are NEVER edited
    in place (an upsert appends a new row and tombstones the old), so any
    prefix of the buffer is immutable and can be shared with views.  The
    device copy is cached per (buffer, uploaded-count): a search after a
    burst of adds uploads once, later searches reuse it — the stacked
    buffer is invalidated by append/seal, not rebuilt per query.
    """

    def __init__(self, dim: int, meta_store=None):
        self.dim = dim
        cap = _DELTA_MIN_CAP
        self._rows = np.zeros((cap, dim), np.float32)
        self._gids = np.full(cap, -1, np.int32)
        self._live = np.zeros(cap, bool)
        # metadata columns grow in lockstep with the rows (codes, not raw
        # values — the Index encodes through its MetadataStore on add)
        self.meta_store = meta_store
        self._meta: dict[str, np.ndarray] | None = None
        if meta_store is not None:
            self._meta = {name: np.zeros(cap, meta_store.dtype(name))
                          for name in meta_store.columns}
        self.count = 0
        self.n_live = 0
        self._dev_lock = threading.Lock()
        self._dev_cache: tuple | None = None   # (buf_obj, count, rows, gids)

    def append(self, x: np.ndarray, gid: int,
               meta: dict[str, int] | None = None) -> int:
        if self.count == self._rows.shape[0]:
            self._rows = np.concatenate([self._rows,
                                         np.zeros_like(self._rows)])
            self._gids = np.concatenate([self._gids,
                                         np.full(self.count, -1, np.int32)])
            self._live = np.concatenate([self._live,
                                         np.zeros(self.count, bool)])
            if self._meta is not None:
                self._meta = {name: np.concatenate([col,
                                                    np.zeros_like(col)])
                              for name, col in self._meta.items()}
        row = self.count
        self._rows[row] = x
        self._gids[row] = gid
        if self._meta is not None:
            for name, col in self._meta.items():
                col[row] = meta[name]
        self._live[row] = True
        self.count = row + 1
        self.n_live += 1
        return row

    def kill(self, row: int) -> None:
        if self._live[row]:
            self._live[row] = False
            self.n_live -= 1

    def live_rows(self) -> tuple[np.ndarray, np.ndarray,
                                 dict[str, np.ndarray] | None]:
        """(rows (m, d), gids (m,), meta columns) of the live prefix —
        the seal payload (meta is None on metadata-less indexes)."""
        idx = np.flatnonzero(self._live[:self.count])
        meta = (None if self._meta is None
                else {name: col[idx].copy()
                      for name, col in self._meta.items()})
        return (np.ascontiguousarray(self._rows[idx]),
                self._gids[idx].copy(), meta)

    def view(self) -> "DeltaView | None":
        """Immutable snapshot of the current live prefix (None if empty)."""
        if self.n_live == 0:
            return None
        return DeltaView(self, self.count, self._live[:self.count].copy())

    def device_rows(self, min_count: int) -> tuple[jax.Array, jax.Array]:
        """Cached device copy of the buffer covering >= min_count rows."""
        with self._dev_lock:
            cache = self._dev_cache
            if (cache is not None and cache[0] is self._rows
                    and cache[1] >= min_count):
                return cache[2], cache[3]
            buf, count = self._rows, self.count
            rows_dev = jnp.asarray(buf)
            gids_dev = jnp.asarray(self._gids)
            self._dev_cache = (buf, count, rows_dev, gids_dev)
            return rows_dev, gids_dev


class DeltaView:
    """Frozen (buffer, count, liveness) triple — one snapshot of the delta."""

    __slots__ = ("_buffer", "count", "live", "_arrays", "_filter_cache")

    def __init__(self, buffer: DeltaBuffer, count: int, live: np.ndarray):
        self._buffer = buffer
        self.count = count
        self.live = live
        self._arrays = None
        self._filter_cache: dict = {}

    @property
    def n_live(self) -> int:
        return int(np.count_nonzero(self.live))

    def _device_arrays(self) -> tuple[jax.Array, jax.Array, jax.Array]:
        if self._arrays is None:
            rows_dev, gids_dev = self._buffer.device_rows(self.count)
            valid = np.zeros(rows_dev.shape[0], bool)
            valid[:self.count] = self.live
            self._arrays = (rows_dev, gids_dev, jnp.asarray(valid))
        return self._arrays

    def filter_valid(self, predicate, store) -> tuple[int, jax.Array | None]:
        """(live match count, device validity mask over the buffer rows).

        Delta rows are few and freshly written, so the predicate is
        evaluated directly over the buffer's column prefixes (per-view
        cached — a view is immutable; the next mutation publishes a new
        one).  The mask covers the buffer's full capacity like the default
        liveness mask, with the same brute-force scan consuming it.
        """
        cached = self._filter_cache.get(predicate)
        if cached is not None:
            return cached
        from repro.filter.metadata import MetaBlock
        buf = self._buffer
        block = MetaBlock({name: col[:self.count]
                           for name, col in buf._meta.items()})
        combined = block.match(predicate, store) & self.live
        n = int(np.count_nonzero(combined))
        dev = None
        if n:
            rows_dev, _ = buf.device_rows(self.count)
            valid = np.zeros(rows_dev.shape[0], bool)
            valid[:self.count] = combined
            dev = jnp.asarray(valid)
        self._filter_cache[predicate] = (n, dev)
        return n, dev

    def search(self, q: jax.Array, params: SearchParams,
               valid: jax.Array | None = None
               ) -> tuple[jax.Array, jax.Array]:
        """(dists, GLOBAL ids) over the live delta rows (brute force).

        ``valid`` optionally overrides the liveness mask (the filtered
        path passes its combined filter+liveness bitmap)."""
        rows_dev, gids_dev, live_valid = self._device_arrays()
        d, li = brute_force_topk(q, rows_dev, params,
                                 valid=live_valid if valid is None else valid)
        return d, _remap_gids(li, gids_dev)


class IndexView:
    """An immutable snapshot of the whole index: what ``search`` reads.

    ``Index`` republishes a fresh view after every mutation; readers pick
    it up with one attribute load and never touch the writer lock.  A view
    handed out via ``Index.snapshot()`` keeps answering from its frozen
    point-in-time state even while the live index mutates or compacts.
    """

    __slots__ = ("segments", "delta", "store")

    def __init__(self, segments: tuple[SealedSegment, ...],
                 delta: DeltaView | None, store=None):
        self.segments = segments
        self.delta = delta
        # the index's MetadataStore (schema + categorical vocab) — None on
        # metadata-less indexes; vocab growth is append-only, so a frozen
        # view may safely share the live store
        self.store = store

    @property
    def n_live(self) -> int:
        n = sum(s.n_live for s in self.segments)
        return n + (self.delta.n_live if self.delta is not None else 0)

    def live_points(self) -> tuple[np.ndarray, np.ndarray]:
        """Canonical (gids, rows) of the live point set, segment order.

        This is the ordering ``compact()`` rebuilds with, and the ordering
        the mutation tests use to build the "equivalent fresh index".
        """
        gids, rows = [], []
        for seg in self.segments:
            idx = np.flatnonzero(seg.live)
            gids.append(seg.gids[idx])
            rows.append(seg.rows[idx])
        if self.delta is not None:
            idx = np.flatnonzero(self.live_delta_mask())
            gids.append(self._buffer_gids()[idx])
            rows.append(self._buffer_rows()[idx])
        if not gids:
            return np.zeros(0, np.int32), np.zeros((0, 0), np.float32)
        return np.concatenate(gids), np.concatenate(rows)

    def filter_match_live(self, predicate) -> np.ndarray:
        """Host predicate-match bits over the live point set, in
        :meth:`live_points` row order.

        This is the sharded path's bitmap compiler (DESIGN.md §15): the
        sharded DB is laid out in exactly ``live_points()`` order, so
        these bits — padded to the even row split and ANDed with the
        pad-row mask — drop straight onto the row-sharded validity
        argument of the mesh query step.  Reuses the per-segment cached
        ``MetaBlock.match`` bitmaps the host-local filtered path warms.
        """
        if self.store is None:
            raise ValueError(
                "predicate given but this index carries no metadata — "
                "build with build_index(..., metadata={col: values}) to "
                "enable filtered search")
        parts = []
        for seg in self.segments:
            idx = np.flatnonzero(seg.live)
            if idx.size:
                parts.append(
                    np.asarray(seg.meta.match(predicate, self.store))[idx])
        if self.delta is not None:
            from repro.filter.metadata import MetaBlock
            buf = self.delta._buffer
            block = MetaBlock({name: col[:self.delta.count]
                               for name, col in buf._meta.items()})
            m = np.asarray(block.match(predicate, self.store))
            parts.append(m[np.flatnonzero(self.delta.live)])
        if not parts:
            return np.zeros(0, bool)
        return np.concatenate(parts)

    # small host-side accessors for live_points (delta internals)
    def live_delta_mask(self) -> np.ndarray:
        return self.delta.live

    def _buffer_gids(self) -> np.ndarray:
        return self.delta._buffer._gids[:self.delta.count]

    def _buffer_rows(self) -> np.ndarray:
        return self.delta._buffer._rows[:self.delta.count]

    def descent_levels(self, params: SearchParams) -> int:
        """Level steps the forest descents of one search are bound to,
        summed over the live sealed segments (``RPFEngine.descent_levels``;
        0 for engines without a forest)."""
        return sum(seg.engine.descent_levels(params)
                   for seg in self.segments
                   if seg.n_live and hasattr(seg.engine, "descent_levels"))

    def search(self, queries, params: SearchParams | None = None,
               **params_kw) -> tuple[jax.Array, jax.Array]:
        """queries (B, d) or (d,) -> (dists (B, k), ids (B, k)).

        Fans out over sealed segments + the delta overlay and merges with
        the associative top-k merge; tombstoned rows are masked inside the
        fused rerank (never surface, never occupy result slots).  Invalid
        slots: dist +inf, id -1.
        """
        params = params if params is not None else SearchParams(**params_kw)
        bad = params.capabilities("local")
        if bad:
            from repro.index.params import CapabilityError
            raise CapabilityError(bad, "local")
        q = jnp.asarray(np.atleast_2d(np.asarray(queries, np.float32)))
        if params.filter is not None:
            return self._search_filtered(q, params)
        segments = self.segments
        if (len(segments) == 1 and self.delta is None
                and segments[0].n_dead == 0 and segments[0].identity_gids):
            # pristine single-segment index: the exact pre-mutation path
            return segments[0].engine.search(q, params)
        parts = []
        for seg in segments:
            if seg.n_live == 0:
                continue
            parts.append(seg.search(q, params))
        if self.delta is not None:
            parts.append(self.delta.search(q, params))
        return self._merge(q, parts, params.k)

    def _merge(self, q, parts, k: int):
        if not parts:
            return (jnp.full((q.shape[0], k), jnp.inf, jnp.float32),
                    jnp.full((q.shape[0], k), -1, jnp.int32))
        if len(parts) == 1:
            return parts[0]
        cat_d = jnp.concatenate([p[0] for p in parts], axis=1)
        cat_i = jnp.concatenate([p[1] for p in parts], axis=1)
        return _merge_parts(cat_d, cat_i, k)

    def _search_filtered(self, q: jax.Array, params: SearchParams
                         ) -> tuple[jax.Array, jax.Array]:
        """Predicate-filtered fan-out (DESIGN.md §13).

        Per segment: compile the predicate into a match bitmap (cached),
        AND with the tombstones, and hand the combined mask to the exact
        ``valid=`` path the engines already serve.  The match counts give
        the filter's TRUE selectivity (the bitmap is exact, not an
        estimate); below the brute-force threshold the query exact-scans
        only the matching rows (the fused kernel issues no DMA for masked
        slots, so cost tracks the matches), otherwise the candidate budget
        is widened by ``repro.filter.predicate.widen_params`` so ~1/s
        fewer surviving candidates still fill k slots.
        """
        from repro.filter.predicate import use_brute_force, widen_params
        if self.store is None:
            from repro.index.params import CapabilityError, Violation
            raise CapabilityError([Violation(
                "filter", "local",
                "params.filter is set but this index carries no metadata",
                "build with build_index(..., metadata={col: values}) to "
                "enable filtered search")], "local")
        pred = params.filter
        seg_parts: list[tuple[SealedSegment, int, jax.Array]] = []
        n_match = 0
        for seg in self.segments:
            if seg.n_live == 0:
                continue
            cnt, vdev = seg.filter_valid(pred, self.store)
            if cnt:
                seg_parts.append((seg, cnt, vdev))
                n_match += cnt
        delta_cnt, delta_valid = 0, None
        if self.delta is not None:
            delta_cnt, delta_valid = self.delta.filter_valid(pred, self.store)
            n_match += delta_cnt
        if n_match == 0:
            return self._merge(q, [], params.k)
        selectivity = n_match / max(self.n_live, 1)
        brute = use_brute_force(selectivity, n_match)
        eff = params if brute else widen_params(params, selectivity)
        parts = []
        for seg, _, vdev in seg_parts:
            if brute:
                d, li = brute_force_topk(q, seg.engine.db_dev, params,
                                         valid=vdev)
                parts.append((d, _remap_gids(li, seg.gids_dev)))
            else:
                parts.append(seg.search(q, eff, valid=vdev))
        if delta_cnt:
            parts.append(self.delta.search(q, params, valid=delta_valid))
        return self._merge(q, parts, params.k)


@functools.partial(jax.jit, static_argnames=("k",))
def _merge_parts(cat_d: jax.Array, cat_i: jax.Array, k: int):
    return merge_topk_pairs(cat_d, cat_i, k)
