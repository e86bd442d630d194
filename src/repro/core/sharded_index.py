"""Distributed random-partition-forest index (multi-pod shard_map runtime).

Sharding model (DESIGN.md §3.1):
  * DB rows sharded over the ``db_axes`` mesh axes (e.g. ("pod", "data")) —
    each DB shard builds forests over *its own rows only*, so index build needs
    ZERO communication (the paper's 'easily parallelizable and distributable'
    property, made concrete).
  * Within a DB shard, the L trees are sharded over ``tree_axis`` ("model"):
    each cell owns L / |model| trees.
  * Query: the query batch is replicated; every (db, tree) cell traverses its
    trees, reranks against its local DB rows via the fused gather+distance+
    top-k path (no (B, M, d) intermediate — see core/pipeline.py), and emits
    a local top-k of (distance, global-id) pairs; a global top-k merge
    all-gathers the tiny (B, k) payloads over model then db axes —
    O(cells * k * 8B) bytes/query, independent of DB size.

Two query surfaces (DESIGN.md §15):
  * ``make_query_fn`` — the raw jit-able SPMD step, ONE fixed program per
    operating point.  Serves the per-cell knobs only; host-driven knobs
    (``probe_schedule``, ``filter``) are rejected with a pointer to
  * ``ShardedIndex`` — the ``Index``-protocol facade that drives those
    steps from the host: it compiles predicate bitmaps onto the row-sharded
    validity argument (the tombstone trick generalized, zero kernel
    changes) and schedules per-query probe rounds over per-width steps.

Fault tolerance: a cell's index state is a pure function of (db shard, rng
key), so recovery from a lost node = rebuild of one shard, no global state.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core.forest import (Forest, ForestConfig, build_forest,
                               gather_candidates, gather_candidates_multi,
                               traverse, traverse_multiprobe)
from repro.core.search import merge_topk_pairs  # noqa: F401  (re-export)


class ShardedForest(NamedTuple):
    """Forest pytree with two leading sharded axes: (db_shards, tree_shards)."""

    forest: Forest      # arrays: (D, T, L_local, ...), P(db_axes, tree_axis)
    n_local: int        # rows per DB shard (static)
    cfg: ForestConfig   # resolved for n_local

    @property
    def trees_per_cell(self) -> int:
        return self.forest.thresh.shape[2]


def _db_spec(db_axes: Sequence[str]) -> P:
    return P(tuple(db_axes))


def build_sharded_index(key: jax.Array, db: jax.Array, cfg: ForestConfig,
                        mesh: Mesh, db_axes: Sequence[str] = ("data",),
                        tree_axis: str = "model") -> ShardedForest:
    """db: (N, d) sharded over rows by ``db_axes``. Returns a ShardedForest."""
    d_shards = 1
    for a in db_axes:
        d_shards *= mesh.shape[a]
    t_shards = mesh.shape[tree_axis]
    n_local = db.shape[0] // d_shards
    l_local = max(1, cfg.n_trees // t_shards)
    local_cfg = cfg._replace(n_trees=l_local).resolved(n_local)

    def _build(db_local):
        db_local = db_local.reshape(n_local, db.shape[1])
        di = jax.lax.axis_index(tuple(db_axes))
        ti = jax.lax.axis_index(tree_axis)
        k = jax.random.fold_in(jax.random.fold_in(key, di), ti)
        forest = build_forest(k, db_local, local_cfg)
        # add the (db, tree) leading shard axes for the out_specs
        return jax.tree.map(lambda x: x[None, None], forest)

    spec = P(tuple(db_axes), tree_axis)
    forest = compat.shard_map(
        _build, mesh=mesh,
        in_specs=(_db_spec(db_axes),),
        out_specs=jax.tree.map(lambda _: spec, Forest(
            proj_idx=0, proj_coef=0, thresh=0, child_base=0, perm=0,
            leaf_offset=0, leaf_count=0, n_nodes=0)),
        check_vma=False,
    )(db)
    return ShardedForest(forest=forest, n_local=n_local, cfg=local_cfg)


def make_query_fn(index_cfg: ForestConfig, n_local: int, mesh: Mesh,
                  db_axes: Sequence[str] = ("data",), tree_axis: str = "model",
                  k: int = 10, metric: str = "l2", dedup: bool = True,
                  kernel_mode: str = "auto", params=None,
                  with_validity: bool = False):
    """Build the jit-able sharded query step: (index, queries, db) -> top-k.

    The returned function is the unit the launcher lowers/compiles for the
    dry-run; :class:`ShardedIndex` (and through it the serving hot loop)
    drives one such step per operating point.  Kept as the compatibility
    wrapper for callers that want the raw step — new code should prefer
    ``ShardedIndex.search``.

    ``params`` (a ``repro.index.SearchParams``) is the unified-API spelling
    of the query knobs; when given it overrides the k/metric/dedup/
    kernel_mode arguments and supplies the candidate-chunk width and the
    multi-probe width (``n_probes`` — each cell descends its local trees to
    that many most-marginal leaves; the wider per-cell candidate set rides
    the same fused id/mask path and the same tiny (B, k) all-gather merge).
    Only the per-cell knobs compile into the ONE fixed SPMD program this
    returns (k, metric, dedup, mode, chunk, n_probes) — a params carrying
    ``adaptive_wave``, ``min_candidates`` or a search-time ``n_trees``
    restriction is rejected per ``SearchParams.capabilities("sharded")``,
    and the host-driven knobs (``probe_schedule``, ``filter``) are rejected
    HERE with a pointer to ``ShardedIndex.search``, which serves them by
    scheduling rounds / compiling bitmaps around steps like this one.

    ``with_validity=True`` grows the step signature to
    ``(index, queries, db, live)`` where ``live`` is an (N,) bool row
    bitmap sharded like the DB rows: the segmented-lifecycle tombstone
    mask (DESIGN.md §8) — and, since DESIGN.md §15, the carrier for
    host-compiled predicate bitmaps too.  Each cell folds its local slice
    into the fused rerank's id/mask path, so a deleted (or filtered-out)
    row never reaches any cell's top-k — serving a mutating snapshot needs
    no index rebuild, only a refreshed bitmap.
    """
    chunk, n_probes = 0, 1
    if params is not None:
        from repro.index.params import CapabilityError, Violation
        bad = list(params.capabilities("sharded"))
        if params.probe_schedule and not any(v.knob == "probe_schedule"
                                             for v in bad):
            bad.append(Violation(
                "probe_schedule", "sharded",
                f"probe_schedule={params.probe_schedule} (make_query_fn "
                f"compiles ONE fixed SPMD program; the schedule's round "
                f"count is data-dependent)",
                "use ShardedIndex.search, which host-schedules rounds "
                "over per-width steps"))
        if params.filter is not None and not any(v.knob == "filter"
                                                 for v in bad):
            bad.append(Violation(
                "filter", "sharded",
                "filter=<predicate> (the raw step consumes a validity "
                "bitmap, not a predicate AST)",
                "use ShardedIndex.search, which compiles the predicate "
                "into the row-sharded validity argument"))
        if bad:
            raise CapabilityError(
                bad, "sharded",
                prefix="make_query_fn cannot compile these params")
        k, metric = params.k, params.metric
        dedup, kernel_mode = params.dedup, params.mode
        chunk, n_probes = params.chunk, params.n_probes
    cfg = index_cfg.resolved(n_local)
    all_axes = tuple(db_axes) + (tree_axis,)

    def _query(forest_cell: Forest, queries: jax.Array, db_local: jax.Array,
               live_local: jax.Array | None = None):
        from repro.core.pipeline import rerank_fused
        forest_cell = jax.tree.map(lambda x: x[0, 0], forest_cell)
        db_local = db_local.reshape(n_local, -1)
        if live_local is not None:
            live_local = live_local.reshape(n_local)
        # 1) descend the local trees (paper: one gather + compare per level;
        #    n_probes > 1 widens to the multi-probe leaf set, DESIGN.md §9)
        if n_probes > 1:
            leaves = traverse_multiprobe(forest_cell, queries, cfg.max_depth,
                                         n_probes)
            cand_ids, mask = gather_candidates_multi(forest_cell, leaves,
                                                     cfg.leaf_pad)
        else:
            leaves = traverse(forest_cell, queries, cfg.max_depth)
            cand_ids, mask = gather_candidates(forest_cell, leaves,
                                               cfg.leaf_pad)
        # 2) fused exact rerank against local DB rows — dedup + tile-streamed
        #    gather + running top-k, no (B, M, d) intermediate per cell;
        #    tombstoned (and filtered-out) rows fold into the same id/mask
        #    path
        loc_d, loc_i = rerank_fused(queries, cand_ids, mask, db_local, k,
                                    metric=metric, mode=kernel_mode,
                                    dedup=dedup, chunk=chunk,
                                    valid=live_local)
        # 3) globalize ids, then tiny all-gather merge over tree + db axes
        di = jax.lax.axis_index(tuple(db_axes))
        glob_i = jnp.where(loc_i >= 0, loc_i + di * n_local, -1)
        gd = jax.lax.all_gather(loc_d, all_axes, axis=1, tiled=True)
        gi = jax.lax.all_gather(glob_i, all_axes, axis=1, tiled=True)
        gd = jnp.where(gi >= 0, gd, jnp.inf)
        if dedup:
            # tree shards over the same row shard surface the same
            # neighbors; without a cross-cell dedup the merged top-k holds
            # each id t_shards times, capping distinct recall at k/t_shards
            order = jnp.argsort(gi, axis=1)
            gi = jnp.take_along_axis(gi, order, axis=1)
            gd = jnp.take_along_axis(gd, order, axis=1)
            dup = jnp.concatenate(
                [jnp.zeros_like(gi[:, :1], bool), gi[:, 1:] == gi[:, :-1]],
                axis=1)
            gd = jnp.where(dup, jnp.inf, gd)
        neg, pos = jax.lax.top_k(-gd, k)
        out_i = jnp.take_along_axis(gi, pos, axis=1)
        return -neg, jnp.where(jnp.isinf(neg), -1, out_i)

    spec = P(tuple(db_axes), tree_axis)
    forest_specs = jax.tree.map(lambda _: spec, Forest(
        proj_idx=0, proj_coef=0, thresh=0, child_base=0, perm=0,
        leaf_offset=0, leaf_count=0, n_nodes=0))

    if with_validity:
        fwd = compat.shard_map(
            _query, mesh=mesh,
            in_specs=(forest_specs, P(), _db_spec(db_axes),
                      _db_spec(db_axes)),
            out_specs=(P(), P()),
            check_vma=False,
        )

        @jax.jit
        def query_step(index: ShardedForest, queries: jax.Array,
                       db: jax.Array, live: jax.Array):
            return fwd(index.forest, queries, db, live)

        return query_step

    fwd = compat.shard_map(
        lambda f, q, db_local: _query(f, q, db_local), mesh=mesh,
        in_specs=(forest_specs, P(), _db_spec(db_axes)),
        out_specs=(P(), P()),
        check_vma=False,
    )

    @jax.jit
    def query_step(index: ShardedForest, queries: jax.Array, db: jax.Array):
        return fwd(index.forest, queries, db)

    return query_step


class ShardedIndex:
    """``Index``-protocol facade over the sharded query path.

    Snapshots an ``repro.index.Index``'s live point set, builds the
    per-cell forests over the mesh, and serves ``search(queries, params)``
    / ``stats()`` / ``violations(params)`` like the host index — replacing
    ``make_query_fn``'s kwarg sprawl with one object that owns the padded
    rows, the validity bitmap, the gid remap and a cache of compiled steps
    (one per operating point actually served).

    Beyond the raw step it serves the two host-driven knobs the SPMD
    program cannot (DESIGN.md §15):

    * ``params.filter`` — the predicate is compiled ONCE host-side into a
      match bitmap in ``live_points()`` row order (exactly the row order
      the sharded DB was laid out in), ANDed with the pad/tombstone
      bitmap, and fed through the existing ``with_validity`` argument: the
      per-segment trick of DESIGN.md §13, with the mesh none the wiser.
      Selectivity is exact (bitmap counts), so the same brute-force-vs-
      widen policy applies: under ``use_brute_force`` the matching rows
      (≤ ~4k by definition) are exact-scanned host-side — distributing a
      sub-batch-sized scan is pure overhead — otherwise ``n_probes`` is
      widened per ``widen_params`` and the query rides the mesh.
    * ``params.probe_schedule`` — the host drives convergence-gated
      rounds at doubling probe widths over per-width compiled steps,
      mirroring ``core.schedule.scheduled_query``: active queries gather
      into pow2-padded buckets, each round REPLACES results (per-cell
      probe leaf sets are monotone prefixes, so the merged global top-k
      at width w sees a superset of every earlier round — replacement is
      sound shard-by-shard for the same reason it is locally), and
      ``tol=0.0`` never converges, making the final round bitwise equal
      to the fixed-cap step.

    ``strict`` controls reject-or-strip for the knobs the mesh cannot
    honor (``capabilities("sharded")``): ``strict=True`` (default) raises
    :class:`repro.index.params.CapabilityError`; ``strict=False`` strips
    exactly the perf knobs ``SearchParams.sharded()`` neutralizes
    (``adaptive_wave``/``min_candidates``/``n_trees``) and counts the
    downgrade in ``stats()``.  A ``filter`` is NEVER stripped in either
    mode — silently dropping one would change which rows come back; a
    filter that cannot be served (no metadata on the index) raises a
    structured error naming the failed capability instead.
    """

    def __init__(self, index, mesh: Mesh,
                 db_axes: Sequence[str] = ("data",),
                 tree_axis: str = "model", strict: bool = True):
        self.index = index
        self.mesh = mesh
        self.db_axes = tuple(db_axes)
        self.tree_axis = tree_axis
        self.strict = bool(strict)
        self._view = index.snapshot()
        gids, rows = self._view.live_points()
        self.n_live = int(gids.shape[0])
        if self.n_live == 0:
            raise ValueError("cannot shard an empty index")
        d_shards = 1
        for a in self.db_axes:
            d_shards *= mesh.shape[a]
        pad = (-self.n_live) % d_shards
        if pad:
            # pad to an even row split; the validity bitmap masks pad rows
            # out of every cell's top-k (same path as tombstones)
            rows = np.concatenate([rows, np.repeat(rows[-1:], pad, axis=0)])
        self._rows_host = np.asarray(rows, np.float32)
        pad_live = np.ones(rows.shape[0], bool)
        pad_live[self.n_live:] = False
        self._pad_live = pad_live
        self._gids = np.asarray(gids, np.int64)
        # rows and row bitmaps live where the step reads them: split over
        # the db axes of the mesh (replicated over the tree axis), so no
        # query re-shards them from one device
        self._row_sharding = NamedSharding(mesh, _db_spec(self.db_axes))
        self._db = jax.device_put(self._rows_host, self._row_sharding)
        self._live = jax.device_put(pad_live, self._row_sharding)
        self._forest = build_sharded_index(
            index.key, self._db, index.spec.forest, mesh,
            db_axes=self.db_axes, tree_axis=tree_axis)
        self._steps: dict = {}           # step params -> compiled mesh step
        self._filters: dict = {}         # predicate -> (n_match, np, jnp)
        self._counters = {
            "queries": 0, "filtered_queries": 0, "brute_filtered_queries": 0,
            "scheduled_queries": 0, "probe_rounds": 0, "probes_processed": 0,
            "stripped_knobs": 0,
        }

    # --------------------------------------------------------- capability
    def _resolve(self, params, kw):
        from repro.index.params import SearchParams
        if params is not None:
            return params
        if kw:
            return SearchParams(**kw)
        tuned = getattr(self.index, "tuned_params", None)
        return tuned if tuned is not None else SearchParams()

    def violations(self, params=None) -> list:
        """``capabilities("sharded")`` of ``params`` (default: the index's
        tuned point) plus the index-dependent entries — currently one: a
        filter on a metadata-less index."""
        from repro.index.params import Violation
        params = self._resolve(params, {})
        bad = params.capabilities("sharded")
        if params.filter is not None and self._view.store is None:
            bad.append(Violation(
                "filter", "sharded",
                "params.filter is set but this index carries no metadata",
                "build with build_index(..., metadata={col: values}) to "
                "enable filtered search"))
        return bad

    def _admit(self, params):
        """Reject-or-strip per ``strict``; returns the params to serve."""
        from repro.index.params import CapabilityError
        bad = self.violations(params)
        if not bad:
            return params
        if self.strict:
            raise CapabilityError(bad, "sharded")
        stripped = params.sharded()
        still = self.violations(stripped)
        if still:
            # whatever survives .sharded() cannot be stripped away — a
            # malformed/unservable filter, an unknown metric: refuse loudly
            raise CapabilityError(still, "sharded")
        self._counters["stripped_knobs"] += len(bad)
        return stripped

    # ------------------------------------------------------------- search
    def search(self, queries, params=None, **params_kw):
        """queries (B, d) or (d,) -> (dists (B, k), GLOBAL ids (B, k)).

        Same contract as ``Index.search`` (invalid slots: dist +inf,
        id -1), answered over the snapshot this object was built from.
        """
        params = self._admit(self._resolve(params, params_kw))
        q = jnp.asarray(np.atleast_2d(np.asarray(queries, np.float32)))
        self._counters["queries"] += int(q.shape[0])
        live, eff = self._live, params
        if params.filter is not None:
            done, a, b = self._filtered_setup(q, params)
            if done:                     # zero-match / host brute regimes
                return a, b
            live, eff = a, b
        if eff.probe_schedule:
            d, gi = self._search_scheduled(q, eff, live)
        else:
            step = self._step(eff)
            with self.mesh:
                d, gi = step(self._forest, q, self._db, live)
        return jnp.asarray(d), self._remap(gi)

    def _filtered_setup(self, q, params):
        """Resolve a filtered query into ``(done, a, b)``: either the
        finished host answer ``(True, dists, ids)`` (zero-match and
        brute-force regimes) or ``(False, live bitmap, widened params)``
        to ride the mesh with."""
        from repro.filter.predicate import use_brute_force, widen_params
        from repro.index.segments import brute_force_topk
        n_match, match_np, match_dev = self._filter_bitmap(params.filter)
        self._counters["filtered_queries"] += int(q.shape[0])
        if n_match == 0:
            b = q.shape[0]
            return (True,
                    jnp.full((b, params.k), jnp.inf, jnp.float32),
                    jnp.full((b, params.k), -1, jnp.int32))
        selectivity = n_match / max(self.n_live, 1)
        if use_brute_force(selectivity, n_match):
            # the matching set is sub-batch-sized: exact-scan it host-side
            # (the same decision IndexView._search_filtered makes, so the
            # sharded path is answer-for-answer the host oracle here)
            self._counters["brute_filtered_queries"] += int(q.shape[0])
            idx = np.flatnonzero(match_np)
            d, li = brute_force_topk(q, jnp.asarray(self._rows_host[idx]),
                                     params)
            li = np.asarray(li)
            gi = np.where(li >= 0, self._gids[idx[np.clip(li, 0, None)]], -1)
            return True, jnp.asarray(d), jnp.asarray(gi)
        eff = widen_params(params, selectivity)
        # widen_params raises the lsh stop threshold too, but the cascade
        # is not served sharded — re-neutralize the non-per-cell knobs
        eff = dataclasses.replace(eff, min_candidates=1, n_trees=0)
        return False, match_dev, eff

    def _filter_bitmap(self, predicate):
        cached = self._filters.get(predicate)
        if cached is None:
            match = self._view.filter_match_live(predicate)
            bits = np.zeros(self._pad_live.shape[0], bool)
            bits[:self.n_live] = match
            cached = (int(np.count_nonzero(bits)), bits,
                      jax.device_put(bits, self._row_sharding))
            self._filters[predicate] = cached
        return cached

    def _step(self, params):
        # the step consumes the filter through the validity argument and
        # the schedule through per-width calls — neither is part of the
        # compiled program, so neither belongs in the cache key
        key = dataclasses.replace(params, filter=None, probe_schedule=0)
        step = self._steps.get(key)
        if step is None:
            step = make_query_fn(self._forest.cfg, self._forest.n_local,
                                 self.mesh, db_axes=self.db_axes,
                                 tree_axis=self.tree_axis, params=key,
                                 with_validity=True)
            self._steps[key] = step
        return step

    def _search_scheduled(self, q, params, live):
        """Host-driven probe rounds over per-width mesh steps — the
        ``scheduled_query`` loop with the fused local query swapped for
        the sharded step (DESIGN.md §14 one level up)."""
        from repro.core.schedule import _bucket, _improvement, probe_widths
        widths = probe_widths(params.probe_schedule)
        b, k = int(q.shape[0]), params.k
        self._counters["scheduled_queries"] += b

        def run(q_batch, w):
            step = self._step(dataclasses.replace(params, n_probes=w))
            with self.mesh:
                return step(self._forest, q_batch, self._db, live)

        best_d, best_i = run(q, widths[0])
        probes_processed = np.full(b, widths[0], np.int64)
        prev_kth = np.array(best_d[:, -1])      # writable host copy
        active = np.arange(b)
        self._counters["probe_rounds"] += 1

        for w in widths[1:]:
            if active.size == 0:
                break
            if active.size == b:
                q_act, n_act = q, b              # full batch: original order
            else:
                n_act = active.size
                padded = np.concatenate(
                    [active, np.full(_bucket(n_act, b) - n_act, active[0])])
                q_act = q[jnp.asarray(padded)]
            d, i = run(q_act, w)
            d_act, i_act = d[:n_act], i[:n_act]
            if active.size == b:
                best_d, best_i = d_act, i_act
            else:
                sel = jnp.asarray(active)
                best_d = best_d.at[sel].set(d_act)
                best_i = best_i.at[sel].set(i_act)
            probes_processed[active] += w
            self._counters["probe_rounds"] += 1
            kth = np.asarray(d_act[:, -1])
            converged = _improvement(prev_kth[active], kth) < params.tol
            prev_kth[active] = kth
            active = active[~converged]

        self._counters["probes_processed"] += int(probes_processed.sum())
        return best_d, best_i

    def _remap(self, i):
        i = np.asarray(i)
        # shard-local positions were globalized over the padded row order;
        # remap to the index's global ids (pad rows are validity-masked, so
        # positions >= n_live never appear in a top-k)
        ok = (i >= 0) & (i < self._gids.shape[0])
        return jnp.asarray(np.where(
            ok, self._gids[np.clip(i, 0, None) % self._gids.shape[0]], -1))

    # -------------------------------------------------------------- stats
    def stats(self) -> dict:
        d_shards = 1
        for a in self.db_axes:
            d_shards *= self.mesh.shape[a]
        return {
            "sharded": True,
            "strict": self.strict,
            "n_live": self.n_live,
            "n_padded": int(self._pad_live.shape[0]) - self.n_live,
            "d_shards": d_shards,
            "t_shards": self.mesh.shape[self.tree_axis],
            "n_local": self._forest.n_local,
            "trees_per_cell": self._forest.trees_per_cell,
            "compiled_steps": len(self._steps),
            "cached_filters": len(self._filters),
            "counters": dict(self._counters),
        }
