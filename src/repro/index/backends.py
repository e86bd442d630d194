"""The registered index backends of the unified API (DESIGN.md §5/§8).

Every candidate-based backend funnels into ``core.pipeline``'s fused
single-pass rerank — the (B, M, d) gathered candidate tensor never
materializes on any of them:

  rpf          random-partition forest, fp32 fused rerank (the paper)
  rpf+int8     same forest, int8 coarse shortlist -> fp32 fused rerank
  lsh-cascade  multi-radius LSH candidates -> shared fused rerank stage
  bruteforce   exact scan through the same fused rerank stage (oracle
               backend: what the others are measured against)

``SearchParams.adaptive_wave`` composes with both rpf backends (early-exit
wave scheduling, core/adaptive.py), as does ``probe_schedule`` (per-query
convergence-gated probe widening, core/schedule.py — DESIGN.md §14);
``expand`` tunes the int8 shortlist; ``n_probes``/``n_trees`` walk the
probes-vs-trees frontier (DESIGN.md §9).
Knobs that do not apply to a backend are inert (lsh-cascade and bruteforce
ignore the forest-only knobs), so one tuned ``SearchParams`` can be carried
across backends safely.

Since the segmented-lifecycle redesign each backend is split in two:

  * an **engine** — the immutable per-segment search core.  Engines are
    built once over a frozen row block (``engine_cls(spec, key, rows)``),
    answer ``search(q, params, valid=None)`` with SEGMENT-LOCAL ids, and
    accept an optional ``valid`` (n,) bool tombstone mask that is threaded
    down the fused pipeline's id/mask path (dead rows never reach the
    top-k).  One engine instance exists per sealed segment.
  * a thin ``Index`` subclass — picks the engine, exposes the legacy
    attribute surface (``index.forest`` / ``.qdb`` / ``.cascade`` resolve
    to the primary segment's engine) and the v1-checkpoint read shim.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.adaptive import adaptive_query
from repro.core.forest import (Forest, ForestConfig, build_forest,
                               descent_levels, tree_depths)
from repro.core.lsh import CascadedLSH
from repro.core.pipeline import fused_query, rerank_fused
from repro.core.quantized import QuantizedDB, quantize_db
from repro.core.schedule import scheduled_query
from repro.index.api import Index, register_backend
from repro.index.params import IndexSpec, SearchParams
from repro.index.segments import brute_force_topk

_FOREST_SKELETON = Forest(proj_idx=0, proj_coef=0, thresh=0, child_base=0,
                          perm=0, leaf_offset=0, leaf_count=0, n_nodes=0)


# ---------------------------------------------------------------------------
# engines: the immutable per-segment search cores
# ---------------------------------------------------------------------------


class RPFEngine:
    """The paper's random-partition-forest core, fused fp32 rerank.

    Engine construction IS forest construction: every build — fresh
    index, delta-buffer seal, ``compact()`` rebuild — funnels through
    ``build_forest``'s batched cross-tree builder (DESIGN.md §10), whose
    compat seed mode keeps rebuilds bitwise-reproducible for the
    compaction-vs-fresh and save/load pins.

    Honors the full probes-vs-trees search surface (DESIGN.md §9):
    ``params.n_probes`` widens the per-tree descent to the most-marginal
    leaves, ``params.n_trees`` restricts the query to a prefix of the
    built forest (trees are independent, so any prefix is a valid smaller
    forest — the prefix sub-pytree is cached per width), and
    ``params.adaptive_wave`` composes with both.  ``params.probe_schedule``
    replaces the fixed probe budget with the per-query convergence-gated
    widening of ``core.schedule`` (DESIGN.md §14); the probes each query
    actually consumed land in ``last_mean_probes`` for the tuner's
    measured-cost discount.

    Every descent is bounded by the forest as built: ``depths`` holds each
    tree's depth (``tree_depths``), the search's depth budget is the
    prefix's deepest tree, and the descent kernel stops each tree at its
    own depth.  Past a tree's deepest node a level changes nothing, so
    the answers are those of the configuration's budget (DESIGN.md §11).
    """

    def __init__(self, spec: IndexSpec, key: jax.Array, rows: np.ndarray):
        self.spec = spec
        self.db = np.ascontiguousarray(np.asarray(rows, np.float32))
        self.db_dev = jnp.asarray(self.db)
        self.forest = build_forest(key, self.db_dev, spec.forest,
                                   tree_chunk=spec.tree_chunk)
        self._init_search_state()

    def _init_search_state(self) -> None:
        self.depths = tree_depths(self.forest)
        self.last_trees_used = self.spec.forest.n_trees
        self.last_mean_probes = 0.0
        self._prefix_cache: dict[int, tuple] = {}

    def _rerank_source(self) -> jax.Array | QuantizedDB:
        return self.db_dev

    def _forest_prefix(self, n_trees: int
                       ) -> tuple[Forest, ForestConfig, np.ndarray, jax.Array]:
        """(forest, cfg, depths, depths on the device) restricted to the
        first ``n_trees`` trees (0 = all); ``cfg.max_depth`` is the
        prefix's deepest tree, within the configuration's budget."""
        total = self.spec.forest.n_trees
        n = total if n_trees <= 0 or n_trees >= total else n_trees
        if n not in self._prefix_cache:
            forest = self.forest if n == total else jax.tree.map(
                lambda a: a[:n], self.forest)
            depths = self.depths[:n]
            budget = self.spec.forest.resolved(self.db.shape[0]).max_depth
            cfg = self.spec.forest._replace(
                n_trees=n, max_depth=min(budget, max(1, int(depths.max()))))
            self._prefix_cache[n] = (forest, cfg, depths, jnp.asarray(depths))
        return self._prefix_cache[n]

    def descent_levels(self, params: SearchParams) -> int:
        """Level steps one search's descents are bound to: over the trees
        searched, each tree's loop bound times ``params.n_probes``."""
        forest, cfg, depths, _ = self._forest_prefix(params.n_trees)
        return params.n_probes * descent_levels(
            depths, cfg.max_depth, forest.proj_idx.shape[-1], params.mode)

    def search(self, q: jax.Array, params: SearchParams,
               valid: jax.Array | None = None
               ) -> tuple[jax.Array, jax.Array]:
        src = self._rerank_source()
        forest, cfg, _, depths = self._forest_prefix(params.n_trees)
        if params.probe_schedule > 0:
            # per-query convergence-gated probe widening (DESIGN.md §14);
            # violations() rejects the adaptive_wave combination upstream
            d, i, _, processed = scheduled_query(
                forest, q, src, params.k, cfg, cap=params.probe_schedule,
                tol=params.tol, metric=params.metric, mode=params.mode,
                chunk=params.chunk, expand=params.expand,
                dedup=params.dedup, valid=valid, depths=depths)
            self.last_trees_used = cfg.n_trees
            self.last_mean_probes = float(processed.mean())
            return d, i
        if params.adaptive_wave > 0:
            d, i, used = adaptive_query(
                forest, q, src, params.k, cfg,
                wave=params.adaptive_wave, tol=params.tol,
                metric=params.metric, mode=params.mode, chunk=params.chunk,
                expand=params.expand, dedup=params.dedup,
                n_probes=params.n_probes, valid=valid, depths=depths)
            self.last_trees_used = used
            self.last_mean_probes = float(params.n_probes)
            return d, i
        self.last_trees_used = cfg.n_trees
        self.last_mean_probes = float(params.n_probes)
        return fused_query(forest, q, src, params.k, cfg,
                           metric=params.metric, dedup=params.dedup,
                           mode=params.mode, chunk=params.chunk,
                           expand=params.expand, n_probes=params.n_probes,
                           valid=valid, depths=depths)

    # ------------------------------------------------------------- save/load
    def state_tree(self) -> dict:
        # self.db stays host-side: Checkpointer snapshots leaves via
        # device_get, which passes numpy arrays through copy-free
        return {"db": self.db, "forest": self.forest}

    @classmethod
    def state_skeleton(cls, spec: IndexSpec) -> dict:
        return {"db": 0, "forest": _FOREST_SKELETON}

    @classmethod
    def from_state(cls, spec: IndexSpec, state: dict) -> "RPFEngine":
        obj = cls.__new__(cls)
        obj.spec = spec
        obj.db = np.asarray(state["db"], np.float32)
        obj.db_dev = jnp.asarray(obj.db)
        obj.forest = state["forest"]
        obj._init_search_state()
        return obj


class RPFInt8Engine(RPFEngine):
    """Same forest; int8 coarse shortlist -> exact fp32 fused rerank.

    ``SearchParams.expand`` sets the shortlist width k' = expand*k; both
    stages honor ``params.metric`` — the coarse stage scores the
    DEQUANTIZED rows under it (DESIGN.md §13), so the shortlist ranks
    like the exact fp32 stage (the per-row int8 calibration stays
    L2-shaped, §11).  The tombstone mask is applied at the coarse stage,
    so dead rows never occupy shortlist slots.
    """

    def __init__(self, spec: IndexSpec, key: jax.Array, rows: np.ndarray):
        super().__init__(spec, key, rows)
        self.qdb = quantize_db(self.db_dev)

    def _rerank_source(self) -> QuantizedDB:
        return self.qdb

    @classmethod
    def from_state(cls, spec: IndexSpec, state: dict) -> "RPFInt8Engine":
        obj = super().from_state(spec, state)
        obj.qdb = quantize_db(obj.db_dev)
        return obj


class LSHEngine:
    """The paper's LSH-cascade baseline behind the same search surface.

    Host-side bucket probe (vectorized: one hash per batch per level), then
    the SAME fused rerank stage as the forest backends — fair accuracy/cost
    comparisons come free.  Hash projections depend only on (seed, d), so
    every segment of the same index hashes identically to a fresh build.
    """

    def __init__(self, spec: IndexSpec, key: jax.Array, rows: np.ndarray):
        self.spec = spec
        self.db = np.ascontiguousarray(np.asarray(rows, np.float32))
        self.db_dev = jnp.asarray(self.db)
        self.cascade = CascadedLSH(
            self.db, list(spec.lsh_radii),
            n_tables=spec.lsh_tables, n_bits=spec.lsh_bits,
            width_scale=spec.lsh_width_scale, seed=spec.seed)
        self.last_mean_candidates = 0.0

    def search(self, q: jax.Array, params: SearchParams,
               valid: jax.Array | None = None
               ) -> tuple[jax.Array, jax.Array]:
        ids, mask = self.cascade.retrieve_batch(
            np.asarray(q), min_candidates=params.min_candidates)
        self.last_mean_candidates = float(mask.sum(1).mean())
        # candidate sets are already unique per query -> dedup not needed
        return rerank_fused(q, jnp.asarray(ids), jnp.asarray(mask),
                            self.db_dev, params.k, metric=params.metric,
                            mode=params.mode, dedup=False, chunk=params.chunk,
                            valid=valid)

    def state_tree(self) -> dict:
        return {"db": self.db}

    @classmethod
    def state_skeleton(cls, spec: IndexSpec) -> dict:
        return {"db": 0}

    @classmethod
    def from_state(cls, spec: IndexSpec, state: dict) -> "LSHEngine":
        # tables are a pure function of (db, spec): rebuild deterministically
        return cls(spec, None, np.asarray(state["db"], np.float32))


class BruteForceEngine:
    """Exact scan routed through the shared fused rerank stage.

    One code path with and without tombstones (the mask only flips score
    slots to +inf), so a mutated bruteforce index answers bitwise
    identically to a fresh build over the live rows — the oracle property
    the mutation tests lean on.
    """

    def __init__(self, spec: IndexSpec, key: jax.Array, rows: np.ndarray):
        self.spec = spec
        self.db = np.ascontiguousarray(np.asarray(rows, np.float32))
        self.db_dev = jnp.asarray(self.db)

    def search(self, q: jax.Array, params: SearchParams,
               valid: jax.Array | None = None
               ) -> tuple[jax.Array, jax.Array]:
        return brute_force_topk(q, self.db_dev, params, valid=valid)

    def state_tree(self) -> dict:
        return {"db": self.db}

    @classmethod
    def state_skeleton(cls, spec: IndexSpec) -> dict:
        return {"db": 0}

    @classmethod
    def from_state(cls, spec: IndexSpec, state: dict) -> "BruteForceEngine":
        return cls(spec, None, np.asarray(state["db"], np.float32))


# ---------------------------------------------------------------------------
# registered Index subclasses: engine choice + legacy attribute surface
# ---------------------------------------------------------------------------


@register_backend("rpf")
class RPFIndex(Index):
    """The paper's random-partition-forest index, fused fp32 rerank."""

    engine_cls = RPFEngine

    @property
    def forest(self) -> Forest:
        """Primary segment's forest (compat with the pre-segment API)."""
        return self._primary_engine.forest

    @property
    def db_dev(self) -> jax.Array:
        return self._primary_engine.db_dev

    @property
    def last_trees_used(self) -> int:
        return self._primary_engine.last_trees_used

    @property
    def last_mean_probes(self) -> float:
        """Mean probes per query the primary engine processed on its last
        search (the scheduled path's honest cumulative charge; equals
        ``params.n_probes`` on the fixed-budget paths)."""
        return self._primary_engine.last_mean_probes

    def _extra_stats(self) -> dict:
        depths = [s.engine.depths for s in self._segments]
        return {"n_trees": self.spec.forest.n_trees,
                "depth_max": [int(d.max()) for d in depths],
                "depth_mean": [float(d.mean()) for d in depths]}

    @classmethod
    def _v1_skeleton(cls, spec: IndexSpec) -> dict:
        return {"db": 0, "key_data": 0, "forest": _FOREST_SKELETON}


@register_backend("rpf+int8")
class RPFInt8Index(RPFIndex):
    """Same forest; int8 coarse shortlist -> exact fp32 fused rerank."""

    engine_cls = RPFInt8Engine

    @property
    def qdb(self) -> QuantizedDB:
        return self._primary_engine.qdb


@register_backend("lsh-cascade")
class LSHCascadeIndex(Index):
    """The paper's LSH-cascade baseline behind the same search surface."""

    engine_cls = LSHEngine

    @property
    def cascade(self) -> CascadedLSH:
        return self._primary_engine.cascade

    @property
    def last_mean_candidates(self) -> float:
        return self._primary_engine.last_mean_candidates

    def _extra_stats(self) -> dict:
        return {"n_levels": len(self.spec.lsh_radii),
                "n_tables": self.spec.lsh_tables}

    @classmethod
    def _v1_skeleton(cls, spec: IndexSpec) -> dict:
        return {"db": 0, "key_data": 0}


@register_backend("bruteforce")
class BruteForceIndex(Index):
    """Exact scan via the shared fused rerank stage (the recall oracle)."""

    engine_cls = BruteForceEngine

    @property
    def db_dev(self) -> jax.Array:
        return self._primary_engine.db_dev

    @classmethod
    def _v1_skeleton(cls, spec: IndexSpec) -> dict:
        return {"db": 0, "key_data": 0}
