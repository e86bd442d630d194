"""Early-exit wave scheduling over tree groups (beyond-paper, DESIGN.md §5).

The L trees are queried in waves of ``wave`` trees; after each wave the
current top-k distances are compared with the previous wave's — when the
relative improvement of the mean k-th distance drops below ``tol`` the search
stops.  Easy queries (dense neighborhoods) finish after 1-2 waves; hard ones
use the full forest — a per-query accuracy-compute tradeoff the static-L
paper configuration cannot express.  Trees are independent (paper §5), so any
prefix of the forest is itself a valid (smaller) forest.

Each wave dispatches through the fused single-pass pipeline
(``core.pipeline.fused_query``): traverse + dedup + chunk-streamed rerank in
one jit, no (B, M, d) intermediate.  Passing a ``QuantizedDB`` as ``db``
composes the early-exit schedule with the int8 shortlist rerank source.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.forest import Forest, ForestConfig
from repro.core.pipeline import fused_query
from repro.core.quantized import QuantizedDB
from repro.core.search import mask_duplicates, merge_topk_pairs


def _merge_dedup(d1, i1, d2, i2, k):
    """Top-k merge that drops repeated ids (the same neighbor is usually
    found by several waves)."""
    d = jnp.concatenate([d1, d2], axis=1)
    i = jnp.concatenate([i1, i2], axis=1)
    keep = mask_duplicates(i, i >= 0)
    d = jnp.where(keep, d, jnp.inf)
    return merge_topk_pairs(d, jnp.where(keep, i, -1), k)


def adaptive_query(forest: Forest, queries: jax.Array,
                   db: jax.Array | QuantizedDB, k: int, cfg: ForestConfig,
                   wave: int = 10, tol: float = 0.01, metric: str = "l2",
                   mode: str = "auto", chunk: int = 0, expand: int = 4,
                   dedup: bool = True, n_probes: int = 1,
                   valid: jax.Array | None = None,
                   depths: jax.Array | None = None):
    """Returns (dists, ids, trees_used). Host-side loop over tree waves.

    ``dedup`` masks duplicate ids within each wave's candidate set; the
    cross-wave merge always drops repeats regardless (a neighbor found by
    several waves must count once).  ``n_probes`` > 1 widens every wave to
    the multi-probe leaf set (DESIGN.md §9) — early exit then trades off
    against probes as well as trees.  ``valid`` optionally masks dead DB
    rows (segment tombstones) inside every wave's fused rerank.
    ``depths`` (L,), each tree's built depth, bounds each wave's descents
    (``fused_query``).
    """
    n_points = db.fp.shape[0] if isinstance(db, QuantizedDB) else db.shape[0]
    cfg = cfg.resolved(n_points)
    n_trees = forest.n_trees
    best_d = jnp.full((queries.shape[0], k), jnp.inf)
    best_i = jnp.full((queries.shape[0], k), -1, jnp.int32)
    prev_kth = None
    used = 0
    for w0 in range(0, n_trees, wave):
        sub = jax.tree.map(lambda a: a[w0:w0 + wave], forest)
        d, i = fused_query(sub, queries, db, k, cfg, metric=metric, mode=mode,
                           chunk=chunk, expand=expand, dedup=dedup,
                           n_probes=n_probes, valid=valid,
                           depths=None if depths is None
                           else depths[w0:w0 + wave])
        best_d, best_i = _merge_dedup(best_d, best_i, d, i, k)
        used = min(w0 + wave, n_trees)
        kth = float(jnp.mean(jnp.where(jnp.isfinite(best_d[:, -1]),
                                       best_d[:, -1], 0.0)))
        if prev_kth is not None and prev_kth > 0 \
                and (prev_kth - kth) / prev_kth < tol:
            break
        prev_kth = kth
    return best_d, best_i, used
