"""Per-query adaptive probe scheduling (beyond-paper, DESIGN.md §14).

The paper fixes one probe budget for the whole batch, so every easy query
pays the p99 price of the hardest one.  Dynamic Continuous Indexing
(Li & Malik 2015, PAPERS.md) makes the budget per-query: retrieve more
candidates only while a query's top-k is still moving.  This module applies
that insight to the multi-probe forest descent (DESIGN.md §9): every query
starts at ``n_probes = 1`` and is re-descended at a doubling probe width —
1, 2, 4, … up to the cap — while its k-th distance keeps improving by more
than ``tol`` per round.  Converged queries drop out of later rounds.

Static shapes throughout (the ragged-to-padded trick): the still-active
queries are gathered into a padded batch whose height is rounded up to the
next power of two — the same staged active-set shrink
``_build_forest_batched`` uses — so each (bucket height, probe width) pair
compiles once and a shrinking batch never retraces.  Pad rows repeat a real
active query (batch-coupled kernels must not see synthetic points) and
their results are discarded.

Each round REPLACES a query's running result rather than merging into it:
probe sets are monotone prefixes (``traverse_multiprobe``'s top-k of
smallest margins at width p is the prefix of the set at width p+1), so the
round at width w sees a superset of every earlier round's candidates and
its exact-rerank result can only improve.  Replacement also makes the
never-converge case exact by construction: with ``tol = 0.0`` no query
ever converges (the improvement is clamped non-negative, and 0 < 0 is
false), so the final round runs the full batch in original order at the
cap — literally the same ``fused_query`` call as the fixed-``n_probes``
path, hence bitwise-identical on every rerank source, including the int8
shortlist whose coarse stage is not candidate-subset-decomposable.

Each round dispatches through the fused single-pass pipeline
(``core.pipeline.fused_query``): traverse + dedup + chunk-streamed rerank
in one jit, no (B, M, d) intermediate.  Passing a ``QuantizedDB`` as
``db`` composes the schedule with the int8 shortlist rerank source, and
``valid`` threads segment tombstones / filter bitmaps through unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.forest import Forest, ForestConfig
from repro.core.pipeline import fused_query
from repro.core.quantized import QuantizedDB

__all__ = ["probe_widths", "scheduled_query"]


def probe_widths(cap: int) -> list[int]:
    """The round schedule: doubling widths 1, 2, 4, … ending exactly at
    ``cap`` (e.g. cap=6 -> [1, 2, 4, 6]).  Doubling keeps the number of
    rounds — and with it the number of compiled (bucket, width) variants —
    logarithmic in the cap."""
    if cap < 1:
        raise ValueError(f"probe cap must be >= 1, got {cap}")
    widths, w = [], 1
    while w < cap:
        widths.append(w)
        w *= 2
    widths.append(cap)
    return widths


def _bucket(n: int, b: int) -> int:
    """Padded height for ``n`` active queries: next power of two, capped at
    the full batch.  Bounds distinct compiled batch heights to log2(B)."""
    p = 1
    while p < n:
        p *= 2
    return min(p, b)


def _improvement(prev_kth: np.ndarray, kth: np.ndarray) -> np.ndarray:
    """Relative k-th-distance improvement per query, the same signal
    ``core.adaptive`` uses across tree waves, made per-query:

      * an infinite previous k-th (top-k not yet filled) never converges;
      * the denominator is |prev| so signed metrics (ip/cosine) behave;
      * clamped at 0 so a round that cannot improve (or, on the int8
        shortlist, slightly regresses) reads as "no improvement" — which
        also makes ``tol = 0.0`` disable early stop exactly (0 < 0 is
        false), the bitwise-parity escape hatch the tests pin.
    """
    with np.errstate(invalid="ignore", divide="ignore"):
        rel = (prev_kth - kth) / np.abs(prev_kth)
    rel = np.where(np.isfinite(prev_kth),
                   np.where(prev_kth == 0.0, 0.0, rel), np.inf)
    return np.maximum(rel, 0.0)


def scheduled_query(forest: Forest, queries: jax.Array,
                    db: jax.Array | QuantizedDB, k: int, cfg: ForestConfig,
                    cap: int, tol: float = 0.01, metric: str = "l2",
                    mode: str = "auto", chunk: int = 0, expand: int = 4,
                    dedup: bool = True, valid: jax.Array | None = None,
                    depths: jax.Array | None = None
                    ) -> tuple[jax.Array, jax.Array, np.ndarray, np.ndarray]:
    """Convergence-gated per-query probe widening up to ``cap`` probes.

    Returns ``(dists (B, k), ids (B, k), probes_final (B,),
    probes_processed (B,))``: ``probes_final`` is the probe width each
    query's answer came from; ``probes_processed`` the total probes it was
    descended at across rounds (1 + 2 + … — the honest compute charge the
    tuner's cost model and the benchmark gate use).

    Host-side loop over rounds, like ``core.adaptive``'s wave loop; all
    array work stays on device.  ``tol = 0.0`` never converges any query,
    making the result bitwise-identical to ``fused_query`` at
    ``n_probes = cap``.  ``depths`` (L,), each tree's built depth, bounds
    every round's descents (``fused_query``).
    """
    n_points = db.fp.shape[0] if isinstance(db, QuantizedDB) else db.shape[0]
    cfg = cfg.resolved(n_points)
    queries = jnp.asarray(queries)
    b = queries.shape[0]
    widths = probe_widths(cap)

    best_d, best_i = fused_query(forest, queries, db, k, cfg, metric=metric,
                                 dedup=dedup, mode=mode, chunk=chunk,
                                 expand=expand, n_probes=widths[0],
                                 valid=valid, depths=depths)
    probes_final = np.full(b, widths[0], np.int32)
    probes_processed = np.full(b, widths[0], np.int32)
    prev_kth = np.array(best_d[:, -1])      # writable host copy
    active = np.arange(b)

    for w in widths[1:]:
        if active.size == 0:
            break
        if active.size == b:
            q_act, n_act = queries, b        # full batch: original order
        else:
            n_act = active.size
            padded = np.concatenate(
                [active, np.full(_bucket(n_act, b) - n_act, active[0])])
            q_act = queries[jnp.asarray(padded)]
        d, i = fused_query(forest, q_act, db, k, cfg, metric=metric,
                           dedup=dedup, mode=mode, chunk=chunk,
                           expand=expand, n_probes=w, valid=valid,
                           depths=depths)
        d_act, i_act = d[:n_act], i[:n_act]
        if active.size == b:
            best_d, best_i = d_act, i_act
        else:
            sel = jnp.asarray(active)
            best_d = best_d.at[sel].set(d_act)
            best_i = best_i.at[sel].set(i_act)
        probes_final[active] = w
        probes_processed[active] += w
        kth = np.asarray(d_act[:, -1])
        converged = _improvement(prev_kth[active], kth) < tol
        prev_kth[active] = kth
        active = active[~converged]

    return best_d, best_i, probes_final, probes_processed
