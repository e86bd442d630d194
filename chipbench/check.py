"""The comparison that decides ``correct``.

Every number compared sits beside its limit in the result.  What is
compared, over the answers the timed path returned:

* ``missing``: requests due in the window that got no answer, or an error,
  by a minute after it closed.  Limit 0.
* ``partition_faults``: the index's stored partition checked against the
  rows (``reference.partition_faults``) before the forest reference reads
  it.  It judges the build.  Limit 0.
* ``dist_err``: over every answered request, the largest gap between a
  served distance and the float64 distance of the served row to that
  request's own query, as a share of the largest of the query's k.
  It catches an answer that reached another request (a padded row
  included) and a rerank computed in a lower precision.
* ``topk_mismatch``: over a sample of the answered requests drawn from the
  seed, the slots whose served id differs from the plain reference's
  forest query (``reference.forest_answers``) where the two ids' float64
  distances are not tied.  It covers the descent (which leaves), the union
  (which rows), the rerank and the merge.  Limit 0.
* ``recall_at_10``: the mean recall@10 of every answered request against
  the exact top-10 (``reference.exact_topk``); the configuration states its
  floor.  It judges the build.

Tie rule (``topk_mismatch``): two rows are tied when their float64
distances differ by at most ``TIE_RTOL`` of the larger plus ``TIE_ATOL``:
the float32 rounding of a sum of a few hundred terms, under which the
program may order them either way.
"""
from __future__ import annotations

import numpy as np

from chipbench import reference

TIE_RTOL = 1e-5
TIE_ATOL = 1e-6
SAMPLE = 256             # requests compared with the forest reference


def served_distances64(metric: str, queries: np.ndarray, rows: np.ndarray,
                       ids: np.ndarray) -> np.ndarray:
    """float64 distance of each served id to its query; inf for id -1."""
    out = np.full(ids.shape, np.inf)
    for j in range(len(ids)):
        ok = (ids[j] >= 0) & (ids[j] < len(rows))
        out[j, ok] = reference.distances64(metric, queries[j],
                                           rows[ids[j][ok]])
    return out


def dist_err(served_d: np.ndarray, exact_d: np.ndarray) -> float:
    """Largest |served - float64| as a share of the row's largest distance."""
    scale = np.max(np.where(np.isfinite(exact_d), exact_d, 0.0), axis=1,
                   keepdims=True)
    both = np.isfinite(served_d) & np.isfinite(exact_d)
    gap = np.where(both, np.abs(served_d - exact_d), 0.0)
    gap = np.where(np.isfinite(served_d) == np.isfinite(exact_d), gap,
                   np.inf)
    return float(np.max(gap / (scale + 1e-30))) if gap.size else 0.0


def topk_mismatch(served_i: np.ndarray, served_e64: np.ndarray,
                  ref_i: np.ndarray, ref_d: np.ndarray) -> int:
    """Slots where the served id is not the reference's, nor tied with it."""
    differ = served_i != ref_i
    tol = TIE_RTOL * np.maximum(np.abs(ref_d), np.abs(served_e64)) + TIE_ATOL
    with np.errstate(invalid="ignore"):
        tied = np.abs(served_e64 - ref_d) <= tol
    return int(np.sum(differ & ~tied))


def recall(served_i: np.ndarray, true_i: np.ndarray) -> float:
    hits = [np.isin(t, s).mean() for s, t in zip(served_i, true_i)]
    return float(np.mean(hits)) if hits else 0.0


def checks(values: dict, limits: dict) -> dict:
    """{name: {"value", "limit", "pass_if"}} in a fixed order."""
    out = {}
    for name in ("missing", "partition_faults", "dist_err", "topk_mismatch",
                 "recall_at_10"):
        limit, pass_if = limits[name]
        out[name] = {"value": values[name], "limit": limit,
                     "pass_if": pass_if}
    return out


def passed(result: dict) -> bool:
    ok = True
    for c in result.values():
        if c["pass_if"] == "<=":
            ok &= c["value"] <= c["limit"]
        else:
            ok &= c["value"] >= c["limit"]
    return bool(ok)
