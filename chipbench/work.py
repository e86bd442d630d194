"""The work a query batch needs, counted from the index, the queries and the
operating point alone: never from a kernel's tiles, padding or slots, so a
later implementation is read against the same work.

* Descent: one node record per (query, tree, probe, node on the probe's
  path from the root to its leaf).  A record is the node's split
  coordinate, threshold and left child: 12 bytes.  Each query is read
  once (d float32).
* Rerank: each query's distinct candidate rows (the union of its probed
  leaves under the configuration's per-leaf read limit) times the row
  bytes (d float32), plus the query.
"""
from __future__ import annotations

import numpy as np

from chipbench import reference

RECORD_BYTES = 12          # int32 coordinate + f32 threshold + int32 child
VALUE_BYTES = 4            # float32 rows and queries


def batch_bytes(forest: dict, queries: np.ndarray, max_depth: int,
                n_probes: int, leaf_pad: int) -> tuple[float, float]:
    """-> (descent bytes, rerank bytes) of one batch of queries (B, d)."""
    b, d = queries.shape
    leaves, path = reference.descend(forest["feat"], forest["thresh"],
                                     forest["child"], queries, max_depth,
                                     n_probes)
    cand = reference.candidates(leaves, forest["perm"],
                                forest["leaf_offset"], forest["leaf_count"],
                                leaf_pad)
    descent = RECORD_BYTES * float(path.sum()) + VALUE_BYTES * b * d
    distinct = distinct_per_row(cand)
    rerank = VALUE_BYTES * d * float(distinct.sum() + b)
    return descent, rerank


def distinct_per_row(ids: np.ndarray) -> np.ndarray:
    """Number of distinct ids >= 0 in each row of (B, M)."""
    s = np.sort(ids, axis=1)
    new = np.ones_like(s, bool)
    new[:, 1:] = s[:, 1:] != s[:, :-1]
    return np.sum(new & (s >= 0), axis=1)
