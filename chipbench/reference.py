"""The plain reference: the paper's forest query and exact k-NN, written out.

Nothing here imports the program.  Two references, and the check of the
data that the second reads:

* ``exact_topk``: the exact k nearest rows of each query over the whole
  corpus, in blocks on the device with matmuls at HIGHEST precision.  It
  defines recall@10 and judges the build: a forest that separates
  neighbours loses recall against it.
* ``forest_answers``: the random-partition forest query of Zhong 2015 §3
  with the multi-probe rule of the repository's DESIGN.md §9, in NumPy:
  descend every tree (K = 1: go right where ``q[feat] >= thresh``), then
  for each extra probe flip the not yet flipped decision of smallest margin
  ``|q[feat] - thresh|`` on the primary path (ties to the shallower depth)
  and descend greedily from there; take the union of the probed leaves'
  point sets and rank it by distances computed in float64.  It reads the
  index's stored partition (split features, thresholds, children and leaf
  point lists) as data, the way a database's reference reads back the rows
  that were written, and judges the descent, the union, the rerank and the
  merge of the timed path: which leaves a query reaches, which rows it
  scores, the distance of each and the k it keeps.
* ``partition_faults``: before that partition is read as data, it is
  checked against the rows: each tree's point list is a permutation of the
  rows, no leaf holds more points than the configuration states (where it
  states a number: rows tied on every coordinate a split could draw cannot
  be split, and sparse histograms have such ties), and every row, sent
  down every tree by the same descent, lands in the leaf whose slice of
  the point list holds it.  That tests every split coordinate,
  threshold, child link and leaf list, so a build fault is not copied into
  the forest reference.

Distances follow the repository's metric definitions: ``l2`` is the squared
Euclidean distance, ``chi2`` is sum (x - y)^2 / (x + y + 1e-12).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHI2_EPS = 1e-12


def distances64(metric: str, q: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """float64 distances of one query (d,) to rows (m, d)."""
    q = q.astype(np.float64)
    x = rows.astype(np.float64)
    if metric == "l2":
        diff = x - q
        return np.einsum("md,md->m", diff, diff)
    if metric == "chi2":
        return np.sum((x - q) ** 2 / (x + q + CHI2_EPS), axis=1)
    raise ValueError(f"unknown metric {metric!r}")


# --------------------------------------------------------------- the forest
def descend(feat, thresh, child, queries, max_depth: int, n_probes: int):
    """Leaves (L, B, n_probes) reached by each query (-1: no such probe),
    and the nodes on each probe's path from the root, its leaf included
    (L, B, n_probes; 0 where there is no probe).

    ``feat``/``thresh``/``child`` are (L, nodes): split coordinate,
    threshold and left child (-1 at a leaf; the right child is left + 1).
    """
    n_trees = feat.shape[0]
    b = queries.shape[0]
    feat = np.clip(feat, 0, queries.shape[1] - 1)   # leaves hold no split
    tree = np.arange(n_trees)[:, None]
    rows = np.arange(b)[None, :]

    def walk(flip_depth):
        node = np.zeros((n_trees, b), np.int64)
        margins = np.full((n_trees, b, max_depth), np.inf, np.float32)
        n_internal = np.zeros((n_trees, b), np.int64)
        for t in range(max_depth):
            f = feat[tree, node]
            th = thresh[tree, node]
            cb = child[tree, node]
            xv = queries[rows, f]                      # (L, B) float32
            right = xv >= th
            right = np.where(flip_depth == t, ~right, right)
            internal = cb >= 0
            margins[:, :, t] = np.where(internal, np.abs(xv - th), np.inf)
            n_internal += internal
            node = np.where(internal, cb + right, node)
        return node, margins, n_internal + 1

    leaf, margins, path = walk(np.full((n_trees, b), -1))
    leaves, paths = [leaf], [path]
    for _ in range(1, n_probes):
        best = margins.min(axis=2)
        first = np.argmin(margins, axis=2)             # shallowest on ties
        np.put_along_axis(margins, first[..., None], np.inf, axis=2)
        alt, _, alt_path = walk(first)
        found = np.isfinite(best)
        leaves.append(np.where(found, alt, -1))
        paths.append(np.where(found, alt_path, 0))
    return np.stack(leaves, axis=2), np.stack(paths, axis=2)


def leaf_of(feat, thresh, child, x, max_depth: int,
            tree_block: int = 8) -> np.ndarray:
    """Leaf (L, N) that each row of ``x`` (N, d) reaches in each tree, by
    the descent of ``descend`` with one probe; a row still at an internal
    node after ``max_depth`` levels stays there."""
    n_trees, n = feat.shape[0], x.shape[0]
    xt = np.ascontiguousarray(x.T)          # a split reads one coordinate
    cols = np.arange(n)
    out = np.empty((n_trees, n), np.int64)
    for lo in range(0, n_trees, tree_block):
        t = np.arange(lo, min(lo + tree_block, n_trees))[:, None]
        node = np.zeros((t.shape[0], n), np.int64)
        for _ in range(max_depth):
            cb = child[t, node]
            internal = cb >= 0
            if not internal.any():
                break
            f = np.clip(feat[t, node], 0, x.shape[1] - 1)
            right = xt[f, cols] >= thresh[t, node]
            node = np.where(internal, cb + right, node)
        out[lo:lo + t.shape[0]] = node
    return out


def partition_faults(forest: dict, rows: np.ndarray, capacity: int | None,
                     max_depth: int) -> int:
    """Places where the stored partition disagrees with the rows (0 for a
    sound build): point-list slots that break the permutation, leaves over
    ``capacity`` (None: not counted), trees whose leaf counts do not sum to
    N, and (tree, row) pairs whose descent ends in a leaf that does not
    list the row."""
    perm = np.asarray(forest["perm"], np.int64)
    n_trees, n = perm.shape
    faults = int(np.sum(np.sort(perm, axis=1) != np.arange(n)))
    count = forest["leaf_count"]
    if capacity is not None:
        faults += int(np.sum(count > capacity))
    faults += int(np.sum(np.abs(count.sum(axis=1, dtype=np.int64) - n)))
    t = np.arange(n_trees)[:, None]
    pos = np.full((n_trees, n), -1, np.int64)     # slot of each row
    ok = (perm >= 0) & (perm < n)
    slot = np.broadcast_to(np.arange(n), perm.shape)
    pos[np.broadcast_to(t, perm.shape)[ok], perm[ok]] = slot[ok]
    leaf = leaf_of(forest["feat"], forest["thresh"], forest["child"], rows,
                   max_depth)
    start = forest["leaf_offset"][t, leaf]
    inside = (pos >= start) & (pos < start + count[t, leaf])
    return faults + int(np.sum(~inside))


def candidates(leaves, perm, leaf_offset, leaf_count, leaf_pad: int):
    """Point ids of the probed leaves, (B, L * P * leaf_pad), -1 = none.

    A leaf contributes its first ``leaf_pad`` stored points: the
    configuration's read limit per leaf (a leaf that the build could not
    split below it, on tied coordinates, holds more).
    """
    n_trees, b, p = leaves.shape
    t = np.arange(n_trees)[:, None, None]
    safe = np.maximum(leaves, 0)
    count = np.where(leaves >= 0,
                     np.minimum(leaf_count[t, safe], leaf_pad), 0)
    slot = np.arange(leaf_pad)
    pos = np.minimum(leaf_offset[t, safe][..., None] + slot,
                     perm.shape[1] - 1)
    ids = np.where(slot < count[..., None], perm[t[..., None], pos], -1)
    return ids.transpose(1, 0, 2, 3).reshape(b, -1)


def forest_answers(forest: dict, rows: np.ndarray, queries: np.ndarray,
                   metric: str, k: int, max_depth: int, n_probes: int,
                   leaf_pad: int, dist_fn=None):
    """Reference (ids (B, k), dists (B, k)) of the forest query.

    ``dist_fn(q, rows) -> distances`` replaces the float64 distances; the
    control passes a lower-precision one.  Unfilled slots: id -1, inf.
    """
    dist_fn = dist_fn or functools.partial(distances64, metric)
    leaves, _ = descend(forest["feat"], forest["thresh"], forest["child"],
                        queries, max_depth, n_probes)
    cand = candidates(leaves, forest["perm"], forest["leaf_offset"],
                      forest["leaf_count"], leaf_pad)
    ids = np.full((len(queries), k), -1, np.int64)
    dists = np.full((len(queries), k), np.inf)
    for j, q in enumerate(queries):
        union = np.unique(cand[j][cand[j] >= 0])
        d = np.asarray(dist_fn(q, rows[union]), np.float64)
        order = np.argsort(d, kind="stable")[:k]
        ids[j, :order.size] = union[order]
        dists[j, :order.size] = d[order]
    return ids, dists


# ------------------------------------------------------------ exact top-k
@functools.partial(jax.jit, static_argnames=("k", "metric", "block"))
def _exact_block(q, rows, k: int, metric: str, block: int):
    """Exact top-k of a query block over rows, scanned in row blocks."""
    n = rows.shape[0]
    pad = -n % block
    rp = jnp.pad(rows, ((0, pad), (0, 0)))
    n_blocks = (n + pad) // block
    hi = jax.lax.Precision.HIGHEST
    qn = jnp.sum(q * q, axis=1, keepdims=True)

    def body(carry, j):
        best_d, best_i = carry
        x = jax.lax.dynamic_slice_in_dim(rp, j * block, block, axis=0)
        if metric == "l2":
            d = (qn - 2.0 * jnp.dot(q, x.T, precision=hi)
                 + jnp.sum(x * x, axis=1)[None, :])
        else:
            d = jnp.sum((q[:, None, :] - x[None]) ** 2
                        / (q[:, None, :] + x[None] + CHI2_EPS), axis=-1)
        ids = j * block + jnp.arange(block)
        d = jnp.where(ids[None, :] < n, d, jnp.inf)
        cat_d = jnp.concatenate([best_d, d], axis=1)
        cat_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, d.shape)], axis=1)
        neg, pos = jax.lax.top_k(-cat_d, k)
        return (-neg, jnp.take_along_axis(cat_i, pos, axis=1)), None

    init = (jnp.full((q.shape[0], k), jnp.inf),
            jnp.full((q.shape[0], k), -1, jnp.int32))
    (_, best_i), _ = jax.lax.scan(body, init, jnp.arange(n_blocks))
    return best_i


def exact_topk(queries: np.ndarray, rows, metric: str, k: int) -> np.ndarray:
    """Exact top-k ids (B, k) of every query, in blocks on the device."""
    rows = jnp.asarray(rows)
    if metric == "l2":
        qb, block = 512, 16384
    else:       # chi2 holds a (qb, block, d) difference tensor
        qb, block = 32, 1024
    out = []
    for s in range(0, len(queries), qb):
        q = np.asarray(queries[s:s + qb], np.float32)
        n_real = len(q)
        if n_real < qb:
            q = np.concatenate([q, np.zeros((qb - n_real, q.shape[1]),
                                            np.float32)])
        out.append(np.asarray(_exact_block(jnp.asarray(q), rows, k, metric,
                                           block))[:n_real])
    return np.concatenate(out) if out else np.zeros((0, k), np.int64)
