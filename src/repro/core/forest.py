"""Random Binary Partition Forest (the paper's core contribution), TPU-native.

Paper semantics (Zhong 2015, §3):
  * L independent random binary partition trees.
  * Internal node test (Eq. 1):  t(x) = sum_k x[d_k] * xi_k - psi >= 0, with the
    random index set {d_k} (size K, default K=1), random coefficients xi in [0,1],
    and psi a *data-adaptive* threshold: a random percentile in [r, 1-r] of the
    projected values of the points at that node.
  * A node is split when it holds more than C (capacity) points, so leaves hold
    between ~r*C and C points and the partition adapts to data density.
  * Query: descend each tree (one coordinate gather + one compare per level, no
    backtracking), union the L leaf point-sets, rerank exactly.  Beyond-paper:
    ``traverse_multiprobe`` widens the descent to the n_probes most marginal
    leaves per tree (DESIGN.md §9); the paper's query is its n_probes=1 case.

TPU-native re-expression (see DESIGN.md §2 and §10):
  * level-synchronous build — all overflowing nodes of a depth split together,
    per-node percentile thresholds computed with one segmented sort per level;
  * batched cross-tree construction — all L trees advance one level together
    as a single (L, N) problem: one flat segmented sort over composite
    (tree, node, projection) keys per level, thresholds read off the same
    sorted pass, and an early exit once no leaf anywhere is overfull;
  * flat SoA tree storage (compact node ids, child_base pointers);
  * CSR leaf storage (perm + offset/count) for O(1) candidate slicing;
  * batched query traversal: a fori_loop of gather+compare over a query batch.

Everything is jit-able with static shapes.  ``build_forest(impl="legacy")``
keeps the original per-tree (vmapped) builder as the parity oracle; under the
default ``seed_mode="compat"`` the batched builder reproduces its Forest
arrays bitwise (tests/test_forest_batched.py pins this).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distances as dist_mod


class ForestConfig(NamedTuple):
    """Hyper-parameters of the random partition forest (paper §3.4)."""

    n_trees: int = 80          # L
    capacity: int = 12         # C: max points per leaf
    split_ratio: float = 0.3   # r in (0, 0.5]
    n_proj: int = 1            # K: coordinates per random test (paper default 1)
    max_depth: int = 0         # 0 -> auto bound from N, C, r
    max_nodes: int = 0         # 0 -> auto bound
    leaf_pad: int = 0          # padded candidate slots per (query, tree); 0 -> C

    def resolved(self, n_points: int) -> "ForestConfig":
        r = float(self.split_ratio)
        rc = max(r * self.capacity, 1.0)
        depth = self.max_depth
        if depth <= 0:
            # depth budget: Eq. 1 guarantees each split keeps <= (1-r) of the
            # points on DISTINCT values, but tie-escape splits on heavily
            # tied data (sparse histograms, raw pixels) can be as uneven as
            # ~85/15 — budget for the worse of the two (traversal is one
            # compare per level, so a generous budget costs little)
            shrink = max(1.0 - r, 0.85)
            depth = int(math.ceil(math.log(max(n_points / rc, 2.0))
                                  / math.log(1.0 / shrink))) + 6
        nodes = self.max_nodes
        if nodes <= 0:
            nodes = int(4.0 * n_points / rc) + 64
        pad = self.leaf_pad if self.leaf_pad > 0 else self.capacity
        return self._replace(max_depth=depth, max_nodes=nodes, leaf_pad=pad)


class Forest(NamedTuple):
    """Flat SoA forest. All arrays carry a leading (L,) tree axis.

    A node is internal iff child_base >= 0; its children are child_base and
    child_base + 1.  Leaf points of node ``n`` of tree ``l`` are
    ``perm[l, leaf_offset[l, n] : leaf_offset[l, n] + leaf_count[l, n]]``.
    """

    proj_idx: jax.Array    # (L, max_nodes, K) int32  random coordinate indices
    proj_coef: jax.Array   # (L, max_nodes, K) f32    random coefficients xi
    thresh: jax.Array      # (L, max_nodes)    f32    psi
    child_base: jax.Array  # (L, max_nodes)    int32  left-child id, -1 for leaf
    perm: jax.Array        # (L, N)            int32  point ids sorted by leaf
    leaf_offset: jax.Array  # (L, max_nodes)   int32
    leaf_count: jax.Array   # (L, max_nodes)   int32
    n_nodes: jax.Array      # (L,)             int32  allocated node count

    @property
    def n_trees(self) -> int:
        return self.thresh.shape[0]

    @property
    def max_nodes(self) -> int:
        return self.thresh.shape[1]


# ---------------------------------------------------------------------------
# build (level-synchronous, single tree; vmapped for the forest)
# ---------------------------------------------------------------------------


def _project(x: jax.Array, idx: jax.Array, coef: jax.Array) -> jax.Array:
    """y_i = sum_k x[i, idx[i, k]] * coef[i, k]  with per-row index sets."""
    gathered = jnp.take_along_axis(x, idx, axis=1)  # (N, K)
    return jnp.sum(gathered * coef, axis=1)


def _build_one_tree(key: jax.Array, x: jax.Array, cfg: ForestConfig) -> Forest:
    """Build a single tree over points ``x`` (N, d). Returns Forest w/o L axis."""
    n, d = x.shape
    m = cfg.max_nodes
    k_proj = cfg.n_proj
    r = cfg.split_ratio

    def level(carry, level_key):
        assign, proj_idx, proj_coef, thresh, child_base, n_nodes = carry
        k_feat, k_coef, k_quant = jax.random.split(level_key, 3)

        counts = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), assign,
                                     num_segments=m)
        is_leaf = child_base < 0
        node_ids = jnp.arange(m, dtype=jnp.int32)
        alive = node_ids < n_nodes
        overfull = is_leaf & alive & (counts > cfg.capacity)

        # --- candidate random tests for every slot (Eq. 1) ----------------
        cand_idx = jax.random.randint(k_feat, (m, k_proj), 0, d,
                                      dtype=jnp.int32)
        cand_coef = jax.random.uniform(k_coef, (m, k_proj), jnp.float32)
        if k_proj == 1:
            cand_coef = jnp.ones_like(cand_coef)  # scale-invariant for K=1
        test_idx = jnp.where(overfull[:, None], cand_idx, proj_idx)
        test_coef = jnp.where(overfull[:, None], cand_coef, proj_coef)

        # --- per-point projections under the candidate tests --------------
        y = _project(x, test_idx[assign], test_coef[assign])  # (N,)

        # --- per-node value range + random percentile threshold -----------
        order = jnp.lexsort((y, assign))
        assign_sorted = assign[order]
        y_sorted = y[order]
        start = jnp.searchsorted(assign_sorted, node_ids, side="left")
        last = jnp.clip(start + counts - 1, 0, n - 1)
        lo = y_sorted[jnp.clip(start, 0, n - 1)]
        hi = y_sorted[last]
        # ties guard: a constant projection can't split — the node stays open
        # and redraws a fresh random coordinate at the next level (the
        # paper's incremental builder has the same retry implicitly)
        degenerate = ~(hi > lo)
        splitting = overfull & ~degenerate

        # --- allocate children compactly -----------------------------------
        n_split = jnp.sum(splitting.astype(jnp.int32))
        rank = jnp.cumsum(splitting.astype(jnp.int32)) - 1
        new_child_base = jnp.where(splitting, n_nodes + 2 * rank, child_base)
        budget_overflow = (n_nodes + 2 * n_split) > m
        new_child_base = jnp.where(budget_overflow, child_base,
                                   new_child_base)
        splitting = jnp.where(budget_overflow, jnp.zeros_like(splitting),
                              splitting)
        new_n_nodes = jnp.where(budget_overflow, n_nodes,
                                n_nodes + 2 * n_split)

        # paper Eq. 1: psi is a uniform random VALUE in the interval between
        # the r and (1-r) percentile points of the sorted projections,
        # psi ~ U[y_{r n}, y_{(1-r) n}]
        u = jax.random.uniform(k_quant, (m,))
        last_idx = jnp.maximum(start, start + counts - 1)
        pos_a = jnp.clip(start + jnp.floor(
            r * counts.astype(jnp.float32)).astype(jnp.int32), start,
            last_idx)
        pos_b = jnp.clip(start + jnp.floor(
            (1.0 - r) * counts.astype(jnp.float32)).astype(jnp.int32), start,
            last_idx)
        a = y_sorted[jnp.clip(pos_a, 0, n - 1)]
        b_ = y_sorted[jnp.clip(pos_b, 0, n - 1)]
        cand_thresh = a + u * (b_ - a)
        # tie escape: on heavily-tied data (sparse histograms, raw MNIST
        # pixels) the percentile interval collapses onto the min value and
        # the left child (y < psi) would be empty; fall back to a uniform
        # value split over the node's full (lo, hi] range — progress is
        # guaranteed since lo < hi for splitting nodes
        cand_thresh = jnp.where(
            cand_thresh > lo, cand_thresh,
            lo + jnp.maximum(u, 0.05) * (hi - lo))

        proj_idx = jnp.where(splitting[:, None], cand_idx, proj_idx)
        proj_coef = jnp.where(splitting[:, None], cand_coef, proj_coef)
        thresh = jnp.where(splitting, cand_thresh, thresh)

        # --- reassign points of splitting nodes ---------------------------
        node_splits = splitting[assign]
        go_right = y >= thresh[assign]
        new_assign = jnp.where(
            node_splits,
            new_child_base[assign] + go_right.astype(jnp.int32),
            assign,
        )
        return (new_assign, proj_idx, proj_coef, thresh, new_child_base,
                new_n_nodes), n_split

    init = (
        jnp.zeros((n,), jnp.int32),                       # assign: all at root
        jnp.zeros((m, k_proj), jnp.int32),                # proj_idx
        jnp.ones((m, k_proj), jnp.float32),               # proj_coef
        jnp.zeros((m,), jnp.float32),                     # thresh
        jnp.full((m,), -1, jnp.int32),                    # child_base
        jnp.asarray(1, jnp.int32),                        # n_nodes (root)
    )
    level_keys = jax.random.split(key, cfg.max_depth)
    (assign, proj_idx, proj_coef, thresh, child_base, n_nodes), _ = jax.lax.scan(
        level, init, level_keys)

    # --- CSR leaf storage -------------------------------------------------
    order = jnp.argsort(assign)
    assign_sorted = assign[order]
    node_ids = jnp.arange(m, dtype=jnp.int32)
    leaf_offset = jnp.searchsorted(assign_sorted, node_ids, side="left")
    leaf_end = jnp.searchsorted(assign_sorted, node_ids, side="right")
    leaf_count = (leaf_end - leaf_offset).astype(jnp.int32)
    leaf_count = jnp.where(child_base < 0, leaf_count, 0)

    return Forest(
        proj_idx=proj_idx,
        proj_coef=proj_coef,
        thresh=thresh,
        child_base=child_base,
        perm=order.astype(jnp.int32),
        leaf_offset=leaf_offset.astype(jnp.int32),
        leaf_count=leaf_count,
        n_nodes=n_nodes,
    )


@functools.partial(jax.jit, static_argnames=("cfg", "tree_chunk"))
def _build_forest_legacy(key: jax.Array, x: jax.Array, cfg: ForestConfig,
                         tree_chunk: int = 0) -> Forest:
    """The original per-tree builder (vmap of ``_build_one_tree``).

    Kept as the parity oracle and benchmark baseline for the batched
    cross-tree builder (DESIGN.md §10); ``seed_mode="compat"`` of the
    batched path is pinned bitwise against this.
    """
    cfg = cfg.resolved(x.shape[0])
    keys = jax.random.split(key, cfg.n_trees)
    build = functools.partial(_build_one_tree, x=x, cfg=cfg)
    if tree_chunk and cfg.n_trees > tree_chunk:
        return jax.lax.map(lambda k: build(k), keys, batch_size=tree_chunk)
    return jax.vmap(lambda k: build(k))(keys)


# ---------------------------------------------------------------------------
# batched cross-tree build (DESIGN.md §10): all L trees advance together
# ---------------------------------------------------------------------------


def _batched_level_draws(keys: jax.Array, cfg: ForestConfig, d: int,
                         seed_mode: str):
    """Per-level RNG for the batched builder.

    compat: ``keys`` is the (L,) per-tree key array — the same
      ``split(key, L)`` the legacy builder starts from — and each level
      reproduces the legacy derivation exactly
      (split(tree_key, depth) -> split(level_key, 3)), so every draw lands
      bitwise where the per-tree builder put it.
    fused:  ``keys`` is one scalar key, split once per level for the whole
      forest; the three draws come out as single (L, m, ...) calls.
      Different (valid) stream, cheaper derivation; opt-in via
      ``build_forest(seed_mode="fused")``.
    """
    L, m, kp, depth = cfg.n_trees, cfg.max_nodes, cfg.n_proj, cfg.max_depth
    if seed_mode == "compat":
        level_keys = jax.vmap(lambda k: jax.random.split(k, depth),
                              out_axes=1)(keys)          # (depth, L)

        def draws(level):
            k3 = jax.vmap(lambda k: jax.random.split(k, 3))(level_keys[level])
            ci = jax.vmap(lambda k: jax.random.randint(
                k, (m, kp), 0, d, dtype=jnp.int32))(k3[:, 0])
            cc = jax.vmap(lambda k: jax.random.uniform(
                k, (m, kp), jnp.float32))(k3[:, 1])
            uu = jax.vmap(lambda k: jax.random.uniform(k, (m,)))(k3[:, 2])
            return ci, cc, uu
    elif seed_mode == "fused":
        level_keys = jax.random.split(keys, depth)       # (depth,)

        def draws(level):
            k_feat, k_coef, k_quant = jax.random.split(level_keys[level], 3)
            ci = jax.random.randint(k_feat, (L, m, kp), 0, d,
                                    dtype=jnp.int32)
            cc = jax.random.uniform(k_coef, (L, m, kp), jnp.float32)
            uu = jax.random.uniform(k_quant, (L, m))
            return ci, cc, uu
    else:
        raise ValueError(f"seed_mode must be compat|fused, got {seed_mode!r}")
    return draws


def _next_pow2(v: int) -> int:
    return 1 << max(0, (int(v) - 1).bit_length())


# below this many points the staged active-set shrink is pure overhead
# (extra compiles + host syncs); single full-width stage instead
_RESTAGE_MIN = 4096
# floor for the compacted sort width: shapes below this recompile for no
# measurable win (the sort is already sub-millisecond)
_STAGE_FLOOR = 256


@functools.partial(jax.jit,
                   static_argnames=("cfg", "seed_mode", "a_cap", "shrink"))
def _build_stage(keys: jax.Array, x: jax.Array, state: tuple,
                 cfg: ForestConfig, seed_mode: str, a_cap: int,
                 shrink: bool) -> tuple:
    """Run build levels from ``state`` until done / depth budget / restage.

    One jitted while_loop over levels at a fixed sort width ``a_cap``:
    the per-level segmented sort (and the occupancy update) covers only
    the ACTIVE points — points sitting in overfull leaves — compacted
    into an (L, a_cap) buffer.  Leaves that are not overfull never split
    again, so the active set only shrinks; when its per-tree maximum
    falls to half of ``a_cap`` (and ``shrink`` allows), the loop exits so
    the driver can relaunch at a smaller width.  ``a_cap == n`` skips the
    compaction scatter entirely (every point is in the sort anyway).

    Bitwise parity with the legacy builder holds because compaction is
    order-preserving: each overfull node's segment holds exactly its own
    points in original index order, so the stable (node, projection) sort
    yields the same per-segment value sequence — and thresholds only ever
    read values inside overfull segments.
    """
    n, d = x.shape
    L, m, kp = cfg.n_trees, cfg.max_nodes, cfg.n_proj
    r = cfg.split_ratio
    compacted = a_cap < n
    draws = _batched_level_draws(keys, cfg, d, seed_mode)
    node_ids = jnp.arange(m, dtype=jnp.int32)[None, :]           # (1, m)
    l_idx = jnp.arange(L, dtype=jnp.int32)[:, None]              # (L, 1)
    tree_off = l_idx * (m + 1)   # m is the pad bucket of each tree

    def cond(carry):
        level, go, active_max = carry[0], carry[1], carry[2]
        keep = go & (level < cfg.max_depth)
        if shrink:
            keep &= 2 * active_max > a_cap
        return keep

    def body(carry):
        (level, _, _, assign, counts, proj_idx, proj_coef, thresh,
         child_base, n_nodes) = carry

        is_leaf = child_base < 0
        alive = node_ids < n_nodes[:, None]
        overfull = is_leaf & alive & (counts > cfg.capacity)

        # --- candidate random tests for every (tree, slot) (Eq. 1) --------
        cand_idx, cand_coef, u = draws(level)
        if kp == 1:
            cand_coef = jnp.ones_like(cand_coef)  # scale-invariant for K=1
        test_idx = jnp.where(overfull[..., None], cand_idx, proj_idx)
        test_coef = jnp.where(overfull[..., None], cand_coef, proj_coef)

        # --- per-point projections under the candidate tests --------------
        y = jax.vmap(lambda ti, tc, a: _project(x, ti[a], tc[a])
                     )(test_idx, test_coef, assign)               # (L, N)

        # --- ONE segmented sort over composite (tree, node, y) keys -------
        # the (tree) key rides the batch axis, (node, projection) are the
        # two sort keys — the same (int, float) comparator as the legacy
        # per-tree lexsort, so the per-segment ordering (and thus every
        # threshold read) matches it bitwise.  Only the sorted projection
        # VALUES are kept: start offsets fall out of the occupancy cumsum
        # (no searchsorted), no argsort + gather.
        if compacted:
            # scatter the active points into the narrow sort buffer;
            # cumsum positions preserve index order, so stability carries
            flag = jnp.take_along_axis(overfull, assign, axis=1)  # (L, N)
            pos = jnp.cumsum(flag.astype(jnp.int32), axis=1) - 1
            row = jnp.where(flag, pos, a_cap)        # inactive -> dropped
            assign_c = jnp.full((L, a_cap), m, jnp.int32
                                ).at[l_idx, row].set(assign, mode="drop")
            y_c = jnp.zeros((L, a_cap), y.dtype
                            ).at[l_idx, row].set(y, mode="drop")
            seg_sizes = jnp.where(overfull, counts, 0)
        else:
            assign_c, y_c = assign, y
            seg_sizes = counts
        _, y_sorted = jax.lax.sort((assign_c, y_c), dimension=1, num_keys=2,
                                   is_stable=True)                # (L, A)

        start = jnp.cumsum(seg_sizes, axis=1) - seg_sizes         # (L, m)
        last = jnp.clip(start + counts - 1, 0, a_cap - 1)

        def at(pos):  # y_sorted value at per-node position (L, m)
            return jnp.take_along_axis(y_sorted,
                                       jnp.clip(pos, 0, a_cap - 1), axis=1)

        lo = at(start)
        hi = at(last)
        # ties guard: a constant projection can't split — the node stays
        # open and redraws a fresh random coordinate at the next level
        degenerate = ~(hi > lo)
        splitting = overfull & ~degenerate

        # --- allocate children compactly (per tree) -----------------------
        n_split = jnp.sum(splitting.astype(jnp.int32), axis=1)    # (L,)
        rank = jnp.cumsum(splitting.astype(jnp.int32), axis=1) - 1
        new_child_base = jnp.where(splitting,
                                   n_nodes[:, None] + 2 * rank, child_base)
        budget_overflow = (n_nodes + 2 * n_split) > m             # (L,)
        new_child_base = jnp.where(budget_overflow[:, None], child_base,
                                   new_child_base)
        splitting = jnp.where(budget_overflow[:, None],
                              jnp.zeros_like(splitting), splitting)
        new_n_nodes = jnp.where(budget_overflow, n_nodes,
                                n_nodes + 2 * n_split)

        # paper Eq. 1: psi ~ U[y_{r n}, y_{(1-r) n}], values read from the
        # SAME sorted pass (the fused percentile-threshold draw)
        last_idx = jnp.maximum(start, start + counts - 1)
        cnt_f = counts.astype(jnp.float32)
        pos_a = jnp.clip(start + jnp.floor(r * cnt_f).astype(jnp.int32),
                         start, last_idx)
        pos_b = jnp.clip(start + jnp.floor((1.0 - r) * cnt_f
                                           ).astype(jnp.int32),
                         start, last_idx)
        a = at(pos_a)
        b_ = at(pos_b)
        cand_thresh = a + u * (b_ - a)
        # tie escape (see _build_one_tree): collapsed percentile interval
        # falls back to a uniform value split over the full (lo, hi] range
        cand_thresh = jnp.where(
            cand_thresh > lo, cand_thresh,
            lo + jnp.maximum(u, 0.05) * (hi - lo))

        proj_idx = jnp.where(splitting[..., None], cand_idx, proj_idx)
        proj_coef = jnp.where(splitting[..., None], cand_coef, proj_coef)
        thresh = jnp.where(splitting, cand_thresh, thresh)

        # --- reassign points of splitting nodes ---------------------------
        node_splits = jnp.take_along_axis(splitting, assign, axis=1)
        go_right = y >= jnp.take_along_axis(thresh, assign, axis=1)
        new_assign = jnp.where(
            node_splits,
            jnp.take_along_axis(new_child_base, assign, axis=1)
            + go_right.astype(jnp.int32),
            assign,
        )

        # --- occupancy update over the active points only -----------------
        # every point of an overfull node is in the compacted set, so
        #   counts' = counts*(not overfull) + seg_count(new node of active)
        # (degenerate nodes re-add their own points; split points land in
        # their children); pads live in the per-tree bucket m, sliced off
        if compacted:
            # new_assign already holds every point's destination node —
            # reuse the active->buffer map from the sort compaction
            moved = jnp.full((L, a_cap), m, jnp.int32
                             ).at[l_idx, row].set(new_assign, mode="drop")
            seg = jax.ops.segment_sum(
                jnp.ones((L * a_cap,), jnp.int32),
                (moved + tree_off).reshape(-1),
                num_segments=L * (m + 1)).reshape(L, m + 1)
            new_counts = jnp.where(overfull, 0, counts) + seg[:, :m]
        else:
            new_counts = jax.ops.segment_sum(
                jnp.ones((L * n,), jnp.int32),
                (new_assign + tree_off).reshape(-1),
                num_segments=L * (m + 1)).reshape(L, m + 1)[:, :m]

        new_overfull = (new_child_base < 0) \
            & (node_ids < new_n_nodes[:, None]) \
            & (new_counts > cfg.capacity)
        go = jnp.any(new_overfull)
        active_max = jnp.max(jnp.sum(
            jnp.where(new_overfull, new_counts, 0), axis=1))
        return (level + 1, go, active_max, new_assign, new_counts, proj_idx,
                proj_coef, thresh, new_child_base, new_n_nodes)

    return jax.lax.while_loop(cond, body, state)


def _build_forest_batched(keys: jax.Array, x: jax.Array, cfg: ForestConfig,
                          seed_mode: str = "compat",
                          restage_min: int = _RESTAGE_MIN) -> Forest:
    """All-L-trees-at-once level-synchronous build (DESIGN.md §10).

    ``keys``: the (L,) per-tree key array in compat mode, one scalar key
    in fused mode (see ``_batched_level_draws``).

    Drives ``_build_stage`` in rounds: the first stage runs at full sort
    width; as the active point set decays, later stages relaunch with the
    sort width halved-or-better (power-of-two buckets, so the number of
    compiled shapes is logarithmic).  The level loop exits as soon as NO
    leaf anywhere is overfull — the depth budget in ``cfg.max_depth`` is
    a worst-case bound (heavily tied data) and typical builds finish in a
    fraction of it; skipped tail levels are bitwise no-ops in the legacy
    scan, so early exit preserves exact parity.
    """
    n, _ = x.shape
    L, m, kp = cfg.n_trees, cfg.max_nodes, cfg.n_proj

    counts0 = jnp.zeros((L, m), jnp.int32).at[:, 0].set(n)
    state = (
        jnp.asarray(0, jnp.int32),                        # level
        jnp.asarray(n > cfg.capacity),                    # go: root overfull
        jnp.asarray(n, jnp.int32),                        # active_max
        jnp.zeros((L, n), jnp.int32),                     # assign: all at root
        counts0,
        jnp.zeros((L, m, kp), jnp.int32),                 # proj_idx
        jnp.ones((L, m, kp), jnp.float32),                # proj_coef
        jnp.zeros((L, m), jnp.float32),                   # thresh
        jnp.full((L, m), -1, jnp.int32),                  # child_base
        jnp.ones((L,), jnp.int32),                        # n_nodes
    )

    if isinstance(x, jax.core.Tracer) or isinstance(keys, jax.core.Tracer):
        # traced caller (shard_map per-device builds, user jit/vmap over
        # the key with a closed-over concrete db, ...): the staged shrink
        # needs host control flow, so run one full-width in-graph stage —
        # the early-exit while_loop still applies
        state = _build_stage(keys, x, state, cfg, seed_mode, n,
                             shrink=False)
    else:
        a_cap = n
        shrink = n >= restage_min
        while True:
            state = _build_stage(keys, x, state, cfg, seed_mode, a_cap,
                                 shrink)
            level, go, active_max = (int(state[0]), bool(state[1]),
                                     int(state[2]))
            if not go or level >= cfg.max_depth:
                break
            nxt = max(_next_pow2(active_max), _STAGE_FLOOR)
            if nxt >= a_cap:      # no shrink possible: run to completion
                shrink = False
                continue
            a_cap = nxt
            shrink = a_cap > _STAGE_FLOOR

    (_, _, _, assign, counts, proj_idx, proj_coef, thresh, child_base,
     n_nodes) = state
    return _finalize_csr(assign, counts, proj_idx, proj_coef, thresh,
                         child_base, n_nodes)


@jax.jit
def _finalize_csr(assign, counts, proj_idx, proj_coef, thresh, child_base,
                  n_nodes) -> Forest:
    """CSR leaf storage: one batched stable int argsort over (L, N)."""
    perm = jnp.argsort(assign, axis=1, stable=True).astype(jnp.int32)
    leaf_offset = (jnp.cumsum(counts, axis=1) - counts).astype(jnp.int32)
    leaf_count = jnp.where(child_base < 0, counts, 0).astype(jnp.int32)
    return Forest(
        proj_idx=proj_idx,
        proj_coef=proj_coef,
        thresh=thresh,
        child_base=child_base,
        perm=perm,
        leaf_offset=leaf_offset,
        leaf_count=leaf_count,
        n_nodes=n_nodes,
    )


def build_forest(key: jax.Array, x: jax.Array, cfg: ForestConfig,
                 tree_chunk: int = 0, impl: str = "batched",
                 seed_mode: str = "compat") -> Forest:
    """Build the L-tree forest.

    ``impl="batched"`` (default) constructs all L trees at once — one
    segmented sort over composite (tree, node) keys per level plus an
    early exit when every leaf fits — and under the default
    ``seed_mode="compat"`` returns Forest arrays bitwise identical to
    ``impl="legacy"`` (the original per-tree builder, kept as the parity
    oracle).  ``seed_mode="fused"`` derives the per-level randomness from
    one key split per level instead of per tree — a different, equally
    valid stream (benchmarks/build_time.py measures both).

    ``tree_chunk`` > 0 builds trees in chunks of that size to bound peak
    memory for very large L (the paper sweeps L up to 640).  In compat
    mode chunking is exact (per-tree key derivation makes the chunks
    independent); in fused mode each chunk folds its index into the key.
    """
    cfg = cfg.resolved(x.shape[0])
    if impl == "legacy":
        return _build_forest_legacy(key, x, cfg, tree_chunk)
    if impl != "batched":
        raise ValueError(f"impl must be batched|legacy, got {impl!r}")
    keys = jax.random.split(key, cfg.n_trees) if seed_mode == "compat" \
        else key
    if tree_chunk and cfg.n_trees > tree_chunk:
        chunks = []
        for i, lo in enumerate(range(0, cfg.n_trees, tree_chunk)):
            width = min(tree_chunk, cfg.n_trees - lo)
            sub_cfg = cfg._replace(n_trees=width)
            sub_keys = keys[lo:lo + width] if seed_mode == "compat" \
                else jax.random.fold_in(key, i)
            chunks.append(_build_forest_batched(sub_keys, x, sub_cfg,
                                                seed_mode=seed_mode))
        return jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *chunks)
    return _build_forest_batched(keys, x, cfg, seed_mode=seed_mode)


# ---------------------------------------------------------------------------
# query: batched traversal + candidate retrieval
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("max_depth",))
def traverse(forest: Forest, queries: jax.Array, max_depth: int) -> jax.Array:
    """Map each query to its leaf node in every tree.

    queries: (B, d) -> leaf ids (L, B). One gather + compare per level, exactly
    the paper's "one random coordinate access ... one float comparison per node
    visited".  This is the ``n_probes = 1`` primitive; see
    :func:`traverse_multiprobe` for the widened descent (DESIGN.md §9).
    """

    def one_tree(tree: Forest):
        def step(_, node):
            idx = tree.proj_idx[node]          # (B, K)
            coef = tree.proj_coef[node]        # (B, K)
            y = jnp.sum(jnp.take_along_axis(queries, idx, axis=1) * coef, axis=1)
            go_right = y >= tree.thresh[node]
            child = tree.child_base[node] + go_right.astype(jnp.int32)
            return jnp.where(tree.child_base[node] < 0, node, child)

        node0 = jnp.zeros((queries.shape[0],), jnp.int32)
        return jax.lax.fori_loop(0, max_depth, step, node0)

    return jax.vmap(one_tree)(forest)


@functools.partial(jax.jit, static_argnames=("max_depth", "n_probes"))
def traverse_multiprobe(forest: Forest, queries: jax.Array, max_depth: int,
                        n_probes: int) -> jax.Array:
    """Priority-ordered multi-probe descent (DESIGN.md §9).

    Maps each query to its ``n_probes`` most marginal leaves per tree:
    probe 0 is the primary leaf (bitwise-identical to :func:`traverse`);
    probes 1..n_probes-1 are bounded best-first re-descents that flip the
    routing decision at the internal node with the smallest signed
    projection margin ``|t(x)| = |y - psi|`` along the primary path and
    then continue greedily to a leaf.  Two descents that diverge at an
    internal node end in disjoint subtrees, so the probes of one tree are
    pairwise-distinct leaves.

    queries: (B, d) -> leaf ids (L, B, n_probes) int32; slots for which no
    alternate exists (shallow paths with fewer than ``n_probes - 1``
    internal nodes) hold -1 and are masked by
    :func:`gather_candidates_multi`.  Static shapes throughout: the probe
    count bounds the expansion, every re-descent is a ``fori_loop`` of the
    same gather+compare step as the primary descent.
    """
    n_alt = max(0, min(n_probes - 1, max_depth))
    b = queries.shape[0]

    def one_tree(tree: Forest):
        def project(node):
            idx = tree.proj_idx[node]          # (B, K)
            coef = tree.proj_coef[node]        # (B, K)
            return jnp.sum(
                jnp.take_along_axis(queries, idx, axis=1) * coef, axis=1)

        def primary_step(node, _):
            y = project(node)
            internal = tree.child_base[node] >= 0
            margin = jnp.where(internal, jnp.abs(y - tree.thresh[node]),
                               jnp.inf)
            child = tree.child_base[node] \
                + (y >= tree.thresh[node]).astype(jnp.int32)
            return jnp.where(internal, child, node), margin

        node0 = jnp.zeros((b,), jnp.int32)
        leaf, margins = jax.lax.scan(primary_step, node0, None,
                                     length=max_depth)
        # margins: (max_depth, B); +inf rows mark depths past the leaf
        probes = [leaf[:, None]]
        if n_alt:
            # the n_alt smallest margins along the path, ascending (ties ->
            # shallower depth, matching the kernel's iterative argmin)
            neg, flip_depth = jax.lax.top_k(-margins.T, n_alt)  # (B, n_alt)
            valid = jnp.isfinite(neg)

            def alt_descend(depth_sel):
                def step(t, node):
                    y = project(node)
                    internal = tree.child_base[node] >= 0
                    go_right = y >= tree.thresh[node]
                    go_right = jnp.where(t == depth_sel, ~go_right, go_right)
                    child = tree.child_base[node] + go_right.astype(jnp.int32)
                    return jnp.where(internal, child, node)

                return jax.lax.fori_loop(0, max_depth, step, node0)

            alts = jax.vmap(alt_descend, in_axes=1, out_axes=1)(flip_depth)
            probes.append(jnp.where(valid, alts, -1))
        out = jnp.concatenate(probes, axis=1)               # (B, <=n_probes)
        if out.shape[1] < n_probes:                          # max_depth-bound
            out = jnp.pad(out, ((0, 0), (0, n_probes - out.shape[1])),
                          constant_values=-1)
        return out

    return jax.vmap(one_tree)(forest)


def _descent_kernel(mode: str, n_proj: int) -> tuple[bool, bool]:
    """-> (the descent runs the HBM kernel, in interpret mode)."""
    from repro.kernels import ops as _ops
    use_pallas, interp = _ops._resolve(mode)
    return use_pallas and n_proj == 1, interp


def descent_levels(depths: np.ndarray, max_depth: int, n_proj: int,
                   mode: str) -> int:
    """Level steps of one :func:`traverse_forest` descent per tree, summed
    over the trees: each tree's own depth (bounded by ``max_depth``) in
    the kernel, ``max_depth`` for every tree in the XLA traversals."""
    if _descent_kernel(mode, n_proj)[0]:
        return int(np.minimum(depths, max_depth).sum())
    return int(max_depth) * len(depths)


def traverse_forest(forest: Forest, queries: jax.Array, max_depth: int,
                    n_probes: int = 1, mode: str = "auto",
                    depths: jax.Array | None = None) -> jax.Array:
    """Mode-dispatched forest descent — the pipeline's traversal entry.

    Routes through the Pallas traversal kernels when the mode policy says
    so (kernels/ops.py: Pallas on TPU or forced) AND the forest uses K = 1
    projections (the paper default, where ``proj_coef`` is identically 1.0
    so the kernel's raw-coordinate compare is bitwise the jnp descent).
    Tree size no longer matters: the HBM-resident kernel (DESIGN.md §11)
    has no node cap, so ``mode="pallas"`` never leaves Pallas.  K > 1
    forests and ref mode use the XLA traversal (:func:`traverse` /
    :func:`traverse_multiprobe`) — on CPU ``"auto"`` resolves there, which
    keeps the historical bitwise pin of the pre-kernel graph.

    ``depths`` (L,), each tree's built depth (:func:`tree_depths`), bounds
    the kernel's per-tree descents; the XLA traversals run ``max_depth``
    levels for every tree.  Either way the leaves are those of any bound
    at or above the trees' depths.

    Returns (L, B) for ``n_probes == 1``, else (L, B, n_probes).
    """
    use_kernel, interp = _descent_kernel(mode, forest.proj_idx.shape[-1])
    if use_kernel:
        from repro.kernels import forest_traverse_hbm as _hbm
        return _hbm.forest_traverse_hbm(
            forest.proj_idx[..., 0], forest.thresh, forest.child_base,
            queries, max_depth, interpret=interp, n_probes=n_probes,
            depths=depths)
    if n_probes == 1:
        return traverse(forest, queries, max_depth)
    return traverse_multiprobe(forest, queries, max_depth, n_probes)


@functools.partial(jax.jit, static_argnames=("pad",))
def gather_candidates_multi(forest: Forest, leaves: jax.Array, pad: int
                            ) -> tuple[jax.Array, jax.Array]:
    """Candidate retrieval for the multi-probe leaf set.

    leaves: (L, B, P) leaf ids with -1 marking absent probes ->
    (B, L*P*pad) candidate ids, (B, L*P*pad) bool mask.  The probe axis
    folds into the candidate axis of the existing padded id/mask contract,
    so the fused rerank, int8 shortlist, tombstone validity and the sharded
    merge all compose without a kernel change (DESIGN.md §9).  For P=1 the
    output is identical to :func:`gather_candidates`.
    """
    L, B, P = leaves.shape
    flat = leaves.reshape(L, B * P)
    slot = jnp.arange(pad, dtype=jnp.int32)

    def one_tree(tree: Forest, leaf: jax.Array):
        ok = leaf >= 0
        safe = jnp.maximum(leaf, 0)
        off = tree.leaf_offset[safe]            # (B*P,)
        cnt = jnp.where(ok, tree.leaf_count[safe], 0)
        pos = off[:, None] + slot[None, :]      # (B*P, pad)
        mask = slot[None, :] < cnt[:, None]
        n = tree.perm.shape[0]
        ids = tree.perm[jnp.clip(pos, 0, n - 1)]
        return jnp.where(mask, ids, 0), mask

    ids, mask = jax.vmap(one_tree)(forest, flat)             # (L, B*P, pad)
    ids = ids.reshape(L, B, P * pad)
    mask = mask.reshape(L, B, P * pad)
    ids = jnp.transpose(ids, (1, 0, 2)).reshape(B, L * P * pad)
    mask = jnp.transpose(mask, (1, 0, 2)).reshape(B, L * P * pad)
    return ids, mask


@functools.partial(jax.jit, static_argnames=("pad",))
def gather_candidates(forest: Forest, leaves: jax.Array, pad: int
                      ) -> tuple[jax.Array, jax.Array]:
    """Retrieve the (padded) union of leaf point-sets.

    leaves: (L, B) leaf node ids -> (B, L*pad) candidate ids, (B, L*pad) bool mask.
    Invalid slots hold id 0 and mask False.
    """
    L, B = leaves.shape
    slot = jnp.arange(pad, dtype=jnp.int32)

    def one_tree(tree: Forest, leaf: jax.Array):
        off = tree.leaf_offset[leaf]            # (B,)
        cnt = tree.leaf_count[leaf]             # (B,)
        pos = off[:, None] + slot[None, :]      # (B, pad)
        mask = slot[None, :] < cnt[:, None]
        n = tree.perm.shape[0]
        ids = tree.perm[jnp.clip(pos, 0, n - 1)]
        return jnp.where(mask, ids, 0), mask

    ids, mask = jax.vmap(one_tree)(forest, leaves)       # (L, B, pad)
    ids = jnp.transpose(ids, (1, 0, 2)).reshape(B, L * pad)
    mask = jnp.transpose(mask, (1, 0, 2)).reshape(B, L * pad)
    return ids, mask


def query_forest(forest: Forest, queries: jax.Array, db: jax.Array, k: int,
                 cfg: ForestConfig, metric: str = "l2", dedup: bool = True,
                 mode: str = "auto", chunk: int = 0
                 ) -> tuple[jax.Array, jax.Array]:
    """End-to-end query: traverse -> dedup -> rerank -> top-k.

    Dispatches through the fused single-pass pipeline (core.pipeline) behind
    the mode policy; the pre-fusion staged composition survives as
    core.pipeline.staged_query (the oracle).

    Returns (dists (B, k), ids (B, k)); invalid slots have id -1 and dist +inf.
    """
    from repro.core import pipeline  # local import to avoid cycle

    return pipeline.fused_query(forest, queries, db, k, cfg, metric=metric,
                                dedup=dedup, mode=mode, chunk=chunk)


# ---------------------------------------------------------------------------
# structural statistics (paper §3.4 discussion; used in tests + benchmarks)
# ---------------------------------------------------------------------------


def _node_depths(child_base: np.ndarray) -> np.ndarray:
    """(L, max_nodes) depth of every built node, -1 for the unbuilt slots.

    One level-by-level frontier walk over all trees at once: a node's
    children sit at ``child_base`` and ``child_base + 1``, so each built
    node is touched once, and the walk takes one NumPy step per level.
    """
    child = np.asarray(child_base)
    depth = np.full(child.shape, -1, np.int32)
    tree = np.arange(child.shape[0])
    node = np.zeros_like(tree)
    level = 0
    while tree.size:
        depth[tree, node] = level
        cb = child[tree, node]
        inner = cb >= 0
        tree = np.repeat(tree[inner], 2)
        node = np.repeat(cb[inner], 2) + np.tile([0, 1], int(inner.sum()))
        level += 1
    return depth


def tree_depths(forest: Forest) -> np.ndarray:
    """(L,) int32 depth of each tree's deepest node; 0 for a single leaf.

    A descent of more levels than its tree's depth changes no leaf and no
    margin (past its leaf a node stays put and its margin is +inf), so
    this is the exact loop bound of every traversal (DESIGN.md §11).
    """
    return _node_depths(forest.child_base).max(axis=1)


def forest_stats(forest: Forest, cfg: ForestConfig, n_points: int) -> dict:
    cfg = cfg.resolved(n_points)
    child = np.asarray(forest.child_base)
    count = np.asarray(forest.leaf_count)
    n_nodes = np.asarray(forest.n_nodes)
    depth = _node_depths(child)
    depth_max = tree_depths(forest)
    stats = []
    for l in range(child.shape[0]):
        alive = np.arange(child.shape[1]) < n_nodes[l]
        leaf = (child[l] < 0) & alive
        occ = count[l][leaf & (count[l] > 0)]
        leaf_depths = depth[l][leaf & (count[l] > 0)]
        stats.append(dict(
            n_nodes=int(n_nodes[l]),
            n_leaves=int(leaf.sum()),
            occ_mean=float(occ.mean()) if occ.size else 0.0,
            occ_max=int(occ.max()) if occ.size else 0,
            overflow_points=int(occ[occ > cfg.capacity].sum()) if occ.size else 0,
            depth_mean=float(leaf_depths.mean()) if leaf_depths.size else 0.0,
            depth_max=int(depth_max[l]),
        ))
    agg = {k: float(np.mean([s[k] for s in stats])) for k in stats[0]}
    agg["per_tree"] = stats
    return agg
