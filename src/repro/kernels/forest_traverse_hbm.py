"""Pallas HBM-resident forest traversal: no SMEM node cap (DESIGN.md §11).

The SMEM kernel (kernels/forest_traverse.py) passes the tree arrays as
scalar-prefetch operands, which caps the tree at the scalar-memory budget
(~64k nodes).  Paper-scale trees (1M rows at C=12 allocate ~1.1M nodes per
tree) need the arrays to stay in HBM; this kernel fetches exactly the node
records a descent touches.

Node records: the copy engine moves whole (1, 128-lane) tiles, so a
4-byte element of an (L, max_nodes) HBM array is not a legal DMA source.
``pack_records`` lays the three tree arrays out as (L, G, 1, 384) int32,
G = ceil(max_nodes / 128): group ``g`` holds nodes ``128 g ..`` with their
feat, thresh bits and child_base in lanes 0-127, 128-255 and 256-383.  One
DMA per (row, level) fetches the 1.5 KB group of the row's node, and a
one-hot lane select picks the node's three fields out of it.

Dataflow per (tree, query-tile) grid step:
  * the record store is a ``memory_space=ANY`` operand — it never leaves
    HBM; the query tile is the only fat VMEM block.
  * The descent is level-synchronous over the tile: at level ``t`` the bq
    per-row record groups already sit in VMEM slot ``t % 2``.  The kernel
    compares level ``t``, computes the per-row child, bounces the child
    ids VMEM -> SMEM (DMA; the copy engine needs scalar indices and
    scalars live in SMEM), and immediately starts the per-row record DMAs
    for level ``t + 1`` into slot ``(t + 1) % 2``.  The multi-probe margin
    bookkeeping then runs while those copies are in flight — fetch of
    level ``i + 1`` overlaps compare of level ``i`` (double buffering).
  * All per-row values are (bq, 1) columns: the coordinate ``q[b, f_b]``
    is a one-hot select along the query's lanes, exact because only one
    term of the sum is non-zero.

Multi-probe: the primary descent records per-level margins, each
alternate re-descends with the smallest-margin decision flipped (ties ->
shallower depth).  Alternates re-fetch their node path from HBM.

Loop bound: grid step ``l`` runs ``depths[l]`` levels per descent, the
tree's built depth (``core.forest.tree_depths``), a scalar-prefetch
operand read from SMEM; ``max_depth`` is static and only sizes the
margins.  Stopping at the deepest node is exact: past it every row sits
on a leaf, where a level step leaves the node unchanged and writes a
+inf margin over +inf, and the alternates' first-min picks a +inf column
only when every column is +inf, which gives -1 at any bound.

Bitwise contract: the float compare chain (coordinate select, ``xv >=
thresh``, ``|xv - thresh|`` margins) is operation-for-operation
``ref.forest_traverse_multiprobe_ref``'s, so leaf ids match it bitwise at
any tree size; tests/test_traverse_hbm.py pins this.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import tpu_compiler_params
from repro.tracing import KERNEL_DESCENT_HBM


REC = 128           # nodes per record group: one lane tile
FIELDS = 3          # feat, thresh bits, child_base


def pack_records(feat: jax.Array, thresh: jax.Array,
                 child_base: jax.Array) -> jax.Array:
    """(L, max_nodes) tree arrays -> the (L, G, 1, 3 * REC) record store."""
    n_trees, n = feat.shape
    g = -(-n // REC)

    def group(a):
        a = jnp.pad(a, ((0, 0), (0, g * REC - n)))
        return a.reshape(n_trees, g, 1, REC)

    return jnp.concatenate(
        [group(feat), group(jax.lax.bitcast_convert_type(thresh, jnp.int32)),
         group(child_base)], axis=-1)


def _kernel(depth_ref, rec_hbm, q_ref, out_ref, rec, nxt_v, nxt_s, sem_rec,
            sem_nxt, *, max_depth: int, n_probes: int, bq: int):
    l = pl.program_id(0)
    depth = depth_ref[l]                   # this tree's levels (SMEM scalar)
    q = q_ref[...]                                   # (bq, d)

    def _record_copy(slot, b):
        """The record-group DMA for row ``b`` into ``slot``."""
        return pltpu.make_async_copy(rec_hbm.at[l, nxt_s[b, 0] // REC],
                                     rec.at[slot, b], sem_rec)

    def start_fetch(slot):
        def body(b, _):
            _record_copy(slot, b).start()
            return 0
        jax.lax.fori_loop(0, bq, body, 0)

    def wait_fetch(slot):
        def body(b, _):
            _record_copy(slot, b).wait()
            return 0
        jax.lax.fori_loop(0, bq, body, 0)

    def hand_to_dma(node):
        """Bounce per-row node ids into SMEM so DMA can index with them."""
        nxt_v[...] = node
        cp = pltpu.make_async_copy(nxt_v, nxt_s, sem_nxt)
        cp.start()
        cp.wait()

    depth_col = jax.lax.broadcasted_iota(jnp.int32, (bq, max_depth), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (bq, REC), 1)
    coord = jax.lax.broadcasted_iota(jnp.int32, q.shape, 1)
    node0 = jnp.zeros((bq, 1), jnp.int32)

    def pick(onehot, r, field):
        return jnp.sum(jnp.where(onehot, r[:, field * REC:(field + 1) * REC],
                                 0), axis=1, keepdims=True)

    def descend(flip):
        """Full double-buffered descent; ``flip`` (bq, 1) is the depth
        whose routing decision is inverted (-1: none — the primary)."""
        hand_to_dma(node0)                 # level 0: every row at the root
        start_fetch(0)

        def step(t, carry):
            node, margins = carry
            slot = jax.lax.rem(t, 2)
            wait_fetch(slot)
            r = rec[slot].reshape(bq, FIELDS * REC)
            onehot = lane == jax.lax.rem(node, REC)
            f = pick(onehot, r, 0)                           # (bq, 1)
            th = jax.lax.bitcast_convert_type(pick(onehot, r, 1),
                                              jnp.float32)
            cb = pick(onehot, r, 2)
            xv = jnp.sum(jnp.where(coord == f, q, 0.0), axis=1,
                         keepdims=True)
            go_right = jnp.where(xv >= th, 1, 0)
            go_right = jnp.where(t == flip, 1 - go_right, go_right)
            internal = cb >= 0
            nxt = jnp.where(internal, cb + go_right, node)
            # issue level t+1 fetches first; the margin bookkeeping below
            # executes while they fly (the double-buffer overlap)
            hand_to_dma(nxt)
            start_fetch(1 - slot)
            margin = jnp.where(internal, jnp.abs(xv - th), jnp.inf)
            margins = jnp.where(depth_col == t, margin, margins)
            return nxt, margins

        margins0 = jnp.full((bq, max_depth), jnp.inf, jnp.float32)
        leaf, margins = jax.lax.fori_loop(0, depth, step, (node0, margins0))
        wait_fetch(jax.lax.rem(depth, 2))       # drain the trailing prefetch
        return leaf, margins

    leaf, margins = descend(jnp.full((bq, 1), -1, jnp.int32))
    out_ref[0, :, 0:1] = leaf

    # bounded best-first expansion: flip the smallest-margin decision per
    # alternate (ties -> shallower depth)
    for p in range(1, n_probes):
        best = jnp.min(margins, axis=1, keepdims=True)               # (bq, 1)
        first = jnp.min(jnp.where(margins == best, depth_col, max_depth),
                        axis=1, keepdims=True)
        margins = jnp.where(depth_col == first, jnp.inf, margins)
        alt, _ = descend(first)
        out_ref[0, :, p:p + 1] = jnp.where(jnp.isfinite(best), alt, -1)


@functools.partial(jax.jit, static_argnames=("max_depth", "bq", "interpret",
                                             "n_probes"))
def forest_traverse_hbm(feat: jax.Array, thresh: jax.Array,
                        child_base: jax.Array, queries: jax.Array,
                        max_depth: int, bq: int = 256,
                        interpret: bool = False, n_probes: int = 1,
                        depths: jax.Array | None = None) -> jax.Array:
    """Whole-forest descent with HBM-resident trees (no node-count cap).

    feat/thresh/child_base (L, max_nodes), queries (B, d).  Returns leaf
    ids (L, B) int32 for ``n_probes == 1``, else (L, B, n_probes) with -1
    marking absent probes — the same ordering as the SMEM kernel and
    ``core.forest.traverse_multiprobe``.  The tree axis rides the grid, so
    one pallas_call serves the forest.  The record store is packed here,
    one copy of the tree arrays per call.

    ``depths`` (L,) int32, each tree's built depth
    (``core.forest.tree_depths``), bounds each tree's descents; the
    answer is the same as at ``max_depth`` for any bound at or above the
    tree's depth.  Without it every tree runs ``max_depth`` levels.
    """
    n_trees = feat.shape[0]
    b, d = queries.shape
    bq = min(bq, -(-b // 8) * 8)
    b_pad = -b % bq
    qp = jnp.pad(queries, ((0, b_pad), (0, 0)))
    records = pack_records(feat, thresh, child_base)
    if depths is None:
        depths = jnp.full((n_trees,), max_depth, jnp.int32)
    depths = jnp.minimum(depths.astype(jnp.int32), max_depth)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                 # per-tree depths -> SMEM
        grid=(n_trees, (b + b_pad) // bq),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),         # records stay in HBM
            pl.BlockSpec((bq, d), lambda t, i, *_: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, n_probes), lambda t, i, *_: (t, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, bq, 1, FIELDS * REC), jnp.int32),  # record groups
            pltpu.VMEM((bq, 1), jnp.int32),    # nxt_v: node-id bounce (VMEM)
            pltpu.SMEM((bq, 1), jnp.int32),    # nxt_s: node ids for DMA
            pltpu.SemaphoreType.DMA,           # record fetches
            pltpu.SemaphoreType.DMA,           # VMEM->SMEM bounce
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, max_depth=max_depth, n_probes=n_probes,
                          bq=bq),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_trees, b + b_pad, n_probes),
                                       jnp.int32),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=KERNEL_DESCENT_HBM,
    )(depths, records, qp)
    out = out[:, :b]
    return out[..., 0] if n_probes == 1 else out


def forest_traverse_hbm_tree(feat: jax.Array, thresh: jax.Array,
                             child_base: jax.Array, queries: jax.Array,
                             max_depth: int, bq: int = 256,
                             interpret: bool = False, n_probes: int = 1
                             ) -> jax.Array:
    """Single K=1 tree, matching ``forest_traverse``'s contract exactly:
    (B,) leaf ids for ``n_probes == 1``, else (B, n_probes)."""
    out = forest_traverse_hbm(feat[None], thresh[None], child_base[None],
                              queries, max_depth, bq=bq, interpret=interpret,
                              n_probes=n_probes)
    return out[0]
