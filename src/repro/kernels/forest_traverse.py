"""Pallas batched forest traversal (K=1 trees): query tile -> leaf ids.

The paper's descent is one coordinate access + one float compare per level.
Here the whole tree and the query tile sit in scalar memory (SMEM), and the
scalar core walks each row down the tree: per level it loads the node's
(feat, thresh, child_base), the row's coordinate ``q[b, feat]``, compares,
and steps to a child.  Scalar memory can only be read one scalar at a
time, so the descent is a loop over rows and levels — no vector work, no
DMA.

``n_probes > 1`` adds the bounded multi-probe expansion of DESIGN.md §9 per
row: the primary descent records per-level projection margins in an SMEM
scratch, then each alternate re-descends with the smallest-margin routing
decision flipped (ties -> shallower depth).

Tree arrays are passed as scalar-prefetch operands, which caps the tree at
the scalar-memory budget (1 MiB on v5e, shared with the query tile:
``SMEM_NODE_CAP`` nodes of 12 B).  The query path never dispatches here:
``core.forest.traverse_forest`` always takes the HBM-resident kernel
(kernels/forest_traverse_hbm.py, DESIGN.md §11), which has no cap;
``ops.traverse_tree`` reaches this kernel (``kernel="smem"``, or ``"auto"``
below the cap).  Its speed against the HBM kernel has not been measured.

Grid = (B/bq,), bq = 8 rows per tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.tracing import KERNEL_DESCENT_SMEM

# Largest tree (allocated max_nodes) this kernel accepts: three 4-byte
# arrays per node plus an (8, d) f32 query tile must fit the 1 MiB scalar
# memory of a v5e (tests/test_tpu_compile.py compiles it at this cap).
SMEM_NODE_CAP = 64 * 1024
BQ = 8


def _kernel(feat_ref, thresh_ref, child_ref, q_ref, out_ref, marg, *,
            max_depth: int, n_probes: int, bq: int):

    def descend(b, flip, record):
        """Walk row ``b`` to a leaf, inverting the decision at depth
        ``flip`` (-1: none); ``record`` stores the per-level margins."""
        def step(t, node):
            f = feat_ref[node]
            th = thresh_ref[node]
            cb = child_ref[node]
            xv = q_ref[b, f]
            internal = cb >= 0
            go_right = jnp.where(xv >= th, 1, 0)
            go_right = jnp.where(t == flip, 1 - go_right, go_right)
            if record:
                marg[t] = jnp.where(internal, jnp.abs(xv - th), jnp.inf)
            return jnp.where(internal, cb + go_right, node)
        return jax.lax.fori_loop(0, max_depth, step, jnp.int32(0))

    def row(b, _):
        out_ref[b, 0] = descend(b, -1, True)
        # bounded best-first expansion: flip the smallest-margin decision
        # per alternate (strict < keeps the shallower depth on ties)
        for p in range(1, n_probes):
            def argmin(t, carry):
                best, first = carry
                m = marg[t]
                take = m < best
                return jnp.where(take, m, best), jnp.where(take, t, first)
            best, first = jax.lax.fori_loop(
                0, max_depth, argmin, (jnp.float32(jnp.inf),
                                       jnp.int32(max_depth)))
            found = first < max_depth

            @pl.when(found)
            def _():
                marg[first] = jnp.inf
                out_ref[b, p] = descend(b, first, False)

            @pl.when(jnp.logical_not(found))
            def _():
                out_ref[b, p] = -1
        return 0

    jax.lax.fori_loop(0, bq, row, 0)


@functools.partial(jax.jit, static_argnames=("max_depth", "interpret",
                                             "n_probes"))
def forest_traverse(feat: jax.Array, thresh: jax.Array, child_base: jax.Array,
                    queries: jax.Array, max_depth: int,
                    interpret: bool = False, n_probes: int = 1) -> jax.Array:
    """Single K=1 tree: feat/thresh/child_base (max_nodes,), queries (B, d).

    Returns leaf node ids (B,) int32 for ``n_probes == 1`` (the historical
    contract), else the multi-probe leaf set (B, n_probes) int32 with -1
    marking absent probes — the same ordering (primary leaf first, then
    ascending projection margin) as ``core.forest.traverse_multiprobe``.
    vmap over trees for the forest.
    """
    b, d = queries.shape
    b_pad = -b % BQ
    qp = jnp.pad(queries, ((0, b_pad), (0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,           # feat, thresh, child_base in SMEM
        grid=((b + b_pad) // BQ,),
        in_specs=[pl.BlockSpec((BQ, d), lambda i, *_: (i, 0),
                               memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((BQ, n_probes), lambda i, *_: (i, 0),
                               memory_space=pltpu.SMEM),
        scratch_shapes=[pltpu.SMEM((max_depth,), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, max_depth=max_depth, n_probes=n_probes,
                          bq=BQ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b + b_pad, n_probes), jnp.int32),
        interpret=interpret,
        name=KERNEL_DESCENT_SMEM,
    )(feat, thresh, child_base, qp)
    return out[:b, 0] if n_probes == 1 else out[:b]
