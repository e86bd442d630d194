"""Fused candidate gather + exact distance + running top-k (the query hot path).

The staged pipeline materializes ``db[cand_ids]`` — a ``(B, M, d)`` tensor —
in HBM between the XLA gather and the rerank kernel, so every candidate row
crosses HBM three times (gather read, gather write, kernel read).  This kernel
closes that seam: candidate ids arrive as a scalar-prefetch operand (SMEM),
the DB stays in HBM, and the kernel DMAs exactly the rows it needs into a
``(bq, bm, d)`` VMEM tile, scores them against the query tile, and folds them
into an on-chip ``(bq, k)`` running top-k.  The gathered tensor never exists
in HBM; per-candidate traffic drops to a single HBM read.

Contract (mirrored by ``kernels.ref.fused_gather_topk_ref``):
  q (B, d) f32/bf16, ids (B, M) int32 with -1 marking invalid slots,
  db (N, d) (read as ``row_store(db)``, below) -> (dists (B, k) f32,
  ids (B, k) int32); invalid: +inf / -1.

The -1 id slot is the kernel's whole masking vocabulary, and it is load
bearing for the segmented mutable index: tombstoned (deleted/upserted) DB
rows are folded into this same id/mask path by ``core.pipeline`` — a dead
row's candidate slot becomes -1 before the kernel, so it issues no DMA,
scores +inf, and can never occupy a top-k slot.  The kernel itself needs
no tombstone concept.

Layout: grid = (B/bq, M/bm), candidate axis innermost ("arbitrary") so the
(bq, k) state lives in the revisited output block across the whole stream.
The tile is (bq, bm) = (8, 128), one f32 vreg of ids and scores.

Row store: the TPU copy engine moves whole (sublane, 128-lane) tiles, and a
2-D (N, d) array in HBM is tiled (8, 128), so one row of it is not a legal
DMA source.  The kernel therefore reads the DB as ``row_store(db)``: an
(N, 1, dp) array, dp = d rounded up to 128 lanes, zero-padded.  Each row is
then its own (1, dp) tile, and one DMA moves exactly one row.  The zero
lanes add nothing to any of the four metrics.  ``core.pipeline`` builds the
store once per rerank call, outside the chunk loop.

SMEM budget: the ids operand is SMEM-resident, so B*M*4 bytes must fit the
scalar memory (1 MiB on v5e).  ``core.pipeline`` chunk-streams the M axis to
stay under that bound; this kernel asserts nothing and trusts its caller.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import tpu_compiler_params
from repro.kernels.common import POS_INF, merge_topk, select_topk_block
from repro.tracing import KERNEL_RERANK

EPS = 1e-12
LANE = 128


def row_store(db: jax.Array) -> jax.Array:
    """(N, d) rows -> the kernel's (N, 1, dp) DMA-able row store."""
    n, d = db.shape
    dp = -(-d // LANE) * LANE
    return jnp.pad(db, ((0, 0), (0, dp - d))).reshape(n, 1, dp)


def _kernel(ids_smem, q_ref, ids_ref, db_ref, out_d_ref, out_i_ref,
            rows, sem, *, bq: int, bm: int, k: int, metric: str):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_d_ref[...] = jnp.full_like(out_d_ref, POS_INF)
        out_i_ref[...] = jnp.full_like(out_i_ref, -1)

    # ---- tile-by-tile HBM row gather -------------------------------------
    # Launch all row DMAs for this (bq, bm) tile, then drain: the copies
    # overlap each other and the queue keeps the HBM pipe full. Invalid
    # slots (id < 0) issue no DMA; their scores are masked to +inf below.
    def _copy(t):
        rid = ids_smem[i * bq + t // bm, j * bm + t % bm]
        return rid, pltpu.make_async_copy(
            db_ref.at[jnp.maximum(rid, 0)], rows.at[t], sem)

    def _start(t, _):
        rid, cp = _copy(t)

        @pl.when(rid >= 0)
        def _():
            cp.start()
        return 0

    def _wait(t, _):
        rid, cp = _copy(t)

        @pl.when(rid >= 0)
        def _():
            cp.wait()
        return 0

    jax.lax.fori_loop(0, bq * bm, _start, 0)
    jax.lax.fori_loop(0, bq * bm, _wait, 0)

    # ---- score the tile ---------------------------------------------------
    dp = rows.shape[-1]
    q = q_ref[...].astype(jnp.float32)[:, None, :]     # (bq, 1, dp)
    c = rows[...].astype(jnp.float32).reshape(bq, bm, dp)
    if metric == "l2":
        diff = q - c
        scores = jnp.sum(diff * diff, axis=-1)
    elif metric == "dot":
        scores = -jnp.sum(q * c, axis=-1)
    elif metric == "chi2":
        scores = jnp.sum((q - c) ** 2 / (q + c + EPS), axis=-1)
    elif metric == "cosine":
        qn = q / (jnp.sqrt(jnp.sum(q * q, -1, keepdims=True)) + EPS)
        cn = c / (jnp.sqrt(jnp.sum(c * c, -1, keepdims=True)) + EPS)
        scores = 1.0 - jnp.sum(qn * cn, axis=-1)
    else:
        raise ValueError(metric)
    ids_vec = ids_ref[...]                             # (bq, bm)
    scores = jnp.where(ids_vec >= 0, scores, POS_INF)

    # ---- fold into the running (bq, k) top-k ------------------------------
    bd, bi = select_topk_block(scores, ids_vec, k)
    md, mi = merge_topk(out_d_ref[...], out_i_ref[...], bd, bi, k)
    out_d_ref[...] = md
    out_i_ref[...] = mi


@functools.partial(jax.jit, static_argnames=("k", "metric", "bq", "bm",
                                             "interpret"))
def fused_gather_topk(q: jax.Array, ids: jax.Array, db: jax.Array, k: int,
                      metric: str = "l2", bq: int = 8, bm: int = LANE,
                      interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """q (B, d), ids (B, M) int32 (-1 = invalid), db -> top-k (B, k).

    ``db`` is the (N, 1, dp) ``row_store``.  Never materializes the
    gathered ``(B, M, d)`` candidate tensor: DB rows are DMA'd HBM -> VMEM
    tile-by-tile inside the kernel.
    """
    b, d = q.shape
    dp = db.shape[-1]
    m = ids.shape[1]
    bq = min(bq, max(1, b))
    bm = min(bm, m)
    b_pad = -b % bq
    m_pad = -m % bm
    qp = jnp.pad(q, ((0, b_pad), (0, dp - d)))
    idsp = jnp.pad(ids, ((0, b_pad), (0, m_pad)), constant_values=-1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                     # ids -> SMEM
        grid=((b + b_pad) // bq, (m + m_pad) // bm),
        in_specs=[
            pl.BlockSpec((bq, dp), lambda i, j, *_: (i, 0)),
            pl.BlockSpec((bq, bm), lambda i, j, *_: (i, j)),
            pl.BlockSpec(memory_space=pl.ANY),  # db stays in HBM
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j, *_: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j, *_: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq * bm, 1, dp), db.dtype),
            pltpu.SemaphoreType.DMA,
        ],
    )
    out_d, out_i = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bm=bm, k=k, metric=metric),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b + b_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((b + b_pad, k), jnp.int32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_RERANK,
    )(idsp, qp, idsp, db)
    out_d, out_i = out_d[:b], out_i[:b]
    return out_d, jnp.where(jnp.isinf(out_d), -1, out_i)
