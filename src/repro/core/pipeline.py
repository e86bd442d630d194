"""Fused single-pass forest query pipeline: traverse -> dedup -> rerank.

The paper's query is "descend the L trees, union the leaf sets, rerank
exactly" (§3).  The staged implementation runs that as four dispatches
(traverse, gather_candidates, mask_duplicates, rerank_topk) with two fat HBM
intermediates: the padded (B, M) candidate matrix and — dominating at
M = L*C and paper-scale d — the gathered (B, M, d) candidate tensor.

This module is the production path: ONE jit that
  1. descends all L trees and assembles the (B, M) id matrix (cheap: int32),
  2. masks duplicate ids (the paper's leaf-set union) in-graph,
  3. streams candidate chunks through the fused gather+distance+top-k kernel
     (kernels/fused_query.py) which DMAs DB rows HBM->VMEM tile-by-tile and
     keeps the running (B, k) state on-chip.
The (B, M, d) tensor never exists; per-candidate HBM traffic drops ~3x
(gather-read + write + kernel-read  ->  one kernel-side read).  See
DESIGN.md §4 for the traffic model.

Chunk streaming serves two masters: it bounds the kernel's SMEM-resident id
operand (B * chunk * 4 bytes) and, in ref mode, bounds the per-chunk gather
to (B, chunk, d).  Chunks are merged with the associative top-k merge, so
the result is invariant to chunking (ties broken toward earlier chunks,
matching a single full-width top-k).  Both rerank sources — fp32 rows and
the int8 shortlist — derive their chunk width and batch-slab height from
the SAME helpers (``pick_rerank_chunk`` / ``pick_rows_budget``), so the
two paths cannot disagree on slab shape.

``core.schedule.scheduled_query`` layers per-query probe scheduling on top
of this module (DESIGN.md §14): it calls ``fused_query`` once per doubling
probe width on a shrinking active-query batch, so everything here — chunk
streaming, both rerank sources, the validity mask — composes with the
schedule unchanged.

The staged path stays available as ``staged_query`` — it is the oracle the
fused path is tested against, never a dispatch target.  Likewise the int8
coarse stage's jnp dequant-gather now lives only in
``kernels.ref.fused_gather_topk_int8_ref`` (the oracle); production
dispatches the fused int8 kernel (DESIGN.md §11).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.forest import (Forest, ForestConfig, gather_candidates,
                               gather_candidates_multi, traverse,
                               traverse_forest)
from repro.core.quantized import QuantizedDB
from repro.core.search import mask_duplicates, merge_topk_pairs, rerank_topk
from repro.kernels import ops
from repro.tracing import DEDUP, DESCENT, RELAYOUT, RERANK

# The kernels keep the (B, chunk) id matrix in SMEM.  A v5e has 1 MiB of
# scalar memory; the compiler accepts a 512 KiB id operand and refuses
# 1 MiB, so half of it is the budget.
SMEM_ID_BUDGET_BYTES = 512 * 1024

# Ref-mode (oracle) reranks gather a (B, chunk, d) block per chunk; bound it
# so the full (B, M, d) tensor never exists on any path.
GATHER_BUDGET_BYTES = 1 << 20


def pick_rerank_chunk(b: int, m: int, d: int, chunk: int, bm: int, k: int,
                      mode: str) -> int:
    """THE candidate-axis chunk policy — shared by the fp32 and the int8
    rerank paths so they cannot disagree on slab shape (previously each
    derived its own budget: SMEM-only vs gather-only, and the int8 path
    ignored the SMEM bound entirely because it had no kernel).

    Width = explicit ``chunk`` if given, else the tighter of
      * the SMEM ids bound: B * chunk * 4 B (the kernels' scalar-prefetch
        operand) — always applies;
      * the gather bound: B * chunk * d * 4 B — applies when ``mode``
        resolves to the jnp oracle, which materializes that block per chunk.
    Never below k: the per-chunk top-k needs k columns to select from,
    matching the staged oracle for any k <= M.
    """
    if chunk > 0:
        return min(max(chunk, k), m)
    floor = -(-k // bm) * bm
    by_budget = SMEM_ID_BUDGET_BYTES // (4 * max(b, 1))
    use_pallas, _ = ops._resolve(mode)
    if not use_pallas:
        by_budget = min(by_budget,
                        GATHER_BUDGET_BYTES // (4 * max(b, 1) * max(d, 1)))
    by_budget = max(bm, (by_budget // bm) * bm)
    return min(m, max(by_budget, floor))


def pick_rows_budget(bq: int, bm: int) -> int:
    """Batch-axis slab height: keeps the SMEM ids operand (rows * chunk *
    4 B) within budget even at minimum chunk width, for any B.  Shared by
    both rerank sources (the other half of the slab-shape contract)."""
    return max(bq, SMEM_ID_BUDGET_BYTES // (4 * bm))


def _stream_rerank(queries, ids, k, fold_chunk, *, d: int, chunk: int,
                   bq: int, bm: int, rows_budget: int, mode: str):
    """Chunk- and slab-stream ``fold_chunk`` over the candidate matrix.

    The rerank source ``fold_chunk`` closes over is laid out once, before
    this streamer runs (``ops.rerank_rows``), never per chunk.

    ``fold_chunk(q_rows, id_rows) -> (dists, ids)`` scores one (rows, c)
    id block (the fused kernel or its oracle); chunks merge through the
    associative top-k, batch slabs ride ``lax.map``.  One streamer for both
    rerank sources = one slab shape.
    """
    b, m = ids.shape

    def stream(q_rows, id_rows):
        rows = q_rows.shape[0]
        c = pick_rerank_chunk(rows, m, d, chunk, bm, k, mode)
        if c >= m:
            return fold_chunk(q_rows, id_rows)
        m_pad = -m % c
        idp = jnp.pad(id_rows, ((0, 0), (0, m_pad)), constant_values=-1)
        n_chunks = (m + m_pad) // c

        def body(carry, blk):
            acc_d, acc_i = carry
            ids_blk = jax.lax.dynamic_slice_in_dim(idp, blk * c, c, axis=1)
            dd, ii = fold_chunk(q_rows, ids_blk)
            cat_d = jnp.concatenate([acc_d, dd], axis=1)
            cat_i = jnp.concatenate([acc_i, ii], axis=1)
            return merge_topk_pairs(cat_d, cat_i, k), None

        init = (jnp.full((rows, k), jnp.inf, jnp.float32),
                jnp.full((rows, k), -1, jnp.int32))
        (best_d, best_i), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
        return best_d, jnp.where(jnp.isinf(best_d), -1, best_i)

    if rows_budget <= 0:
        rows_budget = pick_rows_budget(bq, bm)
    if b <= rows_budget:
        return stream(queries, ids)
    b_pad = -b % rows_budget
    qp = jnp.pad(queries, ((0, b_pad), (0, 0)))
    idp = jnp.pad(ids, ((0, b_pad), (0, 0)), constant_values=-1)
    n_slab = (b + b_pad) // rows_budget
    dd, ii = jax.lax.map(
        lambda s: stream(s[0], s[1]),
        (qp.reshape(n_slab, rows_budget, -1),
         idp.reshape(n_slab, rows_budget, m)))
    return dd.reshape(-1, k)[:b], ii.reshape(-1, k)[:b]


def _union_ids(cand_ids, mask, valid, dedup: bool):
    """(B, M) candidate ids with the dead, masked and (``dedup``) repeated
    slots set to -1: the probed leaves' union, as the rerank reads it."""
    if valid is not None:
        mask = mask & valid[jnp.clip(cand_ids, 0, valid.shape[0] - 1)]
    if dedup:
        mask = mask_duplicates(cand_ids, mask)
    return jnp.where(mask, cand_ids, -1)


@functools.partial(jax.jit, static_argnames=("k", "metric", "mode", "dedup",
                                             "chunk", "bq", "bm",
                                             "rows_budget"))
def rerank_fused(queries: jax.Array, cand_ids: jax.Array, mask: jax.Array,
                 db: jax.Array, k: int, metric: str = "l2",
                 mode: str = "auto", dedup: bool = True, chunk: int = 0,
                 bq: int = 8, bm: int = 128, rows_budget: int = 0,
                 valid: jax.Array | None = None
                 ) -> tuple[jax.Array, jax.Array]:
    """Chunk-streamed fused rerank: (B, M) candidate ids -> top-k.

    Drop-in for search.rerank_topk but never materializes (B, M, d); the
    per-chunk work dispatches through the mode policy (Pallas kernel on TPU
    or forced, jnp reference otherwise).

    ``valid`` is an optional (N,) bool row-validity mask (the segmented
    index's tombstone bitmap): candidates whose DB row is dead are folded
    into the existing id/mask path — their slots become id -1 before the
    kernel, so they issue no DMA and never occupy a top-k slot.
    """
    with jax.named_scope(DEDUP):
        ids = _union_ids(cand_ids, mask, valid, dedup)
    with jax.named_scope(RELAYOUT):
        rows = ops.rerank_rows(db, mode)
    with jax.named_scope(RERANK):
        return _stream_rerank(
            queries, ids, k,
            lambda q_rows, id_rows: ops.fused_rerank(
                q_rows, id_rows, rows, k, metric=metric, mode=mode, bq=bq,
                bm=bm),
            d=queries.shape[1], chunk=chunk, bq=bq, bm=bm,
            rows_budget=rows_budget, mode=mode)


@functools.partial(jax.jit, static_argnames=("k", "expand", "metric", "mode",
                                             "dedup", "chunk", "bq", "bm"))
def rerank_fused_quantized(queries: jax.Array, cand_ids: jax.Array,
                           mask: jax.Array, qdb: QuantizedDB, k: int,
                           expand: int = 4, metric: str = "l2",
                           mode: str = "auto", dedup: bool = True,
                           chunk: int = 0, bq: int = 8, bm: int = 128,
                           valid: jax.Array | None = None
                           ) -> tuple[jax.Array, jax.Array]:
    """int8-shortlist-then-fp32 rerank source for the fused pipeline.

    Stage 1 streams candidate chunks through the fused int8 kernel
    (``ops.fused_rerank_int8``): one packed int8 row DMA'd per candidate
    — ~3.5x fewer HBM bytes than fp32 rows — dequantized in VMEM
    registers, kept as a running coarse top-k' (k' = expand*k) scored under ``metric``,
    so the shortlist ranks like the fp32 rerank of record (the
    quantization scheme stays L2-calibrated — DESIGN.md §11/§13).  The
    jnp dequant-gather this
    stage used to run is now the ref-mode oracle only
    (``kernels.ref.fused_gather_topk_int8_ref``).  Stage 2 reranks only
    the (B, k') shortlist exactly against the fp32 rows through the fused
    gather+distance+top-k kernel.  Neither stage materializes (B, M, d),
    and both derive chunk/slab shape from the same shared helpers as the
    fp32 path.

    ``valid`` (optional (N,) bool tombstone mask) is applied at the coarse
    stage, so dead rows never occupy shortlist slots.

    Matches the staged quantized oracle (core.quantized.staged_rerank_quantized)
    exactly on tie-free data.
    """
    with jax.named_scope(DEDUP):
        ids = _union_ids(cand_ids, mask, valid, dedup)
    kp = min(expand * k, ids.shape[1])
    with jax.named_scope(RELAYOUT):
        q8 = ops.rerank_rows_int8(qdb.q, mode)
    with jax.named_scope(RERANK):
        short_d, short_i = _stream_rerank(
            queries, ids, kp,
            lambda q_rows, id_rows: ops.fused_rerank_int8(
                q_rows, id_rows, q8, qdb.scale, kp, metric=metric,
                mode=mode, bq=bq, bm=bm),
            d=queries.shape[1], chunk=chunk, bq=bq, bm=bm, rows_budget=0,
            mode=mode)
    # exact fp32 rerank of the shortlist only (already deduped)
    return rerank_fused(queries, short_i, short_i >= 0, qdb.fp, k,
                        metric=metric, mode=mode, dedup=False, chunk=chunk,
                        bq=bq, bm=bm)


def _candidates(forest: Forest, queries: jax.Array, max_depth: int,
                leaf_pad: int, n_probes: int, mode: str = "auto"
                ) -> tuple[jax.Array, jax.Array]:
    """Traverse + candidate slice, single- or multi-probe.

    Traversal dispatches through :func:`repro.core.forest.traverse_forest`:
    the Pallas descent kernels when the mode policy says so (SMEM kernel
    below the node cap, HBM-resident kernel above — both bitwise-identical
    to the jnp descent for K = 1), the XLA traversal otherwise.  On CPU
    ``"auto"`` resolves to the jnp path, so ``n_probes == 1`` still traces
    the exact pre-multi-probe graph there (the historical bitwise pin);
    wider probes fold into the candidate axis of the same padded (B, M)
    id/mask contract, so nothing downstream changes.
    """
    with jax.named_scope(DESCENT):
        if n_probes <= 1:
            leaves = traverse_forest(forest, queries, max_depth, 1, mode)
            return gather_candidates(forest, leaves, leaf_pad)
        leaves = traverse_forest(forest, queries, max_depth, n_probes, mode)
        return gather_candidates_multi(forest, leaves, leaf_pad)


@functools.partial(jax.jit, static_argnames=("k", "max_depth", "leaf_pad",
                                             "metric", "mode", "dedup",
                                             "chunk", "bq", "bm", "n_probes"))
def _fused_query_jit(forest: Forest, queries: jax.Array, db: jax.Array,
                     k: int, max_depth: int, leaf_pad: int, metric: str,
                     mode: str, dedup: bool, chunk: int, bq: int, bm: int,
                     n_probes: int, valid: jax.Array | None
                     ) -> tuple[jax.Array, jax.Array]:
    cand_ids, mask = _candidates(forest, queries, max_depth, leaf_pad,
                                 n_probes, mode)
    return rerank_fused(queries, cand_ids, mask, db, k, metric=metric,
                        mode=mode, dedup=dedup, chunk=chunk, bq=bq, bm=bm,
                        valid=valid)


@functools.partial(jax.jit, static_argnames=("k", "max_depth", "leaf_pad",
                                             "metric", "mode", "dedup",
                                             "chunk", "bq", "bm", "expand",
                                             "n_probes"))
def _fused_query_quantized_jit(forest: Forest, queries: jax.Array,
                               qdb: QuantizedDB, k: int, max_depth: int,
                               leaf_pad: int, metric: str, mode: str,
                               dedup: bool, chunk: int, bq: int, bm: int,
                               expand: int, n_probes: int,
                               valid: jax.Array | None
                               ) -> tuple[jax.Array, jax.Array]:
    cand_ids, mask = _candidates(forest, queries, max_depth, leaf_pad,
                                 n_probes, mode)
    return rerank_fused_quantized(queries, cand_ids, mask, qdb, k,
                                  expand=expand, metric=metric, mode=mode,
                                  dedup=dedup, chunk=chunk, bq=bq, bm=bm,
                                  valid=valid)


def fused_query(forest: Forest, queries: jax.Array,
                db: jax.Array | QuantizedDB, k: int, cfg: ForestConfig,
                metric: str = "l2", dedup: bool = True, mode: str = "auto",
                chunk: int = 0, bq: int = 8, bm: int = 128, expand: int = 4,
                n_probes: int = 1, valid: jax.Array | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """End-to-end single-jit forest query (the production hot path).

    ``db`` selects the rerank source: a plain (N, d) f32 array reranks every
    candidate exactly through the fused kernel; a ``QuantizedDB`` runs the
    int8 coarse shortlist (k' = ``expand``*k) first and reranks only the
    shortlist in fp32 — same fused pipeline, pluggable rerank source.
    ``n_probes`` > 1 descends to that many most-marginal leaves per tree
    (DESIGN.md §9) — the wider candidate set rides the same (B, M) id/mask
    path, so it composes with every rerank source and with ``valid``.
    ``valid`` optionally masks dead DB rows (segment tombstones).

    Returns (dists (B, k), ids (B, k)); invalid slots: dist +inf, id -1.
    """
    if isinstance(db, QuantizedDB):
        cfg = cfg.resolved(db.fp.shape[0])
        return _fused_query_quantized_jit(forest, queries, db, k,
                                          cfg.max_depth, cfg.leaf_pad, metric,
                                          mode, dedup, chunk, bq, bm, expand,
                                          n_probes, valid)
    cfg = cfg.resolved(db.shape[0])
    return _fused_query_jit(forest, queries, db, k, cfg.max_depth,
                            cfg.leaf_pad, metric, mode, dedup, chunk, bq, bm,
                            n_probes, valid)


def staged_query(forest: Forest, queries: jax.Array, db: jax.Array, k: int,
                 cfg: ForestConfig, metric: str = "l2", dedup: bool = True
                 ) -> tuple[jax.Array, jax.Array]:
    """The pre-fusion pipeline, kept verbatim as the correctness oracle.

    Four dispatches; materializes (B, M) ids + the (B, M, d) gathered
    candidate tensor between stages.  Benchmarked against the fused path in
    benchmarks/fused_vs_staged.py.
    """
    cfg = cfg.resolved(db.shape[0])
    leaves = traverse(forest, queries, cfg.max_depth)
    cand_ids, mask = gather_candidates(forest, leaves, cfg.leaf_pad)
    return rerank_topk(queries, cand_ids, mask, db, k=k, metric=metric,
                       dedup=dedup)
