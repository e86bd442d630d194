"""Public jit'd wrappers around the Pallas kernels with backend dispatch.

Policy (``mode``):
  * "auto"   — Pallas-compiled on TPU, jnp reference elsewhere (CPU containers
               run the oracle; the kernels are validated via interpret mode in
               the test suite).
  * "pallas" — force the Pallas kernel (interpret=True off-TPU).
  * "ref"    — force the jnp oracle.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax

from repro.kernels import chi2_topk as _chi2
from repro.kernels import distance_topk as _dist
from repro.kernels import embedding_bag as _bag
from repro.kernels import forest_traverse as _trav
from repro.kernels import forest_traverse_hbm as _trav_hbm
from repro.kernels import fused_query as _fused
from repro.kernels import fused_query_int8 as _fused_i8
from repro.kernels import matmul_topk as _mm
from repro.kernels import ref as _ref
from repro.kernels.forest_traverse import SMEM_NODE_CAP

Mode = Literal["auto", "pallas", "ref"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(mode: Mode) -> tuple[bool, bool]:
    """-> (use_pallas, interpret)."""
    if mode == "ref":
        return False, False
    if mode == "pallas":
        return True, not _on_tpu()
    return (True, False) if _on_tpu() else (False, False)


def topk(q, db, k: int, metric: str = "l2", mode: Mode = "auto"):
    """Brute-force fused scoring + top-k. metric in {l2, dot, chi2}."""
    use_pallas, interp = _resolve(mode)
    if metric == "chi2":
        if use_pallas:
            return _chi2.chi2_topk(q, db, k, interpret=interp)
        return _ref.chi2_topk_ref(q, db, k)
    if use_pallas:
        return _mm.matmul_topk(q, db, k, metric=metric, interpret=interp)
    return _ref.matmul_topk_ref(q, db, k, metric=metric)


def rerank_candidates(q, cand, ids, mask, k: int, metric: str = "l2",
                      mode: Mode = "auto"):
    """Fused gathered-candidate distance + top-k."""
    use_pallas, interp = _resolve(mode)
    if use_pallas:
        return _dist.distance_topk(q, cand, ids, mask, k, metric=metric,
                                   interpret=interp)
    return _ref.distance_topk_ref(q, cand, ids, mask, k, metric=metric)


def rerank_rows(db, mode: Mode = "auto"):
    """The fp32 rerank source in the layout ``mode``'s implementation
    reads: the kernel's (N, 1, dp) row store, or the plain (N, d) rows.
    The one place that picks the layout: callers build it once per rerank
    and pass it to ``fused_rerank`` with the same ``mode``."""
    use_pallas, _ = _resolve(mode)
    return _fused.row_store(db) if use_pallas else db


def rerank_rows_int8(q8, mode: Mode = "auto"):
    """The int8 rows in the layout ``mode``'s implementation reads (the
    kernel's packed (N, 1, w) int32 store, or plain (N, d) int8)."""
    use_pallas, _ = _resolve(mode)
    return _fused_i8.pack_int8_rows(q8) if use_pallas else q8


def fused_rerank(q, ids, db, k: int, metric: str = "l2", mode: Mode = "auto",
                 bq: int = 8, bm: int = 128):
    """Fused DB-row gather + distance + top-k over one candidate chunk.

    ids (B, M) int32 with -1 marking invalid slots.  Unlike
    ``rerank_candidates`` this takes the raw DB — the (B, M, d) gathered
    tensor never materializes in HBM (see kernels/fused_query.py).  ``db``
    is ``rerank_rows(rows, mode)``.
    """
    use_pallas, interp = _resolve(mode)
    if use_pallas:
        return _fused.fused_gather_topk(q, ids, db, k, metric=metric, bq=bq,
                                        bm=bm, interpret=interp)
    return _ref.fused_gather_topk_ref(q, ids, db, k, metric=metric)


def fused_rerank_int8(q, ids, q8, scale, k: int, metric: str = "l2",
                      mode: Mode = "auto", bq: int = 8, bm: int = 128):
    """Fused int8-row gather + dequantize + coarse top-k over one chunk.

    ids (B, M) int32 with -1 marking invalid slots; q8 is
    ``rerank_rows_int8(rows, mode)`` of (N, d) int8 rows with per-row f32
    scales; ``metric``
    scores the dequantized rows so the coarse shortlist ranks like the fp32
    rerank of record.  The Pallas kernel DMAs one packed int8 row per
    candidate (kernels/fused_query_int8.py); the ref branch is the retired
    jnp dequant-gather, kept as the oracle.
    """
    use_pallas, interp = _resolve(mode)
    if use_pallas:
        return _fused_i8.fused_gather_topk_int8(q, ids, q8, scale, k,
                                                metric=metric, bq=bq,
                                                bm=bm, interpret=interp)
    return _ref.fused_gather_topk_int8_ref(q, ids, q8, scale, k,
                                           metric=metric)


def embedding_bag(ids, weights, table, mode: Mode = "auto"):
    """Weighted multi-hot embedding-bag (B, H) x (V, D) -> (B, D)."""
    use_pallas, interp = _resolve(mode)
    if use_pallas:
        return _bag.embedding_bag(ids, weights, table, interpret=interp)
    return _ref.embedding_bag_ref(ids, weights, table)


def traverse_tree(feat, thresh, child_base, queries, max_depth: int,
                  mode: Mode = "auto", n_probes: int = 1,
                  kernel: str = "auto"):
    """Single-tree batched descent -> leaf ids.

    (B,) for ``n_probes == 1`` (the historical contract); (B, n_probes)
    multi-probe leaf ids (primary first, then ascending margin, -1 for
    absent probes) otherwise.

    ``kernel`` selects the Pallas variant: "smem" keeps the tree arrays in
    scalar memory (capped at ``SMEM_NODE_CAP`` allocated nodes), "hbm"
    streams node records from HBM with double-buffered DMA (no cap,
    DESIGN.md §11); "auto" picks by tree size — so the Pallas path never
    falls back to jnp on large trees.  Which variant is faster on a chip
    has not been measured.  Both variants are bitwise-identical to each
    other and to the refs.
    """
    use_pallas, interp = _resolve(mode)
    if use_pallas:
        if kernel == "auto":
            kernel = "smem" if feat.shape[0] <= SMEM_NODE_CAP else "hbm"
        if kernel == "hbm":
            return _trav_hbm.forest_traverse_hbm_tree(
                feat, thresh, child_base, queries, max_depth,
                interpret=interp, n_probes=n_probes)
        return _trav.forest_traverse(feat, thresh, child_base, queries,
                                     max_depth, interpret=interp,
                                     n_probes=n_probes)
    if n_probes == 1:
        return _ref.forest_traverse_ref(feat, thresh, child_base, queries,
                                        max_depth)
    return _ref.forest_traverse_multiprobe_ref(feat, thresh, child_base,
                                               queries, max_depth, n_probes)
