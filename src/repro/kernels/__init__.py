"""Pallas TPU kernels for the paper's compute hot-spots + jnp oracles.

Kernels (each <name>.py has the pl.pallas_call; ref.py has the oracle):
  * matmul_topk    -- fused MXU scoring (l2/dot) + streaming top-k
  * chi2_topk      -- fused chi-square scoring + streaming top-k
  * distance_topk  -- fused per-query candidate rerank + top-k (pre-gathered)
  * fused_query    -- DMA row gather + distance + running top-k in one pass
                      (the forest-query hot path; no (B, M, d) intermediate)
  * fused_query_int8 -- the same fused pass over int8 rows (packed four to
                      an int32 word) + per-row scales, dequantized in VMEM
                      registers (the quantized shortlist stage, DESIGN.md §11)
  * embedding_bag  -- scalar-prefetch gather + weighted segment-sum
  * forest_traverse-- scalar partition-tree descent (SMEM-resident tree,
                      capped at SMEM_NODE_CAP nodes); n_probes > 1 adds the
                      multi-probe expansion (DESIGN.md §9)
  * forest_traverse_hbm -- the uncapped variant on the query path: tree
                      records stay in HBM, fetched per level with
                      double-buffered DMA (DESIGN.md §11); bitwise-matches
                      the SMEM kernel
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
