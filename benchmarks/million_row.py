"""Million-row all-Pallas serving gate: 1M x 784 int8 index, zero fallback.

The PR-6 tentpole claim is that the full query path — tree descent, int8
coarse shortlist, fp32 rerank — stays inside Pallas kernels at a scale
where the old dispatch could not: with ~1M rows the per-tree node
allocation passes the 64k SMEM node cap, which used to force
``ops.traverse_tree`` back to jnp, and the int8 coarse stage used to BE a
jnp dequant-gather.  This benchmark builds a 1M x 784 clustered corpus,
serves it through ``pipeline.fused_query`` with a ``QuantizedDB``, and
checks four things:

  * it builds and serves at all (``build_s``, query ``p50_ms``/``p99_ms``
    — timed in mode="auto": the jnp oracle on CPU runners, the kernels on
    TPU; latency history is same-machine so runner speed cancels),
  * zero jnp fallback in the traced mode="pallas" program: the jaxpr holds
    one pallas_call per stage (descent + int8 coarse + fp32 rerank, >= 3)
    and no (B, M, d)-sized gather — the same inspection
    tests/test_index_api.py runs at unit scale,
  * the MEASURED candidate-bytes ratio: valid (deduped) candidate slots
    counted from the actual mask, int8 bytes = valid*(4w+4) + B*k'*4dp
    (packed coarse rows + scales, then the fp32 shortlist; w and dp are
    the kernels' row-store widths) vs fp32 bytes = valid*4dp; gated at
    <= 0.30 (tools/bench_history.py, lower-is-better),
  * kernel parity on a query subsample, interpret mode: the HBM descent
    kernel bitwise-matches the multiprobe ref (and the SMEM kernel when
    the tree fits under the cap; probe 0 matches the single-probe ref),
    and the int8 kernel's ids match its oracle — ``bitwise_equal`` is a
    hard CI gate.

Usage:
  PYTHONPATH=src python -m benchmarks.million_row [--smoke]

--smoke keeps N = 1M (the point of the gate) and trims query iterations.
Writes artifacts/BENCH_million_row.json (uploaded + gated by CI
bench-smoke) and merges into artifacts/bench_results.json.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import record
from repro.core import ForestConfig, build_forest
from repro.core.forest import gather_candidates_multi, traverse_forest
from repro.core.pipeline import fused_query
from repro.core.quantized import quantize_db
from repro.core.search import mask_duplicates
from repro.data.synthetic import clustered_gaussians
from repro.kernels import ref
from repro.kernels.forest_traverse import SMEM_NODE_CAP, forest_traverse
from repro.kernels.forest_traverse_hbm import forest_traverse_hbm
from repro.kernels.fused_query import row_store
from repro.kernels.fused_query_int8 import (fused_gather_topk_int8,
                                            pack_int8_rows)

ARTIFACT = os.path.join(os.path.dirname(__file__), "..", "artifacts",
                        "BENCH_million_row.json")


def _walk_jaxpr(jaxpr, fn):
    for eqn in jaxpr.eqns:
        fn(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(sub, "eqns"):
                    _walk_jaxpr(sub, fn)
                elif hasattr(sub, "jaxpr"):
                    _walk_jaxpr(sub.jaxpr, fn)


def _inspect(jaxpr) -> tuple[int, int]:
    """-> (pallas_call count, largest gather output in elements)."""
    n_pallas, worst = 0, 0

    def see(eqn):
        nonlocal n_pallas, worst
        if eqn.primitive.name == "pallas_call":
            n_pallas += 1
        if eqn.primitive.name == "gather":
            for ov in eqn.outvars:
                worst = max(worst, int(np.prod(ov.aval.shape)))

    _walk_jaxpr(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr, see)
    return n_pallas, worst


def _traversal_parity(forest, rcfg, q, n_probes: int) -> bool:
    """HBM kernel == multiprobe ref per tree (bitwise), probe 0 == the
    single-probe ref, and == the SMEM kernel where that kernel is legal."""
    feat = forest.proj_idx[:, :, 0]
    hbm = np.asarray(forest_traverse_hbm(
        feat, forest.thresh, forest.child_base, q, rcfg.max_depth,
        interpret=True, n_probes=n_probes))
    ok = True
    for t in range(forest.n_trees):
        args = (feat[t], forest.thresh[t], forest.child_base[t], q,
                rcfg.max_depth)
        want = np.asarray(ref.forest_traverse_multiprobe_ref(*args, n_probes))
        ok &= bool((hbm[t] == want).all())
        single = np.asarray(ref.forest_traverse_ref(*args))
        ok &= bool((hbm[t, :, 0] == single).all())
        if forest.max_nodes <= SMEM_NODE_CAP:
            smem = np.asarray(forest_traverse(*args, interpret=True,
                                              n_probes=n_probes))
            ok &= bool((hbm[t] == smem).all())
    return ok


def _int8_parity(qdb, q, seed: int = 0) -> bool:
    """Pallas int8 kernel ids == the jnp dequant-gather oracle on a
    candidate subsample drawn from the full 1M-row table."""
    rng = np.random.default_rng(seed)
    n = qdb.q.shape[0]
    ids = rng.integers(0, n, size=(q.shape[0], 128)).astype(np.int32)
    ids[rng.uniform(size=ids.shape) < 0.1] = -1
    ids = jnp.asarray(ids)
    pd, pi = fused_gather_topk_int8(q, ids, pack_int8_rows(qdb.q),
                                    qdb.scale, 10, interpret=True)
    rd, ri = ref.fused_gather_topk_int8_ref(q, ids, qdb.q, qdb.scale, 10)
    ids_ok = bool((np.asarray(pi) == np.asarray(ri)).all())
    d_ok = bool(np.allclose(np.asarray(pd), np.asarray(rd), rtol=2e-5,
                            atol=2e-5, equal_nan=True))
    return ids_ok and d_ok


def run(n: int, d: int, n_trees: int, capacity: int, n_probes: int, b: int,
        k: int, expand: int, iters: int, parity_b: int) -> dict:
    x = jnp.asarray(clustered_gaussians(n, d, n_clusters=1024, seed=0))
    queries = jnp.asarray(clustered_gaussians(b, d, n_clusters=1024, seed=1))
    cfg = ForestConfig(n_trees=n_trees, capacity=capacity, split_ratio=0.3)
    rcfg = cfg.resolved(n)
    print(f"  corpus: clustered n={n} d={d} L={n_trees} C={capacity} "
          f"P={n_probes} nodes={rcfg.max_nodes} "
          f"(smem_cap={SMEM_NODE_CAP}) depth={rcfg.max_depth}")

    t0 = time.perf_counter()
    forest = jax.block_until_ready(build_forest(jax.random.key(0), x, cfg))
    build_s = time.perf_counter() - t0
    qdb = quantize_db(x)

    # --- serving latency (mode="auto": what this runner actually executes)
    def serve(q):
        return fused_query(forest, q, qdb, k, cfg, n_probes=n_probes)

    jax.block_until_ready(serve(queries))          # compile
    lat = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(serve(queries))
        lat.append(time.perf_counter() - t0)
    p50_ms = float(np.percentile(lat, 50) * 1e3)
    p99_ms = float(np.percentile(lat, 99) * 1e3)

    # --- measured candidate bytes: count the VALID deduped slots the rerank
    # actually scores, from the same traversal the pipeline runs
    leaves = traverse_forest(forest, queries, rcfg.max_depth, n_probes)
    cand_ids, mask = gather_candidates_multi(forest, leaves, rcfg.leaf_pad)
    valid = int(np.asarray(mask_duplicates(cand_ids, mask)).sum())
    m = int(cand_ids.shape[1])
    kp = min(expand * k, m)
    # bytes per DMA'd row, from the kernels' row stores: the packed int8
    # row (+ its 4-byte scale) vs the lane-padded fp32 row
    int8_row = 4 * pack_int8_rows(qdb.q[:1]).shape[-1] + 4
    fp32_row = 4 * row_store(x[:1]).shape[-1]
    int8_bytes = valid * int8_row + b * kp * fp32_row
    fp32_bytes = valid * fp32_row
    bytes_ratio = int8_bytes / fp32_bytes

    # --- zero-fallback inspection of the traced mode="pallas" program
    def pallas_serve(f_, q_, qdb_):
        return fused_query(f_, q_, qdb_, k, cfg, mode="pallas",
                           n_probes=n_probes)

    n_pallas, worst_gather = _inspect(
        jax.make_jaxpr(pallas_serve)(forest, queries, qdb))
    no_fallback = n_pallas >= 3 and worst_gather < b * m * d

    # --- kernel parity (interpret mode) on a query subsample
    qs = queries[:parity_b]
    trav_ok = _traversal_parity(forest, rcfg, qs, n_probes)
    int8_ok = _int8_parity(qdb, qs)

    out = dict(
        n=n, d=d, n_trees=n_trees, capacity=capacity, n_probes=n_probes,
        b=b, k=k, expand=expand,
        max_nodes=rcfg.max_nodes, smem_cap=SMEM_NODE_CAP,
        above_smem_cap=bool(rcfg.max_nodes > SMEM_NODE_CAP),
        build_s=round(build_s, 2),
        p50_ms=round(p50_ms, 2), p99_ms=round(p99_ms, 2),
        valid_candidates=valid,
        int8_candidate_bytes=int(int8_bytes),
        fp32_candidate_bytes=int(fp32_bytes),
        bytes_ratio=round(bytes_ratio, 4),
        n_pallas_calls=int(n_pallas),
        worst_gather_elems=int(worst_gather),
        no_jnp_fallback=bool(no_fallback),
        traversal_bitwise_equal=bool(trav_ok),
        int8_kernel_ids_match=bool(int8_ok),
        bitwise_equal=bool(trav_ok and int8_ok),
    )
    print(f"  build {build_s:.1f}s | query p50 {p50_ms:.1f}ms "
          f"p99 {p99_ms:.1f}ms (B={b}) | bytes {bytes_ratio:.3f}x "
          f"({valid} valid cands) | pallas_calls={n_pallas} "
          f"fallback_free={no_fallback} | traversal={trav_ok} "
          f"int8={int8_ok}")
    assert no_fallback, "mode='pallas' program still contains jnp fallback"
    return out


def main(smoke: bool = False) -> dict:
    print(f"[million_row] smoke={smoke}")
    # N stays at 1M in smoke — the whole point is the above-cap tree;
    # capacity 128 puts the node allocation past the 64k SMEM cap.
    if smoke:
        out = run(n=1_000_000, d=784, n_trees=2, capacity=128, n_probes=8,
                  b=64, k=10, expand=4, iters=8, parity_b=16)
    else:
        out = run(n=1_000_000, d=784, n_trees=4, capacity=128, n_probes=8,
                  b=256, k=10, expand=4, iters=30, parity_b=32)
    out.update(smoke=smoke, backend=jax.default_backend())

    os.makedirs(os.path.dirname(os.path.abspath(ARTIFACT)), exist_ok=True)
    with open(ARTIFACT, "w") as f:
        json.dump(out, f, indent=1)
    record({}, "million_row", out)
    print(f"  -> {os.path.relpath(ARTIFACT)} bytes_ratio="
          f"{out['bytes_ratio']} bitwise={out['bitwise_equal']}")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="CI-size run")
    a = ap.parse_args()
    main(smoke=a.smoke)
