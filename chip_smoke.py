"""Smoke run of the search path on a TPU, through the entry points users call.

One chip (the default): the paper's MNIST-784 deployment
(``repro/configs/rpf_mnist784.py``: 60 000 x 784 f32 rows generated by
``data.synthetic.mnist_like`` from ``--seed``; L = 80, C = 12, r = 0.3,
K = 1, l2) is built with ``build_index`` twice, as ``rpf`` and as
``rpf+int8``.  For each backend and each operating point
(``SearchParams(k=10)`` and ``n_probes=4``) a few hundred single-query
requests go through a ``ServingRuntime``, and the answers are checked
against two references:

  * the same index searched with ``mode="ref"`` (the jnp oracles, on the
    chip): ids must agree except where two ids are tied, which means their
    exact distances (recomputed on the host in float64) differ by at most
    ``TIE_RTOL * d + TIE_ATOL`` (f32 rounding of a 784-term sum); the
    served distances must match the oracle's within the same tolerance;
  * recall@10 against ``exact_knn`` (matmuls at ``HIGHEST`` precision): the
    served recall may not fall below the ``mode="ref"`` recall by more than
    ``RECALL_MARGIN``.

It also checks the descent kernel bitwise against the jnp descent, and
that the query program the runtime dispatches holds one ``tpu_custom_call``
per kernel stage (descent + fused rerank; + the int8 shortlist for
``rpf+int8``): the Pallas kernels ran, nothing fell back to the reference.

``--chips 4`` runs only the mesh path and what it is compared with: a
``ShardedIndex`` over a (2, 2) ("data", "model") mesh of four chips, built
over the same corpus, against the single-device ``Index`` oracle — bitwise
in the filtered brute-force regime, recall in the forest regime.

The last line of standard output is one JSON object naming the device; it
is printed only when every check passed.  Without a TPU the script exits
non-zero before doing any work.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # the four-chip mesh path
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

K = 10
N_QUERIES = 320          # single-query requests per operating point
MAX_BATCH = 64           # the runtime's fixed batch shape
TIMEOUT_S = 300.0        # wait for all requests of one operating point
# kernel stages in one compiled query program: descent + fused rerank,
# and the int8 shortlist before the rerank for rpf+int8
KERNEL_STAGES = {"rpf": 2, "rpf+int8": 3}
TIE_RTOL = 1e-5          # relative f32 rounding allowance of a distance
TIE_ATOL = 1e-6          # absolute allowance near zero distance
RECALL_MARGIN = 0.005    # served recall@10 may trail the ref path by this


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class Checks:
    """Collects named pass/fail results; ``ok`` only if all passed."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        log(f"{'PASS' if ok else 'FAIL'} {name}" + (f": {detail}" if detail
                                                     else ""))
        if not ok:
            self.failed.append(name)
        return ok


def exact_dist64(queries: np.ndarray, db: np.ndarray,
                 ids: np.ndarray) -> np.ndarray:
    """float64 squared-l2 distance of each returned id (inf for -1)."""
    safe = np.clip(ids, 0, None)
    diff = db[safe].astype(np.float64) - queries[:, None, :].astype(np.float64)
    return np.where(ids >= 0, np.sum(diff * diff, axis=-1), np.inf)


def compare_with_ref(queries, db, got_d, got_i, ref_d, ref_i) -> dict:
    """Served vs mode="ref" answers under the stated tie tolerance."""
    tol = TIE_RTOL * np.abs(ref_d) + TIE_ATOL
    dist_ok = bool(np.all(np.abs(got_d - ref_d) <= tol))
    differ = got_i != ref_i
    e_got = exact_dist64(queries, db, got_i)
    e_ref = exact_dist64(queries, db, ref_i)
    tied = np.abs(e_got - e_ref) <= TIE_RTOL * np.abs(e_ref) + TIE_ATOL
    honest = np.abs(got_d - e_got) <= TIE_RTOL * np.abs(e_got) + TIE_ATOL
    return {"dist_ok": dist_ok,
            "n_differ": int(differ.sum()),
            "n_untied": int((differ & ~tied).sum()),
            "n_dishonest": int((~honest).sum()),
            "max_abs_dist_err": float(np.max(np.abs(got_d - ref_d)))}


def recall_at_k(ids: np.ndarray, true_ids: np.ndarray) -> float:
    return float(np.mean([np.isin(t, i).mean()
                          for i, t in zip(ids, true_ids)]))


def serve_all(runtime, queries: np.ndarray, timeout_s: float):
    """Submit every query as its own request; wait for all of them."""
    reqs = [runtime.submit(q) for q in queries]
    deadline = time.perf_counter() + timeout_s
    dists, ids, lat = [], [], []
    n_failed = n_timeout = 0
    first_error = None
    for r in reqs:
        if not r.event.wait(max(0.0, deadline - time.perf_counter())):
            n_timeout += 1
            continue
        if r.error is not None:
            n_failed += 1
            first_error = first_error or r.error
            continue
        dists.append(np.asarray(r.result[0]))
        ids.append(np.asarray(r.result[1]))
        lat.append((r.done_t - r.enqueue_t) * 1e3)
    return (np.stack(dists) if dists else None,
            np.stack(ids) if ids else None,
            n_failed, n_timeout, first_error, np.asarray(lat))


def corpus(args):
    from repro.configs import rpf_mnist784 as deployment
    from repro.data.synthetic import mnist_like
    t0 = time.perf_counter()
    db, _, queries, _ = mnist_like(n=deployment.N_DB, n_test=N_QUERIES,
                                   d=deployment.DIM, seed=args.seed)
    log(f"corpus mnist_like n={db.shape[0]} d={db.shape[1]} "
        f"queries={queries.shape[0]} seed={args.seed} "
        f"({time.perf_counter() - t0:.2f}s on the host)")
    return deployment, db, queries


def dispatched_program(runtime, queries: np.ndarray) -> str:
    """Compiled text of the query program ``runtime`` dispatches: search
    its index as the runtime does (a full batch, the first ladder rung),
    record the jitted pipeline call that search makes, compile that call."""
    from repro.core import pipeline
    names = ("_fused_query_jit", "_fused_query_quantized_jit")
    jitted = {name: getattr(pipeline, name) for name in names}
    calls = []

    def recording(fn):
        def call(*a, **kw):
            calls.append((fn, a, kw))
            return fn(*a, **kw)
        return call

    try:
        for name, fn in jitted.items():
            setattr(pipeline, name, recording(fn))
        runtime.index.search(queries[:runtime.max_batch], runtime.ladder[0])
    finally:
        for name, fn in jitted.items():
            setattr(pipeline, name, fn)
    if len(calls) != 1:
        raise RuntimeError(f"expected one query program, saw {len(calls)}")
    fn, a, kw = calls[0]
    return fn.lower(*a, **kw).compile().as_text()


def run_one_chip(args, check: Checks) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.forest import traverse_forest
    from repro.core.knn import exact_knn
    from repro.index import IndexSpec, SearchParams, build_index
    from repro.serve.runtime import ServingRuntime

    deployment, db, queries = corpus(args)
    t0 = time.perf_counter()
    true_d, true_i = jax.block_until_ready(
        exact_knn(jnp.asarray(queries), jnp.asarray(db), k=K,
                  metric=deployment.METRIC))
    true_i = np.asarray(true_i)
    log(f"exact_knn (HIGHEST precision) {time.perf_counter() - t0:.2f}s")

    for backend in ("rpf", "rpf+int8"):
        spec = IndexSpec(backend=backend, forest=deployment.CONFIG,
                         seed=args.seed)
        t0 = time.perf_counter()
        index = build_index(jax.random.key(args.seed), db, spec)
        jax.block_until_ready(index.forest)
        log(f"{backend}: built {db.shape[0]} x {db.shape[1]} "
            f"L={spec.forest.n_trees} C={spec.forest.capacity} "
            f"r={spec.forest.split_ratio} in "
            f"{time.perf_counter() - t0:.2f}s (compile included)")
        cfg = spec.forest.resolved(db.shape[0])

        for n_probes in (1, 4):
            params = SearchParams(k=K, metric=deployment.METRIC,
                                  n_probes=n_probes)
            tag = f"{backend} n_probes={n_probes}"

            # the descent kernel, bitwise against the jnp descent
            qb = jnp.asarray(queries[:MAX_BATCH])
            leaves_k = traverse_forest(index.forest, qb, cfg.max_depth,
                                       n_probes, "auto")
            leaves_r = traverse_forest(index.forest, qb, cfg.max_depth,
                                       n_probes, "ref")
            check(f"{tag} descent kernel == jnp descent (bitwise)",
                  bool(np.array_equal(np.asarray(leaves_k),
                                      np.asarray(leaves_r))))

            t0 = time.perf_counter()
            runtime = ServingRuntime(index, params=params, degrade=False,
                                     max_batch=MAX_BATCH)
            log(f"{tag}: runtime warmup (compile + one batch) "
                f"{time.perf_counter() - t0:.2f}s")
            try:
                # the program the runtime serves must hold every kernel
                n_calls = dispatched_program(runtime, queries).count(
                    "tpu_custom_call")
                check(f"{tag} compiled query program",
                      n_calls >= KERNEL_STAGES[backend],
                      f"{n_calls} tpu_custom_call op(s), "
                      f"{KERNEL_STAGES[backend]} kernel stages expected")
                t0 = time.perf_counter()
                got_d, got_i, n_failed, n_timeout, err, lat = serve_all(
                    runtime, queries, TIMEOUT_S)
                wall = time.perf_counter() - t0
            finally:
                runtime.stop()
            check(f"{tag} requests", n_failed == 0 and n_timeout == 0,
                  f"{len(queries) - n_failed - n_timeout}/{len(queries)} ok, "
                  f"{n_failed} failed, {n_timeout} timed out"
                  + (f"; first error: {err!r}" if err else ""))
            if got_i is None or n_failed or n_timeout:
                continue
            log(f"{tag}: served {len(queries)} requests in {wall:.3f}s "
                f"(host clock, max_batch={MAX_BATCH}); latency p50 "
                f"{np.percentile(lat, 50):.2f}ms p99 "
                f"{np.percentile(lat, 99):.2f}ms")

            ref_d, ref_i = index.search(
                queries, dataclasses.replace(params, mode="ref"))
            ref_d, ref_i = np.asarray(ref_d), np.asarray(ref_i)
            cmp = compare_with_ref(queries, db, got_d, got_i, ref_d, ref_i)
            check(f"{tag} ids vs mode=ref", cmp["n_untied"] == 0,
                  f"{cmp['n_differ']} of {got_i.size} slots differ, "
                  f"{cmp['n_untied']} not explained by a tie "
                  f"(tie = exact distances within {TIE_RTOL:g}*d + "
                  f"{TIE_ATOL:g})")
            check(f"{tag} distances vs mode=ref",
                  cmp["dist_ok"] and cmp["n_dishonest"] == 0,
                  f"max |d - d_ref| {cmp['max_abs_dist_err']:.3e}; "
                  f"{cmp['n_dishonest']} distance(s) off their exact "
                  f"float64 value")
            r_got = recall_at_k(got_i, true_i)
            r_ref = recall_at_k(ref_i, true_i)
            check(f"{tag} recall@{K}", r_got >= r_ref - RECALL_MARGIN,
                  f"served {r_got:.4f}, mode=ref {r_ref:.4f}, margin "
                  f"{RECALL_MARGIN}")
        del index


def run_four_chips(args, check: Checks) -> None:
    import jax

    from repro import compat
    from repro.core.knn import exact_knn
    from repro.core.sharded_index import ShardedIndex
    from repro.filter import Eq
    from repro.index import IndexSpec, SearchParams, build_index

    deployment, db, queries = corpus(args)
    n = db.shape[0]
    # a categorical column with 40 values: one value matches n/40 rows,
    # under the brute-force threshold (the filtered brute regime)
    meta = {"shop": np.asarray([f"s{i % 40}" for i in range(n)])}
    spec = IndexSpec(backend="rpf", forest=deployment.CONFIG, seed=args.seed)
    t0 = time.perf_counter()
    index = build_index(jax.random.key(args.seed), db, spec, metadata=meta)
    jax.block_until_ready(index.forest)
    log(f"single-device oracle index built in "
        f"{time.perf_counter() - t0:.2f}s")

    mesh = compat.make_mesh((2, 2), ("data", "model"))
    t0 = time.perf_counter()
    sx = ShardedIndex(index, mesh)
    jax.block_until_ready(sx._forest.forest)
    log(f"ShardedIndex on mesh {dict(mesh.shape)} built in "
        f"{time.perf_counter() - t0:.2f}s: {sx.stats()}")

    # rows and trees must really be spread over the four devices
    tree_shards = {s.device.id: s.data.shape
                   for s in sx._forest.forest.thresh.addressable_shards}
    row_shards = {s.device.id: s.data.shape
                  for s in sx._db.addressable_shards}
    log(f"tree shards per device {tree_shards}; row shards per device "
        f"{row_shards}")
    check("trees spread over 4 devices",
          len(tree_shards) == 4 and all(
              s[2] == spec.forest.n_trees // 2
              for s in tree_shards.values()))
    check("rows spread over the data axis",
          len(row_shards) == 4 and all(
              s[0] == sx._db.shape[0] // 2 for s in row_shards.values()))

    # brute regime: both paths exact-scan the same matching rows -> bitwise
    pb = SearchParams(k=K, filter=Eq("shop", "s1"))
    hd, hi = map(np.asarray, index.search(queries, pb))
    sd, si = map(np.asarray, sx.search(queries, pb))
    check("filtered brute regime, sharded == single-device (bitwise)",
          bool(np.array_equal(si, hi) and np.array_equal(sd, hd)),
          f"{int((si != hi).sum())} id slot(s) differ")

    _, true_i = exact_knn(queries, db, k=K, metric=deployment.METRIC)
    true_i = np.asarray(true_i)
    for n_probes in (1, 4):
        p = SearchParams(k=K, n_probes=n_probes)
        t0 = time.perf_counter()
        md, mi = map(np.asarray, sx.search(queries, p))
        t_mesh = time.perf_counter() - t0
        hd, hi = map(np.asarray, index.search(queries, p))
        r_mesh, r_host = recall_at_k(mi, true_i), recall_at_k(hi, true_i)
        check(f"forest regime n_probes={n_probes} recall@{K}",
              r_mesh >= r_host - RECALL_MARGIN,
              f"sharded {r_mesh:.4f}, single-device {r_host:.4f}, margin "
              f"{RECALL_MARGIN} (first sharded call {t_mesh:.2f}s, "
              f"compile included)")
        check(f"forest regime n_probes={n_probes} sorted, finite",
              bool(np.isfinite(md).all()
                   and (np.diff(md, axis=1) >= 0).all()))
        step = sx._step(p)
        n_calls = step.lower(sx._forest, queries, sx._db, sx._live).compile(
        ).as_text().count("tpu_custom_call")
        check(f"forest regime n_probes={n_probes} compiled mesh step",
              n_calls >= 1, f"{n_calls} tpu_custom_call op(s)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend is "
              f"{jax.default_backend()!r}); nothing was run",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    dev = devices[0]
    log(f"device_kind={dev.device_kind} platform={dev.platform} "
        f"count={len(devices)} jax={jax.__version__}")

    check = Checks()
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(args, check)
    else:
        run_one_chip(args, check)
    log(f"total {time.perf_counter() - t0:.2f}s")
    for d in devices[:args.chips]:
        stats = d.memory_stats() or {}
        log(f"device {d.id} peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use', 'not reported')}")
    if check.failed:
        log(f"{len(check.failed)} check(s) failed: {check.failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
