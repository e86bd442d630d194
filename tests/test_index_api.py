"""Unified index API (repro.index): the single public search surface.

Covers the PR-2 acceptance criteria:
  * every registered backend x SearchParams combination matches its staged
    oracle (quantized + adaptive-wave compositions included),
  * save/load roundtrips return bitwise-identical search results,
  * the rpf+int8 and adaptive paths dispatch through the fused pipeline —
    no (B, M, d)-sized gather appears in their jaxprs,
  * the old entry points (query_forest / query_forest_quantized /
    adaptive_query) remain oracle-identical shims,
  * serving-layer contracts: fixed batch shapes (pad to max_batch) and the
    bounded latency ring buffer,
  * vectorized LSH batch candidates == the scalar per-point path.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ForestConfig, exact_knn
from repro.core.adaptive import _merge_dedup, adaptive_query
from repro.core.forest import gather_candidates, traverse
from repro.core.lsh import CascadedLSH
from repro.core.pipeline import (fused_query, rerank_fused_quantized,
                                 staged_query)
from repro.core.quantized import (query_forest_quantized,
                                  staged_query_quantized,
                                  staged_rerank_quantized)
from repro.core.search import rerank_topk
from repro.data.synthetic import clustered_gaussians
from repro.index import (IndexSpec, SearchParams, available_backends,
                         build_index, load_index)

N_DB, N_Q, DIM = 2500, 24, 24
FOREST = ForestConfig(n_trees=12, capacity=10)
LSH_RADII = (0.5, 1.0, 2.0)


@pytest.fixture(scope="module")
def corpus():
    db = clustered_gaussians(N_DB, DIM, n_clusters=16, seed=11)
    db = np.abs(db)            # non-negative so chi2 composes too
    db /= np.linalg.norm(db, axis=1, keepdims=True)
    rng = np.random.default_rng(5)
    q = db[:N_Q] + 0.01 * rng.normal(size=(N_Q, DIM)).astype(np.float32)
    return db, np.abs(q)


def _spec(backend):
    return IndexSpec(backend=backend, forest=FOREST, lsh_radii=LSH_RADII,
                     lsh_tables=8, lsh_bits=8, seed=0)


def _index(corpus, backend):
    return build_index(jax.random.key(0), corpus[0], _spec(backend))


def _assert_match(got, want):
    gd, gi = np.asarray(got[0]), np.asarray(got[1])
    wd, wi = np.asarray(want[0]), np.asarray(want[1])
    assert (gi == wi).all(), f"id mismatch:\n{gi}\nvs\n{wi}"
    finite = np.isfinite(wd)
    assert (finite == np.isfinite(gd)).all()
    np.testing.assert_allclose(gd[finite], wd[finite], rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# staged oracles (pre-fusion compositions, materialize (B, M, d))
# ---------------------------------------------------------------------------


def _staged_adaptive(forest, q, db, k, cfg, wave, tol, qdb=None, expand=4,
                     metric="l2"):
    """The pre-fusion adaptive wave loop (rerank_topk / staged quantized)."""
    n = (qdb.fp if qdb is not None else db).shape[0]
    cfg = cfg.resolved(n)
    best_d = jnp.full((q.shape[0], k), jnp.inf)
    best_i = jnp.full((q.shape[0], k), -1, jnp.int32)
    prev_kth, used = None, 0
    for w0 in range(0, forest.n_trees, wave):
        sub = jax.tree.map(lambda a: a[w0:w0 + wave], forest)
        leaves = traverse(sub, q, cfg.max_depth)
        ids, mask = gather_candidates(sub, leaves, cfg.leaf_pad)
        if qdb is not None:
            d, i = staged_rerank_quantized(q, ids, mask, qdb, k, expand)
        else:
            d, i = rerank_topk(q, ids, mask, db, k=k, metric=metric)
        best_d, best_i = _merge_dedup(best_d, best_i, d, i, k)
        used = min(w0 + wave, forest.n_trees)
        kth = float(jnp.mean(jnp.where(jnp.isfinite(best_d[:, -1]),
                                       best_d[:, -1], 0.0)))
        if prev_kth is not None and prev_kth > 0 \
                and (prev_kth - kth) / prev_kth < tol:
            break
        prev_kth = kth
    return best_d, best_i, used


def _lsh_oracle(index, q, params):
    """Scalar cascade probe + numpy exact rerank, padded to k."""
    k = params.k
    dists = np.full((q.shape[0], k), np.inf, np.float32)
    ids = np.full((q.shape[0], k), -1, np.int64)
    for j in range(q.shape[0]):
        d, i, _ = index.cascade.query(q[j], k=k,
                                      min_candidates=params.min_candidates)
        m = min(k, len(i))
        dists[j, :m], ids[j, :m] = d[:m], i[:m]
    return dists, ids


def _oracle(index, q, params, corpus):
    db_j = jnp.asarray(corpus[0])
    q_j = jnp.asarray(q)
    backend = index.backend
    if backend == "rpf":
        if params.adaptive_wave:
            d, i, _ = _staged_adaptive(index.forest, q_j, db_j, params.k,
                                       FOREST, params.adaptive_wave,
                                       params.tol, metric=params.metric)
            return d, i
        return staged_query(index.forest, q_j, db_j, params.k, FOREST,
                            metric=params.metric, dedup=params.dedup)
    if backend == "rpf+int8":
        if params.adaptive_wave:
            d, i, _ = _staged_adaptive(index.forest, q_j, db_j, params.k,
                                       FOREST, params.adaptive_wave,
                                       params.tol, qdb=index.qdb,
                                       expand=params.expand)
            return d, i
        return staged_query_quantized(index.forest, q_j, index.qdb, params.k,
                                      FOREST, expand=params.expand)
    if backend == "lsh-cascade":
        return _lsh_oracle(index, q, params)
    return exact_knn(q_j, db_j, k=params.k, metric=params.metric)


# ---------------------------------------------------------------------------
# the matrix: every backend x params combination vs its staged oracle
# ---------------------------------------------------------------------------

MATRIX = [
    ("rpf", SearchParams(k=5)),
    ("rpf", SearchParams(k=5, metric="cosine")),
    ("rpf", SearchParams(k=5, metric="chi2")),
    ("rpf", SearchParams(k=5, dedup=False)),
    ("rpf", SearchParams(k=5, chunk=16)),
    ("rpf", SearchParams(k=5, adaptive_wave=4, tol=0.02)),
    ("rpf", SearchParams(k=5, adaptive_wave=5, tol=1e-6)),
    ("rpf+int8", SearchParams(k=5)),
    ("rpf+int8", SearchParams(k=5, expand=2)),
    ("rpf+int8", SearchParams(k=5, chunk=16)),
    ("rpf+int8", SearchParams(k=5, adaptive_wave=4, tol=0.02)),
    ("lsh-cascade", SearchParams(k=5)),
    ("lsh-cascade", SearchParams(k=5, min_candidates=40)),
    ("bruteforce", SearchParams(k=5)),
    ("bruteforce", SearchParams(k=5, metric="dot")),
]


@pytest.mark.parametrize("backend,params", MATRIX,
                         ids=[f"{b}-{i}" for i, (b, _) in enumerate(MATRIX)])
def test_backend_params_matrix_matches_oracle(corpus, backend, params):
    index = _index(corpus, backend)
    got = index.search(corpus[1], params)
    want = _oracle(index, corpus[1], params, corpus)
    _assert_match(got, want)


def test_all_backends_registered():
    assert available_backends() == ["bruteforce", "lsh-cascade", "rpf",
                                    "rpf+int8"]


def test_pallas_mode_spot_check(corpus):
    """The kernel dispatch path (interpret off-TPU) agrees with ref."""
    for backend in ("rpf", "rpf+int8"):
        index = _index(corpus, backend)
        got = index.search(corpus[1], SearchParams(k=4, mode="pallas"))
        want = index.search(corpus[1], SearchParams(k=4, mode="ref"))
        _assert_match(got, want)


def test_search_params_validation():
    with pytest.raises(ValueError):
        SearchParams(mode="fast")
    with pytest.raises(ValueError):
        SearchParams(k=0)
    with pytest.raises(KeyError):
        build_index(None, np.zeros((4, 2), np.float32),
                    IndexSpec(backend="no-such-backend"))


# ---------------------------------------------------------------------------
# deprecation shims: old entry points stay oracle-identical
# ---------------------------------------------------------------------------


def test_old_entry_points_are_oracle_identical(corpus):
    db_j, q_j = jnp.asarray(corpus[0]), jnp.asarray(corpus[1])
    index = _index(corpus, "rpf+int8")
    forest, qdb = index.forest, index.qdb

    from repro.core import query_forest
    _assert_match(query_forest(forest, q_j, db_j, 5, FOREST),
                  staged_query(forest, q_j, db_j, 5, FOREST))
    _assert_match(query_forest_quantized(forest, q_j, qdb, 5, FOREST),
                  staged_query_quantized(forest, q_j, qdb, 5, FOREST))
    d, i, used = adaptive_query(forest, q_j, db_j, 5, FOREST, wave=4,
                                tol=0.02)
    wd, wi, wused = _staged_adaptive(forest, q_j, db_j, 5, FOREST, 4, 0.02)
    assert used == wused
    _assert_match((d, i), (wd, wi))


# ---------------------------------------------------------------------------
# save / load roundtrip: bitwise-identical results
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["rpf", "rpf+int8", "lsh-cascade",
                                     "bruteforce"])
def test_save_load_roundtrip_bitwise(corpus, backend, tmp_path):
    index = _index(corpus, backend)
    params = SearchParams(k=4)
    d0, i0 = map(np.asarray, index.search(corpus[1], params))
    path = os.path.join(tmp_path, "idx")
    index.save(path)
    index2 = load_index(path)
    assert index2.backend == backend
    assert index2.spec == index.spec
    d1, i1 = map(np.asarray, index2.search(corpus[1], params))
    assert np.array_equal(i0, i1)
    assert np.array_equal(d0, d1)          # bitwise, not just allclose
    # the restored index keeps serving: adds are queryable immediately
    novel = corpus[0][0] + 0.25
    nid = index2.add(novel)
    _, i = index2.search(novel[None], SearchParams(k=1))
    assert int(np.asarray(i)[0, 0]) == nid


def test_save_folds_pending_adds(corpus, tmp_path):
    index = _index(corpus, "rpf")
    nid = index.add(corpus[0][0] + 0.5)
    path = os.path.join(tmp_path, "idx")
    index.save(path)
    assert index.stats()["n_overflow"] == 0          # compacted on save
    index2 = load_index(path)
    assert index2.db.shape[0] == N_DB + 1
    _, i = index2.search((corpus[0][0] + 0.5)[None], SearchParams(k=1))
    assert int(np.asarray(i)[0, 0]) == nid


# ---------------------------------------------------------------------------
# acceptance: no (B, M, d) gather in the quantized / adaptive jaxprs
# ---------------------------------------------------------------------------


def _max_gather_elems(jaxpr) -> int:
    """Largest gather output (in elements) anywhere in a jaxpr tree."""
    worst = 0

    def walk(jx):
        nonlocal worst
        for eqn in jx.eqns:
            if eqn.primitive.name == "gather":
                for ov in eqn.outvars:
                    worst = max(worst, int(np.prod(ov.aval.shape)))
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(sub, "eqns"):
                        walk(sub)
                    elif hasattr(sub, "jaxpr"):
                        walk(sub.jaxpr)

    walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return worst


def test_no_bmd_gather_in_fused_paths(corpus):
    """rpf+int8 and adaptive-wave searches go through the fused pipeline:
    nothing in their jaxprs gathers a (B, M, d)-sized tensor."""
    index = _index(corpus, "rpf+int8")
    q = jnp.asarray(corpus[1][:8])
    cfg = FOREST.resolved(N_DB)
    m = cfg.n_trees * cfg.leaf_pad
    bmd = q.shape[0] * m * DIM

    db_j = jnp.asarray(corpus[0])

    def quantized_search(qq, qdb):
        return fused_query(index.forest, qq, qdb, 5, FOREST, mode="pallas",
                           chunk=32)

    def plain_search(qq, db):
        # the same program each adaptive wave traces (on a forest prefix)
        return fused_query(index.forest, qq, db, 5, FOREST, mode="pallas",
                           chunk=32)

    jx_q = jax.make_jaxpr(quantized_search)(q, index.qdb)
    jx_p = jax.make_jaxpr(plain_search)(q, db_j)
    assert _max_gather_elems(jx_q) < bmd, "quantized path gathers (B,M,d)"
    assert _max_gather_elems(jx_p) < bmd, "fused path gathers (B,M,d)"

    def quantized_rerank(qq, ids, mask, qdb):
        return rerank_fused_quantized(qq, ids, mask, qdb, 5, mode="pallas",
                                      chunk=32)

    ids = jnp.zeros((8, m), jnp.int32)
    mask = jnp.ones((8, m), bool)
    jx_r = jax.make_jaxpr(quantized_rerank)(q, ids, mask, index.qdb)
    assert _max_gather_elems(jx_r) < bmd

    # sanity: the checker DOES see the staged oracle's full-width gather
    def staged(qq, db):
        return staged_query(index.forest, qq, db, 5, FOREST)

    assert _max_gather_elems(jax.make_jaxpr(staged)(q, db_j)) >= bmd


# ---------------------------------------------------------------------------
# vectorized LSH batch path == scalar per-point path
# ---------------------------------------------------------------------------


def test_lsh_batch_candidates_match_scalar(corpus):
    db, q = corpus
    cascade = CascadedLSH(db, list(LSH_RADII), n_tables=6, n_bits=8, seed=3)
    level = cascade.levels[0]
    batch_sets = level.candidate_sets(q)
    for j in range(q.shape[0]):
        assert batch_sets[j] == level.candidates(q[j])
    ids, mask = level.candidates_batch(q, pad_multiple=32)
    assert ids.shape == mask.shape and ids.shape[1] % 32 == 0
    for j in range(q.shape[0]):
        assert set(ids[j][mask[j]].tolist()) == batch_sets[j]

    # cascade semantics: per-query early stop matches the scalar retrieve
    for mc in (1, 30):
        sets = cascade.retrieve_sets(q, min_candidates=mc)
        for j in range(q.shape[0]):
            assert sets[j] == set(cascade.retrieve(q[j], mc).tolist())


# ---------------------------------------------------------------------------
# serving contracts: fixed batch shapes + bounded latency buffer
# ---------------------------------------------------------------------------


def test_serve_batch_pads_to_max_batch(corpus):
    from repro.serve.ann_serve import make_ann_server
    db = corpus[0][:600]
    index, batcher = make_ann_server(
        db, IndexSpec(backend="rpf", forest=ForestConfig(n_trees=6)),
        k=3, max_batch=8, max_wait_s=0.01)
    seen_shapes = []
    orig_search = index.search

    def spying_search(qq, params=None, **kw):
        seen_shapes.append(np.asarray(qq).shape)
        return orig_search(qq, params, **kw)

    index.search = spying_search
    try:
        for n in (1, 3, 7):                 # distinct logical batch sizes
            rs = [batcher.submit(db[j]) for j in range(n)]
            for j, r in enumerate(rs):
                assert r.event.wait(30)
                assert int(r.result[1][0]) == j     # self is the 1-NN
    finally:
        batcher.stop()
    assert seen_shapes and all(s == (8, db.shape[1]) for s in seen_shapes), \
        f"expected fixed (max_batch, d) shapes, saw {seen_shapes}"


def test_batcher_counters_hold_after_100_requests():
    from repro.serve.batching import DynamicBatcher
    sizes = []

    def serve(ps):
        sizes.append(len(ps))
        return [0 for _ in ps]

    b = DynamicBatcher(serve, max_batch=4, max_wait_s=0.001).start()
    for _ in range(100):
        b(np.zeros(3))
    b.stop()
    st = b.stats
    assert st["requests"] == sum(sizes) == 100
    assert st["batches"] == len(sizes) == b.batch_no + 1
    assert st["mean_batch"] == pytest.approx(100 / len(sizes))
    assert max(sizes) <= 4 and 0 <= st["depth_peak"] <= 4
    assert st["stopped"] == "drained" and st["failed_on_stop"] == 0
    assert "p99_latency_ms" not in st      # the batcher times nothing
