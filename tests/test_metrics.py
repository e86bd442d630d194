"""Metric registry (DESIGN.md §13): every metric on every backend vs the
exact oracle, alias canonicalization, int8 coarse-stage metric parity,
and tuning under a non-default metric.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distances import METRIC_ALIASES, METRICS, canonical_metric
from repro.core.forest import ForestConfig
from repro.core.knn import exact_knn
from repro.core.quantized import quantize_db
from repro.index import IndexSpec, SearchParams, build_index
from repro.index.tune import tune
from repro.kernels import ref
from repro.kernels.fused_query_int8 import (fused_gather_topk_int8,
                                            pack_int8_rows)

SEED = 0
BACKENDS = ["bruteforce", "rpf", "rpf+int8", "lsh-cascade"]
USER_METRICS = ["l2", "chi2", "cosine", "ip"]


@pytest.fixture(scope="module")
def corpus(shared_builds):
    db = shared_builds.clustered_db(2000, 16, n_clusters=16, seed=SEED)
    db = np.abs(db)                       # non-negative so chi2 composes
    db /= np.linalg.norm(db, axis=1, keepdims=True)
    rng = np.random.default_rng(1)
    q = np.abs(db[:16] + 0.003 * rng.normal(size=(16, 16)).astype(np.float32))
    return db, q


def _spec(backend):
    return IndexSpec(backend=backend,
                     forest=ForestConfig(n_trees=12, capacity=24),
                     lsh_radii=(0.5, 1.0, 2.0), lsh_tables=8, lsh_bits=8,
                     seed=0)


def _recall(ids, oracle_ids, k):
    return np.mean([len(set(a[a >= 0].tolist()) & set(b.tolist())) / k
                    for a, b in zip(np.asarray(ids), np.asarray(oracle_ids))])


# ---------------------------------------------------------------------------
# registry + aliases
# ---------------------------------------------------------------------------


def test_canonical_metric():
    assert canonical_metric("ip") == "dot"
    assert canonical_metric("inner_product") == "dot"
    assert canonical_metric("euclidean") == "l2"
    assert canonical_metric("chi2") == "chi2"
    with pytest.raises(ValueError, match="unknown metric"):
        canonical_metric("manhattan")
    assert set(METRIC_ALIASES.values()) <= set(METRICS)


def test_params_canonicalize_aliases():
    assert SearchParams(metric="ip") == SearchParams(metric="dot")
    assert SearchParams(metric="euclidean") == SearchParams()
    # unknown metrics survive construction; violations() reports them
    p = SearchParams(metric="manhattan")
    assert any("manhattan" in v for v in p.violations())


# ---------------------------------------------------------------------------
# every metric x every backend vs the exact oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("metric", USER_METRICS)
def test_metric_backend_recall_vs_oracle(corpus, backend, metric):
    db, q = corpus
    idx = build_index(jax.random.key(SEED), db, _spec(backend))
    p = SearchParams(k=10, metric=metric, n_probes=4, min_candidates=2000)
    d, ids = idx.search(q, p)
    gd, gi = exact_knn(jnp.asarray(q), jnp.asarray(db), 10, metric=metric)
    rec = _recall(ids, gi, 10)
    floor = 1.0 if backend in ("bruteforce", "lsh-cascade") else 0.9
    assert rec >= floor, f"{backend}/{metric}: recall {rec:.3f} < {floor}"
    # returned distances are the metric's own values, ascending
    dn = np.asarray(d)
    assert (np.diff(dn, axis=1) >= -1e-6).all()


def test_ip_and_dot_identical(corpus):
    db, q = corpus
    idx = build_index(jax.random.key(SEED), db, _spec("rpf"))
    d1, i1 = idx.search(q, SearchParams(k=10, metric="ip"))
    d2, i2 = idx.search(q, SearchParams(k=10, metric="dot"))
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    assert np.array_equal(np.asarray(d1), np.asarray(d2))


# ---------------------------------------------------------------------------
# int8 coarse stage scores under the metric (kernel == ref, all metrics)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("metric", ["l2", "dot", "chi2", "cosine"])
def test_int8_kernel_ref_parity_per_metric(corpus, metric):
    db, q = corpus
    qdb = quantize_db(jnp.asarray(db))
    rng = np.random.default_rng(3)
    ids = rng.integers(0, len(db), size=(8, 96)).astype(np.int32)
    ids[ids % 7 == 0] = -1                      # invalid slots mix in
    ids = jnp.asarray(ids)
    qj = jnp.asarray(q[:8])
    kd, ki = fused_gather_topk_int8(qj, ids, pack_int8_rows(qdb.q),
                                    qdb.scale, 10, metric=metric,
                                    interpret=True)
    rd, ri = ref.fused_gather_topk_int8_ref(qj, ids, qdb.q, qdb.scale, 10,
                                            metric=metric)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(kd), np.asarray(rd),
                               rtol=1e-5, atol=1e-5)


def test_int8_backend_unfiltered_l2_matches_prior_contract(corpus):
    """metric='l2' through the int8 backend keeps its pre-metric-registry
    semantics: the coarse stage's l2 branch is structurally the original
    scoring, so results equal the ref-mode (oracle) dispatch bitwise."""
    db, q = corpus
    idx = build_index(jax.random.key(SEED), db, _spec("rpf+int8"))
    d1, i1 = idx.search(q, SearchParams(k=10, mode="auto"))
    d2, i2 = idx.search(q, SearchParams(k=10, mode="ref"))
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# tuner under a non-default metric
# ---------------------------------------------------------------------------


def test_tune_with_metric(corpus):
    db, q = corpus
    idx = build_index(jax.random.key(SEED), db, _spec("rpf"))
    tuned = tune(idx, q, target_recall=0.85, k=10, metric="cosine",
                 probe_grid=(1, 2, 4), tree_fracs=(1.0,))
    assert tuned.metric == "cosine"
    d, ids = idx.search(q, tuned)
    _, gi = exact_knn(jnp.asarray(q), jnp.asarray(db), 10, metric="cosine")
    assert _recall(ids, gi, 10) >= 0.85
