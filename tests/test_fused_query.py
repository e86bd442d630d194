"""Fused single-pass query pipeline vs the staged oracle.

The fused path (core/pipeline.py + kernels/fused_query.py) must reproduce the
staged composition (traverse -> gather -> mask_duplicates -> rerank_topk)
exactly: bitwise on ids, to fp tolerance on distances.  Test data uses
continuous random vectors, so distance ties occur only between identical
candidate ids — bitwise id parity is well-defined under any tie-break.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ForestConfig
from repro.core.pipeline import fused_query, rerank_fused, staged_query
from repro.core.search import rerank_topk
from repro.kernels import ops, ref

RNG = np.random.default_rng(7)
TOL = dict(rtol=2e-5, atol=2e-5)


def _corpus(n, d, metric, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    if metric == "chi2":
        x = np.abs(x)      # chi2 wants non-negative histogram features
    return jnp.asarray(x)


def _shared_forest(shared_builds, n, d, metric, seed, key_seed, cfg):
    """One cached (db, forest) per distinct (corpus, cfg, key) — the
    parametrized parity tests below would otherwise rebuild it per case."""
    db = shared_builds.normal_db(n, d, seed, nonneg=(metric == "chi2"))
    forest, _ = shared_builds.forest(key_seed, cfg, db)
    return db, forest


def _assert_match(fused, staged):
    fd, fi = fused
    sd, si = staged
    assert (np.asarray(fi) == np.asarray(si)).all(), \
        f"id mismatch:\n{np.asarray(fi)}\nvs\n{np.asarray(si)}"
    sd_np, fd_np = np.asarray(sd), np.asarray(fd)
    finite = np.isfinite(sd_np)
    assert (finite == np.isfinite(fd_np)).all()
    np.testing.assert_allclose(fd_np[finite], sd_np[finite], **TOL)


# ---------------------------------------------------------------------------
# end-to-end pipeline parity (forest-driven, ragged real leaf sizes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("metric", ["l2", "dot", "chi2", "cosine"])
@pytest.mark.parametrize("dedup", [True, False])
@pytest.mark.parametrize("mode", ["ref", "pallas"])
def test_fused_matches_staged(metric, dedup, mode, shared_builds):
    cfg = ForestConfig(n_trees=6, capacity=10)
    db, forest = _shared_forest(shared_builds, 1500, 24, metric, 1, 0, cfg)
    q = _corpus(13, 24, metric, seed=2)
    staged = staged_query(forest, q, db, 5, cfg, metric=metric, dedup=dedup)
    fused = fused_query(forest, q, db, 5, cfg, metric=metric, dedup=dedup,
                        mode=mode)
    _assert_match(fused, staged)


@pytest.mark.parametrize("mode", ["ref", "pallas"])
def test_fused_chunked_matches_unchunked(mode, shared_builds):
    """Result must be invariant to the candidate-chunk width."""
    cfg = ForestConfig(n_trees=8, capacity=8)
    db, forest = _shared_forest(shared_builds, 1200, 16, "l2", 3, 1, cfg)
    q = _corpus(9, 16, "l2", seed=4)
    staged = staged_query(forest, q, db, 4, cfg)
    for chunk in (16, 24, 64):     # including non-divisors of M = 8*8
        fused = fused_query(forest, q, db, 4, cfg, mode=mode, chunk=chunk)
        _assert_match(fused, staged)


@pytest.mark.parametrize("mode", ["ref", "pallas"])
def test_fused_b1_edge(mode, shared_builds):
    """B=1: the degenerate serving case (single online query)."""
    cfg = ForestConfig(n_trees=4, capacity=12)
    db, forest = _shared_forest(shared_builds, 800, 12, "l2", 5, 2, cfg)
    q = _corpus(1, 12, "l2", seed=6)
    staged = staged_query(forest, q, db, 3, cfg)
    fused = fused_query(forest, q, db, 3, cfg, mode=mode, chunk=8)
    _assert_match(fused, staged)


def test_rerank_fused_batch_slabbing():
    """B beyond the SMEM row budget must slab the batch, same results."""
    db = _corpus(500, 8, "l2", seed=20)
    q = _corpus(70, 8, "l2", seed=21)
    ids = jnp.asarray(RNG.integers(0, 500, size=(70, 30)).astype(np.int32))
    mask = jnp.ones((70, 30), bool)
    want = rerank_topk(q, ids, mask, db, k=4)
    for mode in ("ref", "pallas"):
        got = rerank_fused(q, ids, mask, db, 4, mode=mode, rows_budget=16)
        _assert_match(got, want)


@pytest.mark.parametrize("mode", ["ref", "pallas"])
def test_rerank_fused_k_exceeds_chunk(mode):
    """k wider than the streaming chunk: chunk must clamp up, not crash."""
    db = _corpus(400, 10, "l2", seed=22)
    q = _corpus(5, 10, "l2", seed=23)
    ids = jnp.asarray(RNG.integers(0, 400, size=(5, 64)).astype(np.int32))
    mask = jnp.ones((5, 64), bool)
    want = rerank_topk(q, ids, mask, db, k=20)
    got = rerank_fused(q, ids, mask, db, 20, mode=mode, chunk=16)
    _assert_match(got, want)


def test_fused_ragged_leaf_sizes(shared_builds):
    """Tiny capacity -> heavily ragged leaves -> many invalid padded slots."""
    cfg = ForestConfig(n_trees=5, capacity=4, split_ratio=0.45)
    db, forest = _shared_forest(shared_builds, 400, 8, "l2", 7, 3, cfg)
    q = _corpus(6, 8, "l2", seed=8)
    staged = staged_query(forest, q, db, 4, cfg)
    for mode in ("ref", "pallas"):
        _assert_match(fused_query(forest, q, db, 4, cfg, mode=mode), staged)


# ---------------------------------------------------------------------------
# rerank_fused parity on synthetic candidate matrices (controlled edge cases)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("metric", ["l2", "dot", "chi2"])
@pytest.mark.parametrize("mode", ["ref", "pallas"])
def test_rerank_fused_matches_rerank_topk(metric, mode):
    db = _corpus(300, 20, metric, seed=9)
    q = _corpus(7, 20, metric, seed=10)
    ids = jnp.asarray(RNG.integers(0, 300, size=(7, 50)).astype(np.int32))
    mask = jnp.asarray(RNG.uniform(size=(7, 50)) < 0.8)
    staged = rerank_topk(q, ids, mask, db, k=6, metric=metric, dedup=True)
    fused = rerank_fused(q, ids, mask, db, 6, metric=metric, mode=mode,
                         dedup=True, chunk=16)
    _assert_match(fused, staged)


@pytest.mark.parametrize("mode", ["ref", "pallas"])
def test_rerank_fused_all_duplicate_row(mode):
    """A row whose candidates are all the same id: dedup keeps exactly one."""
    db = _corpus(100, 10, "l2", seed=11)
    q = _corpus(3, 10, "l2", seed=12)
    ids = jnp.full((3, 24), 42, jnp.int32)
    mask = jnp.ones((3, 24), bool)
    d, i = rerank_fused(q, ids, mask, db, 4, mode=mode, dedup=True, chunk=8)
    d, i = np.asarray(d), np.asarray(i)
    assert (i[:, 0] == 42).all()
    assert (i[:, 1:] == -1).all()           # only one unique candidate
    assert np.isinf(d[:, 1:]).all()
    np.testing.assert_allclose(
        d[:, 0], np.sum((np.asarray(q) - np.asarray(db)[42]) ** 2, -1), **TOL)


@pytest.mark.parametrize("mode", ["ref", "pallas"])
def test_rerank_fused_all_masked(mode):
    db = _corpus(50, 6, "l2", seed=13)
    q = _corpus(2, 6, "l2", seed=14)
    ids = jnp.zeros((2, 12), jnp.int32)
    mask = jnp.zeros((2, 12), bool)
    d, i = rerank_fused(q, ids, mask, db, 3, mode=mode)
    assert np.isinf(np.asarray(d)).all()
    assert (np.asarray(i) == -1).all()


# ---------------------------------------------------------------------------
# kernel-level: pallas fused_gather_topk vs its jnp oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,m,n,d", [(4, 24, 200, 16), (9, 100, 500, 48),
                                     (1, 7, 60, 5)])
@pytest.mark.parametrize("metric", ["l2", "dot", "chi2"])
def test_fused_kernel_matches_oracle(b, m, n, d, metric):
    rng = np.random.default_rng(b * m)
    db = np.abs(rng.normal(size=(n, d))).astype(np.float32)
    q = np.abs(rng.normal(size=(b, d))).astype(np.float32)
    ids = rng.integers(0, n, size=(b, m)).astype(np.int32)
    ids[rng.uniform(size=ids.shape) < 0.15] = -1      # invalid slots
    pd, pi = ops.fused_rerank(jnp.asarray(q), jnp.asarray(ids),
                              ops.rerank_rows(jnp.asarray(db), "pallas"), 5,
                              metric=metric, mode="pallas")
    rd, ri = ref.fused_gather_topk_ref(jnp.asarray(q), jnp.asarray(ids),
                                       jnp.asarray(db), 5, metric=metric)
    rd_np = np.asarray(rd)
    finite = np.isfinite(rd_np)
    np.testing.assert_allclose(np.asarray(pd)[finite], rd_np[finite], **TOL)
    assert (np.isfinite(np.asarray(pd)) == finite).all()
    # continuous data: finite-distance ids are tie-free -> exact
    assert (np.asarray(pi)[finite] == np.asarray(ri)[finite]).all()
