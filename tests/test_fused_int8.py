"""Fused int8-row rerank kernel + quantized pipeline parity (DESIGN.md §11).

The Pallas kernel (kernels/fused_query_int8.py) DMAs d + 4 bytes per
candidate — the int8 row plus its f32 scale — and dequantizes in VMEM
registers.  Its oracle is ``ref.fused_gather_topk_int8_ref``, the retired
jnp dequant-gather.  End to end, ``pipeline.rerank_fused_quantized`` must
reproduce the staged quantized oracle (full (B, M, d) int8 gather) exactly
on tie-free data, in both ref and pallas modes and under any chunking.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ForestConfig
from repro.core.pipeline import fused_query, rerank_fused_quantized
from repro.core.quantized import (quantize_db, staged_query_quantized,
                                  staged_rerank_quantized)
from repro.kernels import ops, ref

RNG = np.random.default_rng(29)
TOL = dict(rtol=2e-5, atol=2e-5)


def _corpus(n, d, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))


def _assert_match(got, want):
    gd, gi = got
    wd, wi = want
    assert (np.asarray(gi) == np.asarray(wi)).all(), \
        f"id mismatch:\n{np.asarray(gi)}\nvs\n{np.asarray(wi)}"
    wd_np, gd_np = np.asarray(wd), np.asarray(gd)
    finite = np.isfinite(wd_np)
    assert (finite == np.isfinite(gd_np)).all()
    np.testing.assert_allclose(gd_np[finite], wd_np[finite], **TOL)


# ---------------------------------------------------------------------------
# kernel-level: pallas int8 kernel vs its jnp oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,m,n,d", [(4, 24, 200, 16), (9, 100, 500, 48),
                                     (1, 7, 60, 5)])
@pytest.mark.parametrize("k", [5, 33])
def test_int8_kernel_matches_oracle(b, m, n, d, k):
    if k > m:
        pytest.skip("k wider than the candidate axis")
    rng = np.random.default_rng(b * m + k)
    qdb = quantize_db(jnp.asarray(rng.normal(size=(n, d)).astype(np.float32)))
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    ids = rng.integers(0, n, size=(b, m)).astype(np.int32)
    ids[rng.uniform(size=ids.shape) < 0.15] = -1      # invalid slots
    ids = jnp.asarray(ids)
    pd, pi = ops.fused_rerank_int8(q, ids,
                                   ops.rerank_rows_int8(qdb.q, "pallas"),
                                   qdb.scale, k, mode="pallas")
    rd, ri = ref.fused_gather_topk_int8_ref(q, ids, qdb.q, qdb.scale, k)
    rd_np = np.asarray(rd)
    finite = np.isfinite(rd_np)
    np.testing.assert_allclose(np.asarray(pd)[finite], rd_np[finite], **TOL)
    assert (np.isfinite(np.asarray(pd)) == finite).all()
    # continuous data: finite-distance ids are tie-free -> exact
    assert (np.asarray(pi)[finite] == np.asarray(ri)[finite]).all()


@pytest.mark.parametrize("mode", ["ref", "pallas"])
def test_int8_kernel_all_masked(mode):
    qdb = quantize_db(_corpus(50, 6, seed=1))
    q = _corpus(2, 6, seed=2)
    ids = jnp.full((2, 12), -1, jnp.int32)
    d, i = ops.fused_rerank_int8(q, ids, ops.rerank_rows_int8(qdb.q, mode),
                                 qdb.scale, 3, mode=mode)
    assert np.isinf(np.asarray(d)).all()
    assert (np.asarray(i) == -1).all()


def test_int8_kernel_dequant_is_exact():
    """Dequantized distances are exact vs an explicit fp recomputation —
    the kernel's register dequant is the same f32 op chain as the oracle."""
    qdb = quantize_db(_corpus(80, 12, seed=3))
    q = _corpus(4, 12, seed=4)
    ids = jnp.asarray(RNG.integers(0, 80, size=(4, 20)).astype(np.int32))
    pd, pi = ops.fused_rerank_int8(q, ids,
                                   ops.rerank_rows_int8(qdb.q, "pallas"),
                                   qdb.scale, 6, mode="pallas")
    deq = (np.asarray(qdb.q).astype(np.float32)
           * np.asarray(qdb.scale)[:, None])
    want = np.sum((np.asarray(q)[:, None, :]
                   - deq[np.asarray(ids)]) ** 2, axis=-1)
    got_d = np.asarray(pd)
    for r in range(4):
        np.testing.assert_allclose(got_d[r], np.sort(want[r])[:6], **TOL)


# ---------------------------------------------------------------------------
# pipeline: rerank_fused_quantized vs the staged quantized oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["ref", "pallas"])
@pytest.mark.parametrize("expand", [2, 4])
def test_rerank_quantized_matches_staged(mode, expand):
    qdb = quantize_db(_corpus(600, 20, seed=5))
    q = _corpus(7, 20, seed=6)
    ids = jnp.asarray(RNG.integers(0, 600, size=(7, 60)).astype(np.int32))
    mask = jnp.asarray(RNG.uniform(size=(7, 60)) < 0.85)
    want = staged_rerank_quantized(q, ids, mask, qdb, 5, expand=expand)
    got = rerank_fused_quantized(q, ids, mask, qdb, 5, expand=expand,
                                 mode=mode)
    _assert_match(got, want)


@pytest.mark.parametrize("mode", ["ref", "pallas"])
def test_rerank_quantized_chunk_invariance(mode):
    """Coarse shortlist must be invariant to the streaming chunk width."""
    qdb = quantize_db(_corpus(500, 16, seed=7))
    q = _corpus(5, 16, seed=8)
    ids = jnp.asarray(RNG.integers(0, 500, size=(5, 48)).astype(np.int32))
    mask = jnp.ones((5, 48), bool)
    want = staged_rerank_quantized(q, ids, mask, qdb, 4)
    for chunk in (16, 24, 64):      # incl. non-divisors of M = 48
        got = rerank_fused_quantized(q, ids, mask, qdb, 4, chunk=chunk,
                                     mode=mode)
        _assert_match(got, want)


@pytest.mark.parametrize("mode", ["ref", "pallas"])
def test_rerank_quantized_valid_mask(mode):
    """Tombstoned rows must never reach the shortlist."""
    qdb = quantize_db(_corpus(300, 10, seed=9))
    q = _corpus(4, 10, seed=10)
    ids = jnp.asarray(RNG.integers(0, 300, size=(4, 40)).astype(np.int32))
    mask = jnp.ones((4, 40), bool)
    valid = jnp.asarray(RNG.uniform(size=300) < 0.7)
    want = staged_rerank_quantized(q, ids, mask & valid[ids], qdb, 4)
    got = rerank_fused_quantized(q, ids, mask, qdb, 4, mode=mode,
                                 valid=valid)
    _assert_match(got, want)
    dead = ~np.asarray(valid)
    got_i = np.asarray(got[1])
    assert not dead[got_i[got_i >= 0]].any()


@pytest.mark.parametrize("mode", ["ref", "pallas"])
def test_fused_query_quantized_end_to_end(mode, shared_builds):
    """Forest-driven: fused int8 pipeline vs the staged quantized oracle."""
    cfg = ForestConfig(n_trees=6, capacity=10)
    db = shared_builds.normal_db(1200, 24, 11)
    forest, _ = shared_builds.forest(4, cfg, db)
    qdb = quantize_db(db)
    q = _corpus(9, 24, seed=12)
    want = staged_query_quantized(forest, q, qdb, 5, cfg)
    got = fused_query(forest, q, qdb, 5, cfg, mode=mode)
    _assert_match(got, want)
