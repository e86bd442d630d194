"""Bitwise parity of the batched cross-tree builder vs the legacy oracle.

The batched builder (DESIGN.md §10) must reproduce the legacy per-tree
builder EXACTLY under ``seed_mode="compat"`` — every Forest array, every
dtype, every config — because three families of existing pins rest on
deterministic builds: multi-probe probe-0 bitwise, save/load roundtrip,
and compaction-vs-fresh.  ``seed_mode="fused"`` draws a different (valid)
stream and is checked against the structural invariants instead.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ForestConfig
from repro.core.forest import (_build_forest_legacy, build_forest,
                               forest_stats, tree_depths)

TIED = "tied"  # heavily tied coordinates: exercises tie-escape + redraws


def _corpus(n, d, dtype=np.float32, kind="normal", seed=0):
    rng = np.random.default_rng(seed)
    if kind == TIED:
        # sparse-histogram-like: most entries exactly 0, few quantized
        x = rng.integers(0, 4, size=(n, d)).astype(np.float32)
        x[rng.uniform(size=x.shape) < 0.7] = 0.0
    else:
        x = rng.normal(size=(n, d))
    return jnp.asarray(x.astype(dtype))


def _assert_forests_bitwise(got, want):
    for name in want._fields:
        a, b = np.asarray(getattr(want, name)), np.asarray(getattr(got, name))
        np.testing.assert_array_equal(
            b, a, err_msg=f"batched builder diverges on Forest.{name}")
        assert a.dtype == b.dtype, (name, a.dtype, b.dtype)


# ---------------------------------------------------------------------------
# bitwise matrix: dtypes x depths x ragged leaf sizes x tie-heavy data
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
@pytest.mark.parametrize("n,d,cfg_kw", [
    (700, 16, dict(n_trees=6, capacity=12)),
    (701, 16, dict(n_trees=5, capacity=5, split_ratio=0.45)),   # ragged
    (256, 8, dict(n_trees=3, capacity=9, split_ratio=0.12)),
    (300, 12, dict(n_trees=4, capacity=8, max_depth=4)),        # depth-capped
    (300, 12, dict(n_trees=4, capacity=8, n_proj=2)),           # K=2 tests
])
def test_batched_bitwise_matches_legacy(dtype, n, d, cfg_kw):
    x = _corpus(n, d, dtype=dtype, seed=n + d)
    cfg = ForestConfig(**cfg_kw)
    key = jax.random.key(n)
    want = _build_forest_legacy(key, x, cfg.resolved(n))
    got = build_forest(key, x, cfg)
    _assert_forests_bitwise(got, want)


def test_batched_bitwise_on_tied_data():
    """Tie-escape splits + degenerate-node redraws follow the same path."""
    x = _corpus(900, 24, kind=TIED, seed=3)
    cfg = ForestConfig(n_trees=6, capacity=10)
    key = jax.random.key(11)
    want = _build_forest_legacy(key, x, cfg.resolved(900))
    got = build_forest(key, x, cfg)
    _assert_forests_bitwise(got, want)


def test_batched_bitwise_under_node_budget_pressure():
    """A tight max_nodes budget trips the allocation-overflow guard; the
    batched builder must freeze the same trees at the same level."""
    x = _corpus(600, 8, seed=9)
    cfg = ForestConfig(n_trees=4, capacity=4, max_nodes=96)
    key = jax.random.key(2)
    want = _build_forest_legacy(key, x, cfg.resolved(600))
    got = build_forest(key, x, cfg)
    _assert_forests_bitwise(got, want)


def test_staged_shrink_bitwise_matches_single_stage():
    """Force the multi-stage active-set shrink on a small corpus (tiny
    ``restage_min``): stage relaunches at narrower sort widths must not
    perturb a single bit — compaction is order-preserving, so each
    overfull segment sorts to the same value sequence."""
    from repro.core.forest import _build_forest_batched
    x = _corpus(1200, 16, seed=8)
    cfg = ForestConfig(n_trees=5, capacity=6).resolved(1200)
    key = jax.random.key(3)
    want = _build_forest_legacy(key, x, cfg)
    keys = jax.random.split(key, cfg.n_trees)
    got = _build_forest_batched(keys, x, cfg, restage_min=64)
    _assert_forests_bitwise(got, want)
    # tied data through the staged path too (degenerate redraw nodes keep
    # their points active across stage boundaries)
    xt = _corpus(1000, 12, kind=TIED, seed=10)
    cfg = ForestConfig(n_trees=4, capacity=8).resolved(1000)
    want = _build_forest_legacy(key, xt, cfg)
    got = _build_forest_batched(jax.random.split(key, 4), xt, cfg,
                                restage_min=64)
    _assert_forests_bitwise(got, want)


def test_tree_chunk_bitwise_matches_unchunked():
    """Compat-mode chunking slices the same per-tree key split."""
    x = _corpus(500, 12, seed=4)
    cfg = ForestConfig(n_trees=10, capacity=12)
    key = jax.random.key(5)
    full = build_forest(key, x, cfg)
    for chunk in (1, 3, 4, 10):
        _assert_forests_bitwise(build_forest(key, x, cfg, tree_chunk=chunk),
                                full)
    # and the chunked legacy path agrees too (three-way pin)
    _assert_forests_bitwise(
        _build_forest_legacy(key, x, cfg.resolved(500), tree_chunk=3), full)


def test_build_forest_traceable():
    """build_forest must stay wrappable in jit/vmap (the pre-batched
    builder was itself @jax.jit): a traced key with a concrete closed-over
    db takes the in-graph single-stage path, bitwise-equal to the host
    driver; same inside shard_map-style tracing of both args."""
    x = _corpus(900, 10, seed=12)
    cfg = ForestConfig(n_trees=4, capacity=8)
    want = build_forest(jax.random.key(9), x, cfg)

    got_k = jax.jit(lambda k: build_forest(k, x, cfg))(jax.random.key(9))
    _assert_forests_bitwise(got_k, want)
    got_kx = jax.jit(lambda k, d: build_forest(k, d, cfg))(
        jax.random.key(9), x)
    _assert_forests_bitwise(got_kx, want)


def test_tiny_corpus_no_split_edge():
    """N <= capacity: the early-exit loop must not run at all; both
    builders return the single-root-leaf forest."""
    x = _corpus(8, 4, seed=6)
    cfg = ForestConfig(n_trees=3, capacity=12)
    key = jax.random.key(1)
    want = _build_forest_legacy(key, x, cfg.resolved(8))
    got = build_forest(key, x, cfg)
    _assert_forests_bitwise(got, want)
    assert int(np.asarray(got.n_nodes).max()) == 1


# ---------------------------------------------------------------------------
# fused seed mode: different stream, same structural contract
# ---------------------------------------------------------------------------


def test_fused_seed_mode_valid_partition():
    n = 1200
    x = _corpus(n, 16, seed=7)
    cfg = ForestConfig(n_trees=6, capacity=12)
    f = build_forest(jax.random.key(0), x, cfg, seed_mode="fused")
    perm = np.asarray(f.perm)
    for tree in range(cfg.n_trees):
        assert sorted(perm[tree]) == list(range(n))
    stats = forest_stats(f, cfg, n)
    assert stats["occ_max"] <= cfg.capacity
    assert stats["overflow_points"] == 0


def test_impl_and_seed_mode_validation():
    x = _corpus(100, 4)
    cfg = ForestConfig(n_trees=2, capacity=8)
    with pytest.raises(ValueError, match="impl"):
        build_forest(jax.random.key(0), x, cfg, impl="nope")
    with pytest.raises(ValueError, match="seed_mode"):
        build_forest(jax.random.key(0), x, cfg, seed_mode="nope")


# The hypothesis any-(data, config, seed) version of the bitwise invariant
# lives in test_property.py::test_batched_builder_bitwise_invariant (that
# module carries the optional-dependency skip).


# ---------------------------------------------------------------------------
# tree_depths: the built depth, the descent's exact loop bound
# ---------------------------------------------------------------------------


def _sweep_depths(forest) -> np.ndarray:
    """Per-node forward sweep, one tree at a time (the oracle)."""
    child = np.asarray(forest.child_base)
    n_nodes = np.asarray(forest.n_nodes)
    out = []
    for l in range(child.shape[0]):
        depth = np.full(child.shape[1], -1, np.int32)
        depth[0] = 0
        for i in range(int(n_nodes[l])):
            if child[l, i] >= 0:
                depth[child[l, i]] = depth[i] + 1
                depth[child[l, i] + 1] = depth[i] + 1
        out.append(depth.max())
    return np.asarray(out, np.int32)


@pytest.mark.parametrize("n,cfg_kw,impl", [
    (700, dict(n_trees=6, capacity=12), "batched"),
    (701, dict(n_trees=5, capacity=5, split_ratio=0.45), "legacy"),
    (300, dict(n_trees=4, capacity=8, max_depth=3), "batched"),   # capped
    (10, dict(n_trees=3, capacity=12), "batched"),                # N <= C
])
def test_tree_depths_matches_sweep(n, cfg_kw, impl):
    x = _corpus(n, 12, seed=n)
    cfg = ForestConfig(**cfg_kw)
    f = build_forest(jax.random.key(n), x, cfg, impl=impl)
    got = tree_depths(f)
    np.testing.assert_array_equal(got, _sweep_depths(f))
    assert got.shape == (cfg.n_trees,)
    assert (got <= cfg.resolved(n).max_depth).all()
    if n <= cfg.capacity:
        assert (got == 0).all()
    assert forest_stats(f, cfg, n)["depth_max"] == float(got.mean())
