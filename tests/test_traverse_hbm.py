"""HBM-resident traversal kernel parity (DESIGN.md §11).

Three-way bitwise agreement at every tree size: the HBM kernel (node records
double-buffer-DMA'd per descent level) must equal the SMEM kernel (whole
tree scalar-prefetched; only legal below ``SMEM_NODE_CAP``) and the jnp
refs, for single- and multi-probe descents.  Leaf ids are integers and the
float compare chain is operation-identical across the three, so every
comparison here is exact (== / array_equal), not toleranced.

The cap-straddling hypothesis sweep lives in test_property.py; this file is
the deterministic matrix.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ForestConfig, build_forest
from repro.core.forest import (traverse, traverse_forest, traverse_multiprobe,
                               tree_depths)
from repro.index import IndexSpec, SearchParams, build_index, load_index
from repro.kernels import ops, ref
from repro.kernels.forest_traverse import SMEM_NODE_CAP, forest_traverse
from repro.kernels.forest_traverse_hbm import (forest_traverse_hbm,
                                               forest_traverse_hbm_tree)


def _forest(n=700, d=20, n_trees=2, seed=0, **cfg_kw):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    cfg = ForestConfig(n_trees=n_trees, **cfg_kw)
    f = build_forest(jax.random.key(seed), x, cfg)
    return f, cfg.resolved(n), x


# ---------------------------------------------------------------------------
# kernel-level three-way parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_probes", [1, 2, 5])
@pytest.mark.parametrize("b", [1, 33, 64])
def test_hbm_matches_smem_and_ref(n_probes, b):
    f, rcfg, x = _forest()
    q = x[:b]
    hbm = forest_traverse_hbm(f.proj_idx[:, :, 0], f.thresh, f.child_base,
                              q, rcfg.max_depth, interpret=True,
                              n_probes=n_probes)
    for t in range(f.n_trees):
        args = (f.proj_idx[t, :, 0], f.thresh[t], f.child_base[t], q,
                rcfg.max_depth)
        smem = forest_traverse(*args, interpret=True, n_probes=n_probes)
        if n_probes == 1:
            r = ref.forest_traverse_ref(*args)
        else:
            r = ref.forest_traverse_multiprobe_ref(*args, n_probes)
        np.testing.assert_array_equal(np.asarray(hbm[t]), np.asarray(smem))
        np.testing.assert_array_equal(np.asarray(hbm[t]), np.asarray(r))


def test_hbm_probe0_is_single_probe():
    """Probe 0 of the multi-probe output is bitwise the single descent."""
    f, rcfg, x = _forest(seed=3)
    q = x[:21]
    single = forest_traverse_hbm(f.proj_idx[:, :, 0], f.thresh, f.child_base,
                                 q, rcfg.max_depth, interpret=True)
    multi = forest_traverse_hbm(f.proj_idx[:, :, 0], f.thresh, f.child_base,
                                q, rcfg.max_depth, interpret=True, n_probes=4)
    np.testing.assert_array_equal(np.asarray(multi[:, :, 0]),
                                  np.asarray(single))


def test_hbm_above_cap_parity():
    """A tree allocated past SMEM_NODE_CAP (dead padding nodes — the cap is
    about array bytes, not reachable nodes) still matches the refs."""
    f, rcfg, x = _forest(n=400, d=12, seed=5, max_nodes=SMEM_NODE_CAP + 512)
    assert f.max_nodes > SMEM_NODE_CAP
    q = x[:17]
    hbm = forest_traverse_hbm(f.proj_idx[:, :, 0], f.thresh, f.child_base,
                              q, rcfg.max_depth, interpret=True, n_probes=3)
    for t in range(f.n_trees):
        r = ref.forest_traverse_multiprobe_ref(
            f.proj_idx[t, :, 0], f.thresh[t], f.child_base[t], q,
            rcfg.max_depth, 3)
        np.testing.assert_array_equal(np.asarray(hbm[t]), np.asarray(r))


def test_single_tree_wrapper_contract():
    f, rcfg, x = _forest(seed=7)
    q = x[:9]
    one = forest_traverse_hbm_tree(f.proj_idx[0, :, 0], f.thresh[0],
                                   f.child_base[0], q, rcfg.max_depth,
                                   interpret=True)
    assert one.shape == (9,)
    multi = forest_traverse_hbm_tree(f.proj_idx[0, :, 0], f.thresh[0],
                                     f.child_base[0], q, rcfg.max_depth,
                                     interpret=True, n_probes=3)
    assert multi.shape == (9, 3)
    np.testing.assert_array_equal(np.asarray(multi[:, 0]), np.asarray(one))


# ---------------------------------------------------------------------------
# dispatch: ops.traverse_tree kernel selection + forest-level routing
# ---------------------------------------------------------------------------


def test_ops_dispatch_picks_hbm_above_cap():
    """mode="pallas" must serve any tree size: SMEM kernel below the cap,
    HBM kernel above — and both agree with ref."""
    small, rs, xs = _forest(n=300, d=10, seed=11)
    big, rb, xb = _forest(n=300, d=10, seed=11,
                          max_nodes=SMEM_NODE_CAP + 256)
    assert small.max_nodes <= SMEM_NODE_CAP < big.max_nodes
    for f, rcfg, x in ((small, rs, xs), (big, rb, xb)):
        q = x[:13]
        got = ops.traverse_tree(f.proj_idx[0, :, 0], f.thresh[0],
                                f.child_base[0], q, rcfg.max_depth,
                                mode="pallas")
        want = ops.traverse_tree(f.proj_idx[0, :, 0], f.thresh[0],
                                 f.child_base[0], q, rcfg.max_depth,
                                 mode="ref")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ops_dispatch_forced_kernels_agree():
    f, rcfg, x = _forest(seed=13)
    q = x[:11]
    args = (f.proj_idx[0, :, 0], f.thresh[0], f.child_base[0], q,
            rcfg.max_depth)
    for n_probes in (1, 3):
        smem = ops.traverse_tree(*args, mode="pallas", n_probes=n_probes,
                                 kernel="smem")
        hbm = ops.traverse_tree(*args, mode="pallas", n_probes=n_probes,
                                kernel="hbm")
        np.testing.assert_array_equal(np.asarray(smem), np.asarray(hbm))


@pytest.mark.parametrize("n_probes", [1, 3])
def test_traverse_forest_pallas_matches_jnp(n_probes):
    """The pipeline's traversal entry: Pallas routing is bitwise the XLA
    descent (K=1 coefficients are identically 1.0)."""
    f, rcfg, x = _forest(seed=17, n_trees=3)
    q = x[:19]
    got = traverse_forest(f, q, rcfg.max_depth, n_probes, mode="pallas")
    if n_probes == 1:
        want = traverse(f, q, rcfg.max_depth)
    else:
        want = traverse_multiprobe(f, q, rcfg.max_depth, n_probes)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_traverse_forest_k2_falls_back():
    """K > 1 forests (coefficients matter) must use the XLA traversal."""
    f, rcfg, x = _forest(seed=19, n_proj=2)
    q = x[:7]
    got = traverse_forest(f, q, rcfg.max_depth, 1, mode="pallas")
    want = traverse(f, q, rcfg.max_depth)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# the built-depth bound: per-tree loop bounds change nothing
# ---------------------------------------------------------------------------


def _ragged_forest():
    """Three trees of different built depths, the last a single leaf."""
    f, rcfg, x = _forest(n=500, d=16, n_trees=3, seed=23)
    f = f._replace(child_base=f.child_base.at[2].set(-1))
    depths = tree_depths(f)
    assert depths[0] != depths[1] and depths[2] == 0, depths
    return f, rcfg, x, depths


@pytest.mark.parametrize("n_probes", [1, 4])
def test_hbm_per_tree_depths_bitwise(n_probes):
    """Each tree stopped at its own depth (and the forest's deepest as the
    static bound) gives the budget's leaves and probe slots, bitwise."""
    f, rcfg, x, depths = _ragged_forest()
    q = x[:37]
    args = (f.proj_idx[:, :, 0], f.thresh, f.child_base, q)
    budget = forest_traverse_hbm(*args, rcfg.max_depth, interpret=True,
                                 n_probes=n_probes)
    per_tree = forest_traverse_hbm(*args, rcfg.max_depth, interpret=True,
                                   n_probes=n_probes,
                                   depths=jnp.asarray(depths))
    tight = forest_traverse_hbm(*args, int(depths.max()), interpret=True,
                                n_probes=n_probes,
                                depths=jnp.asarray(depths))
    np.testing.assert_array_equal(np.asarray(per_tree), np.asarray(budget))
    np.testing.assert_array_equal(np.asarray(tight), np.asarray(budget))
    if n_probes > 1:
        assert (np.asarray(budget)[2, :, 1:] == -1).all()   # one-leaf tree


def _at_budget(index):
    """Re-bound every engine of ``index`` at the configuration's depth
    budget: the descent as it ran before the built depth bounded it."""
    for seg in index._view.segments:
        eng = seg.engine
        budget = eng.spec.forest.resolved(eng.db.shape[0]).max_depth
        eng.depths = np.full_like(eng.depths, budget)
        eng._prefix_cache.clear()


@pytest.mark.parametrize("mode", ["auto", "pallas"])
@pytest.mark.parametrize("backend", ["rpf", "rpf+int8"])
def test_index_search_bounded_matches_budget(backend, mode, tmp_path):
    """Index.search bounded by the built depth == at the depth budget,
    dists and ids bitwise, after compact() and after save/load."""
    rng = np.random.default_rng(29)
    db = rng.normal(size=(300, 12)).astype(np.float32)
    q = db[:9] + 0.05 * rng.normal(size=(9, 12)).astype(np.float32)
    spec = IndexSpec(backend=backend,
                     forest=ForestConfig(n_trees=4, capacity=8))
    params = SearchParams(k=5, n_probes=3, mode=mode)
    index = build_index(jax.random.key(3), db, spec)
    for row in rng.normal(size=(20, 12)).astype(np.float32):
        index.add(row)
    index.delete(list(range(0, 30, 4)))
    index.compact()
    index.save(str(tmp_path / "idx"))
    loaded = load_index(str(tmp_path / "idx"))
    for ix in (index, loaded):
        eng = ix._primary_engine
        built = [tree_depths(s.engine.forest) for s in ix._view.segments]
        assert ix.stats()["depth_max"] == [int(d.max()) for d in built]
        assert ix.stats()["depth_mean"] == [float(d.mean()) for d in built]
        assert eng._forest_prefix(0)[1].max_depth == int(eng.depths.max())
        assert eng.depths.max() < spec.forest.resolved(
            eng.db.shape[0]).max_depth
        bounded = [np.asarray(a) for a in ix.search(q, params)]
        _at_budget(ix)
        budget = [np.asarray(a) for a in ix.search(q, params)]
        np.testing.assert_array_equal(bounded[1], budget[1])
        np.testing.assert_array_equal(bounded[0], budget[0])
