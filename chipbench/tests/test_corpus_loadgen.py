"""The copied generators: the seed fixes the corpus, the queries and the
arrivals; the shapes and statistics are those of the originals."""
import numpy as np
import pytest

from chipbench import corpus, loadgen

MNIST = {"generator": "mnist_like", "design_seed": 1, "n": 500, "d": 784, "n_classes": 10,
         "intrinsic_dim": 12, "noise": 0.02}
ISS = {"generator": "iss_like", "design_seed": 1, "n": 500, "d": 595, "n_models": 72,
       "sparsity": 0.15}
BIG_SEED = 2**31 + 12345


@pytest.mark.parametrize("spec", [MNIST, ISS], ids=["mnist", "iss"])
def test_seed_fixes_corpus_and_queries(spec):
    a_rows, a_q = map(np.asarray, corpus.generate(spec, BIG_SEED, 40))
    b_rows, b_q = map(np.asarray, corpus.generate(spec, BIG_SEED, 40))
    c_rows, c_q = map(np.asarray, corpus.generate(spec, BIG_SEED + 1, 40))
    assert a_rows.shape == (500, spec["d"]) and a_q.shape == (40, spec["d"])
    assert np.array_equal(a_rows, b_rows) and np.array_equal(a_q, b_q)
    assert not np.array_equal(a_rows, c_rows)
    # every query is distinct: none can repeat inside a window
    assert len({r.tobytes() for r in a_q}) == len(a_q)


@pytest.mark.parametrize("spec", [MNIST, ISS], ids=["mnist", "iss"])
def test_design_seed_fixes_the_classes(spec):
    """The classes are the configuration's; the seed draws the sample."""
    rows, _ = map(np.asarray, corpus.generate(spec, 7, 10))
    other = dict(spec, design_seed=spec["design_seed"] + 1)
    moved, _ = map(np.asarray, corpus.generate(other, 7, 10))
    assert not np.array_equal(rows, moved)


def test_mnist_like_statistics():
    rows, _ = map(np.asarray, corpus.generate(MNIST, 3, 10))
    assert np.allclose(np.linalg.norm(rows, axis=1), 1.0, atol=1e-5)
    assert rows.min() >= 0.0
    # pixels of the clipped blobs: most are exactly zero, as in the original
    assert 0.2 < np.mean(rows == 0.0) < 0.9


def test_iss_like_statistics():
    rows, _ = map(np.asarray, corpus.generate(ISS, 3, 10))
    assert np.allclose(rows.sum(axis=1), 1.0, atol=1e-5)
    assert rows.min() >= 0.0
    # sparse histograms: about 15 % prototype support plus 1 % extra
    assert 0.1 < np.mean(rows > 0) < 0.3


def test_seed_key_takes_large_seeds():
    k1 = corpus.seed_key(2**40 + 7)
    k2 = corpus.seed_key(7)
    assert not np.array_equal(np.asarray(corpus.jax.random.key_data(k1)),
                              np.asarray(corpus.jax.random.key_data(k2)))


def test_schedule_is_fixed_by_seed_and_holds_the_same_work():
    a = loadgen.schedule(400.0, 10.0, np.random.default_rng([BIG_SEED, 1]))
    b = loadgen.schedule(400.0, 10.0, np.random.default_rng([BIG_SEED, 1]))
    c = loadgen.schedule(400.0, 10.0, np.random.default_rng([BIG_SEED + 1, 1]))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len(a) == len(c) == 4000
    assert np.all(np.diff(a) >= 0) and 0 <= a[0] and a[-1] < 10.0
    # exponential gaps at the stated rate
    gaps = np.diff(a)
    assert abs(gaps.mean() - 1 / 400) < 0.1 / 400
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1


def test_pool_sizes():
    assert loadgen.pool_size({"kind": "open", "rate_per_s": 400.0}, 20) == 8000
    assert loadgen.pool_size({"kind": "closed", "concurrency": 256,
                              "max_rate_per_s": 2000}, 20) == 40256


def test_nearest_rank():
    v = np.arange(1, 101, dtype=float)
    assert loadgen.nearest_rank(v, 0.5) == 50.0
    assert loadgen.nearest_rank(v, 0.99) == 99.0
    assert loadgen.nearest_rank(np.append(v[:-1], np.inf), 0.999) == np.inf
