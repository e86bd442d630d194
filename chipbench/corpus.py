"""Seeded corpora and query streams, generated on the device in one call.

These are the benchmark's own copies of the repository's stand-ins for the
paper's data sets (``mnist_like`` and ``iss_like``), ported to
``jax.random`` so that a run makes its 60 000 x 784 (or 250 736 x 595)
rows on the chip in one jitted call instead of on the host.  The shapes and
the statistics are those of the originals:

* ``mnist_like``: ``n_classes`` class manifolds in d = side x side
  dimensions.  Each class is an affine map of an ``intrinsic_dim`` latent
  Gaussian (scale 0.35) through gaussian blobs on the pixel grid, plus
  ``noise`` Gaussian pixel noise, clipped to [0, 1] and normalised to unit
  l2 norm.
* ``iss_like``: d-dimensional non-negative sparse histograms from
  ``n_models`` prototypes (Gamma(2, 1) masses on a ``sparsity`` share of
  the bins), multiplicative Gamma(8, 1/8) noise, a sprinkle of extra
  support (1 % of bins, Gamma(1.5) x 0.002), normalised to sum 1.

The generator owns the corpus and the query stream together, since the
queries are drawn from the same classes.  The classes themselves (the blobs
and means, or the prototypes) are the data set's design, fixed by the
configuration's ``design_seed`` as a real data set's distribution is fixed;
the rows and the queries are drawn from the run's seed.  So every seed
serves the same kind of data, and a seed changes the sample, not the
problem.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (also > 2**32)."""
    if seed < 0:
        raise ValueError(f"--seed must be non-negative, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _blobs(cx, cy, sx, sy, side):
    """Gaussian blobs on a side x side grid -> (..., side * side)."""
    yy, xx = jnp.meshgrid(jnp.arange(side, dtype=jnp.float32),
                          jnp.arange(side, dtype=jnp.float32), indexing="ij")
    g = jnp.exp(-((xx - cx[..., None, None]) ** 2 / (2 * sx[..., None, None] ** 2)
                  + (yy - cy[..., None, None]) ** 2
                  / (2 * sy[..., None, None] ** 2)))
    return g.reshape(*cx.shape, side * side)


@functools.partial(jax.jit, static_argnames=("n", "n_queries", "d",
                                             "n_classes", "intrinsic_dim"))
def mnist_like(design, key, *, n: int, n_queries: int, d: int,
               n_classes: int, intrinsic_dim: int, noise: float):
    """-> (rows (n, d), queries (n_queries, d)), f32, unit l2 norm."""
    side = math.isqrt(d)
    if side * side != d:
        raise ValueError(f"mnist_like needs a square width, got d={d}")
    k_centre, k_scale, k_mean = jax.random.split(design, 3)
    k_rows, k_queries = jax.random.split(key)
    shape = (n_classes, intrinsic_dim)
    cx, cy = (jax.random.uniform(k, shape, minval=4.0, maxval=side - 4.0)
              for k in jax.random.split(k_centre))
    sx, sy = (jax.random.uniform(k, shape, minval=1.5, maxval=5.0)
              for k in jax.random.split(k_scale))
    bases = _blobs(cx, cy, sx, sy, side)                  # (C, I, d)
    mx, my = (jax.random.uniform(k, (n_classes,), minval=8.0,
                                 maxval=side - 8.0)
              for k in jax.random.split(k_mean))
    six = jnp.full((n_classes,), 6.0)
    mean = 0.5 * _blobs(mx, my, six, six, side)           # (C, d)
    flat = bases.reshape(n_classes * intrinsic_dim, d)

    def sample(k, m):
        kl, kz, ke = jax.random.split(k, 3)
        labels = jax.random.randint(kl, (m,), 0, n_classes)
        z = jax.random.normal(kz, (m, intrinsic_dim)) * 0.35
        # z placed in its class's block: one matmul instead of an
        # (m, intrinsic_dim, d) gather of the bases
        zc = z[:, None, :] * jax.nn.one_hot(labels, n_classes)[:, :, None]
        x = mean[labels] + jnp.dot(zc.reshape(m, -1), flat,
                                   precision=jax.lax.Precision.HIGHEST)
        x = x + noise * jax.random.normal(ke, (m, d))
        x = jnp.clip(x, 0.0, 1.0)
        return x / (jnp.linalg.norm(x, axis=1, keepdims=True) + 1e-12)

    return sample(k_rows, n), sample(k_queries, n_queries)


@functools.partial(jax.jit, static_argnames=("n", "n_queries", "d",
                                             "n_models"))
def iss_like(design, key, *, n: int, n_queries: int, d: int, n_models: int,
             sparsity: float):
    """-> (rows (n, d), queries (n_queries, d)), f32, non-negative, sum 1."""
    kp, km = jax.random.split(design)
    k_rows, k_queries = jax.random.split(key)
    protos = jax.random.gamma(kp, 2.0, (n_models, d))
    protos = protos * (jax.random.uniform(km, (n_models, d)) < sparsity)
    protos = protos / (protos.sum(axis=1, keepdims=True) + 1e-12)

    def sample(k, m):
        kl, kg, ku, kx = jax.random.split(k, 4)
        labels = jax.random.randint(kl, (m,), 0, n_models)
        g = jax.random.gamma(kg, 8.0, (m, d)) / 8.0
        x = protos[labels] * g
        extra = jax.random.uniform(ku, (m, d)) < 0.01
        x = x + extra * jax.random.gamma(kx, 1.5, (m, d)) * 0.002
        return x / (x.sum(axis=1, keepdims=True) + 1e-12)

    return sample(k_rows, n), sample(k_queries, n_queries)


GENERATORS = {"mnist_like": mnist_like, "iss_like": iss_like}


def generate(corpus: dict, seed: int, n_queries: int):
    """The configuration's ``corpus`` entry -> (rows, queries) on device."""
    params = dict(corpus)
    fn = GENERATORS[params.pop("generator")]
    design = seed_key(params.pop("design_seed"))
    return fn(design, seed_key(seed), n_queries=n_queries, **params)
