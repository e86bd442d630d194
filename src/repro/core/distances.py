"""Distance metrics used by the paper.

The paper evaluates with Euclidean distance (MNIST-784) and the Chi-Square
divergence (ISS-595, Eq. in §4):  chi2(x, y) = sum_k (x_k - y_k)^2 / (x_k + y_k).

All pairwise forms are written to be shard- and tile-friendly: the L2 pairwise
uses the |x|^2 - 2 x.y + |y|^2 expansion so the inner term is an MXU matmul.
Those matmuls run at ``HIGHEST`` precision: at the default, a TPU multiplies
f32 operands in bf16 passes, and the exact reference would rank in bf16.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

EPS = 1e-12

# ---------------------------------------------------------------------------
# point-to-point / point-to-set forms
# ---------------------------------------------------------------------------


def l2_sq(x: jax.Array, y: jax.Array) -> jax.Array:
    """Squared Euclidean distance along the last axis (broadcasting)."""
    d = x - y
    return jnp.sum(d * d, axis=-1)


def chi2(x: jax.Array, y: jax.Array) -> jax.Array:
    """Chi-square divergence along the last axis (broadcasting).

    Inputs are assumed non-negative (histogram features, per the paper).
    """
    num = (x - y) ** 2
    den = x + y
    return jnp.sum(num / (den + EPS), axis=-1)


def neg_dot(x: jax.Array, y: jax.Array) -> jax.Array:
    """Negative inner product (so that smaller == more similar, like a distance)."""
    return -jnp.sum(x * y, axis=-1)


def cosine_dist(x: jax.Array, y: jax.Array) -> jax.Array:
    xn = x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + EPS)
    yn = y / (jnp.linalg.norm(y, axis=-1, keepdims=True) + EPS)
    return 1.0 - jnp.sum(xn * yn, axis=-1)


METRICS: dict[str, Callable[[jax.Array, jax.Array], jax.Array]] = {
    "l2": l2_sq,
    "chi2": chi2,
    "dot": neg_dot,
    "cosine": cosine_dist,
}

# user-facing aliases -> the canonical kernel spelling.  "ip" is the public
# inner-product name (SearchParams.metric accepts it); the kernels and refs
# keep scoring under "dot", so every dispatch site canonicalizes first.
METRIC_ALIASES: dict[str, str] = {
    "ip": "dot",
    "inner_product": "dot",
    "euclidean": "l2",
}


def canonical_metric(name: str) -> str:
    """Alias-resolve + validate a metric name (the one metric registry).

    Every surface that takes a metric string — ``SearchParams``,
    ``exact_knn``, the tuner — funnels through here, so "ip" and "dot"
    are the same operating point everywhere and an unknown metric fails
    loudly at the API boundary instead of as a kernel KeyError.
    """
    m = METRIC_ALIASES.get(name, name)
    if m not in METRICS:
        known = sorted(set(METRICS) | set(METRIC_ALIASES))
        raise ValueError(f"unknown metric {name!r} (known: {known})")
    return m

# ---------------------------------------------------------------------------
# pairwise (Q, d) x (N, d) -> (Q, N) forms
# ---------------------------------------------------------------------------


def _matmul_t(a: jax.Array, b: jax.Array) -> jax.Array:
    """a @ b.T in full f32 precision on every backend."""
    return jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)


def pairwise_l2_sq(q: jax.Array, db: jax.Array) -> jax.Array:
    """(Q, d) x (N, d) -> (Q, N), via the matmul expansion (MXU-friendly)."""
    qn = jnp.sum(q * q, axis=-1)[:, None]
    dn = jnp.sum(db * db, axis=-1)[None, :]
    cross = _matmul_t(q, db)
    out = qn - 2.0 * cross + dn
    return jnp.maximum(out, 0.0)


def pairwise_chi2(q: jax.Array, db: jax.Array) -> jax.Array:
    """(Q, d) x (N, d) -> (Q, N) chi-square. O(Q*N*d) elementwise (VPU-bound)."""
    x = q[:, None, :]
    y = db[None, :, :]
    return jnp.sum((x - y) ** 2 / (x + y + EPS), axis=-1)


def pairwise_dot(q: jax.Array, db: jax.Array) -> jax.Array:
    return -_matmul_t(q, db)


def pairwise_cosine(q: jax.Array, db: jax.Array) -> jax.Array:
    qn = q / (jnp.linalg.norm(q, axis=-1, keepdims=True) + EPS)
    dn = db / (jnp.linalg.norm(db, axis=-1, keepdims=True) + EPS)
    return 1.0 - _matmul_t(qn, dn)


PAIRWISE: dict[str, Callable[[jax.Array, jax.Array], jax.Array]] = {
    "l2": pairwise_l2_sq,
    "chi2": pairwise_chi2,
    "dot": pairwise_dot,
    "cosine": pairwise_cosine,
}


@functools.partial(jax.jit, static_argnames=("metric",))
def pairwise(q: jax.Array, db: jax.Array, metric: str = "l2") -> jax.Array:
    return PAIRWISE[metric](q, db)


def normalize_rows(x: jax.Array) -> jax.Array:
    """Unit-normalize rows (the paper normalizes MNIST vectors to norm 1)."""
    return x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + EPS)
