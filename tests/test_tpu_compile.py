"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: blocks off the (8, 128) tiling, DMAs of slices narrower than a
tile, gathers Mosaic cannot lower, scalar memory over its 1 MiB.  These
tests compile each kernel at the widths of the paper's deployments
(MNIST-784 and ISS-595, B = 64, M = 2048 candidates, N = 60 000 rows, L = 80
trees of 66 730 nodes) for a v5e that is described, not attached.  Nothing
runs; a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture, never at import
time: only one process at a time may load the TPU compiler's library, and
under several test workers only the worker given this file loads it.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import forest_traverse, forest_traverse_hbm, fused_query
from repro.kernels import fused_query_int8
from repro.tracing import (KERNEL_DESCENT_HBM, KERNEL_DESCENT_SMEM,
                           KERNEL_RERANK, KERNEL_RERANK_INT8)

B, M, N, L, NODES, DEPTH = 64, 2048, 60_000, 80, 66_730, 66


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _store(layout, shape, dtype):
    """(shape, dtype) of the kernel's row store for (N, d) rows."""
    out = jax.eval_shape(layout, jax.ShapeDtypeStruct(shape, dtype))
    return out.shape, out.dtype


def _assert_kernel(compiled, *names):
    """The program holds a kernel, and each of ``names`` is the op name of
    one (``pallas_call(name=...)``; the trace names the op the same)."""
    kernel_ops = [line for line in compiled.as_text().splitlines()
                  if 'custom_call_target="tpu_custom_call"' in line]
    assert kernel_ops
    for name in names:
        assert any(line.lstrip().startswith(f"%{name}.")
                   for line in kernel_ops), name


@pytest.mark.parametrize("metric,d", [("l2", 784), ("chi2", 595),
                                      ("l2", 960), ("cosine", 128),
                                      ("dot", 96)])
def test_fused_gather_topk_compiles(one_chip, metric, d):
    compiled = _compile(
        one_chip,
        lambda q, ids, db: fused_query.fused_gather_topk(q, ids, db, 10,
                                                         metric=metric),
        ((B, d), jnp.float32), ((B, M), jnp.int32),
        _store(fused_query.row_store, (N, d), jnp.float32))
    _assert_kernel(compiled, KERNEL_RERANK)


@pytest.mark.parametrize("d", [784, 595])
def test_fused_gather_topk_int8_compiles(one_chip, d):
    compiled = _compile(
        one_chip,
        lambda q, ids, q8, s: fused_query_int8.fused_gather_topk_int8(
            q, ids, q8, s, 40),
        ((B, d), jnp.float32), ((B, M), jnp.int32),
        _store(fused_query_int8.pack_int8_rows, (N, d), jnp.int8),
        ((N,), jnp.float32))
    _assert_kernel(compiled, KERNEL_RERANK_INT8)


@pytest.mark.parametrize("n_probes", [1, 4])
def test_forest_traverse_hbm_compiles(one_chip, n_probes):
    compiled = _compile(
        one_chip,
        lambda f, t, c, q: forest_traverse_hbm.forest_traverse_hbm(
            f, t, c, q, DEPTH, n_probes=n_probes),
        ((L, NODES), jnp.int32), ((L, NODES), jnp.float32),
        ((L, NODES), jnp.int32), ((B, 784), jnp.float32))
    _assert_kernel(compiled, KERNEL_DESCENT_HBM)


@pytest.mark.parametrize("n_probes", [1, 4])
def test_forest_traverse_smem_compiles_at_cap(one_chip, n_probes):
    cap = forest_traverse.SMEM_NODE_CAP
    compiled = _compile(
        one_chip,
        lambda f, t, c, q: forest_traverse.forest_traverse(
            f, t, c, q, DEPTH, n_probes=n_probes),
        ((cap,), jnp.int32), ((cap,), jnp.float32), ((cap,), jnp.int32),
        ((B, 784), jnp.float32))
    _assert_kernel(compiled, KERNEL_DESCENT_SMEM)


@pytest.mark.parametrize("backend", ["rpf", "rpf+int8"])
def test_fused_query_pipeline_compiles(one_chip, backend, monkeypatch):
    """The whole query program as ``Index.search`` runs it on a TPU: row
    store layout, descent kernel, candidate gather, fused rerank(s)."""
    from repro.configs.rpf_mnist784 import CONFIG
    from repro.core import pipeline
    from repro.core.forest import Forest
    from repro.core.quantized import QuantizedDB
    from repro.kernels import ops
    # on this CPU host mode="auto" resolves to the oracles; steer it to the
    # compiled kernels, as it resolves on a TPU
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = CONFIG.resolved(N)
    n, d = cfg.max_nodes, 784

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    forest = Forest(proj_idx=s((L, n, 1), jnp.int32),
                    proj_coef=s((L, n, 1), jnp.float32),
                    thresh=s((L, n), jnp.float32),
                    child_base=s((L, n), jnp.int32),
                    perm=s((L, N), jnp.int32),
                    leaf_offset=s((L, n), jnp.int32),
                    leaf_count=s((L, n), jnp.int32),
                    n_nodes=s((L,), jnp.int32))
    if backend == "rpf":
        src = s((N, d), jnp.float32)
    else:
        src = QuantizedDB(q=s((N, d), jnp.int8), scale=s((N,), jnp.float32),
                          fp=s((N, d), jnp.float32))
    compiled = jax.jit(
        lambda f, q, db: pipeline.fused_query(f, q, db, 10, CONFIG)
    ).lower(forest, s((8, d), jnp.float32), src).compile()
    n_kernels = compiled.as_text().count("tpu_custom_call")
    assert n_kernels >= (2 if backend == "rpf" else 3), n_kernels
    _assert_kernel(compiled, KERNEL_DESCENT_HBM, KERNEL_RERANK,
                   *([KERNEL_RERANK_INT8] if backend == "rpf+int8" else []))
