"""A CPU rehearsal of a whole run: set-up, window, comparison and result
line, at a tiny size, with the Pallas kernels in interpret mode.  The
command itself refuses to run without a TPU; these tests call the
harness's internals instead."""
import json
import os

import numpy as np
import pytest

from chipbench import run as harness
from chipbench.spans import SpannedIndex

TINY = {
    "name": "tiny-rpf",
    "corpus": {"generator": "mnist_like", "design_seed": 1, "n": 2000, "d": 256,
               "n_classes": 10, "intrinsic_dim": 12, "noise": 0.02},
    "metric": "l2",
    "index": {"backend": "rpf", "n_trees": 4, "capacity": 12,
              "split_ratio": 0.3, "n_proj": 1, "leaf_pad": 12},
    "search": {"k": 10, "n_probes": 2},
    "serving": {"max_batch": 16, "max_wait_s": 0.002, "degrade": False,
                "gc_freeze_after_warmup": True},
    "trace": {"programs": ["_fused_query_jit"],
              "kernels": ["descent", "rerank"]},
    "limits": {"dist_err": 1e-4, "recall_at_10": 0.3, "leaf_points": 12},
}
TINY_ISS = dict(TINY, name="tiny-iss", metric="chi2",
                corpus={"generator": "iss_like", "design_seed": 1, "n": 2000, "d": 96,
                        "n_models": 8, "sparsity": 0.15},
                search={"k": 10, "n_probes": 1},
                limits={"dist_err": 1e-4, "recall_at_10": 0.1})   # tied bins
OPEN = {"kind": "open", "rate_per_s": 40}
CLOSED = {"kind": "closed", "concurrency": 32, "max_rate_per_s": 20000}


def load_bench() -> dict:
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def rehearse(config, mix, tmp_path, trace=False, **kw):
    return harness.run_cell(config, mix, seed=2**31 + 99, seconds=1.5,
                            trace=trace, mode="pallas",
                            work_dir=str(tmp_path), grace_s=120.0, **kw)


@pytest.mark.parametrize("config,mix,cell", [
    (TINY, OPEN, "mnist784-rpf.steady"),
    (TINY, CLOSED, "mnist784-rpf.bulk"),
    (TINY_ISS, OPEN, "mnist784-rpf.steady"),
], ids=["open-l2", "closed-l2", "open-chi2"])
def test_rehearsal_end_to_end(config, mix, cell, tmp_path):
    out = rehearse(config, mix, tmp_path)
    checks = out["checks"]
    assert checks["missing"]["value"] == 0
    assert checks["partition_faults"]["value"] == 0
    assert checks["topk_mismatch"]["value"] == 0
    assert checks["dist_err"]["value"] < 1e-5
    assert out["attempted"] > 0 and out["failed"] == 0
    device = {"platform": "cpu", "kind": "cpu", "count": 1,
              "memory_peak_bytes": 0}
    line = harness.result_line(load_bench(), cell, False, out, device)
    assert line["correct"] is True
    names = {m["name"] for m in harness.cell_metrics(load_bench(), cell,
                                                     False)}
    assert set(line["metrics"]) == names
    for m in line["metrics"].values():
        assert np.isfinite(m["value"]) and m["value"] > 0
    assert list(line)[-1] == "checks"
    json.dumps(line)


def test_spans_delegate_and_keep_the_batches_they_time():
    """The proxy times each search, keeps its batch when asked, and hands
    every other attribute through, so ServingRuntime serves it as is."""
    class Fake:
        spec = "the spec"

        def search(self, q, params=None):
            return q.sum(axis=1), params

    proxy = SpannedIndex(Fake(), keep_batches=True)
    q = np.arange(12, dtype=np.float32).reshape(4, 3)
    d, p = proxy.search(q, "params")
    assert d.tolist() == [3.0, 12.0, 21.0, 30.0] and p == "params"
    assert proxy.spec == "the spec"
    assert len(proxy.spans) == 1 and len(proxy.batches) == 1
    assert np.array_equal(proxy.batches[0], q)
    start, end = proxy.spans[0]
    assert start <= end
