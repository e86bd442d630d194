"""The program's spans and scopes (``repro.tracing``), on the CPU.

A profiler trace around a few batches served by ``ServingRuntime`` holds
the six host spans with their counters, nested by time; the query
program's lowered text holds the four scope names for both rerank
sources; and a running profiler changes no answer."""
from __future__ import annotations

import glob
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import tracing
from repro.core import ForestConfig, pipeline
from repro.core.quantized import quantize_db
from repro.index import IndexSpec, SearchParams, build_index
from repro.serve import ServingRuntime

MAX_BATCH = 8
WAVES = (3, 8, 5, 1)           # requests sent together, one wave at a time
HOST_SPANS = (tracing.SERVE_BATCH, tracing.SERVE_COLLECT,
              tracing.SERVE_ASSEMBLE, tracing.INDEX_SEARCH,
              tracing.SERVE_FETCH, tracing.SERVE_COMPLETE)
SCOPES = (tracing.DESCENT, tracing.DEDUP, tracing.RELAYOUT, tracing.RERANK)


@pytest.fixture(scope="module")
def corpus(shared_builds):
    db = np.asarray(shared_builds.clustered_db(1000, 16, n_clusters=8,
                                               seed=3))
    q = db[np.random.default_rng(4).integers(0, len(db), 32)] + 0.003
    return db, q.astype(np.float32)


@pytest.fixture(scope="module")
def index(corpus):
    db, _ = corpus
    spec = IndexSpec(backend="rpf",
                     forest=ForestConfig(n_trees=4, capacity=16))
    return build_index(jax.random.key(3), db, spec)


def _serve_waves(runtime, queries):
    answers, j = [], 0
    for n in WAVES:
        reqs = [runtime.submit(queries[j + i]) for i in range(n)]
        for r in reqs:
            assert r.event.wait(60)
            answers.append(r.result)
        j += n
    return answers


@pytest.fixture(scope="module")
def traced(index, corpus, tmp_path_factory):
    """(host spans by name, answers with the profiler on, answers off)."""
    _, q = corpus
    runtime = ServingRuntime(index, params=SearchParams(k=5, n_probes=2),
                             degrade=False, max_batch=MAX_BATCH,
                             max_wait_s=0.02)
    try:
        off = _serve_waves(runtime, q)
        trace_dir = str(tmp_path_factory.mktemp("trace"))
        jax.profiler.start_trace(trace_dir)
        try:
            on = _serve_waves(runtime, q)
        finally:
            jax.profiler.stop_trace()
    finally:
        runtime.stop()
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    spans = {name: [] for name in HOST_SPANS}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in spans:
                    spans[e.name].append((e.start_ns, e.start_ns
                                          + e.duration_ns, dict(e.stats)))
    for got in spans.values():
        got.sort(key=lambda s: s[0])
    return spans, on, off


def test_every_span_is_in_the_trace(traced):
    spans, _, _ = traced
    n = len(spans[tracing.SERVE_BATCH])
    assert n >= len(WAVES)
    for name in HOST_SPANS:
        assert len(spans[name]) == n, name


def test_batches_are_numbered_in_sequence(traced):
    spans, _, _ = traced
    numbers = [c["batch"] for _, _, c in spans[tracing.SERVE_BATCH]]
    assert numbers == list(range(numbers[0], numbers[0] + len(numbers)))
    for name in (tracing.SERVE_COLLECT, tracing.SERVE_ASSEMBLE,
                 tracing.SERVE_FETCH, tracing.SERVE_COMPLETE):
        assert [c["batch"] for _, _, c in spans[name]] == numbers, name


def test_rows_and_padding_add_up(traced):
    spans, _, _ = traced
    rows = [c["rows"] for _, _, c in spans[tracing.SERVE_BATCH]]
    pads = [c["pad"] for _, _, c in spans[tracing.SERVE_ASSEMBLE]]
    assert sum(rows) == sum(WAVES)
    assert all(1 <= r <= MAX_BATCH for r in rows)
    assert pads == [MAX_BATCH - r for r in rows]
    assert all(c["depth"] >= 0 for _, _, c in spans[tracing.SERVE_BATCH])
    assert all(c["segments"] == 1 for _, _, c in spans[tracing.INDEX_SEARCH])


def test_spans_nest_in_their_batch_in_order(traced):
    spans, _, _ = traced
    inner = (tracing.SERVE_COLLECT, tracing.SERVE_ASSEMBLE,
             tracing.INDEX_SEARCH, tracing.SERVE_FETCH,
             tracing.SERVE_COMPLETE)
    for j, (start, end, _) in enumerate(spans[tracing.SERVE_BATCH]):
        parts = [spans[name][j] for name in inner]
        assert all(start <= s <= e <= end for s, e, _ in parts)
        # one after the other, never overlapping
        assert all(a[1] <= b[0] for a, b in zip(parts, parts[1:]))


def test_answers_do_not_change_with_the_profiler_on(traced):
    _, on, off = traced
    assert len(on) == len(off) == sum(WAVES)
    for (d_on, i_on), (d_off, i_off) in zip(on, off):
        np.testing.assert_array_equal(i_on, i_off)
        np.testing.assert_array_equal(d_on, d_off)


@pytest.mark.parametrize("source", ["fp32", "int8"])
def test_query_program_carries_the_scope_names(index, corpus, source):
    db, q = corpus
    forest = index.forest
    cfg = ForestConfig(n_trees=4, capacity=16).resolved(len(db))
    args = (forest, q[:8])
    if source == "fp32":
        lowered = pipeline._fused_query_jit.lower(
            *args, db, 5, cfg.max_depth, cfg.leaf_pad, "l2", "pallas", True,
            0, 8, 128, 2, None)
    else:
        lowered = pipeline._fused_query_quantized_jit.lower(
            *args, quantize_db(db), 5, cfg.max_depth, cfg.leaf_pad, "l2",
            "pallas", True, 0, 8, 128, 4, 2, None)
    text = lowered.as_text(debug_info=True)
    for scope in SCOPES:
        assert f"{scope}/" in text, scope


def test_a_span_costs_microseconds_with_no_profiler():
    n = 20_000
    t0 = time.perf_counter()
    for j in range(n):
        with tracing.span(tracing.SERVE_BATCH, batch=j) as s:
            s.set_metadata(rows=j)
    assert (time.perf_counter() - t0) / n < 50e-6
