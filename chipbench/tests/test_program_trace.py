"""The program's spans and scopes read back from a trace recorded on a
TPU v5 lite.

``data/program.xplane.pb`` (``record_program_trace.py``) holds four waves
of 16, 5, 16 and 9 single-query requests to 128-float rows, served by
``ServingRuntime`` in batches of up to 16, behind the benchmark's
``SpannedIndex``.  The readers
of the program's spans must read it; on ``data/small.xplane.pb``, which
holds no program span, they read nothing, and the benchmark's own
reduction of that trace gives the numbers it gave before them."""
import os

import pytest

from chipbench import program_trace as pt
from chipbench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")
PROGRAM = os.path.join(DATA, "program.xplane.pb")
SMALL = os.path.join(DATA, "small.xplane.pb")
WAVES = (16, 5, 16, 9)
MAX_BATCH = 16
PROGRAMS = ("_fused_query_jit",)


@pytest.fixture(scope="module")
def spans():
    return pt.host_spans(PROGRAM)


@pytest.fixture(scope="module")
def window(spans):
    batches = [s for s in spans if s.name == pt.SERVE_BATCH]
    return batches[0].start, batches[-1].end


@pytest.fixture(scope="module")
def program(window):
    return pt.read(PROGRAM, *window, PROGRAMS)


def by_name(spans, name):
    return [s for s in spans if s.name == name]


def test_names_are_the_programs():
    from repro import tracing
    assert pt.SPANS == (tracing.SERVE_BATCH, tracing.SERVE_COLLECT,
                        tracing.SERVE_ASSEMBLE, tracing.INDEX_SEARCH,
                        tracing.SERVE_FETCH, tracing.SERVE_COMPLETE)
    assert pt.SCOPES == (tracing.DESCENT, tracing.DEDUP, tracing.RELAYOUT,
                         tracing.RERANK)


def test_host_spans_and_counters(spans):
    batches = by_name(spans, pt.SERVE_BATCH)
    assert len(batches) >= len(WAVES)
    for name in pt.SPANS:
        assert len(by_name(spans, name)) == len(batches), name
    numbers = [s.counters["batch"] for s in batches]
    assert numbers == list(range(numbers[0], numbers[0] + len(numbers)))
    rows = [s.counters["rows"] for s in batches]
    assert sum(rows) == sum(WAVES)
    pads = [s.counters["pad"] for s in by_name(spans, pt.SERVE_ASSEMBLE)]
    assert pads == [MAX_BATCH - r for r in rows]


def test_index_search_lies_in_the_benchmarks_span(spans):
    _, bench = trace.load(PROGRAM)
    calls = by_name(spans, pt.INDEX_SEARCH)
    assert len(calls) == len(bench)
    for call, outer in zip(calls, bench):
        assert outer.start <= call.start <= call.end <= outer.end


def test_each_execution_belongs_to_one_dispatch(spans):
    """Each execution of the query program starts nearest the start of its
    own ``index.search`` span.  On a trace this short the device's clock
    sits up to a few tenths of a millisecond off the host's (``trace.py``),
    so an execution may appear to start just before its span."""
    chips, _ = trace.load(PROGRAM)
    modules = [m for m in chips[0]["XLA Modules"]
               if any(p in m.name for p in PROGRAMS)]
    calls = by_name(spans, pt.INDEX_SEARCH)
    assert len(modules) == len(calls)
    owner = [min(range(len(calls)),
                 key=lambda j: abs(calls[j].start - m.start))
             for m in modules]
    assert owner == list(range(len(calls)))


# rows of 128 floats are already the rerank kernel's row store: the
# relayout is a free bitcast and no op carries its scope in this trace
SCOPED = ("rpf.descent", "rpf.dedup", "rpf.rerank")


def test_every_scope_and_kernel_name_is_in_the_trace():
    scopes = pt._scopes(PROGRAM)
    assert set(scopes.values()) == set(SCOPED)
    chips, _ = trace.load(PROGRAM)
    names = {trace.op_name(e.name).split(".")[0]
             for e in chips[0]["XLA Ops"] if trace.is_kernel(e.name)}
    assert names == {"rpf_descent_hbm", "rpf_rerank"}


def test_scope_times(program, spans, window):
    n = len(by_name(spans, pt.SERVE_BATCH))
    assert program.executions == n
    ms = {s: program.scope_ms(s) for s in SCOPED}
    assert all(v is not None and v > 0 for v in ms.values()), ms
    assert program.scope_ms("rpf.relayout") is None
    # the same seconds as the ops' own self times, grouped by their scope
    chips, _ = trace.load(PROGRAM)
    lo, hi = window
    ops = [e for e in chips[0]["XLA Ops"] if lo <= e.start < hi]
    scope_of = {trace.op_name(k): v for k, v in pt._scopes(PROGRAM).items()}
    self_s = trace.self_times(ops)
    for scope in SCOPED:
        want = sum(s for op, s in self_s.items() if scope_of.get(op) == scope)
        assert 1e-3 * ms[scope] * n == pytest.approx(want, rel=1e-9)
    # and no more than the executions' device time
    step = sum(m.dur for m in chips[0]["XLA Modules"]
               if any(p in m.name for p in PROGRAMS))
    assert 1e-3 * sum(ms.values()) * n <= step


def test_front_end_readings(program, spans):
    batches = by_name(spans, pt.SERVE_BATCH)
    rows = sum(s.counters["rows"] for s in batches)
    assert program.batch_fill() == pytest.approx(
        100.0 * rows / (MAX_BATCH * len(batches)))
    for name in pt.SPANS:
        want = sum(s.dur for s in by_name(spans, name)) / len(batches)
        assert program.mean_ms(name) == pytest.approx(1e3 * want)
    idle = program.idle_front_end_ms()
    front = sum(s.dur for s in spans if s.name in pt.FRONT_END)
    assert 0 < idle <= 1e3 * front / len(batches)


def test_host_spans_of_a_cpu_trace(tmp_path):
    """The spans as the program writes them now, traced on the CPU (no
    device plane: no scope, no execution)."""
    import glob

    import jax
    import numpy as np

    from repro.core import ForestConfig
    from repro.index import IndexSpec, SearchParams, build_index
    from repro.serve import ServingRuntime
    rows = np.random.default_rng(0).standard_normal((512, 8), np.float32)
    index = build_index(jax.random.key(0), rows, IndexSpec(
        backend="rpf", forest=ForestConfig(n_trees=2, capacity=12)))
    runtime = ServingRuntime(index, params=SearchParams(k=4), degrade=False,
                             max_batch=4, max_wait_s=0.01)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for n in (3, 4, 1):
            reqs = [runtime.submit(r) for r in rows[:n]]
            assert all(r.event.wait(60) for r in reqs)
    finally:
        jax.profiler.stop_trace()
        runtime.stop()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    spans = pt.host_spans(path)
    assert {s.name for s in spans} == set(pt.SPANS)
    batches = by_name(spans, pt.SERVE_BATCH)
    assert sum(s.counters["rows"] for s in batches) == 8
    r = pt.read(path, batches[0].start, batches[-1].end, PROGRAMS)
    assert r.batch_fill() == pytest.approx(100.0 * 8 / (4 * len(batches)))
    assert r.mean_ms(pt.INDEX_SEARCH) > 0 and r.mean_ms(pt.SERVE_COLLECT) > 0
    assert r.executions == 0 and r.scope_ms("rpf.dedup") is None


def test_readings_of_spans_made_by_hand():
    def span(name, start, end, **counters):
        return pt.Span(name, start, end, counters)
    spans = {
        pt.SERVE_BATCH: [span(pt.SERVE_BATCH, 0.0, 10.0, batch=7, rows=3),
                         span(pt.SERVE_BATCH, 10.0, 20.0, batch=8, rows=4)],
        pt.SERVE_COLLECT: [span(pt.SERVE_COLLECT, 0.0, 2.0, batch=7),
                           span(pt.SERVE_COLLECT, 10.0, 14.0, batch=8)],
        pt.SERVE_ASSEMBLE: [span(pt.SERVE_ASSEMBLE, 2.0, 3.0, batch=7, pad=1),
                            span(pt.SERVE_ASSEMBLE, 14.0, 15.0, batch=8,
                                 pad=0)],
        pt.SERVE_COMPLETE: [span(pt.SERVE_COMPLETE, 9.0, 10.0, batch=7)]}
    idle = [(1.0, 2.5), (8.0, 12.0)]
    r = pt.ProgramTrace(spans, {"rpf.dedup": 0.5}, 2, idle)
    assert r.batch_fill() == pytest.approx(100.0 * 7 / 8)
    assert r.mean_ms(pt.SERVE_COLLECT) == pytest.approx(3e3)
    assert r.mean_ms(pt.INDEX_SEARCH) is None
    # idle inside collect, assemble, complete: 1.5 + 1 + 2, over 2 batches
    assert r.idle_front_end_ms() == pytest.approx(1e3 * 4.5 / 2)
    assert r.scope_ms("rpf.dedup") == pytest.approx(250.0)
    assert r.scope_ms("rpf.relayout") is None


def test_overlap():
    assert pt._overlap([(0, 2), (3, 5)], [(1, 4)]) == 2
    assert pt._overlap([(0, 1)], [(1, 2)]) == 0
    assert pt._overlap([], [(0, 1)]) == 0


def test_a_trace_without_the_programs_spans_reads_nothing():
    r = pt.read(SMALL, 0.0, 1e9, ("jit_step",))
    assert r.executions == 3 and r.scope_seconds == {}
    assert r.batch_fill() is None and r.idle_front_end_ms() is None
    assert all(r.mean_ms(n) is None for n in pt.SPANS)
    assert all(r.scope_ms(s) is None for s in pt.SCOPES)


def test_the_benchmarks_reduction_reads_as_before():
    """``trace.reduce`` on ``small.xplane.pb``, pinned."""
    _, host = trace.load(SMALL)
    spans = [(h.start - 1000.0, h.end - 1000.0) for h in host]
    r = trace.reduce(SMALL, spans, spans[0][0], spans[-1][1], ("jit_step",))
    assert r.window_s == pytest.approx(0.0070149900000160414, rel=1e-9)
    assert r.busy_s == pytest.approx(8.969e-06, rel=1e-9)
    assert r.calls == [0, 1, 2]
    assert r.op_seconds == pytest.approx({"first_kernel.1": 2.281e-06,
                                          "sin.1": 4.832e-06,
                                          "step.1": 1.856e-06}, rel=1e-6)
    totals = dict(r.breakdown()["idle_gaps"][:2])
    assert totals == pytest.approx({
        "all gaps between Index.search calls": 0.006369061,
        "all gaps in Index.search": 0.00063696}, rel=1e-6)
