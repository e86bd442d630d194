"""The trace reduction, on a small trace recorded on a TPU v5 lite.

``data/small.xplane.pb`` holds three executions of a jitted ``step`` with
two Pallas kernels (``first_kernel``, then one named after ``step``) around
a ``sin``, each inside a ``chipbench.search`` span, 2 ms apart."""
import os

import numpy as np
import pytest

from chipbench import trace

SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")
OFFSET = 1000.0           # any host clock: the reduction finds the offset


@pytest.fixture(scope="module")
def loaded():
    return trace.load(SMALL)


def spans_on_host(host):
    return [(h.start - OFFSET, h.end - OFFSET) for h in host]


def test_layout(loaded):
    chips, host = loaded
    assert len(chips) == 1 and len(host) == 3
    assert {"XLA Modules", "XLA Ops"} <= set(chips[0])
    ops = chips[0]["XLA Ops"]
    assert [trace.op_name(e.name) for e in ops[:3]] == [
        "first_kernel.1", "sin.1", "step.1"]
    assert [trace.is_kernel(e.name) for e in ops[:3]] == [True, False, True]


def test_reduce(loaded):
    chips, host = loaded
    spans = spans_on_host(host)
    t0, t_end = spans[0][0] - 1e-4, spans[-1][1]
    r = trace.reduce(SMALL, spans, t0, t_end, ("jit_step",))
    ops = chips[0]["XLA Ops"]
    assert r.window_s == pytest.approx(t_end - t0)
    # busy: the nine op intervals, none overlapping
    assert r.busy_s == pytest.approx(sum(e.dur for e in ops), rel=1e-9)
    assert len(r.executions) == 3 and r.calls == [0, 1, 2]
    for module, kernels in r.executions:
        assert [trace.op_name(k.name) for k in kernels] == ["first_kernel.1",
                                                            "step.1"]
        assert module.dur > sum(k.dur for k in kernels)
    seconds = r.kernel_seconds(["descent", "rerank"])
    assert seconds["descent"] == pytest.approx(sum(e.dur for e in ops[0::3]))
    assert seconds["rerank"] == pytest.approx(sum(e.dur for e in ops[2::3]))
    # every kernel past the list takes the last role
    assert r.kernel_seconds(["all"])["all"] == pytest.approx(
        sum(seconds.values()))
    # idle: the rest of the window, split into gaps by host activity
    assert sum(s for _, s in r.gaps) == pytest.approx(r.window_s - r.busy_s)
    labels = {label for label, _ in r.gaps}
    assert labels == {"in Index.search", "between Index.search calls"}
    between = sum(s for label, s in r.gaps if label != "in Index.search")
    assert between > 0.005        # the 2 ms sleeps and the Python between


def test_breakdown(loaded):
    _, host = loaded
    spans = spans_on_host(host)
    r = trace.reduce(SMALL, spans, spans[0][0], spans[-1][1],
                     ("jit_step",))
    b = r.breakdown()
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "sin.1" and set(names) == {"sin.1", "first_kernel.1",
                                                  "step.1"}
    assert len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][0] == "all gaps between Index.search calls"
    assert all(s > 0 for _, s in b["device_ops"] + b["idle_gaps"])


def test_gap_labels_see_waiting_requests(loaded):
    _, host = loaded
    spans = spans_on_host(host)
    t0, t_end = spans[0][0], spans[-1][1]
    # one request due at the start and answered after the last call
    due, done = np.array([t0]), np.array([t_end])
    r = trace.reduce(SMALL, spans, t0, t_end, ("jit_step",), due, done)
    labels = {label for label, _ in r.gaps}
    assert "between Index.search calls, requests waiting" in labels
    assert "between Index.search calls, no request waiting" not in labels


def test_self_times_nest():
    ev = trace.Event
    ops = [ev("%while.1 = x", 0.0, 10.0), ev("%k.1 = y", 1.0, 4.0),
           ev("%k.1 = y", 5.0, 9.0), ev("%f.2 = z", 11.0, 12.0)]
    assert trace.self_times(ops) == {"while.1": 3.0, "k.1": 7.0, "f.2": 1.0}


def test_reduce_without_spans_reads_nothing():
    assert trace.reduce(SMALL, [], 0.0, 1.0, ("jit_step",)) is None
