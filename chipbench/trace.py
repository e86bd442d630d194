"""Reduce a profiler trace (``.xplane.pb``) of the window to what readers read.

Layout of a TPU trace as ``jax.profiler.ProfileData`` reads it: one plane
per chip named ``/device:TPU:<n>`` with the lines ``XLA Modules`` (one
event per execution of a compiled program, named ``jit_<fn>(<hash>)``) and
``XLA Ops`` (one event per HLO op, named by the op's HLO text: ``%name.N =
<shape> <opcode>(...)``; a Pallas kernel is a ``custom-call`` with the
target ``tpu_custom_call``); host planes
hold the benchmark's ``chipbench.search`` spans.  All events share one
clock, in nanoseconds from the start of the trace.

* busy / idle: the union of the ``XLA Ops`` intervals inside the window,
  averaged over the chips; idle is the rest of the window.
* executions of the query program: ``XLA Modules`` events whose name holds
  one of the function names the configuration lists (``trace.programs``),
  each given to the ``Index.search`` call it overlaps most (the device's
  clock may sit a little off the host's), for the calls that started in
  the window.
* kernels: the Pallas kernels inside one execution, in the order they
  start, given the roles that the configuration lists (``trace.kernels``):
  the i-th kernel takes the i-th role and every kernel past the list the
  last one.  The query program's data dependences fix that order: for the
  forest query the tree descent comes first, then the rerank's calls.
* idle gaps, labelled by what the host was doing: inside an
  ``Index.search`` call (dispatch, copies, fetching the answers) or between
  calls, with or without requests waiting.

The window is placed on the trace's clock by the ``chipbench.search``
spans, which the benchmark also keeps on the host's clock.
"""
from __future__ import annotations

import collections
import dataclasses
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SEARCH_SPAN = "chipbench.search"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float          # seconds on the trace's clock
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


def op_name(hlo_text: str) -> str:
    """``%fusion.3 = s32[...] fusion(...)`` -> ``fusion.3``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def is_kernel(hlo_text: str) -> bool:
    """A Pallas kernel: a custom call to the TPU's kernel target."""
    return 'custom_call_target="tpu_custom_call"' in hlo_text


def load(path: str) -> tuple[list[dict], list[Event]]:
    """-> (per chip {line: [Event]}, host ``chipbench.search`` spans)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    chips, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                lines[line.name] = sorted(
                    (Event(e.name, e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9)
                     for e in line.events), key=lambda e: e.start)
            chips.append(lines)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events if e.name == SEARCH_SPAN)
    return chips, sorted(host, key=lambda e: e.start)


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def self_times(ops: list[Event]) -> dict:
    """Seconds per op name, less the time of ops nested inside it."""
    total = collections.defaultdict(float)
    stack: list[list] = []               # [event, time of direct children]
    for ev in ops:
        while stack and ev.start >= stack[-1][0].end:
            done, child = stack.pop()
            total[op_name(done.name)] += done.dur - child
        if stack:
            stack[-1][1] += ev.dur
        stack.append([ev, 0.0])
    for done, child in stack:
        total[op_name(done.name)] += done.dur - child
    return total


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    executions: list            # [(module Event, [kernel Event, ...])]
    calls: list                 # index of the search span of each execution
    op_seconds: dict            # op name -> self seconds, first chip
    gaps: list                  # [(label, seconds)], first chip

    def breakdown(self) -> dict:
        top_ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])
        by_label = collections.defaultdict(float)
        for label, sec in self.gaps:
            by_label[label] += sec
        totals = sorted(by_label.items(), key=lambda kv: -kv[1])
        longest = sorted(self.gaps, key=lambda g: -g[1])
        return {"device_ops": [[n, s] for n, s in top_ops[:10]],
                "idle_gaps": ([["all gaps " + n, s] for n, s in totals]
                              + [["one gap " + n, s] for n, s in longest]
                              )[:10]}

    def kernel_seconds(self, roles) -> dict:
        """{role: kernel seconds summed over the executions}: the i-th
        kernel of an execution takes ``roles[i]``, later ones the last."""
        out = dict.fromkeys(roles, 0.0)
        for _, kernels in self.executions:
            for j, k in enumerate(kernels):
                out[roles[min(j, len(roles) - 1)]] += k.dur
        return out


def reduce(path: str, spans, t0: float, t_end: float, programs,
           due=None, done=None) -> Reduced | None:
    """Reduce the trace at ``path`` to the window [t0, t_end) (perf_counter
    seconds).  ``spans`` are the benchmark's host spans of the traced
    ``Index.search`` calls, in order; ``programs`` the function names of
    the query program; ``due``/``done`` (perf_counter) the
    requests' scheduled arrivals and completions, for labelling gaps.
    Returns None where the trace holds no device or no span to place the
    window by."""
    chips, host = load(path)
    if not chips or not host or len(host) != len(spans):
        return None
    offset = float(np.median([h.start - s for h, (s, _) in zip(host, spans)]))
    lo, hi = t0 + offset, t_end + offset
    busy = []
    for lines in chips:
        ops = lines.get("XLA Ops", [])
        busy.append(union(((e.start, e.end) for e in ops), lo, hi))
    busy_s = float(np.mean([sum(e - s for s, e in u) for u in busy]))

    first = chips[0]
    ops = first.get("XLA Ops", [])
    kernels = [e for e in ops if is_kernel(e.name)]
    modules = [m for m in first.get("XLA Modules", [])
               if any(p in m.name for p in programs)]
    executions, calls = [], []
    starts = np.array([h.start for h in host])
    for m in modules:                # each to the search call it overlaps most
        j = int(np.searchsorted(starts, m.start, side="right")) - 1
        near = [i for i in (j - 1, j, j + 1) if 0 <= i < len(host)]
        j = max(near, key=lambda i: min(m.end, host[i].end)
                - max(m.start, host[i].start))
        if lo <= host[j].start < hi and j not in calls:
            inside = [k for k in kernels if m.start <= k.start < m.end]
            executions.append((m, inside))
            calls.append(j)
    in_window = [e for e in ops if lo <= e.start < hi]

    gaps = []
    edges = [lo] + [x for s, e in busy[0] for x in (s, e)] + [hi]
    for s, e in zip(edges[::2], edges[1::2]):
        if e > s:
            gaps.append((_label(0.5 * (s + e), host, starts, offset, due,
                                done), e - s))
    return Reduced(window_s=t_end - t0, busy_s=busy_s, executions=executions,
                   calls=calls, op_seconds=dict(self_times(in_window)), gaps=gaps)


def _label(t: float, host: list[Event], starts: np.ndarray, offset: float,
           due, done) -> str:
    """What the host was doing at trace time ``t``."""
    j = int(np.searchsorted(starts, t, side="right")) - 1
    if j >= 0 and t < host[j].end:
        return "in Index.search"
    if due is None:
        return "between Index.search calls"
    t -= offset
    waiting = np.any((due <= t) & ~(done <= t))
    return ("between Index.search calls, requests waiting" if waiting
            else "between Index.search calls, no request waiting")
