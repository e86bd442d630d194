"""Read the program's own spans and scopes from a profiler trace.

The program (``src/repro/tracing.py``) writes host spans with integer
counters into the profiler's trace, on the clock of the device's ops, and
names the scopes of its query program and its kernels.  This module reads
them back from the ``.xplane.pb`` of a traced window:

* host spans: every event on a host plane named like one of ``SPANS``,
  with its counters (``jax.profiler.ProfileData`` gives them as stats);
* device ops: the ``XLA Ops`` of the first chip, each with its scope, the
  innermost ``rpf.*`` part of the op's ``tf_op`` path.  ``ProfileData``
  does not give an op's metadata, so the scope map is decoded from the
  XSpace protocol buffer itself (``_scopes``), for the few fields needed.

``read`` cuts both to a window on the trace's clock and gives the numbers
the per-layer metrics of the program's layers read.  A trace of a program
without these spans or scopes reads as empty: every number is None.
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np

from chipbench import trace

# the names of src/repro/tracing.py (tests/test_program_trace.py keeps
# them equal); written out so that this reader runs against any program
SERVE_BATCH = "serve.batch"
SERVE_COLLECT = "serve.collect"
SERVE_ASSEMBLE = "serve.assemble"
INDEX_SEARCH = "index.search"
SERVE_FETCH = "serve.fetch"
SERVE_COMPLETE = "serve.complete"
SPANS = (SERVE_BATCH, SERVE_COLLECT, SERVE_ASSEMBLE, INDEX_SEARCH,
         SERVE_FETCH, SERVE_COMPLETE)
SCOPES = ("rpf.descent", "rpf.dedup", "rpf.relayout", "rpf.rerank")
FRONT_END = (SERVE_COLLECT, SERVE_ASSEMBLE, SERVE_COMPLETE)

SCOPE = re.compile(r"(?:^|/)(rpf\.[a-z]+)(?=/|:|$)")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float          # seconds on the trace's clock
    end: float
    counters: dict

    @property
    def dur(self) -> float:
        return self.end - self.start


# ------------------------------------------------- the XSpace wire format
def _fields(buf: memoryview):
    """(field number, value) of one message: an int for varint and fixed
    fields, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wire} in a trace")
        yield field, value


def _varint(buf: memoryview, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _entry(entry: memoryview) -> tuple[int, memoryview]:
    """(key, value message) of one map entry (fields 1 and 2)."""
    got = dict(_fields(entry))
    return got.get(1, 0), got.get(2, entry[:0])


def _scopes(path: str) -> dict[str, str]:
    """{op's HLO text: its scope} over the device planes' event metadata.

    Fields read: XSpace.planes (1); XPlane.name (2), .event_metadata (4)
    and .stat_metadata (5), maps keyed by id; XEventMetadata.name (2) and
    .stats (5); XStatMetadata.name (2); XStat.metadata_id (1) and its
    value, .str_value (5) or .ref_value (7), the id of a stat metadata
    whose name is the string."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out: dict[str, str] = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for g, v in fields if g == 2), "")
        if not trace.DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for key, meta in (_entry(v) for g, v in fields if g == 5):
            stat_names[key] = next(
                (bytes(w).decode() for h, w in _fields(meta) if h == 2), "")
        tf_op = {k for k, n in stat_names.items() if n == "tf_op"}
        for _, meta in (_entry(v) for g, v in fields if g == 4):
            op, scope_path = "", ""
            for h, w in _fields(meta):
                if h == 2:
                    op = bytes(w).decode()
                elif h == 5:
                    stat = dict(_fields(w))
                    if stat.get(1) in tf_op:
                        scope_path = (bytes(stat[5]).decode() if 5 in stat
                                      else stat_names.get(stat.get(7), ""))
            found = SCOPE.findall(scope_path)
            if op and found:
                out[op] = found[-1]
    return out


# ------------------------------------------------------------- the reading
def host_spans(path: str) -> list[Span]:
    """The program's host spans in the trace, in the order they start."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend(Span(e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9,
                            dict(e.stats))
                       for e in line.events if e.name in SPANS)
    return sorted(out, key=lambda s: s.start)


@dataclasses.dataclass
class ProgramTrace:
    spans: dict            # span name -> [Span] that start in the window
    scope_seconds: dict    # scope -> device self seconds in the window
    executions: int        # executions of the query program in the window
    idle: list             # [(start, end)] device idle in the window

    def mean_ms(self, name: str) -> float | None:
        got = self.spans.get(name, [])
        return 1e3 * sum(s.dur for s in got) / len(got) if got else None

    def batch_fill(self) -> float | None:
        """Useful rows over the rows the query program computed (%)."""
        pad = {s.counters.get("batch"): s.counters.get("pad", 0)
               for s in self.spans.get(SERVE_ASSEMBLE, [])}
        rows = used = 0
        for s in self.spans.get(SERVE_BATCH, []):
            b = s.counters.get("batch")
            if "rows" in s.counters and b in pad:
                rows += s.counters["rows"]
                used += s.counters["rows"] + pad[b]
        return 100.0 * rows / used if used else None

    def idle_front_end_ms(self) -> float | None:
        """Device-idle time inside the front end's spans, per batch."""
        batches = len(self.spans.get(SERVE_BATCH, []))
        if not batches:
            return None
        front = trace.union(((s.start, s.end) for n in FRONT_END
                             for s in self.spans.get(n, [])),
                            -np.inf, np.inf)
        return 1e3 * _overlap(self.idle, front) / batches

    def scope_ms(self, scope: str) -> float | None:
        """Device self time of the scope's ops per execution."""
        if not self.executions or scope not in self.scope_seconds:
            return None
        return 1e3 * self.scope_seconds[scope] / self.executions


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(path: str, lo: float, hi: float, programs) -> ProgramTrace:
    """The program's spans and scoped device time in the window [lo, hi)
    on the trace's clock; ``programs`` names the query program's
    functions, as ``trace.reduce`` takes them."""
    chips, _ = trace.load(path)
    spans: dict[str, list[Span]] = {}
    for s in host_spans(path):
        if lo <= s.start < hi:
            spans.setdefault(s.name, []).append(s)
    if not chips:
        return ProgramTrace(spans, {}, 0, [])
    first = chips[0]
    ops = [e for e in first.get("XLA Ops", []) if lo <= e.start < hi]
    scopes = _scopes(path)
    # self time by scope: trace.self_times keys on the op name before " = "
    by_scope = trace.self_times([trace.Event(
        f"%{scopes.get(e.name, '')} = ", e.start, e.end) for e in ops])
    scope_seconds = {k: v for k, v in by_scope.items() if k}
    executions = sum(1 for m in first.get("XLA Modules", [])
                     if lo <= m.start < hi
                     and any(p in m.name for p in programs))
    busy = trace.union(((e.start, e.end) for e in first.get("XLA Ops", [])),
                       lo, hi)
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    return ProgramTrace(spans, scope_seconds, executions, idle)
