"""Spans around the program's layers, taken from the benchmark's side.

``SpannedIndex`` stands between ``ServingRuntime`` and the ``Index`` it
serves: it delegates every attribute and times each ``search`` call, up to
the moment its answers are ready on the host (the runtime converts them to
NumPy right after, so the span adds no wait of its own).  With a trace on
it also writes the span into the profiler's trace (``chipbench.search``),
on the clock of the device's events, and keeps the batch's real queries
for counting the work: ``ServingRuntime`` pads a short batch to its fixed
size by repeating the last query, and the padding is no work a user asked
for.

A search call that takes longer than ``stall_s`` has every thread's Python
stack written to standard error while it still runs, and each call keeps
the CPU seconds all of the process's threads spent in it
(``cpu_s``): the process's rare stalls of a second or more happen inside
these calls, and the two tell a busy process from a waiting one.
"""
from __future__ import annotations

import faulthandler
import sys
import time

import jax
import numpy as np


def real_rows(queries) -> int:
    """Rows of a batch before the runtime's padding, which repeats the last
    real query.  The window never sends one query twice, so the rows equal
    to the last one after the first of them are padding."""
    q = np.asarray(queries)
    same = np.all(q == q[-1], axis=1)
    n = len(q)
    while n > 1 and same[n - 2]:
        n -= 1
    return n


class SpannedIndex:
    SPAN = "chipbench.search"

    def __init__(self, index, keep_batches: bool = False,
                 stall_s: float | None = None):
        self._index = index
        self.keep_batches = keep_batches
        self.stall_s = stall_s
        self.spans: list[tuple[float, float]] = []
        self.cpu_s: list[float] = []
        self.batches: list = []

    def __getattr__(self, name):
        return getattr(self._index, name)

    def search(self, queries, params=None, **params_kw):
        armed = self.stall_s
        if armed:
            faulthandler.dump_traceback_later(armed, file=sys.stderr)
        c0, t0 = time.process_time(), time.perf_counter()
        with jax.profiler.TraceAnnotation(self.SPAN):
            out = jax.block_until_ready(
                self._index.search(queries, params, **params_kw))
        self.spans.append((t0, time.perf_counter()))
        self.cpu_s.append(time.process_time() - c0)
        if armed:
            faulthandler.cancel_dump_traceback_later()
        if self.keep_batches:
            self.batches.append(queries[:real_rows(queries)])
        return out
