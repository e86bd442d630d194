"""Span, scope and kernel names of the serving path and the query program.

A span is a ``jax.profiler.TraceAnnotation``.  With no profiler running it
costs about a microsecond; with one running it lands in the profiler's
trace, on the clock of the device's ops, with its counters as event stats.
A running profiler is the only switch.

Host spans, on the serving thread, nested by time; every span of one batch
but ``index.search`` carries the batch's number as ``batch``:

* ``serve.batch``: the first request taken off the queue to the last one
  finished; ``rows`` (requests in the batch) and ``depth`` (requests still
  queued when the batch closed).  A high ``depth`` means arrivals outrun
  the server.
* ``serve.collect``: the first request taken to the batch closing, full or
  at ``max_wait_s``: how long the batcher waited for company.
* ``serve.assemble``: stacking the requests and padding to ``max_batch``;
  ``pad`` is the rows of repeated query the device computes for nothing.
* ``index.search``: ``Index.search`` until it returns, which is before the
  device finishes: the checks, the queries' copy to the device and the
  dispatch of the query program; ``segments``, the parts it fanned out to.
* ``serve.fetch``: the answers' copy to the host, which waits for the
  device first.
* ``serve.complete``: handing every request its answer.

Named scopes of the query program (an op's scope path is the ``tf_op``
stat of its event metadata in the trace): ``rpf.descent`` (the trees'
descent and the candidate gather), ``rpf.dedup`` (validity and duplicate
masks), ``rpf.relayout`` (the rerank kernel's row store), ``rpf.rerank``
(the streamed rerank kernels and the top-k merges).  The kernels carry
the ``KERNEL_*`` names as their op names.
"""
from __future__ import annotations

import jax

SERVE_BATCH = "serve.batch"
SERVE_COLLECT = "serve.collect"
SERVE_ASSEMBLE = "serve.assemble"
INDEX_SEARCH = "index.search"
SERVE_FETCH = "serve.fetch"
SERVE_COMPLETE = "serve.complete"

DESCENT = "rpf.descent"
DEDUP = "rpf.dedup"
RELAYOUT = "rpf.relayout"
RERANK = "rpf.rerank"

KERNEL_DESCENT_HBM = "rpf_descent_hbm"
KERNEL_DESCENT_SMEM = "rpf_descent_smem"
KERNEL_RERANK = "rpf_rerank"
KERNEL_RERANK_INT8 = "rpf_rerank_int8"


def span(name: str, **counters: int) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` with integer ``counters``; use it as a
    context manager, and add counters known only later with
    ``set_metadata``."""
    return jax.profiler.TraceAnnotation(name, **counters)
