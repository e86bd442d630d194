"""step_device_ms: mean device time of one execution of the query program
(``core/pipeline.py`` ``_fused_query_jit``), from the profiler trace."""


def read(run):
    if run.trace is None or not run.trace.executions:
        return None
    ex = run.trace.executions
    return 1e3 * sum(m.dur for m, _ in ex) / len(ex)
