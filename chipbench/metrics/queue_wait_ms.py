"""queue_wait_ms: mean time from a request's scheduled arrival to the start
of the Index.search call that serves it, over the answered requests due in
the window.  The serving call is the last one to end before the request
completes."""
import numpy as np


def read(run):
    w = run.window
    if not run.spans:
        return None
    starts = np.array([s for s, _ in run.spans])
    ends = np.array([e for _, e in run.spans])
    done = w.done
    ok = np.isfinite(done) & (w.due < w.t_end)
    if not ok.any():
        return None
    call = np.searchsorted(ends, done[ok], side="right") - 1
    if (call < 0).any():
        return None
    return float(1e3 * np.mean(starts[call] - w.due[ok]))
