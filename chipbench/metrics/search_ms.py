"""search_ms: mean time of one Index.search call the runtime makes (the
benchmark's span around it, answers ready on the host), over the calls
that start in the window."""


def read(run):
    w = run.window
    spans = [e - s for s, e in run.spans if w.t0 <= s < w.t_end]
    return 1e3 * sum(spans) / len(spans) if spans else None
