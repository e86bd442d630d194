"""batch_gap_ms: mean time from the end of one Index.search call to the
start of the next, over the calls that start in the window: the front
end's stacking, padding, completions and batching wait."""


def read(run):
    w = run.window
    spans = [(s, e) for s, e in run.spans if w.t0 <= s < w.t_end]
    gaps = [b[0] - a[1] for a, b in zip(spans, spans[1:])]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
