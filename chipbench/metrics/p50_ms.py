"""p50_ms: median latency of every request due in the window, from its
scheduled arrival to its completion; a missing answer counts as infinite."""


def read(run):
    from chipbench.loadgen import nearest_rank
    return nearest_rank(run.latencies_ms, 0.50)
