"""p95_ms: 95th percentile (nearest rank) of the same population as p50_ms."""


def read(run):
    from chipbench.loadgen import nearest_rank
    return nearest_rank(run.latencies_ms, 0.95)
