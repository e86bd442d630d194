"""Record ``data/program.xplane.pb`` on a TPU, the trace that
``test_program_trace.py`` reads:

    python3 chipbench/tests/record_program_trace.py <out.xplane.pb>

A 4 096 x 128 corpus, 4 trees, two probes, served by ``ServingRuntime`` in
batches of up to 16 behind the benchmark's ``SpannedIndex``; four waves of
``WAVES`` requests inside the trace, with the profiler set as
``chipbench/run.py`` sets it.  It refuses to run without a TPU.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

WAVES = (16, 5, 16, 9)
MAX_BATCH = 16


def main(out: str) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np

    from chipbench.spans import SpannedIndex
    from repro.core import ForestConfig
    from repro.index import IndexSpec, SearchParams, build_index
    from repro.serve import ServingRuntime

    if jax.default_backend() != "tpu":
        print(f"needs a TPU; JAX has {jax.default_backend()!r}",
              file=sys.stderr)
        return 3
    rows = np.random.default_rng(0).standard_normal((4096, 128))
    index = build_index(jax.random.key(0), rows.astype(np.float32),
                        IndexSpec(backend="rpf", forest=ForestConfig(
                            n_trees=4, capacity=12)))
    runtime = ServingRuntime(SpannedIndex(index),
                             params=SearchParams(k=10, n_probes=2),
                             degrade=False, max_batch=MAX_BATCH,
                             max_wait_s=0.002)
    queries = rows[:sum(WAVES)].astype(np.float32) + 0.01
    trace_dir = tempfile.mkdtemp(prefix="program_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    try:
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        j = 0
        for n in WAVES:
            reqs = [runtime.submit(q) for q in queries[j:j + n]]
            for r in reqs:
                r.event.wait(60)
            j += n
        jax.profiler.stop_trace()
        runtime.stop()
        path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        shutil.copy(path, out)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"{out}: {os.path.getsize(out)} bytes", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
