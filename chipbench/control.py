"""The control of the comparison that decides ``correct``, on the chip.

The control is the plain reference put in the program's place and computed
in the nearest precision below the one the configuration states: the
configuration states exact float32 distances, so the control ranks each
probed union by distances computed in bfloat16 on the device.  It must come
out as not correct.  For each seed, one process runs the cell's set-up and
a window at the cell's own load, reads the program's numbers as a run does,
and then the control's numbers on the same sampled requests:

    python3 chipbench/control.py --workload mnist784-rpf.steady \
        --seconds 5 --seeds 101 102 103

Each seed prints one JSON line with both readings; the limits in the
configurations were set from these and from the benchmark's own runs.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import run as harness  # noqa: E402


@functools.lru_cache(maxsize=None)
def _bf16_fn(metric: str):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def dist(q, x):
        q = q.astype(jnp.bfloat16)
        x = x.astype(jnp.bfloat16)
        if metric == "l2":
            return jnp.sum((x - q) ** 2, axis=1, dtype=jnp.bfloat16)
        return jnp.sum((x - q) ** 2 / (x + q + jnp.bfloat16(1e-12)), axis=1,
                       dtype=jnp.bfloat16)
    return dist


def bf16_distances(metric: str, q, rows):
    """Distances of one query to rows, computed in bfloat16 on the device
    (rows padded to a multiple of 512, so few shapes compile)."""
    import numpy as np
    m = rows.shape[0]
    pad = -m % 512
    x = np.concatenate([rows, np.zeros((pad, rows.shape[1]), rows.dtype)])
    return np.asarray(_bf16_fn(metric)(q, x), np.float64)[:m]


def readings(config: dict, run, dist_fn=bf16_distances) -> dict:
    """The control's numbers on the run's sampled requests."""
    from chipbench import check, reference
    metric, k = config["metric"], config["search"]["k"]
    ctl_i, ctl_d = reference.forest_answers(
        run.forest, run.rows, run.sample_queries, metric, k, run.max_depth,
        run.n_probes, run.leaf_pad,
        dist_fn=functools.partial(dist_fn, metric))
    e64 = check.served_distances64(metric, run.sample_queries, run.rows,
                                   ctl_i)
    return {"dist_err": check.dist_err(ctl_d, e64),
            "topk_mismatch": check.topk_mismatch(ctl_i, e64, run.ref_i,
                                                 run.ref_d),
            "recall_at_10_sample": check.recall(ctl_i, run.sample_true_i)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench, cell, config, mix = harness.cell_spec(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # not under /tmp
    import jax
    try:
        n_chips = len(jax.devices("tpu"))
    except RuntimeError:
        n_chips = 0
    if n_chips < cell["chips"]:
        print(f"{args.workload} needs {cell['chips']} TPU chip(s)",
              file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    for seed in args.seeds:
        out = harness.run_cell(config, mix, seed=seed, seconds=args.seconds,
                               trace=False, chips=cell["chips"])
        program = {n: c["value"] for n, c in out["checks"].items()}
        control = readings(config, out["run"])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "program": program, "control": control}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
