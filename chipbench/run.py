"""Run one benchmark cell once and print its result as the last line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration and its traffic mix are found by name:
``BENCHMARK.json`` names the cell's ``config`` and ``traffic``, read from
``chipbench/configs/<config>.json`` and ``chipbench/traffic/<traffic>.json``;
each metric is read by ``chipbench/metrics/<metric>.py``.  A run makes the
corpus and the queries from the seed on the device, builds the index with
``build_index``, serves it through ``ServingRuntime`` (explicit operating
point, no degradation ladder), warms the one batch shape up, and then
drives the traffic for ``--seconds``.  After the window it checks the
answers against the plain reference (``chipbench/check.py``).  With
``--trace 0`` it reports the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics, read from a profiler trace of the window.

It refuses to run without as many TPU chips as the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "chipbench")
WORK_DIR = os.path.join(ROOT, ".chipbench")      # caches and traces
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

GRACE_S = 60.0           # how long answers due in the window are awaited
STALL_S = 0.35           # a search call this long dumps every thread's stack


def log(msg: str) -> None:
    print(f"[chipbench {time.perf_counter() - T_START:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


# ------------------------------------------------------------ found by name
def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def cell_spec(workload: str, root: str = ROOT) -> tuple[dict, dict, dict,
                                                         dict]:
    """-> (benchmark, cell, configuration, traffic mix) of a cell name."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    bench_dir = os.path.join(root, "chipbench")
    config = load_json(os.path.join(bench_dir, "configs",
                                    cell["config"] + ".json"))
    mix = load_json(os.path.join(bench_dir, "traffic",
                                 cell["traffic"] + ".json"))
    return bench, cell, config, mix


def cell_metrics(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics this cell reports in a run of this kind."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def reader(name: str, bench_dir: str = BENCH_DIR):
    """The ``read(run)`` function of ``chipbench/metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------ one cell run
class Run:
    """What a metric reader can read about one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self._work = None

    def work(self):
        """(descent bytes, rerank bytes) of each search call in the window,
        over the real queries of its batch."""
        if self._work is None:
            from chipbench import work
            self._work = [work.batch_bytes(self.forest, q, self.max_depth,
                                           self.n_probes, self.leaf_pad)
                          for q in self.window_batches]
        return self._work


def forest_arrays(forest) -> dict:
    import numpy as np
    return {"feat": np.asarray(forest.proj_idx)[..., 0],
            "thresh": np.asarray(forest.thresh),
            "child": np.asarray(forest.child_base),
            "perm": np.asarray(forest.perm),
            "leaf_offset": np.asarray(forest.leaf_offset),
            "leaf_count": np.asarray(forest.leaf_count)}


class CompileEvents:
    """JAX's tracing, lowering, compiling and compile-cache events while
    ``on``: nothing should compile inside the window."""

    def __init__(self):
        import jax.monitoring
        self.on = False
        self.events: list[tuple[str, str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **kw):
        if self.on and ("/compile" in event or "cache" in event):
            self.events.append((event.rsplit("/", 1)[-1],
                                str(kw.get("fun_name", "")), duration))

    def close(self):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self)

    def summary(self) -> str:
        if not self.events:
            return "nothing traced or compiled"
        top = sorted(self.events, key=lambda e: -e[2])[:5]
        return (f"{len(self.events)} events, longest: " + "; ".join(
            f"{e} {f} {1e3 * d:.1f} ms" for e, f, d in top))


def host_usage():
    """(CPU seconds of all threads, major faults, voluntary and involuntary
    context switches) of this process so far."""
    import resource

    import numpy as np
    r = resource.getrusage(resource.RUSAGE_SELF)
    return np.array([r.ru_utime + r.ru_stime, r.ru_majflt, r.ru_nvcsw,
                     r.ru_nivcsw])


def run_cell(config: dict, mix: dict, *, seed: int, seconds: float,
             trace: bool, chips: int = 1, mode: str = "auto",
             t_start: float = T_START, work_dir: str = WORK_DIR,
             grace_s: float = GRACE_S, fault=None) -> dict:
    """Set up, drive the window, check, and collect what readers read.

    ``fault(index) -> index`` wraps the built index (tests break the timed
    path with it).  Returns the ``Run`` and the checks.
    """
    import jax
    import numpy as np

    from chipbench import check, corpus, loadgen, reference
    from chipbench.spans import SpannedIndex
    from repro.core.forest import ForestConfig
    from repro.index import IndexSpec, SearchParams, build_index
    from repro.serve.runtime import ServingRuntime

    devices = jax.devices()[:chips]
    metric, k = config["metric"], config["search"]["k"]
    n_probes = config["search"]["n_probes"]

    # ---- set-up: corpus, build, runtime warm-up
    rows, queries = corpus.generate(config["corpus"], seed,
                                    loadgen.pool_size(mix, seconds))
    rows, queries = np.asarray(rows), np.asarray(queries)  # frees the device
    log(f"corpus {rows.shape} and {len(queries)} queries from seed {seed}")
    ix = config["index"]
    fcfg = ForestConfig(**{n: v for n, v in ix.items() if n != "backend"})
    index = build_index(jax.random.fold_in(corpus.seed_key(seed), 1), rows,
                        IndexSpec(backend=ix["backend"], forest=fcfg))
    jax.block_until_ready(index.forest)
    log("index built")
    served = fault(index) if fault else index
    proxy = SpannedIndex(served, keep_batches=trace)
    sv = config["serving"]
    params = SearchParams(k=k, metric=metric, n_probes=n_probes, mode=mode)
    runtime = ServingRuntime(proxy, params=params, degrade=sv["degrade"],
                             max_batch=sv["max_batch"],
                             max_wait_s=sv["max_wait_s"])
    warm = [runtime.submit(r) for r in rows[:sv["max_batch"]]]
    for r in warm:
        r.event.wait(600)
    freeze = sv.get("gc_freeze_after_warmup", False)
    if freeze:
        # the deployment's process tuning, stated in its configuration:
        # what set-up made lives as long as the server, so it is kept out
        # of the collector's full passes
        gc.collect()
        gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.2f}s")

    # ---- the window
    n_before = len(proxy.spans)
    compiles = CompileEvents()
    trace_dir = os.path.join(work_dir, "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1        # the benchmark's spans, no more
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        # the profiler's first moments stall the host for about a second:
        # let them pass on one batch of corpus rows before the window
        settle = [runtime.submit(r) for r in rows[-sv["max_batch"]:]]
        for r in settle:
            r.event.wait(600)
        time.sleep(1.0)
    gc_pauses = []                     # the collector's pauses, for the log
    gc_start = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_start[0] = time.perf_counter()
        else:
            gc_pauses.append((time.perf_counter() - gc_start[0],
                              info["generation"]))
    gc.callbacks.append(on_gc)
    compiles.on = True
    proxy.stall_s = STALL_S
    usage = host_usage()
    try:
        window = loadgen.drive(runtime, queries, mix, seconds,
                               np.random.default_rng([seed, 1]), grace_s)
    finally:
        usage = host_usage() - usage
        proxy.stall_s = None
        compiles.on = False
        compiles.close()
        gc.callbacks.remove(on_gc)
        if freeze:
            gc.unfreeze()
        if trace:
            jax.profiler.stop_trace()
        runtime.stop()
    if window.n_queries_left == 0 and mix["kind"] == "closed":
        raise RuntimeError("the window used up its query pool; raise the "
                           "mix's max_rate_per_s")
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    spans = proxy.spans[n_before:]
    cpu_s = proxy.cpu_s[n_before:]
    batches = proxy.batches[n_before:] if trace else []
    cfg = fcfg.resolved(rows.shape[0])
    forest = forest_arrays(index.forest)
    del index, served, proxy, runtime
    gc.collect()

    # ---- answers
    reqs = window.requests
    done = window.done
    answered = np.flatnonzero(np.isfinite(done))
    due_in = window.due < window.t_end
    lat_ms = np.where(np.isfinite(done), (done - window.due) * 1e3, np.inf)
    n_missing = int(np.sum(due_in & ~np.isfinite(done)))
    got_d = np.full((len(reqs), k), np.nan)
    got_i = np.full((len(reqs), k), -2, np.int64)
    for j in answered:
        d, i = reqs[j].result
        if np.shape(d) == (k,) and np.shape(i) == (k,):
            got_d[j], got_i[j] = d, i
    starts = [s for s, _ in spans if window.t0 <= s < window.t_end]
    log(f"window: {len(reqs)} requests, {len(answered)} answered, "
        f"{n_missing} missing; latency p99 "
        f"{loadgen.nearest_rank(lat_ms[due_in], 0.99):.1f} ms, max "
        f"{np.max(lat_ms):.1f} ms; sent up "
        f"to {1e3 * np.max(window.sent - window.due):.1f} ms late; longest "
        f"time between search calls "
        f"{1e3 * max(np.diff(starts), default=0):.1f} ms; "
        f"{len(gc_pauses)} garbage collections, longest "
        f"{1e3 * max(gc_pauses, default=(0, 0))[0]:.1f} ms (generation "
        f"{max(gc_pauses, default=(0, -1))[1]})")
    longest = sorted(range(len(spans)), key=lambda j: spans[j][0]
                     - spans[j][1])[:3]
    log("host over the window (until the last answer): CPU "
        f"{usage[0]:.2f}s, {usage[1]:.0f} major faults, {usage[2]:.0f} "
        f"voluntary and {usage[3]:.0f} involuntary context switches; "
        "longest search calls (wall ms / process CPU ms): " + ", ".join(
            f"{1e3 * (spans[j][1] - spans[j][0]):.1f}/{1e3 * cpu_s[j]:.1f}"
            for j in longest) + f"; JAX: {compiles.summary()}")

    # ---- the comparison, after the window, outside set-up
    t_ref = time.perf_counter()
    faults = reference.partition_faults(
        forest, rows, config["limits"].get("leaf_points"), cfg.max_depth)
    log(f"partition checked against the rows in "
        f"{time.perf_counter() - t_ref:.2f}s")
    true_i = reference.exact_topk(queries[answered], rows, metric, k)
    e64 = check.served_distances64(metric, queries[answered], rows,
                                   got_i[answered])
    sample = np.sort(np.random.default_rng([seed, 2]).choice(
        answered, size=min(check.SAMPLE, answered.size), replace=False))
    ref_i, ref_d = reference.forest_answers(
        forest, rows, queries[sample], metric, k, cfg.max_depth, n_probes,
        cfg.leaf_pad)
    pos = np.searchsorted(answered, sample)
    values = {
        "missing": n_missing,
        "partition_faults": faults,
        "dist_err": check.dist_err(got_d[answered], e64),
        "topk_mismatch": check.topk_mismatch(got_i[sample], e64[pos],
                                             ref_i, ref_d),
        "recall_at_10": check.recall(got_i[answered], true_i),
    }
    limits = {"missing": (0, "<="), "partition_faults": (0, "<="),
              "dist_err": (config["limits"]["dist_err"], "<="),
              "topk_mismatch": (0, "<="),
              "recall_at_10": (config["limits"]["recall_at_10"], ">=")}
    checks = check.checks(values, limits)
    log(f"reference compared in {time.perf_counter() - t_ref:.2f}s")

    run = Run(setup_s=setup_s, seconds=seconds,
              window=window, latencies_ms=lat_ms[due_in],
              n_answered_in_window=int(np.sum(done <= window.t_end)),
              recall=values["recall_at_10"], spans=spans,
              window_batches=[], kernel_roles=config["trace"]["kernels"],
              forest=forest, max_depth=cfg.max_depth,
              n_probes=n_probes, leaf_pad=cfg.leaf_pad, trace=None,
              rows=rows, sample_queries=queries[sample], ref_i=ref_i,
              ref_d=ref_d, sample_true_i=true_i[pos],
              peak_bytes=int(peak), device_kind=devices[0].device_kind)
    if trace:
        from chipbench import trace as trace_mod
        path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        run.trace = trace_mod.reduce(path, spans, window.t0, window.t_end,
                                     config["trace"]["programs"],
                                     window.due, done)
        if run.trace is None:
            log("trace: nothing to read (no device plane, or the trace's "
                "search spans do not match the host's)")
        else:
            run.window_batches = [batches[j] for j in run.trace.calls]
            per_call = collections.Counter(len(k) for _, k in
                                           run.trace.executions)
            log(f"trace: {len(spans)} search calls, "
                f"{len(run.trace.executions)} executions of the query program "
                f"in the window, kernels per execution {dict(per_call)}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    return {"run": run, "checks": checks,
            "attempted": int(np.sum(due_in)), "failed": n_missing}


def result_line(bench: dict, workload: str, trace: bool, out: dict,
                device: dict, bench_dir: str = BENCH_DIR) -> dict:
    from chipbench import check
    run = out["run"]
    metrics = {}
    for m in cell_metrics(bench, workload, trace):
        value = reader(m["name"], bench_dir)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": check.passed(out["checks"]),
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": dict(device)}
    if trace and run.trace is not None:
        line["device"]["busy_s"] = run.trace.busy_s
        line["device"]["window_s"] = run.trace.window_s
        line["breakdown"] = run.trace.breakdown()
    line["checks"] = out["checks"]
    return line


def enable_compile_cache(work_dir: str = WORK_DIR) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, keeping
    every program the cell compiles."""
    import jax
    cache = os.path.join(work_dir, "jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, cell, config, mix = cell_spec(args.workload)
    if cell["chips"] != 1:
        log(f"{args.workload} asks for {cell['chips']} chips; the harness "
            "serves one index on one chip and builds no mesh; not run")
        return 3
    # the TPU runtime would otherwise log to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    try:
        devices = jax.devices("tpu")
    except RuntimeError:
        devices = []
    if len(devices) < cell["chips"]:
        log(f"{args.workload} needs {cell['chips']} TPU chip(s); JAX sees "
            f"{len(devices)} (backend {jax.default_backend()!r}); not run")
        return 3
    log(f"compile cache {enable_compile_cache()}")
    out = run_cell(config, mix, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), chips=cell["chips"])
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["run"].peak_bytes}
    line = result_line(bench, args.workload, bool(args.trace), out, device)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (pass if {c['pass_if']} "
            f"{c['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
