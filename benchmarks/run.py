"""Benchmark harness: one module per paper table/figure + roofline.

  PYTHONPATH=src python -m benchmarks.run [--paper-scale]

Prints ``name,us_per_call,derived`` CSV rows (harness contract) and writes
artifacts/bench_results.json consumed by EXPERIMENTS.md.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (autoscale, build_time, fig4_mnist, fig5_iss,
                        filtered_search, fused_vs_staged, million_row,
                        probe_schedule, recall_frontier, retrieval_compare,
                        roofline_table, serving_slo, speedup_table,
                        tree_stats)
from benchmarks.common import csv_row, record


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--paper-scale", action="store_true",
                   help="full N=60000/250736 runs (slow on CPU)")
    p.add_argument("--only", default="",
                   help="comma list: fig4,fig5,speedup,tree,retrieval,"
                        "fused,frontier,build,roof,million,serving,"
                        "filtered,schedule,autoscale")
    args = p.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    fast = not args.paper_scale
    only = set(args.only.split(",")) if args.only else None

    results: dict = {}
    rows: list[str] = []

    def want(name):
        return only is None or name in only

    if want("fig4"):
        r = fig4_mnist.main(fast=fast)
        record(results, "fig4_mnist", r)
        best = max(r["rpf"], key=lambda x: x["recall"])
        rows.append(csv_row(
            "fig4_rpf_best", best["query_us"],
            f"recall={best['recall']:.4f}@L={best['L']}"
            f";frac={best['frac_searched']:.4f}"))
        if r["lsh"]:
            bl = max(r["lsh"], key=lambda x: x["recall"])
            rows.append(csv_row(
                "fig4_lsh_best", bl["query_us"],
                f"recall={bl['recall']:.4f};frac={bl['frac_searched']:.4f}"))
    if want("fig5"):
        r = fig5_iss.main(fast=fast)
        record(results, "fig5_iss", r)
        best = max(r["rpf"], key=lambda x: x["recall"])
        rows.append(csv_row(
            "fig5_rpf_best", best["query_us"],
            f"recall={best['recall']:.4f}@L={best['L']}"
            f";frac={best['frac_searched']:.4f}"))
    if want("speedup"):
        r = speedup_table.main(fast=fast)
        record(results, "speedup_table", r)
        rows.append(csv_row(
            "speedup_vs_exhaustive", r["indexed_us"],
            f"wallclock={r['wallclock_speedup']}x"
            f";bytes={r['bytes_speedup']}x;recall={r['recall']:.3f}"))
    if want("tree"):
        r = tree_stats.main(fast=fast)
        record(results, "tree_stats", r)
        rows.append(csv_row(
            "tree_stats", 0.0,
            f"occ_max={r['occ_max']};depth_mean={r['depth_mean']:.1f}"))
    if want("retrieval"):
        r = retrieval_compare.main(fast=fast)
        record(results, "retrieval_compare", r)
        rows.append(csv_row(
            "retrieval_rpf", r["rpf_us"],
            f"recall_vs_brute={r['recall_vs_brute']:.3f}"
            f";reduction={r['reduction']}x"))
    if want("fused"):
        r = fused_vs_staged.main(smoke=fast)
        record(results, "fused_vs_staged", r)
        worst = min(r["rows"], key=lambda x: x["speedup"])
        rows.append(csv_row(
            "fused_vs_staged", worst["fused_us"],
            f"speedup={worst['speedup']}x"
            f";traffic={worst['traffic_ratio']:.1f}x"
            f";ids_match={r['all_ids_match']}"))
    if want("frontier"):
        r = recall_frontier.main(smoke=fast)
        record(results, "recall_frontier", r)
        rows.append(csv_row(
            "recall_frontier", 0.0,
            f"single_trees={r['single_probe_trees_at_target']}"
            f";multi_trees={r['multi_probe_trees_at_target']}"
            f";saved={r['trees_saved_ratio']}x"))
    if want("build"):
        r = build_time.main(smoke=fast)
        record(results, "build_time", r)
        rows.append(csv_row(
            "forest_build", r["batched_s"] * 1e6,
            f"speedup={r['speedup']}x;fused={r['fused_speedup']}x"
            f";bitwise={r['bitwise_equal']}"))
    if want("million"):
        r = million_row.main(smoke=fast)
        record(results, "million_row", r)
        rows.append(csv_row(
            "million_row", r["p50_ms"] * 1e3,
            f"p99_ms={r['p99_ms']};bytes_ratio={r['bytes_ratio']}"
            f";bitwise={r['bitwise_equal']}"
            f";fallback_free={r['no_jnp_fallback']}"))
    if want("serving"):
        r = serving_slo.main(smoke=fast)
        record(results, "serving_slo", r)
        rows.append(csv_row(
            "serving_slo", r["p99_ms_at_rated_qps"] * 1e3,
            f"rated_qps={r['rated_qps']}"
            f";recall={r['recall_at_rated']:.3f}"
            f";shed2x={r['overload']['shed_fraction']:.2f}"
            f";slo_ok={r['slo_ok']};shed_nonzero={r['shed_nonzero']}"))
    if want("filtered"):
        r = filtered_search.main(smoke=fast)
        record(results, "filtered_search", r)
        worst = min(r["rows"], key=lambda c: c["recall"])
        rows.append(csv_row(
            "filtered_search", worst["us_per_query"],
            f"worst={worst['backend']}/{worst['metric']}"
            f"@s={worst['selectivity']}"
            f";recall={worst['recall']:.3f}"
            f";gate001={r['recall_001_ok']};all={r['recall_all_ok']}"
            f";no_leaks={r['no_leaks']}"))
    if want("schedule"):
        r = probe_schedule.main(smoke=fast)
        record(results, "probe_schedule", r)
        rows.append(csv_row(
            "probe_schedule", r["p99_scheduled_ms"] * 1e3,
            f"mean_probes={r['mean_probes_scheduled']}"
            f"/fixed={r['fixed_n_probes']}"
            f";recall={r['recall_scheduled']:.3f}"
            f";p99_ratio={r['p99_ratio']}"
            f";gates={r['recall_ok']}/{r['probes_below_fixed']}"
            f"/{r['p99_ok']}"))
    if want("autoscale"):
        r = autoscale.main(smoke=fast)
        record(results, "autoscale", r)
        rows.append(csv_row(
            "autoscale", r["scaled_leg"]["p99_ms"] * 1e3,
            f"replicas={r['replicas_after_leg1']}"
            f";shed_scaled={r['shed_after_scaleup']:.3f}"
            f";shed_static={r['static_control']['shed_fraction']:.2f}"
            f";gates={r['scaled_up']}/{r['shed_recovered']}"
            f"/{r['no_flapping']}"))
    if want("roof"):
        r = roofline_table.main(fast=fast)
        record(results, "roofline", r)
        if r:
            worst = min(r.values(), key=lambda t: t["roofline_fraction"]
                        if t["roofline_fraction"] > 0 else 9e9)
            rows.append(csv_row(
                "roofline_worst_cell", 0.0,
                f"{worst['arch']}/{worst['cell']}"
                f";frac={worst['roofline_fraction']:.3f}"))

    print("\n=== CSV (name,us_per_call,derived) ===")
    for row in rows:
        print(row)


if __name__ == "__main__":
    main()
