"""Everything of one configuration, mix or metric is a file of its own that
the harness finds by name: a later change adds a cell with new files and
entries only, and edits no file that is there."""
import json
import os
import shutil

from chipbench import run as harness
from chipbench.tests.test_rehearsal import TINY


def test_new_mix_and_metric_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(harness.ROOT, "chipbench"),
                    root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    # the new files
    (root / "chipbench" / "configs" / "tiny-rpf.json").write_text(
        json.dumps(TINY))
    (root / "chipbench" / "traffic" / "dummy-trickle.json").write_text(
        json.dumps({"kind": "open", "rate_per_s": 20}))
    (root / "chipbench" / "metrics" / "dummy_batches.py").write_text(
        '"""dummy_batches: search calls that started in the window."""\n'
        "def read(run):\n"
        "    w = run.window\n"
        "    return float(sum(w.t0 <= s < w.t_end for s, _ in run.spans))\n")
    # and the new entries
    bench["workloads"].append({"name": "tiny-rpf.trickle", "config":
                               "tiny-rpf", "traffic": "dummy-trickle",
                               "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "dummy_batches", "unit": "calls",
                                "better": "higher", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": ["tiny-rpf.trickle"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    bench, cell, config, mix = harness.cell_spec("tiny-rpf.trickle",
                                                 root=str(root))
    assert config == TINY and mix == {"kind": "open", "rate_per_s": 20}
    out = harness.run_cell(config, mix, seed=5, seconds=1.0, trace=False,
                           mode="pallas", work_dir=str(tmp_path / "work"),
                           grace_s=120.0)
    device = {"platform": "cpu", "kind": "cpu", "count": 1,
              "memory_peak_bytes": 0}
    line = harness.result_line(bench, "tiny-rpf.trickle", False, out, device,
                               bench_dir=str(root / "chipbench"))
    assert line["correct"]
    assert line["metrics"]["dummy_batches"]["value"] >= 1
    assert line["metrics"]["dummy_batches"]["unit"] == "calls"
    assert {"setup_s", "qps", "recall_at_10"} <= set(line["metrics"])
    # metrics listed for other cells stay out
    assert "p95_ms" not in line["metrics"]
