"""Serving runtime subsystem tests (DESIGN.md §12).

Covers the PR-7 surface: manifest v4 round-trip + v3/v2/v1 read shims,
open-loop load-generator determinism, the batcher's shutdown contract
(drain vs fail-fast — no submitter ever hangs), degradation-ladder
construction + the "shedding never makes the tail worse" property, the
capacity planner's model math, and per-shard tuning.

The ladder/overload tests run against a fake index whose search cost is a
deterministic sleep proportional to the operating point's probe budget —
wall-clock enough to exercise queueing, deterministic enough for CI.
"""
from __future__ import annotations

import glob
import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import ForestConfig
from repro.index import (IndexSpec, SearchParams, build_index, load_index,
                         tune, tune_sharded)
from repro.serve import (BatcherStopped, DynamicBatcher, ServingRuntime,
                         arrival_schedule, build_ladder, loadgen, planner,
                         uniform_shard_params)
from repro.serve.runtime import _ladder_cost

SEED = 0


@pytest.fixture(scope="module")
def corpus(shared_builds):
    db = shared_builds.clustered_db(2000, 16, n_clusters=16, seed=SEED)
    q = db[np.random.default_rng(1).integers(0, len(db), 32)] + 0.003
    return db, np.asarray(q)


def _build(db, n_trees=8, capacity=32):
    spec = IndexSpec(backend="rpf",
                     forest=ForestConfig(n_trees=n_trees, capacity=capacity))
    return build_index(jax.random.key(SEED), db, spec)


# ---------------------------------------------------------------------------
# manifest v4 round-trip + read shims
# ---------------------------------------------------------------------------


def _manifest_path(root: str) -> str:
    return glob.glob(os.path.join(root, "step_*", "manifest.json"))[0]


def test_manifest_v4_roundtrip(tmp_path, corpus):
    db, q = corpus
    index = _build(db)
    tuned = tune(index, q, target_recall=0.8, k=10, probe_grid=(1, 2, 4),
                 tree_fracs=(1.0,))
    shard_params, _ = tune_sharded(index, q, n_shards=2, target_recall=0.8,
                                   k=10, probe_grid=(1, 2, 4))
    plan_payload = {"plan": {"qps": 500.0, "slo_p99_ms": 25.0,
                             "n_shards": 1, "n_replicas": 1, "batch": 32,
                             "rated_qps_per_replica": 700.0,
                             "predicted_p99_ms": 11.0, "utilization": 0.7,
                             "recall_target": 0.8},
                    "traffic_model": {"c0_s": 1e-3, "c1_s": 1e-5,
                                      "max_wait_s": 2e-3, "batch_grid": [1],
                                      "measured_s": [1e-3],
                                      "rows_per_query": 8.0}}
    index.serving_plan = plan_payload
    d0, i0 = map(np.asarray, index.search(q))

    path = str(tmp_path / "v4")
    index.save(path)
    with open(_manifest_path(path)) as fh:
        man = json.load(fh)
    assert man["extra"]["format"] == 5
    assert man["extra"]["meta_schema"] is None   # no metadata attached

    loaded = load_index(path)
    # the full v4 payload survives: tuned point, per-shard points, plan
    assert loaded.tuned_params == tuned
    assert loaded.shard_params == tuple(shard_params)
    assert loaded.serving_plan == plan_payload
    d1, i1 = map(np.asarray, loaded.search(q))
    assert np.array_equal(i0, i1) and np.array_equal(d0, d1)   # bitwise

    # and the runtime stands up from it without retuning
    rt = ServingRuntime.load(path, warmup=False)
    assert rt.params == uniform_shard_params(shard_params)
    assert rt.max_batch == 32          # from the persisted plan
    assert ServingRuntime.manifest_plan(loaded).qps == 500.0
    assert ServingRuntime.manifest_traffic_model(loaded).c0_s == 1e-3
    rt.stop()


@pytest.mark.parametrize("fmt", [3, 2])
def test_manifest_v3_v2_read_shims(tmp_path, corpus, fmt):
    db, q = corpus
    index = _build(db)
    tuned = tune(index, q, target_recall=0.8, k=10, probe_grid=(1, 2, 4),
                 tree_fracs=(1.0,))
    index.shard_params = (tuned, tuned)
    index.serving_plan = {"plan": None, "traffic_model": None}
    d0, i0 = map(np.asarray, index.search(q, tuned))

    path = str(tmp_path / f"v{fmt}")
    index.save(path)
    # rewrite the manifest as the older writer would have produced it
    mp = _manifest_path(path)
    with open(mp) as fh:
        man = json.load(fh)
    man["extra"]["format"] = fmt
    man["extra"].pop("meta_schema")
    man["extra"].pop("shard_params")
    man["extra"].pop("serving_plan")
    if fmt == 2:
        man["extra"].pop("tuned_params")
    with open(mp, "w") as fh:
        json.dump(man, fh)

    legacy = load_index(path)
    assert legacy.shard_params is None
    assert legacy.serving_plan is None
    assert legacy.tuned_params == (tuned if fmt == 3 else None)
    d1, i1 = map(np.asarray, legacy.search(q, tuned))
    assert np.array_equal(i0, i1) and np.array_equal(d0, d1)


def test_manifest_v1_read_shim_serves(tmp_path, corpus):
    """A pre-segment flat checkpoint still stands a runtime up."""
    from repro.checkpoint.checkpointer import Checkpointer
    db, q = corpus
    index = _build(db)
    path = str(tmp_path / "v1")
    Checkpointer(path, keep=1).save(
        0, {"db": index.db, "key_data": jax.random.key_data(index.key),
            "forest": index.forest},
        extra={"spec": index.spec.to_dict(), "backend": "rpf"})
    legacy = load_index(path)
    assert legacy.tuned_params is None and legacy.shard_params is None
    rt = ServingRuntime(legacy, params=SearchParams(k=5, n_probes=2),
                        max_batch=8, warmup=False)
    d, i = rt(q[0])
    assert i.shape == (5,) and np.isfinite(d).all()
    rt.stop()


# ---------------------------------------------------------------------------
# open-loop load generator
# ---------------------------------------------------------------------------


def test_arrival_schedule_deterministic():
    a = arrival_schedule(500.0, 1000, seed=7)
    b = arrival_schedule(500.0, 1000, seed=7)
    c = arrival_schedule(500.0, 1000, seed=8)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a[0] == 0.0 and np.all(np.diff(a) >= 0)
    # exponential gaps at rate qps: mean inter-arrival ~ 1/qps
    assert np.mean(np.diff(a)) == pytest.approx(1 / 500.0, rel=0.2)
    with pytest.raises(ValueError):
        arrival_schedule(0.0, 10)


def test_open_loop_charges_from_scheduled_time():
    """Latency is charged from the SCHEDULED arrival, not the submit call —
    the no-coordinated-omission property: a stalled server shows up in
    every queued request's tail, not just the one it stalled on."""
    stall = threading.Event()

    def fn(batch):
        stall.wait(0.2)
        return [0 for _ in batch]

    b = DynamicBatcher(fn, max_batch=4, max_wait_s=0.001).start()
    rep = loadgen.run_open_loop(b, np.zeros((4, 2), np.float32), qps=400.0,
                                n_requests=40, seed=0, timeout_s=10.0)
    b.stop()
    assert rep["n_ok"] == 40 and rep["n_failed"] == 0
    # the first batch stalls ~200ms; requests scheduled meanwhile queue up
    # behind it and must be charged that wait
    assert rep["p50_ms"] > 50.0
    assert rep["p999_ms"] >= rep["p99_ms"] >= rep["p50_ms"]


def test_launcher_counts_failed_requests_as_lost():
    """A kernel fault inside a batch reaches each request as an error (the
    batcher catches it); the serving launcher must count those requests and
    exit non-zero instead of printing n_ok/n and exiting 0."""
    from repro.launch import serve as launcher

    def fault(batch):
        raise RuntimeError("kernel fault")

    b = DynamicBatcher(fault, max_batch=4, max_wait_s=0.001).start()
    rep = loadgen.run_open_loop(b, np.zeros((4, 2), np.float32), qps=400.0,
                                n_requests=8, seed=0, timeout_s=10.0)
    b.stop()
    assert rep["n_ok"] == 0 and rep["n_failed"] == 8
    assert launcher._n_lost([rep]) == 8
    assert launcher._n_lost([dict(rep, n_failed=0, n_timeout=1)]) == 1
    assert launcher._n_lost([dict(rep, n_failed=0)]) == 0


# ---------------------------------------------------------------------------
# batcher shutdown contract (the PR-6 stop() bug)
# ---------------------------------------------------------------------------


def _slow_echo(delay_s):
    def fn(batch):
        time.sleep(delay_s)
        return list(batch)
    return fn


def test_stop_drain_serves_every_queued_request():
    b = DynamicBatcher(_slow_echo(0.02), max_batch=4,
                       max_wait_s=0.001).start()
    reqs = [b.submit(j) for j in range(32)]      # ~8 batches of backlog
    b.stop(drain=True)
    assert all(r.event.is_set() for r in reqs)
    assert all(r.error is None and r.result == j
               for j, r in enumerate(reqs))
    assert b.stats["stopped"] == "drained"
    assert b.stats["failed_on_stop"] == 0
    assert b.stats["requests"] == 32


def test_stop_no_drain_fails_pending_fast():
    b = DynamicBatcher(_slow_echo(0.05), max_batch=4,
                       max_wait_s=0.001).start()
    reqs = [b.submit(j) for j in range(32)]
    t0 = time.perf_counter()
    b.stop(drain=False)
    took = time.perf_counter() - t0
    # worker finishes its in-flight batch then exits; queued work FAILS
    # instead of being served (32 reqs would otherwise take ~0.4s)
    assert took < 0.3
    assert all(r.event.is_set() for r in reqs)    # nobody hangs
    failed = [r for r in reqs if isinstance(r.error, BatcherStopped)]
    assert len(failed) >= 1
    assert b.stats["stopped"] == "failed"
    assert b.stats["failed_on_stop"] == len(failed)
    assert len(failed) + b.stats["requests"] == 32


def test_submit_after_stop_fail_fast():
    b = DynamicBatcher(_slow_echo(0.0), max_batch=4).start()
    b.stop()
    req = b.submit(1)
    assert req.event.is_set() and isinstance(req.error, BatcherStopped)
    with pytest.raises(BatcherStopped):
        b(2)


def test_concurrent_submitters_never_hang_across_stop():
    b = DynamicBatcher(_slow_echo(0.01), max_batch=8,
                       max_wait_s=0.001).start()
    outcomes: list = []

    def client(i):
        try:
            outcomes.append(("ok", b(i, timeout=10.0)))
        except BatcherStopped:
            outcomes.append(("stopped", i))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(24)]
    for t in threads:
        t.start()
    time.sleep(0.02)
    b.stop(drain=False)
    for t in threads:
        t.join(timeout=10.0)
        assert not t.is_alive()      # the contract: no submitter hangs
    assert len(outcomes) == 24


# ---------------------------------------------------------------------------
# degradation ladder
# ---------------------------------------------------------------------------


def test_build_ladder_strictly_cheaper():
    base = SearchParams(k=10, n_probes=8)
    ladder = build_ladder(base, total_trees=16)
    assert ladder[0] == base
    costs = [_ladder_cost(p, 16) for p in ladder]
    assert all(a > b for a, b in zip(costs, costs[1:]))
    # probes step down before trees, trees floor at total//4
    assert [p.n_probes for p in ladder[:4]] == [8, 4, 2, 1]
    assert all((p.n_trees or 16) >= 4 for p in ladder)
    # adaptive-wave base points skip the tree rungs (waves already scale)
    wav = build_ladder(SearchParams(n_probes=4, adaptive_wave=2), 16)
    assert all(p.n_trees == 0 for p in wav)
    # degenerate base: ladder is just the base point
    assert build_ladder(SearchParams(n_probes=1, n_trees=4), 16) == \
        (SearchParams(n_probes=1, n_trees=4),)


def test_uniform_shard_params_covers_every_shard():
    a = SearchParams(k=10, n_probes=2, expand=2, n_trees=4)
    c = SearchParams(k=10, n_probes=8, expand=4, n_trees=4)
    u = uniform_shard_params([a, c])
    assert u.n_probes == 8 and u.expand == 4
    assert u.sharded_violations() == []     # mesh-legal by construction
    with pytest.raises(ValueError):
        uniform_shard_params([])


class _FakeIndex:
    """Index stand-in whose search cost is a deterministic sleep scaling
    with the probe budget — makes overload timing reproducible."""

    def __init__(self, per_probe_s=0.002, n_trees=8):
        self.spec = IndexSpec(backend="rpf",
                              forest=ForestConfig(n_trees=n_trees))
        self.tuned_params = SearchParams(k=5, n_probes=8)
        self.shard_params = None
        self.serving_plan = None

    def search(self, q, params):
        time.sleep(0.002 * params.n_probes)
        n = q.shape[0]
        return (np.zeros((n, params.k), np.float32),
                np.tile(np.arange(params.k), (n, 1)))

    def live_points(self):
        rows = np.zeros((64, 4), np.float32)
        return np.arange(64), rows


def _overload_run(degrade: bool, qps: float, n: int):
    rt = ServingRuntime(_FakeIndex(), max_batch=8, max_wait_s=0.002,
                        slo_p99_ms=50.0, degrade=degrade)
    rep = loadgen.run_open_loop(rt, np.zeros((8, 4), np.float32), qps,
                                n_requests=n, seed=3, timeout_s=60.0)
    stats = rt.stats()
    rt.stop()
    return rep, stats


def test_ladder_sheds_and_never_worsens_the_tail():
    """Past saturation, degrade=True must (a) actually shed, (b) keep the
    tail no worse than the no-ladder control at the same offered load.

    Rung 0 costs 16ms/batch-of-8 (=500 qps capacity); 700 qps offered is
    ~1.4x saturation, while rung 1 (4 probes) clears it with headroom.
    """
    rep_ctl, stats_ctl = _overload_run(degrade=False, qps=700.0, n=350)
    rep_lad, stats_lad = _overload_run(degrade=True, qps=700.0, n=350)
    assert stats_ctl["n_rungs"] == 1 and stats_ctl["shed_steps"] == 0
    assert rep_lad["n_ok"] == rep_ctl["n_ok"] == 350     # nobody dropped
    assert stats_lad["shed_steps"] > 0
    assert rep_lad["shed_fraction"] > 0.0
    assert rep_lad["p99_ms"] <= rep_ctl["p99_ms"]
    assert rep_lad["p999_ms"] <= rep_ctl["p999_ms"]


def test_ladder_idle_stays_on_rung_zero():
    rt = ServingRuntime(_FakeIndex(), max_batch=8, max_wait_s=0.002,
                        slo_p99_ms=200.0, degrade=True)
    for _ in range(4):
        d, i = rt(np.zeros(4, np.float32))
        assert i.shape == (5,)
    stats = rt.stats()
    rt.stop()
    assert stats["rung"] == 0
    assert stats["shed_steps"] == 0 and stats["requests_degraded"] == 0


# ---------------------------------------------------------------------------
# capacity planner
# ---------------------------------------------------------------------------


def test_fit_affine_recovers_model():
    c0, c1 = 2e-3, 5e-5
    grid = np.array([1, 8, 32, 64])
    lat = c0 + c1 * grid
    m0, m1 = planner.fit_affine(grid, lat)
    assert m0 == pytest.approx(c0, rel=1e-6)
    assert m1 == pytest.approx(c1, rel=1e-6)
    # single measurement: all cost attributed to the per-row term
    s0, s1 = planner.fit_affine([8], [4e-4])
    assert s0 == 0.0 and s1 == pytest.approx(5e-5)


def test_traffic_model_roundtrip_and_p99():
    m = planner.TrafficModel(c0_s=1e-3, c1_s=1e-5, max_wait_s=2e-3,
                             batch_grid=(1, 8), measured_s=(1e-3, 1.1e-3),
                             rows_per_query=64.0)
    assert planner.TrafficModel.from_dict(m.to_dict()) == m
    t = m.service_s(32)
    # below saturation the queueing tail is finite and grows with load;
    # at/over saturation it is infinite
    lam_sat = 32 / t
    assert m.p99_s(0.5 * lam_sat, 32) < m.p99_s(0.9 * lam_sat, 32)
    assert m.p99_s(1.1 * lam_sat, 32) == float("inf")
    # sharding s-ways cuts the per-row term s-ways
    assert m.service_s(32, n_shards=4) < m.service_s(32)


def test_rated_qps_and_plan_monotonicity():
    m = planner.TrafficModel(c0_s=1e-3, c1_s=1e-4, max_wait_s=2e-3,
                             batch_grid=(1,), measured_s=(1.1e-3,),
                             rows_per_query=0.0)
    loose = planner.rated_qps(m, slo_p99_ms=50.0, batch=32)
    tight = planner.rated_qps(m, slo_p99_ms=10.0, batch=32)
    assert 0 < tight < loose            # tighter SLO -> lower rated rate
    assert planner.rated_qps(m, slo_p99_ms=1.0, batch=32) == 0.0  # < t(B)

    p_small = planner.plan(m, qps=200.0, slo_p99_ms=50.0)
    p_big = planner.plan(m, qps=4000.0, slo_p99_ms=50.0)
    total_small = p_small.n_replicas * p_small.n_shards
    assert p_big.n_replicas * p_big.n_shards >= total_small
    assert p_big.predicted_p99_ms <= 50.0
    assert planner.CapacityPlan.from_dict(p_big.to_dict()) == p_big
    with pytest.raises(ValueError):     # SLO below c0: nothing can fit
        planner.plan(m, qps=100.0, slo_p99_ms=0.5, max_shards=1,
                     batch_grid=(1,))


# ---------------------------------------------------------------------------
# distributed tuning
# ---------------------------------------------------------------------------


def test_tune_sharded_persists_and_is_deterministic(corpus):
    db, q = corpus
    index = _build(db)
    sp1, report1 = tune_sharded(index, q, n_shards=2, target_recall=0.7,
                                k=10, probe_grid=(1, 2, 4))
    sp2, _ = tune_sharded(index, q, n_shards=2, target_recall=0.7,
                          k=10, probe_grid=(1, 2, 4))
    assert sp1 == sp2                           # deterministic
    assert len(sp1) == 2
    assert all(p.sharded_violations() == [] for p in sp1)
    assert index.shard_params == tuple(sp1)     # persisted on the index
    # per-shard rows report owned-neighbor recall; the summary row carries
    # the implied global recall = sum of owned hits / all true neighbors
    shard_rows = [r for r in report1 if r["shard"] >= 0]
    assert {r["shard"] for r in shard_rows} == {0, 1}
    assert all(0.0 <= r["recall_owned"] <= 1.0 for r in shard_rows)
    summary = [r for r in report1 if "implied_global_recall" in r]
    assert summary and 0.0 < summary[0]["implied_global_recall"] <= 1.0
