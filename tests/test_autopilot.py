"""Autopilot service tests (DESIGN §8): observe → decide → repartition.

Covers the session's automatic ExecutionRecords, history compaction, the
what-if cost model, generation swap consistency, advisor decision
application (host + d2d), the deterministic drift scenario via tick(),
the background thread mode, and the LRU-bounded shuffle-plan cache.
"""

import time

import numpy as np
import pytest

import repro.data.device_repartition as dr
from repro.api import Session
from repro.core import (GreedySelector, HistoryStore, apply_decision,
                        author_integrator, enumerate_candidates,
                        partitioning_creation)
from repro.core.dsl import reddit_loader
from repro.data.partition_store import PartitionStore
from repro.service import (Autopilot, AutopilotConfig, LogicalClock,
                           Observer, WhatIfCostModel, drift_tables,
                           q_orderkey, run_drift_scenario)

ORDERKEY_SIG = "scan/attr:orderkey/partition[hash]"
PARTKEY_SIG = "scan/attr:partkey/partition[hash]"


def _seed_store(backend="host", **kw):
    tables = drift_tables(**kw)
    store = PartitionStore(num_workers=8, backend=backend)
    for name, data in tables.items():
        store.write(name, data)
    return store


# ---------------------------------------------------------------------------
# Observe: automatic ExecutionRecords
# ---------------------------------------------------------------------------

def test_engine_run_auto_records_history():
    store = _seed_store(n_lineitem=2000)
    hist = HistoryStore()
    sess = Session(store)
    wl = q_orderkey()
    _, stats = sess.run(wl, history=hist, timestamp=42.0)

    assert len(hist.records) == 1
    rec = hist.records[0]
    assert rec.app_id == "q-orderkey"
    assert rec.ir_signature == wl.graph.graph_signature()
    assert rec.timestamp == 42.0
    assert rec.latency == stats.wall_s > 0
    assert rec.input_bytes == stats.input_bytes > 0
    assert rec.output_bytes == stats.output_bytes > 0
    assert rec.inputs == ["lineitem", "orders"]
    assert rec.outputs == ["q_orderkey_out"]
    # per-candidate stats measured at the partition nodes
    st = rec.candidate_stats[ORDERKEY_SIG]
    assert 0 < st["selectivity"] <= 1.0
    assert st["distinct_keys"] > 0 and st["num_objects"] > 0
    assert st["object_bytes"] >= st["key_bytes"] > 0
    # the IR is retained for candidate enumeration
    assert hist.ir_of(rec.ir_signature) is not None


def test_engine_constructor_history_and_hooks():
    store = _seed_store(n_lineitem=1000)
    hist = HistoryStore()
    sess = Session(store, history=hist)
    seen = []
    sess.add_run_hook(lambda wl, stats: seen.append(stats))
    sess.run(q_orderkey())
    assert len(hist.records) == 1 and len(seen) == 1
    assert seen[0].candidate_stats    # hooks see the measured stats


def test_observer_attach_and_auto_compact():
    store = _seed_store(n_lineitem=1000)
    sess = Session(store)
    obs = Observer(clock=LogicalClock(), max_records=3,
                   compact_slack=1).attach(sess)
    for _ in range(6):
        sess.run(q_orderkey())
    assert obs.records_seen == 6
    # bounded: 3 verbatim + at most one aggregate per skeleton (1 here)
    assert len(obs.history.records) <= 4
    assert sum(r.weight for r in obs.history.records) == 6.0
    assert obs.compacted_total > 0
    # timestamps are the logical clock's ticks
    assert obs.history.records[-1].timestamp == 6.0


# ---------------------------------------------------------------------------
# HistoryStore.compact
# ---------------------------------------------------------------------------

def _two_group_history(n=6, path=None):
    hist = HistoryStore(path)
    loader = reddit_loader("loader", "raw", "submissions", "json")
    consumer = author_integrator()
    c = enumerate_candidates(consumer.graph, "submissions")[0]
    for t in range(n):
        hist.log_workload(loader, timestamp=10.0 * t, latency=5.0,
                          input_bytes=1e9)
        hist.log_workload(consumer, timestamp=10.0 * t + 5, latency=20.0,
                          input_bytes=2e9,
                          candidate_stats={c.signature(): {
                              "selectivity": 0.1 + 0.01 * t,
                              "distinct_keys": 1e6 - t,
                              "num_objects": 2e7}})
    return hist, loader, consumer, c


def test_compact_bounds_log_and_preserves_aggregates():
    hist, loader, consumer, c = _two_group_history(n=6)
    assert len(hist.records) == 12
    thru_before = hist.overall_throughput()
    removed = hist.compact(max_records=4)
    assert removed > 0
    # bound: max_records verbatim + one aggregate per old skeleton (2)
    assert len(hist.records) <= 4 + 2
    assert hist.total_runs() == 12.0                 # weights preserved
    assert hist.overall_throughput() == pytest.approx(thru_before)
    # feature semantics survive: max selectivity / min distinct keys
    merged = [r for r in hist.records if r.weight > 1]
    assert merged
    for r in merged:
        if c.signature() in r.candidate_stats:
            st = r.candidate_stats[c.signature()]
            assert st["selectivity"] >= 0.1
            assert st["distinct_keys"] < 1e6
    # skeleton graph still has both groups and the producer→consumer edge
    groups, edges = hist.skeleton_graph()
    assert len(groups) == 2 and len(edges) >= 1
    # idempotent once within bounds
    assert hist.compact(max_records=len(hist.records)) == 0


def test_compact_rewrites_jsonl(tmp_path):
    path = str(tmp_path / "hist.jsonl")
    hist, *_ = _two_group_history(n=6, path=path)
    hist.compact(max_records=2)
    reloaded = HistoryStore(path)
    assert len(reloaded.records) == len(hist.records)
    assert reloaded.total_runs() == 12.0
    assert any(r.weight > 1 for r in reloaded.records)


def test_compacted_history_keeps_advisor_decision():
    hist, loader, consumer, c = _two_group_history(n=6)
    dec_before = partitioning_creation(loader, "submissions", hist,
                                       selector=GreedySelector(),
                                       dataset_bytes=2e9)
    hist.compact(max_records=2)
    dec_after = partitioning_creation(loader, "submissions", hist,
                                      selector=GreedySelector(),
                                      dataset_bytes=2e9)
    assert dec_before.candidate.signature() == c.signature()
    assert dec_after.candidate.signature() == c.signature()


# ---------------------------------------------------------------------------
# Cost model
# ---------------------------------------------------------------------------

def test_cost_model_calibration_and_window():
    cm = WhatIfCostModel(default_bandwidth=1e9)
    assert cm.shuffle_throughput() == 1e9            # prior
    cm.observe_shuffle(nbytes=1e6, seconds=0.01)     # 100 MB/s measured
    assert cm.shuffle_throughput() == pytest.approx(1e8)
    assert cm.repartition_throughput() == pytest.approx(1e8)  # falls back
    cm.observe_repartition(nbytes=1e6, seconds=0.02)
    assert cm.repartition_throughput() == pytest.approx(5e7)

    # window'd scoring against a real consumer IR
    hist = HistoryStore()
    wl = q_orderkey()
    for t in (1.0, 2.0, 3.0):
        hist.log_workload(wl, timestamp=t, latency=0.1, input_bytes=1e6)
    cand = enumerate_candidates(wl.graph, "lineitem")[0]
    s_all = cm.score("lineitem", 1e6, 8, cand, None, hist, now=4.0)
    assert s_all.runs_in_window == 3.0
    assert s_all.benefit_s == pytest.approx(
        3 * cm.shuffle_seconds(1e6, 8))
    s_win = cm.score("lineitem", 1e6, 8, cand, None, hist, now=4.0,
                     window_s=1.5)
    assert s_win.runs_in_window == 1.0               # only the t=3 run
    # current layout already equal → zero benefit
    s_same = cm.score("lineitem", 1e6, 8, cand, cand, hist, now=4.0)
    assert s_same.benefit_s == 0.0 and s_same.shuffles_delta == 0.0
    # hysteresis/horizon gate
    assert s_all.worth_it(1.0, horizon=4.0)
    assert not s_same.worth_it(1.0, horizon=4.0)


def test_cost_model_loads_bench_snapshot():
    import os
    bench = os.path.join(os.path.dirname(__file__), "..", "BENCH_pr2.json")
    cm = WhatIfCostModel()
    loaded = cm.load_bench_json(bench)
    assert loaded > 0
    assert cm.repartition_cal.samples == loaded
    assert cm.repartition_throughput() > 0
    # unparseable paths are a no-op, never an exception
    assert cm.load_bench_json("/nonexistent.json") == 0


# ---------------------------------------------------------------------------
# Generations: atomic swap, old-reader consistency
# ---------------------------------------------------------------------------

def test_generation_swap_keeps_old_reader_consistent():
    store = _seed_store(n_lineitem=3000)
    wl = q_orderkey()
    cand = enumerate_candidates(wl.graph, "lineitem")[0]

    reader = store.read("lineitem")                  # reader holds gen 0
    snapshot = {k: np.asarray(v).copy()
                for k, v in reader.gather().items()}
    assert reader.generation == 0

    new, moved = store.repartition(reader, cand, swap=True)
    assert moved > 0
    assert store.read("lineitem") is new
    assert new.generation == 1 and new.name == "lineitem"
    assert store.generation_of("lineitem") == 1

    # the old generation still reads bit-identically mid/post swap
    after = reader.gather()
    assert set(after) == set(snapshot)
    for k in snapshot:
        np.testing.assert_array_equal(after[k], snapshot[k])
        assert after[k].dtype == snapshot[k].dtype
    # superseded generations stay resolvable (bounded retention)
    assert store.read("lineitem", generation=0) is reader
    assert store.read("lineitem", generation=1) is new
    with pytest.raises(KeyError):
        store.read("lineitem", generation=7)


def test_generation_retention_bound():
    store = PartitionStore(num_workers=4, max_retired_generations=2)
    wl = q_orderkey()
    cand = enumerate_candidates(wl.graph, "lineitem")[0]
    store.write("lineitem", drift_tables(n_lineitem=500)["lineitem"])
    for _ in range(4):
        store.repartition(store.read("lineitem"), cand, swap=True)
    assert store.generation_of("lineitem") == 4
    store.read("lineitem", generation=3)             # retained
    with pytest.raises(KeyError):
        store.read("lineitem", generation=0)         # aged out


# ---------------------------------------------------------------------------
# Decide→apply: advisor decision applied d2d, shuffle elided, bits equal
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["host", "device"])
def test_apply_decision_end_to_end(backend):
    rng = np.random.default_rng(0)
    n_sub, n_auth = 4000, 500
    subs = {"author": rng.integers(0, n_auth, n_sub),
            "score": rng.integers(0, 100, n_sub).astype(np.float32)}
    auths = {"author": np.arange(n_auth, dtype=np.int64),
             "karma": rng.integers(0, 100, n_auth).astype(np.float32)}
    store = PartitionStore(num_workers=8, backend=backend)
    store.write("raw", subs)
    store.write("authors", auths)

    hist = HistoryStore()
    sess = Session(store, backend=backend, history=hist)
    loader = reddit_loader("loader", "raw", "submissions", "json")
    consumer = author_integrator()
    clock = LogicalClock()
    sess.run(loader, timestamp=clock())
    vals0, st0 = sess.run(consumer, timestamp=clock())
    assert st0.shuffles_performed == 2 and st0.shuffles_elided == 0

    # Alg. 3 decision from the auto-recorded history, applied in place
    dec = partitioning_creation(loader, "submissions", hist,
                                dataset_bytes=store.read("submissions").nbytes)
    assert dec.candidate.is_keyed
    gen0 = store.generation_of("submissions")
    new, moved = apply_decision(store, dec)
    assert new.generation == gen0 + 1 and moved > 0
    if backend == "device":
        last = store.write_log[-1]
        assert last["name"] == "submissions" and last.get("path") == "d2d"

    vals1, st1 = sess.run(consumer, timestamp=clock())
    assert st1.shuffles_elided == 1                  # submissions side
    assert st1.shuffles_performed == 1               # authors still shuffles

    # bit-identical join output across generations
    join_node = max(n for n, nd in consumer.graph.nodes.items()
                    if nd.kind == "join")
    for out0, out1 in [(vals0[join_node], vals1[join_node])]:
        assert out0.num_rows == out1.num_rows
        k0 = np.lexsort((out0.columns["score"], out0.columns["author"]))
        k1 = np.lexsort((out1.columns["score"], out1.columns["author"]))
        for col in out0.columns:
            a, b = out0.columns[col][k0], out1.columns[col][k1]
            np.testing.assert_array_equal(a, b)
            assert a.dtype == b.dtype


# ---------------------------------------------------------------------------
# The drift scenario — deterministic via tick()
# ---------------------------------------------------------------------------

def _assert_drift_report(rep):
    # phase A: round-robin layout, every run pays all 3 shuffles
    assert all(r.shuffles == 3 and r.elided == 0 for r in rep.phase_a)
    # the service autonomously partitions lineitem+orders on orderkey
    applied_a = {a.dataset: a for a in rep.tick_a.applied}
    assert {"lineitem", "orders"} <= set(applied_a)
    assert applied_a["lineitem"].decision.candidate.signature() \
        == ORDERKEY_SIG
    assert applied_a["lineitem"].generation == 1
    # post-decision: both join shuffles elided, only the aggregate shuffles
    assert rep.post_a.elided == 2 and rep.post_a.shuffles == 1
    assert rep.post_a.shuffle_bytes < rep.phase_a[0].shuffle_bytes
    # bit-identical across generations
    for k in rep.result_pre_a:
        np.testing.assert_array_equal(rep.result_pre_a[k],
                                      rep.result_post_a[k])
        assert rep.result_pre_a[k].dtype == rep.result_post_a[k].dtype
    # drift: the early tick cannot flip lineitem (cooldown), the late tick
    # re-partitions it to partkey as the orderkey mix ages out of window
    assert "lineitem" not in {a.dataset for a in rep.tick_b_mid.applied}
    applied_b = {a.dataset: a for a in rep.tick_b.applied}
    assert applied_b["lineitem"].decision.candidate.signature() \
        == PARTKEY_SIG
    assert applied_b["lineitem"].generation == 2
    assert rep.lineitem_generations == [0, 1, 2]
    # post-drift: the partkey joins skip their shuffles again
    assert rep.post_b.elided == 2 and rep.post_b.shuffles == 1
    for k in rep.result_pre_b:
        np.testing.assert_array_equal(rep.result_pre_b[k],
                                      rep.result_post_b[k])


def test_drift_scenario_host_deterministic():
    rep = run_drift_scenario(backend="host")
    _assert_drift_report(rep)
    # history stayed observed throughout
    assert rep.autopilot.history.total_runs() == len(rep.phase_a) \
        + len(rep.phase_b) + 2


def test_drift_scenario_device_d2d():
    rep = run_drift_scenario(backend="device")
    _assert_drift_report(rep)
    # decisions were applied through the device-to-device fast path
    applied = {a.dataset: a for a in rep.tick_a.applied}
    assert applied["lineitem"].path == "d2d"
    applied_b = {a.dataset: a for a in rep.tick_b.applied}
    assert applied_b["lineitem"].path == "d2d"


def test_background_thread_mode():
    store = _seed_store(n_lineitem=2000)
    sess = Session(store)
    ap = Autopilot(sess, config=AutopilotConfig(min_runs=2.0,
                                                hysteresis=0.5,
                                                cooldown_ticks=0))
    for _ in range(3):
        sess.run(q_orderkey())
    ap.start(period_s=0.02)
    try:
        deadline = time.time() + 20.0
        while time.time() < deadline:
            p = store.read("lineitem").partitioner
            if p is not None and p.is_keyed:
                break
            time.sleep(0.02)
    finally:
        ap.stop()
    assert ap.optimizer.last_error is None
    assert store.read("lineitem").partitioner.signature() == ORDERKEY_SIG
    assert store.generation_of("lineitem") >= 1


# ---------------------------------------------------------------------------
# Plan cache: LRU bound + stats reset (service longevity)
# ---------------------------------------------------------------------------

def test_plan_cache_lru_bound_and_reset():
    rng = np.random.default_rng(0)
    old_cap = dr.plan_cache_capacity()
    dr.clear_plan_cache()
    try:
        dr.set_plan_cache_capacity(2)
        for n in (100, 1000, 10_000):        # three distinct shape buckets
            cols = {"v": rng.integers(0, 99, n).astype(np.float32)}
            keys = rng.integers(0, 1_000, n).astype(np.int64)
            dr.device_rebucket(cols, keys, 8)
        stats = dr.plan_cache_stats()
        assert stats["plans"] <= 2                   # LRU bound holds
        assert stats["evictions"] >= 1
        assert stats["traces"] == 3                  # monotone incl. evicted

        dr.reset_plan_cache_stats()
        stats = dr.plan_cache_stats()
        assert stats["traces"] == 0 and stats["calls"] == 0
        assert stats["plans"] <= 2                   # plans survive a reset

        # a live plan serves without retracing after the reset
        n = 10_000
        cols = {"v": rng.integers(0, 99, n).astype(np.float32)}
        keys = rng.integers(0, 1_000, n).astype(np.int64)
        dr.device_rebucket(cols, keys, 8)
        stats = dr.plan_cache_stats()
        assert stats["calls"] == 1 and stats["traces"] == 0
    finally:
        dr.set_plan_cache_capacity(old_cap)
        dr.clear_plan_cache()


def test_plan_cache_capacity_validation():
    with pytest.raises(ValueError):
        dr.set_plan_cache_capacity(0)
