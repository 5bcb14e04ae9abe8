"""Skew-adaptive partitioning tests (DESIGN §12).

Covers the variable-capacity layout end-to-end: CapacityMap planning and
slot arithmetic, the heavy-hitter sketch, bucketed-vs-uniform scatter
bit-identity (hypothesis sweeps over dtypes/skew/zero-row partitions and
d2d-vs-host), the no-retrace guarantee across skew levels, store-level
padded/valid accounting, SaltedPartitioner semantics, the durable
round-trip of the capacity map, and the Autopilot's salt/rebucket
decisions under injected calibrations.
"""

import numpy as np
import pytest

import repro.data.device_repartition as dr
from repro.api import Session
from repro.core import author_integrator, enumerate_candidates, \
    partitioning_match
from repro.core.partitioner import SaltedPartitioner
from repro.data.capacity import (CapacityMap, bucket_capacity,
                                 plan_capacity_map, valid_slot_index)
from repro.data.partition_store import PartitionStore
from repro.data.skew import HeavyHitterSketch, zipf_keys
from repro.service import (Autopilot, AutopilotConfig, LogicalClock,
                           aggregate_result, drift_tables, q_orderkey)

ORDERKEY_SIG = "scan/attr:orderkey/partition[hash]"


# ---------------------------------------------------------------------------
# CapacityMap: buckets, planning, slot arithmetic
# ---------------------------------------------------------------------------

def test_bucket_capacity_powers_of_two():
    assert bucket_capacity(0) == 0
    assert bucket_capacity(1) == 1
    assert bucket_capacity(2) == 2
    assert bucket_capacity(3) == 4
    assert bucket_capacity(1025) == 2048


def test_capacity_map_from_counts_and_offsets():
    cm = CapacityMap.from_counts(np.array([5, 0, 17, 2]))
    np.testing.assert_array_equal(cm.capacities, [8, 0, 32, 2])
    np.testing.assert_array_equal(cm.offsets, [0, 8, 8, 40])
    assert cm.total_slots == 42
    assert cm.num_partitions == 4
    assert not cm.is_uniform()
    assert cm == CapacityMap.of([8, 0, 32, 2])
    assert cm != CapacityMap.of([8, 0, 32, 4])
    assert (cm == None) is False                       # noqa: E711
    assert hash(cm) == hash(CapacityMap.of([8, 0, 32, 2]))


def test_plan_capacity_map_balanced_stays_uniform():
    # near-balanced counts: bucketing buys < the threshold — stay uniform
    assert plan_capacity_map(np.array([100, 101, 99, 100])) is None
    assert plan_capacity_map(np.zeros(4, np.int64)) is None
    assert plan_capacity_map(np.array([], np.int64)) is None
    # one hot partition: bucketed total beats m × bucket(max) easily
    cm = plan_capacity_map(np.array([1000, 10, 10, 10]))
    assert cm is not None
    assert cm.total_slots < 4 * bucket_capacity(1000) * 0.75


def test_valid_slot_index_orders_rows_worker_major():
    counts = np.array([2, 0, 3])
    offs = np.array([0, 2, 2])        # packed buckets (cap == count here)
    np.testing.assert_array_equal(valid_slot_index(counts, offs),
                                  [0, 1, 2, 3, 4])
    uni = np.array([0, 4, 8])         # uniform capacity 4
    np.testing.assert_array_equal(valid_slot_index(counts, uni),
                                  [0, 1, 8, 9, 10])
    assert valid_slot_index(np.zeros(3, np.int64), uni).size == 0


# ---------------------------------------------------------------------------
# Heavy-hitter sketch + zipf generator
# ---------------------------------------------------------------------------

def test_sketch_finds_guaranteed_heavy_hitter():
    rng = np.random.default_rng(0)
    keys = np.concatenate([np.full(600, 7), rng.integers(100, 10_000, 400)])
    rng.shuffle(keys)
    sk = HeavyHitterSketch(k=4).update(keys)
    # freq 0.6 > n/(k+1): guaranteed among the counters, lower-bounded
    hits = dict(sk.heavy_hitters(0.25))
    assert 7 in hits
    assert sk.max_fraction() <= 0.6 + 1e-9     # never overestimates
    assert sk.max_fraction() >= 0.6 - 1.0 / (sk.k + 1)


def test_sketch_batched_updates_and_empty():
    sk = HeavyHitterSketch(k=2)
    assert sk.max_fraction() == 0.0 and sk.heavy_hitters(0.1) == []
    for _ in range(5):
        sk.update([1, 1, 1, 2, 3])
    assert max(sk.counters(), key=sk.counters().get) == 1
    with pytest.raises(ValueError):
        HeavyHitterSketch(k=0)


def test_zipf_keys_deterministic_and_bounded():
    a = zipf_keys(1000, 50, 1.2, seed=3)
    b = zipf_keys(1000, 50, 1.2, seed=3)
    np.testing.assert_array_equal(a, b)
    assert a.dtype == np.int64
    assert a.min() >= 0 and a.max() < 50
    # skewed: the hottest key dominates a uniform draw's share
    frac = np.bincount(a).max() / 1000
    assert frac > 5 * (1 / 50)


# ---------------------------------------------------------------------------
# Bucketed scatter: bit-identical to the uniform layout (deterministic
# sweeps; the hypothesis generalization lives in test_skew_properties.py)
# ---------------------------------------------------------------------------

PAYLOAD_DTYPES = (np.float32, np.int32, np.float64, np.int64)


@pytest.mark.parametrize("m,dom", [(2, 0), (5, 1), (16, 2), (9, 3)])
def test_bucketed_scatter_rows_equal_uniform(m, dom):
    rng = np.random.default_rng(dom)
    n = 257
    keys = rng.integers(0, 2 ** 31 - 1, n) % (4 ** dom + 1)
    data = {"k": keys,
            "v": (np.arange(n) * 3).astype(PAYLOAD_DTYPES[dom]),
            "mat": np.arange(2 * n, dtype=np.float32).reshape(n, 2)}
    pids_d, hist = dr.device_partition_ids(keys, m)
    counts = np.asarray(hist).astype(np.int64)
    cmap = CapacityMap.from_counts(counts)     # force bucketing (zero-cap
                                               # partitions included)
    uni = dr.device_scatter_padded(data, pids_d, counts)
    buck = dr.device_scatter_padded(data, pids_d, counts, capacity_map=cmap)
    cap = int(counts.max())
    uni_off = np.arange(m, dtype=np.int64) * cap
    vidx_u = valid_slot_index(counts, uni_off)
    vidx_b = valid_slot_index(counts, cmap.offsets)
    for k, v in data.items():
        got_u = np.asarray(uni[k]).reshape((m * cap,) + v.shape[1:])[vidx_u]
        got_b = np.asarray(buck[k])[vidx_b]
        assert got_b.dtype == v.dtype, k
        np.testing.assert_array_equal(got_u, got_b, err_msg=k)


@pytest.mark.parametrize("alpha", [1.05, 1.3, 2.5])
@pytest.mark.parametrize("device", [False, True])
def test_adaptive_store_gather_equals_uniform_store(alpha, device):
    """Store-level bit-identity: the same keyed write through an adaptive
    store (capacity map allowed) and a plain store (always uniform) must
    gather back identical flat rows — host path and d2d path both."""
    m, n = 8, 300
    keys = zipf_keys(n, n, alpha, seed=7)
    cols = {"author": keys,
            "v64": np.arange(n, dtype=np.int64),     # hybrid 64-bit path
            "v32": np.arange(n, dtype=np.float32)}
    cand = enumerate_candidates(author_integrator().graph, "submissions")[0]
    backend = "device" if device else "host"
    out = {}
    for adaptive in (False, True):
        store = PartitionStore(m, backend=backend,
                               adaptive_capacity=adaptive)
        ds = store.write("submissions", cols, cand)
        out[adaptive] = (ds, ds.gather())
    ds_u, flat_u = out[False]
    ds_a, flat_a = out[True]
    assert ds_u.capacity_map is None
    np.testing.assert_array_equal(ds_u.counts, ds_a.counts)
    for k in flat_u:
        assert flat_a[k].dtype == flat_u[k].dtype, k
        np.testing.assert_array_equal(np.asarray(flat_u[k]),
                                      np.asarray(flat_a[k]), err_msg=k)


def test_d2d_repartition_bucketed_equals_host():
    """Device-to-device repartition with a capacity map matches the host
    gather+rewrite route bit for bit."""
    n, m = 5000, 8
    keys = zipf_keys(n, n, 1.3, seed=1)
    cols = {"author": keys, "v": np.arange(n, dtype=np.float32)}
    cand = enumerate_candidates(author_integrator().graph, "submissions")[0]
    dstore = PartitionStore(m, backend="device", adaptive_capacity=True)
    ds = dstore.write("submissions", cols)             # round-robin
    new, moved = dstore.repartition(ds, cand, name="reparted")
    assert new.capacity_map is not None and moved > 0

    hstore = PartitionStore(m, backend="host", adaptive_capacity=True)
    hds = hstore.write("submissions", cols)
    hnew = hstore.write("reparted", hds.gather(), cand)
    np.testing.assert_array_equal(new.counts, hnew.counts)
    assert hnew.capacity_map == new.capacity_map
    fd, fh = new.gather(), hnew.gather()
    for k in fh:
        np.testing.assert_array_equal(np.asarray(fd[k]), np.asarray(fh[k]),
                                      err_msg=k)


def test_skew_levels_share_one_scatter_trace():
    """The no-retrace regression: capacity buckets ride the plan as a
    traced offsets array, so changing skew (new CapacityMap, same shape
    buckets) never re-traces the fused scatter."""
    n, m = 4096, 8
    rng = np.random.default_rng(0)
    data = {"v": rng.normal(size=n).astype(np.float32)}
    dr.clear_plan_cache()
    dr.reset_plan_cache_stats()
    try:
        traces = []
        for alpha in (1.1, 1.5, 2.5):
            keys = zipf_keys(n, n, alpha, seed=2)
            pids_d, hist = dr.device_partition_ids(keys, m)
            counts = np.asarray(hist).astype(np.int64)
            cmap = CapacityMap.from_counts(counts)
            dr.device_scatter_padded(data, pids_d, counts, capacity_map=cmap)
            traces.append(dr.plan_cache_stats()["traces"])
        assert traces[1] == traces[0] and traces[2] == traces[0], traces
    finally:
        dr.clear_plan_cache()
        dr.reset_plan_cache_stats()


# ---------------------------------------------------------------------------
# StoredDataset.skew() + padded/valid accounting in the write log
# ---------------------------------------------------------------------------

def test_skew_and_padding_accounting():
    n, m = 4000, 8
    keys = zipf_keys(n, n, 2.5, seed=0)
    cols = {"author": keys, "v": np.arange(n, dtype=np.float32)}
    cand = enumerate_candidates(author_integrator().graph, "submissions")[0]
    store = PartitionStore(m)                      # uniform capacities
    ds = store.write("submissions", cols, cand)
    assert ds.skew() > 2.0
    assert ds.padded_bytes > ds.valid_bytes > 0
    assert ds.padding_waste() == ds.padded_bytes - ds.valid_bytes
    stats = store.write_stats()
    assert stats["padded_bytes"] >= ds.padded_bytes
    assert stats["valid_bytes"] >= ds.valid_bytes
    assert stats["max_skew"] >= ds.skew() - 1e-9

    rr = store.write("balanced", {"v": np.arange(n, dtype=np.float32)})
    assert rr.skew() == pytest.approx(1.0, abs=0.01)


def test_rebucket_is_local_nondestructive_and_idempotent():
    n, m = 4000, 8
    keys = zipf_keys(n, n, 2.5, seed=0)
    cols = {"author": keys, "v": np.arange(n, dtype=np.float32)}
    cand = enumerate_candidates(author_integrator().graph, "submissions")[0]
    store = PartitionStore(m)
    ds = store.write("submissions", cols, cand)
    flat = ds.gather()
    gen0 = ds.generation

    new, moved = store.rebucket("submissions")
    assert moved == 0
    assert new.capacity_map is not None
    assert new.generation > gen0
    assert new.partitioner is ds.partitioner        # elisions preserved
    assert new.padded_bytes < ds.padded_bytes
    for k in flat:
        np.testing.assert_array_equal(new.gather()[k], flat[k], err_msg=k)
    assert store.write_log[-1]["path"] == "rebucket"

    again, moved2 = store.rebucket("submissions")   # planned == current
    assert moved2 == 0 and again.generation == new.generation


def test_durable_roundtrip_preserves_capacity_map(tmp_path):
    n, m = 3000, 8
    keys = zipf_keys(n, n, 1.5, seed=4)
    cols = {"author": keys, "v": np.arange(n, dtype=np.float32)}
    cand = enumerate_candidates(author_integrator().graph, "submissions")[0]
    root = str(tmp_path / "store")
    store = PartitionStore(m, root=root, adaptive_capacity=True)
    ds = store.write("submissions", cols, cand)
    assert ds.capacity_map is not None
    flat = ds.gather()

    re = PartitionStore(m, root=root)              # reattach from disk
    ds2 = re.read("submissions")
    assert ds2.capacity_map == ds.capacity_map
    np.testing.assert_array_equal(ds2.counts, ds.counts)
    for k in flat:
        np.testing.assert_array_equal(np.asarray(ds2.gather()[k]), flat[k],
                                      err_msg=k)


# ---------------------------------------------------------------------------
# SaltedPartitioner
# ---------------------------------------------------------------------------

def test_salted_partitioner_spreads_hot_keys_only():
    cand = enumerate_candidates(author_integrator().graph, "submissions")[0]
    salted = SaltedPartitioner(
        graph=cand.graph, strategy=cand.strategy,
        source_dataset=cand.source_dataset, origin=cand.origin,
        hot_keys=(7,), salt_factor=4)
    m = 8
    keys = np.array([7] * 100 + [3] * 10 + [11] * 10)
    data = {"author": keys}
    pids = salted.partition_ids(data, m)
    base = cand.partition_ids(data, m)
    hot = keys == 7
    # cold rows: identical to the plain hash layout
    np.testing.assert_array_equal(pids[~hot], np.asarray(base)[~hot])
    # hot rows: sprayed across exactly salt_factor partitions
    assert len(np.unique(pids[hot])) == 4
    # the salted signature never matches a consumer (Alg. 4): consumers
    # re-shuffle, which is what makes salting correctness-free
    assert "salt4[7]" in salted.signature()
    res = partitioning_match(salted, "submissions",
                             author_integrator().graph)
    assert not res.partition_nodes
    assert salted.kernel_dispatchable is False


def test_salted_store_write_bit_identical():
    n, m = 2000, 8
    keys = zipf_keys(n, n, 2.5, seed=0)
    cols = {"author": keys, "v": np.arange(n, dtype=np.float32)}
    cand = enumerate_candidates(author_integrator().graph, "submissions")[0]
    hot = int(np.bincount(keys).argmax())
    salted = SaltedPartitioner(
        graph=cand.graph, strategy=cand.strategy,
        source_dataset=cand.source_dataset, origin=cand.origin,
        hot_keys=(hot,), salt_factor=4)
    for backend in ("host", "device"):
        plain = PartitionStore(m, backend=backend).write(
            "submissions", cols, cand)
        forked = PartitionStore(m, backend=backend).write(
            "submissions", cols, salted)
        assert forked.skew() < plain.skew()
        a = {k: np.sort(np.asarray(v).reshape(v.shape[0], -1), axis=0)
             for k, v in plain.gather().items()}
        b = {k: np.sort(np.asarray(v).reshape(v.shape[0], -1), axis=0)
             for k, v in forked.gather().items()}
        for k in a:     # same multiset of rows, different placement
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


# ---------------------------------------------------------------------------
# Autopilot skew actions: hot-key salting + capacity rebucketing
# ---------------------------------------------------------------------------

def _skewed_session(skew=1.5, **cfg_kw):
    tables = drift_tables(n_lineitem=4000, skew=skew)
    store = PartitionStore(num_workers=8)
    for name, data in tables.items():
        store.write(name, data)
    sess = Session(store)
    cfg = AutopilotConfig(min_runs=2.0, hysteresis=0.5, cooldown_ticks=0,
                          skew_actions=True, **cfg_kw)
    ap = Autopilot(sess, clock=LogicalClock(), config=cfg)
    return store, sess, ap


def test_autopilot_salts_hot_key_dataset():
    store, sess, ap = _skewed_session()
    wl = q_orderkey()
    for _ in range(3):
        sess.run(wl)
    vals0, _ = sess.run(wl)
    ref = aggregate_result(vals0, wl)
    # injected calibrations: fast network (repartitions are cheap), slow
    # storage (padding waste is expensive) — the skew-action sweet spot
    ap.cost_model.observe_shuffle(1e9, 0.1)
    ap.cost_model.observe_io(1e6, 1.0)

    rep1 = ap.tick()              # classic keyed repartition lands first
    assert ("lineitem", "repartition") in {(a.dataset, a.kind)
                                           for a in rep1.applied}
    ds = store.read("lineitem")
    assert ds.partitioner.signature() == ORDERKEY_SIG
    assert ds.skew() >= 2.0       # zipf orderkeys under the hash layout
    waste = ds.padding_waste()

    rep2 = ap.tick()              # skew phase: hot-key split
    applied = {(a.dataset, a.kind) for a in rep2.applied}
    assert ("lineitem", "salt") in applied
    a = next(x for x in rep2.applied if x.kind == "salt")
    assert a.decision is not None
    assert "salt" in a.decision.candidate.signature()
    assert a.decision.candidate.hot_keys     # sketched at apply time
    ds2 = store.read("lineitem")
    assert "salt" in ds2.partitioner.signature()
    assert ds2.skew() < ds.skew()
    assert ds2.padding_waste() < waste
    # correctness: salted layouts never match, consumers re-shuffle —
    # results stay bit-identical
    vals, stats = sess.run(wl)
    assert stats.shuffles_performed >= 1
    got = aggregate_result(vals, wl)
    for k in ref:
        np.testing.assert_array_equal(got[k], ref[k], err_msg=k)
    # no flip-flop: the next tick does not salt again
    rep3 = ap.tick()
    assert ("lineitem", "salt") not in {(x.dataset, x.kind)
                                        for x in rep3.applied}


def test_autopilot_rebuckets_skewed_layout():
    # hot_key_fraction > 1 disables salting: the fallback action must be
    # a local rebucket under a fresh capacity map
    store, sess, ap = _skewed_session(hot_key_fraction=2.0)
    wl = q_orderkey()
    for _ in range(2):
        sess.run(wl)
    vals0, _ = sess.run(wl)
    ref = aggregate_result(vals0, wl)
    ap.cost_model.observe_shuffle(1e9, 0.1)
    ap.cost_model.observe_io(1e6, 1.0)

    ap.tick()                      # keyed repartition (uniform capacity)
    ds = store.read("lineitem")
    assert ds.capacity_map is None and ds.padding_waste() > 0
    gen = ds.generation

    rep2 = ap.tick()
    a = next(x for x in rep2.applied
             if x.dataset == "lineitem" and x.kind == "rebucket")
    assert a.decision is None and a.moved_bytes == 0
    assert a.path == "rebucket"
    assert a.score.padding_benefit_s > 0
    ds2 = store.read("lineitem")
    assert ds2.capacity_map is not None
    assert ds2.generation > gen
    assert ds2.padded_bytes < ds.padded_bytes
    assert ds2.partitioner.signature() == ORDERKEY_SIG   # layout survives
    # the generation flip invalidated cached plans; elisions still hold
    vals, stats = sess.run(wl)
    assert stats.shuffles_elided >= 1
    got = aggregate_result(vals, wl)
    for k in ref:
        np.testing.assert_array_equal(got[k], ref[k], err_msg=k)
    # idempotent: planned map == current map ⇒ no third action
    rep3 = ap.tick()
    assert ("lineitem", "rebucket") not in {(x.dataset, x.kind)
                                            for x in rep3.applied}


def test_skew_actions_default_follows_store_flag():
    tables = drift_tables(n_lineitem=2000, skew=1.5)
    store = PartitionStore(num_workers=8)          # adaptive_capacity=False
    for name, data in tables.items():
        store.write(name, data)
    sess = Session(store)
    ap = Autopilot(sess, clock=LogicalClock(),
                   config=AutopilotConfig(min_runs=2.0, hysteresis=0.5,
                                          cooldown_ticks=0))
    for _ in range(3):
        sess.run(q_orderkey())
    ap.cost_model.observe_shuffle(1e9, 0.1)
    ap.cost_model.observe_io(1e6, 1.0)
    ap.tick()
    rep2 = ap.tick()
    # skew_actions=None + non-adaptive store ⇒ no salt/rebucket ever
    assert all(a.kind == "repartition" for a in rep2.applied)


def test_autopilot_unsalts_cooled_hot_key():
    """Salt → cool → unsalt round-trips bit-identically (PR 7 leftover).

    While the key is hot, nothing may unwind the split — the skew phase
    owns salted layouts and its hot_key_cooled gate holds.  Once the
    observed hot-key share drops below the unsalt threshold (default
    hot_key_fraction/2), the plain keyed layout comes back, consumers
    elide again, and results match the salted era bit-for-bit."""
    store, sess, ap = _skewed_session(window_s=6.0)
    wl = q_orderkey()
    for _ in range(3):
        sess.run(wl)
    ap.cost_model.observe_shuffle(1e9, 0.1)
    ap.cost_model.observe_io(1e6, 1.0)
    ap.tick()                               # keyed repartition
    ap.tick()                               # hot-key salt
    assert "salt" in store.read("lineitem").partitioner.signature()

    # still hot: a fat repartition calibration makes unwinding cheap, but
    # the hot_key_cooled gate must keep the split in place (no flip-flop)
    ap.cost_model.observe_repartition(1e9, 0.1)
    rep_hot = ap.tick()
    assert not any(a.kind in ("unsalt", "repartition") and
                   a.dataset == "lineitem" for a in rep_hot.applied)
    assert "salt" in store.read("lineitem").partitioner.signature()
    w = next(r for r in rep_hot.why
             if r["dataset"] == "lineitem" and r["action"] == "unsalt")
    assert not w["accepted"]
    assert not next(g for g in w["gates"]
                    if g["gate"] == "hot_key_cooled")["passed"]

    # the key cools: same schema, uniform orderkeys, salted layout kept
    cooled = drift_tables(n_lineitem=4000, skew=0.0, seed=1)
    store.write("lineitem", cooled["lineitem"],
                partitioner=store.read("lineitem").partitioner)
    ref_vals, ref_stats = sess.run(wl)      # salted era: shuffles paid
    assert ref_stats.shuffles_performed >= 1
    ref = aggregate_result(ref_vals, wl)
    for _ in range(6):                      # hot records age out of window
        sess.run(wl)

    rep = ap.tick()
    a = next(x for x in rep.applied if x.kind == "unsalt")
    assert a.dataset == "lineitem" and a.decision is not None
    assert "salt" not in a.decision.candidate.signature()
    ds = store.read("lineitem")
    assert ds.partitioner.signature() == ORDERKEY_SIG
    w = next(r for r in rep.why
             if r["dataset"] == "lineitem" and r["action"] == "unsalt")
    assert w["accepted"]
    assert next(g for g in w["gates"]
                if g["gate"] == "hot_key_cooled")["passed"]

    # round trip: the keyed layout matches Alg. 4 again and the results
    # are bit-identical to the salted era
    vals, stats = sess.run(wl)
    assert stats.shuffles_elided >= 1
    got = aggregate_result(vals, wl)
    for k in ref:
        np.testing.assert_array_equal(got[k], ref[k], err_msg=k)
    # stable: the next tick neither re-salts nor re-unsalts
    rep2 = ap.tick()
    assert not any(x.kind in ("salt", "unsalt") for x in rep2.applied)
