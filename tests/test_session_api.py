"""Session API tests (DESIGN §9): planner/executor split, plan cache,
backend registry and explain() golden output.

Covers: fresh sessions agree bit for bit (and host agrees with device),
pure plan-cache hits on re-runs of an unchanged workload (0 new traces),
layout-generation flips invalidating exactly the affected plans,
deterministic ``explain``, the ``UnknownBackendError`` bugfix (every
entry point), and the gated per-candidate measurement pass.
"""

import numpy as np
import pytest

import lachesis
from repro.api import RunResult, Session
from repro.core import (UnknownBackendError, author_integrator,
                        enumerate_candidates, pagerank_iteration)
from repro.core.backends import REGISTRY, Backend, BackendRegistry
from repro.core.executor import StalePlanError, TableVal
from repro.data.partition_store import PartitionStore


# -- fixtures ----------------------------------------------------------------

def _reddit_data(n_sub=3000, n_auth=500, seed=0):
    rng = np.random.default_rng(seed)
    subs = {"author": rng.integers(0, n_auth, n_sub).astype(np.int64),
            "score": rng.normal(size=n_sub).astype(np.float32)}
    auths = {"author": np.arange(n_auth, dtype=np.int64),
             "karma": rng.normal(size=n_auth).astype(np.float32)}
    return subs, auths


def _seeded_store(partitioned: bool, backend: str = "host", m: int = 8):
    wl = author_integrator()
    subs, auths = _reddit_data()
    store = PartitionStore(num_workers=m, backend=backend)
    if partitioned:
        store.write("submissions", subs,
                    enumerate_candidates(wl.graph, "submissions")[0])
        store.write("authors", auths,
                    enumerate_candidates(wl.graph, "authors")[0])
    else:
        store.write("submissions", subs)
        store.write("authors", auths)
    return wl, store


def _assert_same_values(va, vb):
    assert set(va) == set(vb)
    for nid in va:
        a, b = va[nid], vb[nid]
        if isinstance(a, TableVal):
            np.testing.assert_array_equal(a.counts, b.counts)
            assert set(a.columns) == set(b.columns)
            for k in a.columns:
                x, y = np.asarray(a.columns[k]), np.asarray(b.columns[k])
                assert x.dtype == y.dtype, (nid, k)
                np.testing.assert_array_equal(x, y)


# -- parity between fresh sessions -------------------------------------------

@pytest.mark.parametrize("partitioned", [False, True])
def test_session_parity_with_engine(partitioned):
    wl, store = _seeded_store(partitioned)
    res = Session(store).run(wl)
    assert isinstance(res, RunResult)

    wl2, store2 = _seeded_store(partitioned)
    vals, stats = Session(store2).run(wl2)
    _assert_same_values(res.values, vals)
    assert res.stats.shuffles_performed == stats.shuffles_performed
    assert res.stats.shuffles_elided == stats.shuffles_elided
    assert res.stats.shuffle_bytes == stats.shuffle_bytes


def test_session_parity_host_device():
    wl_h, host = _seeded_store(False, backend="host")
    wl_d, dev = _seeded_store(False, backend="device")
    res_h = Session(host, backend="host").run(wl_h)
    res_d = Session(dev, backend="device").run(wl_d)
    _assert_same_values(res_h.values, res_d.values)
    assert res_d.stats.device_repartitions == \
        res_d.stats.shuffles_performed == 2
    assert res_h.stats.device_repartitions == 0


def test_session_pagerank_matches_engine():
    """A write-back workload (pagerank writes the ranks it scans): every
    run flips the layout generation, so each run re-plans — and results
    stay identical to a fresh session's."""
    def build():
        n, fanout = 600, 4
        rng = np.random.default_rng(1)
        neighbors = rng.integers(0, n, (n, fanout)).astype(np.int64)
        pages = {"url": np.arange(n, dtype=np.int64), "neighbors": neighbors}
        ranks = {"url": np.arange(n, dtype=np.int64),
                 "rank": np.full(n, 1.0 / n, np.float64)}
        wl = pagerank_iteration()

        def emit(cols):
            contrib = np.repeat((cols["rank"] / fanout)[:, None], fanout, 1)
            return {"url": cols["neighbors"], "contrib": contrib}
        for node in wl.graph.nodes.values():
            if node.params.get("tag") == "emit_contribs":
                node.params["fn"] = emit
        store = PartitionStore(num_workers=4)
        store.write("pages", pages,
                    enumerate_candidates(wl.graph, "pages")[0])
        store.write("ranks", ranks,
                    enumerate_candidates(wl.graph, "ranks")[0])
        return wl, store

    wl, store = build()
    sess = Session(store)
    r1 = sess.run(wl)
    assert r1.stats.plan_cache_hit is False
    assert r1.stats.shuffles_elided >= 2        # co-partitioned on url
    # the run's own write flipped the ranks generation: the cached plan is
    # stale, the next plan lookup is a miss (exact invalidation)
    _plan, hit = sess.planner.physical(wl, "host")
    assert hit is False

    wl2, store2 = build()
    v1, _ = Session(store2).run(wl2)
    _assert_same_values(r1.values, v1)


# -- plan cache ---------------------------------------------------------------

def test_plan_cache_hit_and_exact_generation_invalidation():
    wl, store = _seeded_store(True)
    subs, _auths = _reddit_data()
    sess = Session(store)

    r1 = sess.run(wl)
    assert r1.stats.plan_cache_hit is False
    r2 = sess.run(wl)
    assert r2.stats.plan_cache_hit is True
    _assert_same_values(r1.values, r2.values)

    # a second workload scanning a *different* dataset
    other = lachesis.Workload("other")
    o = other.scan("other_ds")
    other.aggregate(o, key=o["k"], reducer="sum")
    store.write("other_ds", {"k": np.arange(50) % 5,
                             "v": np.ones(50, np.float64)})
    assert sess.run(other).stats.plan_cache_hit is False
    assert sess.run(other).stats.plan_cache_hit is True

    # flip submissions' layout generation: the author workload must
    # re-plan, the other workload's plan must stay cached
    store.write("submissions", subs)            # round-robin now, gen+1
    r3 = sess.run(wl)
    assert r3.stats.plan_cache_hit is False
    assert r3.stats.shuffles_performed > r2.stats.shuffles_performed
    assert sess.run(other).stats.plan_cache_hit is True

    st = sess.plan_cache_stats()
    assert st["misses"] == 3 and st["hits"] == 3 and st["size"] == 3


def test_plan_cache_no_retrace_on_device_reruns():
    wl, store = _seeded_store(False, backend="device")
    sess = Session(store, backend="device")
    sess.run(wl)                                # traces the shuffle plans
    base = sess.plan_cache_stats()["traces"]
    for _ in range(3):
        res = sess.run(wl)
        assert res.stats.plan_cache_hit is True
    assert sess.plan_cache_stats()["traces"] == base


def test_stale_plan_rejected():
    wl, store = _seeded_store(True)
    sess = Session(store)
    plan = sess.plan(wl)
    subs, _ = _reddit_data()
    store.write("submissions", subs)            # generation flip
    with pytest.raises(StalePlanError):
        sess.executor.execute(plan)
    # but Session.run re-plans transparently
    assert sess.run(wl).stats.plan_cache_hit is False


def test_run_replans_transparently_on_race(monkeypatch):
    """A layout swap landing between the plan-cache lookup and execution
    (background Autopilot) must trigger a silent re-plan, not an error."""
    wl, store = _seeded_store(True)
    subs, _ = _reddit_data()
    sess = Session(store)
    stale_plan = sess.plan(wl)                  # pins submissions@gen0
    ref = sess.run(wl)

    store.write("submissions", subs,
                enumerate_candidates(wl.graph, "submissions")[0])  # gen1
    real_physical = sess.planner.physical
    raced = {"n": 0}

    def physical_racing(workload, backend):
        if raced["n"] == 0:                     # first lookup: the race —
            raced["n"] += 1                     # hand back the stale plan
            return stale_plan, True
        return real_physical(workload, backend)

    monkeypatch.setattr(sess.planner, "physical", physical_racing)
    res = sess.run(wl)                          # no StalePlanError escapes
    assert raced["n"] == 1                      # retry went through re-plan
    assert res.plan is not stale_plan
    assert res.plan.key.layout != stale_plan.key.layout
    _assert_same_values(res.values, ref.values)  # same partitioner ⇒ same rows


def test_failed_run_keeps_implicit_workload():
    _, store = _seeded_store(True)
    sess = Session(store)
    subs = sess.scan("submissions")
    auths = sess.scan("authors")
    j = sess.join(subs, auths,
                  left_key=subs.parse("json")["author"],
                  right_key=auths.parse("csv")["author"],
                  tag="author_join")
    sess.write_result(j, "integrated")
    with pytest.raises(UnknownBackendError):
        sess.run(backend="devcie")
    assert sess.current is not None             # not lost by the failure
    res = sess.run()                            # retry succeeds and clears
    assert sess.current is None
    assert res.stats.shuffles_elided == 2


def test_invalidate_and_lru_bound():
    wl, store = _seeded_store(True)
    sess = Session(store, plan_cache_capacity=1)
    sess.run(wl)
    assert sess.plan_cache_stats()["size"] == 1
    assert sess.invalidate("submissions") == 1
    assert sess.plan_cache_stats()["size"] == 0
    sess.run(wl)
    sess.run(wl, backend="device")              # evicts the host plan
    st = sess.plan_cache_stats()
    assert st["size"] == 1 and st["evictions"] == 1


# -- explain ------------------------------------------------------------------

def _golden_store(backend="host"):
    wl = author_integrator()
    subs = {"author": np.arange(100, dtype=np.int64) % 20,
            "score": np.ones(100, np.float32)}
    auths = {"author": np.arange(20, dtype=np.int64),
             "karma": np.ones(20, np.float32)}
    sess = Session(num_workers=4, backend=backend)
    sess.write("submissions", subs,
               enumerate_candidates(wl.graph, "submissions")[0])
    sess.write("authors", auths)
    return wl, sess


GOLDEN_HOST_EXPLAIN = """\
PhysicalPlan author-integrator backend=host workers=4 matching=on
  ir: 26f88a8d53ad
  layout: authors@gen0[roundrobin] submissions@gen0[scan/parse:json/attr:author/partition[hash]]
  steps:
    [  0] scan submissions rows=100 gen=0
    [  1] scan authors rows=20 gen=0
    [  2] parse:json
    [  3] attr:author
    [  4] parse:csv
    [  5] attr:author
    [  6] partition[hash] key<-n3 src=submissions ELIDED (Alg.4 static: layout matches scan/parse:json/attr:author/partition[hash])
    [  7] partition[hash] key<-n5 src=authors op=host_argsort bucket=dynamic shuffle
    [  8] join
    [  9] write integrated
  shuffles: elided=1 performed=1"""


def test_explain_golden_and_deterministic():
    wl, sess = _golden_store()
    assert sess.explain(wl) == GOLDEN_HOST_EXPLAIN
    # deterministic: a fresh identical session + freshly traced workload
    # produces the identical dump, and repeated calls are stable
    wl2, sess2 = _golden_store()
    assert sess2.explain(wl2) == sess.explain(wl)


def test_explain_device_shows_op_and_bucket():
    wl = author_integrator()
    sess = Session(num_workers=4, backend="device")
    sess.write("submissions", {"author": np.arange(100, dtype=np.int64) % 20,
                               "score": np.ones(100, np.float32)})
    sess.write("authors", {"author": np.arange(20, dtype=np.int64),
                           "karma": np.ones(20, np.float32)})
    txt = sess.explain(wl)
    # per partition node: bound backend op + static ShufflePlan bucket
    assert "op=device_rebucket bucket=B128 shuffle" in txt
    assert "op=device_rebucket bucket=B32 shuffle" in txt
    assert txt == sess.explain(wl)              # deterministic
    # and the elided case still renders under the device backend
    wl2, sess2 = _golden_store(backend="device")
    assert "ELIDED (Alg.4 static" in sess2.explain(wl2)


# -- backend registry (ISSUE 4 satellite bugfix) ------------------------------

@pytest.mark.parametrize("bad", ["devise", "Device", "gpu", ""])
def test_unknown_backend_all_entry_points(bad):
    wl, store = _seeded_store(False)
    for ctor in (lambda: Session(store, backend=bad),
                 lambda: PartitionStore(backend=bad),
                 lambda: Session(store).run(wl, backend=bad),
                 lambda: Session(store).plan(wl, backend=bad)):
        with pytest.raises(UnknownBackendError) as ei:
            ctor()
        msg = str(ei.value)
        assert repr(bad) in msg
        assert "host" in msg and "device" in msg    # lists what IS registered
    # both historical failure spellings remain catchable
    assert issubclass(UnknownBackendError, KeyError)
    assert issubclass(UnknownBackendError, ValueError)


def test_matching_toggle_forwards_to_planner():
    """`sess.matching = False` disables Alg. 4 elision (the knob lives in
    the Planner), on a live session and on a fresh one alike."""
    wl, store = _seeded_store(True)
    sess = Session(store)
    assert sess.run(wl).stats.shuffles_elided == 2
    sess.matching = False
    st = sess.run(wl).stats
    assert st.shuffles_elided == 0 and st.shuffles_performed == 2
    fresh = Session(store)
    fresh.matching = False
    _, est = fresh.run(wl)
    assert est.shuffles_elided == 0 and est.shuffles_performed == 2


def test_custom_device_resident_backend_stores_on_device():
    """A registered backend with device_resident=True must get
    device-resident columns — capability, not the literal name — and the
    session's own ``registry=`` must reach the store it creates."""
    import jax
    reg = BackendRegistry()
    reg.register(Backend("host"))
    reg.register(Backend("device", device_resident=True,
                         kernel_shuffle=True, device_relay=True))
    reg.register(Backend("mydev", device_resident=True,
                         kernel_shuffle=True, device_relay=True))
    store = PartitionStore(num_workers=4, backend="mydev", registry=reg)
    ds = store.write("t", {"k": np.arange(64, dtype=np.int32)})
    assert any(isinstance(v, jax.Array) for v in ds.columns.values())
    assert ds.backend == "device"               # columns live on device

    # end-to-end through a Session with its own registry
    sess = Session(num_workers=4, backend="mydev", registry=reg)
    subs, auths = _reddit_data(400, 80)
    sess.write("submissions", subs)
    sess.write("authors", auths)
    wl = author_integrator()
    res = sess.run(wl)
    assert res.stats.device_repartitions == res.stats.shuffles_performed == 2
    host = Session(num_workers=4)               # host oracle, bit-identical
    host.write("submissions", subs)
    host.write("authors", auths)
    _assert_same_values(res.values, host.run(wl).values)


def test_param_twins_do_not_share_plans():
    """Two structurally identical workloads with different UDFs / write
    targets must not collide in the plan cache (the IR signature is
    structural by design; the param fingerprint disambiguates)."""
    store = PartitionStore(num_workers=4)
    store.write("t", {"v": np.arange(32, dtype=np.float64)})
    sess = Session(store)

    def make(mult, out):
        wl = lachesis.Workload(f"x{mult}")
        s = wl.scan("t")
        m = wl.map(s, fn=lambda c, _k=mult: {"v": c["v"] * _k}, tag="scale")
        wl.write(m, out)
        return wl

    wl2, wl100 = make(2, "out2"), make(100, "out100")
    assert wl2.graph.graph_signature() == wl100.graph.graph_signature()
    r2 = sess.run(wl2)
    r100 = sess.run(wl100)
    assert r100.stats.plan_cache_hit is False   # no silent collision
    np.testing.assert_array_equal(               # worker-segment order
        np.sort(store.read("out2").gather()["v"]), np.arange(32) * 2.0)
    np.testing.assert_array_equal(               # wl100's fn + target ran
        np.sort(store.read("out100").gather()["v"]), np.arange(32) * 100.0)
    # same workload object re-runs still hit
    assert sess.run(wl2).stats.plan_cache_hit is True
    # and rebuilt param-free workloads keep hitting across objects
    _, pstore = _seeded_store(True)
    psess = Session(pstore)
    psess.run(author_integrator())
    assert psess.run(author_integrator()).stats.plan_cache_hit is True


def test_registry_capabilities_and_plugging():
    reg = BackendRegistry()
    reg.register(Backend("host"))
    reg.register(Backend("device", device_resident=True,
                         kernel_shuffle=True, device_relay=True))
    assert [b.name for b in reg.with_capability(kernel_shuffle=True)] \
        == ["device"]
    with pytest.raises(ValueError):
        reg.register(Backend("host"))           # no silent overwrite
    assert "host" in REGISTRY and "device" in REGISTRY
    assert REGISTRY.get("device").partition_op("hash") == "device_rebucket"
    assert REGISTRY.get("host").partition_op("hash") == "host_argsort"
    assert REGISTRY.get("host").partition_op("range") == "host_range"


# -- measurement-pass gating (ISSUE 4 satellite bugfix) -----------------------

def test_candidate_measurement_gated_behind_observation(monkeypatch):
    import repro.core.executor as ex
    calls = []
    orig = ex._record_candidate_stats
    monkeypatch.setattr(
        ex, "_record_candidate_stats",
        lambda *a, **k: (calls.append(1), orig(*a, **k))[1])

    wl, store = _seeded_store(False)
    sess = Session(store)
    res = sess.run(wl)                          # unobserved run
    assert calls == []                          # measurement pass skipped
    assert res.stats.candidate_stats is None
    assert res.stats.candidate_measure_passes == 0

    seen = []
    sess.add_run_hook(lambda w, s: seen.append(s))
    res2 = sess.run(wl)                         # observed run
    assert len(calls) == 2                      # one pass per partition node
    assert res2.stats.candidate_measure_passes == 2
    assert res2.stats.candidate_stats           # hooks see measured stats
    assert seen and seen[0] is res2.stats


# -- session DSL passthrough --------------------------------------------------

def test_session_implicit_workload_builder():
    _, store = _seeded_store(True)
    sess = Session(store)
    subs = sess.scan("submissions")
    auths = sess.scan("authors")
    j = sess.join(subs, auths,
                  left_key=subs.parse("json")["author"],
                  right_key=auths.parse("csv")["author"],
                  tag="author_join")
    sess.write_result(j, "integrated")
    assert sess.current is not None
    res = sess.run()                            # runs + clears the implicit wl
    assert sess.current is None
    assert res.stats.shuffles_elided == 2       # same IR ⇒ same elisions
    ref = Session(store).run(author_integrator())
    assert res.workload.graph.graph_signature() \
        == ref.workload.graph.graph_signature()
    _assert_same_values(res.values, ref.values)
    with pytest.raises(ValueError, match="no workload"):
        sess.run()


def test_session_autopilot_attach():
    wl, store = _seeded_store(False)
    sess = Session(store)
    ap = sess.autopilot()
    sess.run(wl)
    assert ap.history.total_runs() == 1         # observed automatically
    assert ap.session is sess


def test_observer_no_double_log_with_shared_history():
    """Exactly one ExecutionRecord per run, however the HistoryStore is
    shared (double records would double the run rates the cost model
    prices from) — and runs on a session that does NOT share it must
    still be recorded."""
    from repro.core import HistoryStore
    wl, store = _seeded_store(False)
    h = HistoryStore()
    sess = Session(store, history=h)
    ap = sess.autopilot(history=h)
    sess.run(wl)
    assert h.total_runs() == 1
    assert ap.observer.records_seen == 1
    sess.run(wl)
    assert h.total_runs() == 2

    # per-call history override sharing the observer's store: still one
    sess2 = Session(store)                      # no constructor history
    ap2 = sess2.autopilot()
    sess2.run(wl, history=ap2.history)
    assert ap2.history.total_runs() == 1

    # a second session attached to the same observer WITHOUT sharing the
    # history must not be silently dropped
    _, store3 = _seeded_store(False)
    sess3 = Session(store3)
    ap2.observer.attach(sess3)
    sess3.run(wl)
    assert ap2.history.total_runs() == 2


def test_compile_pins_key_layout_not_live_store():
    """compile(key=...) must resolve datasets at the key's pinned
    generations, so a concurrent swap between key computation and compile
    cannot cache a plan that disagrees with its key."""
    wl, store = _seeded_store(True)
    sess = Session(store)
    key0 = sess.planner.plan_key(wl, "host")
    subs, _ = _reddit_data()
    store.write("submissions", subs)            # live store moves to gen1 rr
    plan = sess.planner.compile(sess.planner.logical(wl), "host", key=key0)
    scan = next(s for s in plan.steps
                if s.kind == "scan" and s.dataset == "submissions")
    assert scan.generation == 0                 # pinned, not live
    # elision was decided against the pinned partitioned gen0 layout,
    # not the live round-robin gen1 one
    assert len(plan.elided) == 2
