"""Tab. 2 + Tab. 3 + Fig. 11 — offline/online overhead analysis.

Offline (Tab. 2): skeleton-graph construction + IR signature creation over
synthetic trace archives shaped like the WTA sources (workflow count ×
tasks-per-workflow).  Online consumer side (Fig. 11): Alg. 4 matching cost
per query.  Producer side (Tab. 3) is measured in bench_reddit.

Repartition backends (DESIGN §5): host-vs-device repartition comparison on
the TPC-H, Reddit, and PageRank workloads — the same consumer run over a
round-robin store (every shuffle real) with the numpy path and with the
Pallas hash-partition kernel path.  Off-TPU the kernel runs in interpret
mode (Python-speed), so the comparison there is a correctness/coverage
signal, not a perf number; on TPU the same rows measure the compiled path.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import (HistoryStore, author_integrator,
                        enumerate_candidates, pagerank_iteration,
                        partitioning_match)
from repro.core.dsl import reddit_loader
from repro.data.partition_store import PartitionStore
from repro.core.history import ExecutionRecord

from .common import emit, run_consumer, scale

# (name, workflows, tasks/workflow) — WTA-shaped, scaled to CPU budget
TRACES = [
    ("Pegasus-like", 56, 180),
    ("Shell-like", 3_403, 3),
    ("Askalon-like", 4_583, 36),
    ("SPEC-like", 400, 70),
    ("Google-like-1pct", 4_941, 36),
]


def synth_history(n_workflows, tasks_per_wf, seed=0) -> HistoryStore:
    rng = np.random.default_rng(seed)
    hist = HistoryStore()
    n_groups = max(4, n_workflows // 50)    # recurrence: ~50 runs per group
    for i in range(n_workflows):
        g = int(rng.integers(0, n_groups))
        hist.log(ExecutionRecord(
            app_id=f"app{g}", timestamp=float(i),
            ir_signature=f"sig{g}",
            inputs=[f"ds{g}"], outputs=[f"ds{(g + 1) % n_groups}"],
            latency=float(rng.uniform(1, 100)),
            input_bytes=float(rng.uniform(1e8, 1e10))))
    return hist


def offline_overheads():
    for name, wf, tpw in TRACES:
        wf, tpw = scale(wf, 200), scale(tpw, 20)
        hist = synth_history(wf, tpw)
        t0 = time.perf_counter()
        groups, edges = hist.skeleton_graph()
        sg_ms = (time.perf_counter() - t0) * 1e3

        # signature creation for `tasks` IR graphs (reuse the reddit IR as a
        # representative task graph; paper hashes each workload's IR once)
        wl = author_integrator()
        n_sigs = min(tpw, 200)
        t0 = time.perf_counter()
        for _ in range(n_sigs):
            wl.graph.graph_signature()
        sn_ms = (time.perf_counter() - t0) * 1e3 * (tpw / n_sigs)
        emit(f"offline_{name}", sg_ms * 1e3,
             f"workflows={wf} SG-latency={sg_ms:.1f}ms "
             f"SN-latency~{sn_ms:.1f}ms groups={len(groups)} "
             f"edges={len(edges)}")


def online_consumer_matching():
    wl = author_integrator()
    cand = enumerate_candidates(wl.graph, "submissions")[0]
    n = scale(2000, 200)
    t0 = time.perf_counter()
    for _ in range(n):
        res = partitioning_match(cand, "submissions", wl.graph)
    per = (time.perf_counter() - t0) / n
    emit("online_consumer_match", per * 1e6,
         f"matched={res.matched} checked={res.checked} "
         f"(paper Fig.11: sub-second; here {per * 1e3:.3f} ms/query)")


def _backend_cases():
    """The three acceptance workloads, each with round-robin-stored inputs
    so every partition node performs a real repartition."""
    from .bench_pagerank import make_graph, wire_emit_fn
    from .bench_reddit import make_data
    from .bench_tpch import make_tables, q_orders_lineitem

    subs, auths = make_data(scale(100_000, 5_000), scale(25_000, 1_200))
    yield ("reddit", author_integrator(),
           {"submissions": subs, "authors": auths})

    pages, ranks = make_graph(scale(100_000, 5_000), fanout=5)
    yield ("pagerank", wire_emit_fn(pagerank_iteration(), 5),
           {"pages": pages, "ranks": ranks})

    orders, lineitem, part = make_tables()
    yield ("tpch_q04like", q_orders_lineitem(),
           {"orders": orders, "lineitem": lineitem, "part": part})


def repartition_backends(workers: int = 8):
    from repro.configs import lachesis_paper
    backends = lachesis_paper.get().engine_backends
    for name, wl, tables in _backend_cases():
        res = {}
        for backend in backends:
            store = PartitionStore(workers)
            for tname, data in tables.items():
                store.write(tname, data)           # rr ⇒ shuffles all run
            res[backend] = run_consumer(store, wl, repeats=2,
                                        backend=backend)
        h, d = res["host"], res["device"]
        assert d["device_repartitions"] == d["shuffles"] > 0
        emit(f"repartition_{name}_device", d["wall_s"] * 1e6,
             f"host={h['wall_s'] * 1e6:.0f}us "
             f"device/host={d['wall_s'] / h['wall_s']:.2f}x "
             f"shuffles={d['shuffles']} "
             f"device_repartitions={d['device_repartitions']} "
             f"bytes={d['shuffle_bytes']}")


# -- single-pass device shuffle (ISSUE 2): argsort vs counting-sort plans ----

def _shuffle_data(n: int, m: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    cols = {"score": rng.normal(size=n).astype(np.float32),
            "weight": rng.normal(size=n).astype(np.float32),
            "ups": rng.integers(0, 1000, n).astype(np.int32),
            "vec": rng.normal(size=(n, 2)).astype(np.float32),
            "author": rng.integers(0, n, n).astype(np.int64)}  # hybrid 64-bit
    keys = cols["author"]
    return cols, keys


def _legacy_rebucket(columns, key_vals, m):
    """The PR 1 device re-bucket, reproduced for comparison: un-jitted
    O(N log N) ``jnp.argsort`` + one eager gather and one host sync *per
    column* (pids via the jitted oracle so the comparison isolates the
    shuffle, not interpret-mode kernel overhead)."""
    import jax.numpy as jnp
    from repro.data.device_repartition import (device_partition_ids,
                                               dtype_roundtrips)
    key_vals = np.asarray(key_vals).reshape(-1)
    pids, hist = device_partition_ids(key_vals, m, use_kernel=False)
    order = jnp.argsort(pids, stable=True)
    out = {}
    for k, v in columns.items():
        v = np.asarray(v)
        if dtype_roundtrips(v.dtype):
            out[k] = np.asarray(jnp.take(jnp.asarray(v), order, axis=0))
        else:
            out[k] = v[np.asarray(order)]
    out["__key__"] = out.get("__key__", key_vals[np.asarray(order)])
    return out, np.asarray(hist).astype(np.int64)


def _best_of(fn, repeats=3):
    best = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        dt = time.perf_counter() - t0
        best = dt if best is None or dt < best else best
    return best, out


def device_repartition_scaling(n: int = 1_000_000, m: int = 32):
    """The acceptance rows: host counting-sort baseline, PR 1 argsort
    device path, and the jitted single-pass plan, same data, same machine.
    Always full-size — these rows are the perf trajectory."""
    from repro.core.ir import _mix_hash
    from repro.data.device_repartition import (clear_plan_cache,
                                               device_rebucket,
                                               plan_cache_stats)
    import jax.numpy as jnp
    cols, keys = _shuffle_data(n, m)

    def host():
        pids = np.asarray(_mix_hash(jnp.asarray(keys))).astype(np.int64) % m
        order = np.argsort(pids, kind="stable")
        out = {k: v[order] for k, v in cols.items()}
        out["__key__"] = keys[order]
        return out, np.bincount(pids, minlength=m)

    t_host, (ref_cols, ref_counts) = _best_of(host)
    t_legacy, (leg_cols, leg_counts) = _best_of(
        lambda: _legacy_rebucket(cols, keys, m))
    clear_plan_cache()
    device_rebucket(cols, keys, m)            # trace once, outside the timer
    t_plan, (new_cols, new_counts) = _best_of(
        lambda: device_rebucket(cols, keys, m))
    stats = plan_cache_stats()

    for k in ref_cols:                        # the speedup must be bit-exact
        np.testing.assert_array_equal(ref_cols[k], leg_cols[k])
        np.testing.assert_array_equal(ref_cols[k], new_cols[k])
    speedup = t_legacy / t_plan
    emit(f"repartition_host_n{n:.0e}_m{m}".replace("e+0", "e"),
         t_host * 1e6,
         "host numpy stable-argsort re-bucket (engine host-path baseline)")
    emit(f"repartition_device_argsort_n{n:.0e}_m{m}".replace("e+0", "e"),
         t_legacy * 1e6, "PR1 path: eager argsort + per-column gather/sync")
    emit(f"repartition_device_n{n:.0e}_m{m}".replace("e+0", "e"),
         t_plan * 1e6,
         f"single-pass plan: counting-sort + packed gather "
         f"speedup_vs_argsort={speedup:.2f}x traces={stats['traces']} "
         f"plans={stats['plans']} (target >=2x)")


def d2d_repartition(n: int = 1_000_000, m: int = 32):
    """Device-to-device StoredDataset repartition vs the PR 1 route
    (host gather() + full re-write).  Always full-size."""
    from repro.data.partition_store import PartitionStore
    cols, _ = _shuffle_data(n, m, seed=1)
    wl = author_integrator()
    cand = enumerate_candidates(wl.graph, "submissions")[0]

    store = PartitionStore(m, backend="device")
    ds = store.write("submissions", cols)              # round-robin layout

    def via_host():                                    # PR 1 repartition
        flat = ds.gather()
        return store.write("h_reparted", flat, cand)

    def via_d2d():
        new, _ = store.repartition(ds, cand, name="d_reparted")
        return new

    t_host, ds_h = _best_of(via_host, repeats=2)
    via_d2d()                                          # trace once
    t_d2d, ds_d = _best_of(via_d2d, repeats=2)
    np.testing.assert_array_equal(ds_h.counts, ds_d.counts)
    fh, fd = ds_h.gather(), ds_d.gather()
    for k in fh:
        np.testing.assert_array_equal(fh[k], fd[k])
    emit(f"repartition_d2d_n{n:.0e}_m{m}".replace("e+0", "e"),
         t_d2d * 1e6,
         f"device→device, no host gather; gather+rewrite={t_host * 1e6:.0f}us "
         f"speedup={t_host / t_d2d:.2f}x path={store.write_log[-1].get('path')}"
         f" (CPU host<->device copies are zero-copy; the elided gather is a"
         f" real transfer on TPU)")


# -- skew-adaptive capacity (DESIGN §12): zipf keys, split/merge layouts ----

def device_repartition_skew(n: int = 1_000_000, m: int = 32):
    """Skew rows: the same d2d repartition over Zipf-keyed data, with and
    without the capacity map, against a balanced-key baseline.  The map
    must hold padded bytes near the uniform baseline (≤1.3×) where the
    plain uniform-capacity layout blows up (≥2×), without retracing the
    scatter plan per skew level (offsets are a traced argument)."""
    from repro.data.device_repartition import plan_cache_stats
    from .common import zipf_keys
    n = scale(n, 120_000)
    wl = author_integrator()
    cand = enumerate_candidates(wl.graph, "submissions")[0]

    def reparted(alpha, adaptive):
        cols, _ = _shuffle_data(n, m, seed=2)
        if alpha is not None:
            cols["author"] = zipf_keys(n, n, alpha,
                                       rng=np.random.default_rng(11))
        store = PartitionStore(m, backend="device",
                               adaptive_capacity=adaptive)
        ds = store.write("submissions", cols)       # round-robin layout

        def go():
            new, _ = store.repartition(ds, cand, name="reparted")
            return new

        go()                                        # trace once
        t, out = _best_of(go, repeats=2)
        return t, out

    t_uni, ds_uni = reparted(None, True)    # balanced ⇒ map planner says no
    t_cm, ds_cm = reparted(1.1, True)
    t_plain, ds_plain = reparted(1.1, False)

    assert ds_uni.capacity_map is None
    assert ds_cm.capacity_map is not None
    fc, fp = ds_cm.gather(), ds_plain.gather()      # bit-identical layouts
    for k in fc:
        np.testing.assert_array_equal(fc[k], fp[k])

    pu, pc, pp = (float(d.padded_bytes) for d in (ds_uni, ds_cm, ds_plain))
    # power-of-two buckets guarantee padded < 2× valid whatever the skew;
    # in practice the map stays near the balanced-key baseline while the
    # uniform-capacity layout scales with the hottest partition
    assert pc < 2.0 * float(ds_cm.valid_bytes), (pc, ds_cm.valid_bytes)
    assert pc <= 1.5 * pu, (pc, pu)                 # map holds the padding
    assert pp >= 2.0 * pu, (pp, pu)                 # without it, skew pays

    # no-retrace bound: further skew levels hit the same traced plans
    before = plan_cache_stats()["traces"]
    for alpha in (1.05, 1.2, 1.5):
        reparted(alpha, True)
    traces = plan_cache_stats()["traces"]
    assert traces == before, (traces, before)

    suffix = f"n{n:.0e}_m{m}".replace("e+0", "e")
    emit(f"repartition_unikey_{suffix}", t_uni * 1e6,
         f"balanced keys, uniform capacity: padded_bytes={int(pu)} "
         f"skew={ds_uni.skew():.2f}")
    emit(f"repartition_zipf_{suffix}", t_cm * 1e6,
         f"zipf(1.1) keys, capacity map: padded_bytes={int(pc)} "
         f"valid_bytes={int(ds_cm.valid_bytes)} "
         f"padded_vs_uniform={pc / pu:.2f}x (bound <2x valid) "
         f"skew={ds_cm.skew():.2f} "
         f"buckets={len(ds_cm.capacity_map.bucket_set())} "
         f"vs_unikey={t_cm / t_uni:.2f}x traces_flat={traces}=={before}")
    emit(f"repartition_zipf_nocmap_{suffix}", t_plain * 1e6,
         f"zipf(1.1) keys, uniform capacity: padded_bytes={int(pp)} "
         f"padding_waste={int(ds_plain.padding_waste())} "
         f"padded_vs_uniform={pp / pu:.2f}x (>=2x — what the map removes) "
         f"vs_unikey={t_plain / t_uni:.2f}x")


# -- planner/executor split (ISSUE 4): plan compile vs exec, cached re-runs --

def plan_compile_vs_exec(workers: int = 8):
    """Session planning cost vs execution cost, plus cached-plan re-run
    rows: a plan-cache hit must show ~zero planning cost and a flat
    ShufflePlan trace counter across repeated ``session.run``."""
    from repro.api import Session
    from repro.data.device_repartition import plan_cache_stats
    from .bench_reddit import make_data

    subs, auths = make_data(scale(100_000, 5_000), scale(25_000, 1_200))
    wl = author_integrator()
    for backend in ("host", "device"):
        store = PartitionStore(workers)
        store.write("submissions", subs)       # rr ⇒ both shuffles real
        store.write("authors", auths)
        sess = Session(store, backend=backend)

        t0 = time.perf_counter()
        sess.plan(wl)                          # cold: logical + compile
        t_compile = time.perf_counter() - t0
        t0 = time.perf_counter()
        sess.plan(wl)                          # warm: pure cache hit
        t_hit = time.perf_counter() - t0

        sess.run(wl)                           # traces the device plans once
        base_traces = sess.plan_cache_stats()["traces"]
        best_exec, planning = float("inf"), float("inf")
        for _ in range(3):                     # cached re-runs
            t0 = time.perf_counter()
            res = sess.run(wl)
            best_exec = min(best_exec, time.perf_counter() - t0)
            planning = min(planning, res.stats.planning_s)
            assert res.stats.plan_cache_hit
        stats = sess.plan_cache_stats()
        # the no-retrace guarantee: repeated runs of an unchanged workload
        # on an unchanged layout generation never re-trace
        assert stats["traces"] == base_traces, (stats, base_traces)
        if backend == "host":
            emit("plan_compile_vs_exec", t_compile * 1e6,
                 f"exec={best_exec * 1e6:.0f}us hit={t_hit * 1e6:.1f}us "
                 f"compile/exec={t_compile / best_exec:.3f} "
                 f"hits={stats['hits']} misses={stats['misses']}")
        emit(f"plan_cached_rerun_{backend}", best_exec * 1e6,
             f"planning={planning * 1e6:.1f}us (cache hit) "
             f"traces_flat={stats['traces']}=={base_traces} "
             f"plan_cache={stats['hits']}h/{stats['misses']}m "
             f"dev_plan_stats={plan_cache_stats()['plans']}plans")


# -- observability overhead (DESIGN §13): tracing off/sampled/full rows -----

def tracing_overhead(workers: int = 8):
    """The §13 overhead contract: with tracing **off**, the plan-cache-hit
    run path must stay within 2%.  The assert is deterministic — spans a
    hit-run would record × the measured per-disabled-span-call cost,
    against the measured hit wall — instead of differencing two noisy
    end-to-end walls (whose jitter dwarfs a nanosecond-scale guard)."""
    from repro import obs
    from repro.api import Session
    from .bench_reddit import make_data

    subs, auths = make_data(scale(100_000, 5_000), scale(25_000, 1_200))
    store = PartitionStore(workers)
    store.write("submissions", subs)
    store.write("authors", auths)
    sess = Session(store)
    wl = author_integrator()
    sess.run(wl)                                   # compile + trace once

    def best_run(repeats=5):
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            res = sess.run(wl)
            best = min(best, time.perf_counter() - t0)
            assert res.stats.plan_cache_hit
        return best

    obs.disable()
    t_off = best_run()
    # disabled-span unit cost: one module-global load + the shared no-op
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.span("bench.noop"):
            pass
    per_call = (time.perf_counter() - t0) / n
    # spans a single cache-hit run records (the sites the off-path pays)
    obs.enable("full")
    obs.clear_spans()
    sess.run(wl)
    spans_per_run = len(obs.finished_spans())
    t_full = best_run()
    obs.configure(mode="sampled", sample_every=16)
    t_sampled = best_run()
    obs.disable()
    obs.clear_spans()

    modeled = spans_per_run * per_call
    budget = 0.02 * t_off
    assert modeled < budget, (
        f"tracing-off overhead blew the 2% budget: {spans_per_run} spans x "
        f"{per_call * 1e9:.0f}ns = {modeled * 1e6:.2f}us vs budget "
        f"{budget * 1e6:.2f}us (hit wall {t_off * 1e6:.0f}us)")
    emit("tracing_off_cache_hit", t_off * 1e6,
         f"spans/run={spans_per_run} "
         f"per_disabled_span={per_call * 1e9:.0f}ns "
         f"modeled_overhead={modeled / t_off * 100:.3f}% (budget 2%)")
    emit("tracing_sampled_cache_hit", t_sampled * 1e6,
         f"sample_every=16 vs_off={t_sampled / t_off:.2f}x")
    emit("tracing_full_cache_hit", t_full * 1e6,
         f"vs_off={t_full / t_off:.2f}x spans/run={spans_per_run}")


# -- telemetry overhead (DESIGN §15): durable RunProfile append cost --------

def telemetry_overhead(workers: int = 8):
    """The §15 overhead contract: recording one RunProfile per run into
    the durable telemetry history must stay within the same 2% cache-hit
    budget tracing gets.  Deterministic like the §13 assert — measured
    per-append cost × the one append a run performs, against the
    measured hit wall — not a diff of two noisy end-to-end walls."""
    import tempfile

    from repro.api import Session
    from repro.obs.telemetry import RunProfile
    from .bench_reddit import make_data

    subs, auths = make_data(scale(100_000, 5_000), scale(25_000, 1_200))
    wl = author_integrator()
    with tempfile.TemporaryDirectory() as root:
        sess = Session(store_path=root, num_workers=workers)
        sess.store.write("submissions", subs)
        sess.store.write("authors", auths)
        sess.run(wl)                               # compile + trace once

        best = float("inf")
        for _ in range(5):                         # durable-store hit wall
            t0 = time.perf_counter()
            res = sess.run(wl)
            best = min(best, time.perf_counter() - t0)
            assert res.stats.plan_cache_hit

        # per-append unit cost on the same (warm) store handle
        tele = sess.telemetry_store
        profile = RunProfile(t=0.0, workload="bench", process="bench",
                             wall_s=best)
        n = 2_000
        t0 = time.perf_counter()
        for _ in range(n):
            tele.record_run(profile)
        per_record = (time.perf_counter() - t0) / n

        modeled = per_record                       # one append per run
        budget = 0.02 * best
        assert modeled < budget, (
            f"telemetry_record blew the 2% budget: {per_record * 1e6:.2f}us "
            f"per append vs budget {budget * 1e6:.2f}us "
            f"(hit wall {best * 1e6:.0f}us)")
        stats = tele.stats()
        emit("telemetry_record", per_record * 1e6,
             f"modeled_overhead={modeled / best * 100:.3f}% (budget 2%) "
             f"hit_wall={best * 1e6:.0f}us appends={stats['appends']} "
             f"compactions={stats['compactions']} (bounded history)")


def main():
    offline_overheads()
    online_consumer_matching()
    repartition_backends()
    device_repartition_scaling()
    d2d_repartition()
    device_repartition_skew()
    plan_compile_vs_exec()
    tracing_overhead()
    telemetry_overhead()


if __name__ == "__main__":
    main()
