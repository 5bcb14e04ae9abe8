"""Chip smoke: drive Lachesis's device path once on a TPU and check it.

Run from the root of a checkout, on a machine with a TPU::

    python chip_smoke.py                # one chip, TPC-H-shaped SF 10
    python chip_smoke.py --chips 4      # only the mesh repartition path

One process and no fallback: without a TPU it exits nonzero before any
work.  Every table is generated from ``--seed``.  Phases on one chip:

* kernels: ``hash_partition``, ``hash_partition_padded`` and
  ``scatter_perm`` compiled at N = 2^24, m in {32, 256}, each bit-identical
  to its ``ref.py`` oracle;
* dispatch and shuffle: TPC-H-shaped lineitem/orders/part (the spec's row
  counts, 32-bit keys) written round-robin through
  ``Session(backend="device")``; the q04-like join/aggregate; a rewrite
  under the extracted partitioners (the join's shuffles elided, a
  per-order aggregate with none); a device-to-device repartition of
  lineitem onto partkey and the q17-like query.  Every result equals the
  host backend's and a numpy reference that does not use the package;
* serving: 16 concurrent submits through ``Session.serve(max_workers=8)``,
  each equal to its serial result;
* durable: write and flush under ``store_path=``, reopen in a second
  Session; the partitioned consumer moves no shuffle bytes.

``--chips 4`` runs only the mesh path: lineitem stored on one device is
repartitioned onto a 4-device mesh, twice (the second time from the
sharded layout), and the q17-like result is compared with the one-chip run.

The last line of stdout is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

import lachesis  # noqa: E402
from benchmarks.bench_tpch import q_lineitem_part, q_orders_lineitem  # noqa: E402
from repro.core import enumerate_candidates  # noqa: E402
from repro.data import device_repartition as dr  # noqa: E402
from repro.kernels.hash_partition.ops import (  # noqa: E402
    padded_partition_ids, partition_ids, scatter_permutation)
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

KERNEL_ROWS = 1 << 24
KERNEL_PARTITIONS = (32, 256)
WORKERS = 32
SERVE_REQUESTS = 16
SERVE_THREADS = 8
STORE_DIR = ROOT / ".smoke_store"
PLATFORM = "tpu"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*args) -> None:
    print(*args, flush=True)


def require_tpu():
    """JAX's first device, which must be a TPU: there is no CPU branch."""
    dev = jax.devices()[0]
    if dev.platform != PLATFORM:
        raise SystemExit(
            f"chip_smoke: no TPU found (JAX's first device is "
            f"{dev.platform!r}); run it on a machine with a TPU chip")
    return dev


class CompileMeter:
    """Backend compile seconds (a cache hit counts its retrieval) and the
    persistent cache's hits and misses, from JAX's monitoring events."""

    def __init__(self):
        self.seconds, self.programs, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_kw):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.programs += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self) -> str:
        return (f"compile: {self.programs} programs in "
                f"{self.seconds:.2f} s; persistent cache hits {self.hits}, "
                f"misses {self.misses}")


def log_memory(what: str) -> None:
    st = jax.devices()[0].memory_stats() or {}
    log(f"memory after {what}: bytes_in_use={st.get('bytes_in_use')} "
        f"peak_bytes_in_use={st.get('peak_bytes_in_use')}")


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

def _same_device(a, b) -> bool:
    return a.shape == b.shape and a.dtype == b.dtype and \
        bool(jnp.array_equal(a, b))


def phase_kernels(seed: int) -> None:
    n = KERNEL_ROWS
    keys = jax.random.randint(jax.random.key(seed), (n,), 0, 2 ** 31 - 1,
                              jnp.int32)
    n_valid = jnp.int32(n * 3 // 4 + 1)
    for m in KERNEL_PARTITIONS:
        t0 = time.perf_counter()
        got = partition_ids(keys, m, interpret=False, use_kernel=True)
        want = partition_ids(keys, m, use_kernel=False)
        assert all(map(_same_device, got, want)), f"hash_partition m={m}"
        pgot = padded_partition_ids(keys, n_valid, m, interpret=False,
                                    use_kernel=True)
        pwant = padded_partition_ids(keys, n_valid, m, use_kernel=False)
        assert all(map(_same_device, pgot, pwant)), \
            f"hash_partition_padded m={m}"
        # the plain and the overflow-partition pid sets, as the store's
        # write path and the fused plans feed them
        for pids, counts in (want, pwant):
            dest = scatter_permutation(pids, counts, interpret=False,
                                       use_kernel=True)
            oracle = scatter_permutation(pids, counts, use_kernel=False)
            assert _same_device(dest, oracle), \
                f"scatter_perm m={counts.shape[0]}"
        log(f"kernels: N={n} m={m} hash_partition, hash_partition_padded "
            f"(n_valid={int(n_valid)}), scatter_perm (m={m} and m={m + 1}) "
            f"bit-identical to ref.py ({time.perf_counter() - t0:.2f} s "
            f"with compiles)")


# ---------------------------------------------------------------------------
# TPC-H-shaped tables and the numpy reference
# ---------------------------------------------------------------------------

def make_tables(sf: float, seed: int):
    """lineitem/orders/part at scale factor ``sf`` with dbgen's row counts
    and key rules: sparse orderkeys (8 of every 32), 1-7 lines per order,
    partkeys uniform over 200,000*SF parts.  Keys are int32, which holds
    every orderkey up to SF 300.  Prices follow dbgen's retail-price
    formula, kept to 1/64 so that every sum is exact in float64 and the
    reference is independent of summation order."""
    rng = np.random.default_rng(seed)
    n_orders = int(1_500_000 * sf)
    n_parts = int(200_000 * sf)
    n_cust = int(150_000 * sf)
    i = np.arange(n_orders, dtype=np.int64)
    orderkey = ((i // 8) * 32 + i % 8 + 1).astype(np.int32)
    orders = {"orderkey": orderkey,
              "custkey": rng.integers(1, n_cust + 1, n_orders,
                                      dtype=np.int32),
              "odate": rng.integers(0, 2406, n_orders, dtype=np.int32)}
    lines = np.repeat(orderkey, rng.integers(1, 8, n_orders))
    n_lines = lines.size
    partkey = rng.integers(1, n_parts + 1, n_lines, dtype=np.int32)
    qty = rng.integers(1, 51, n_lines).astype(np.float32)
    pk = np.arange(1, n_parts + 1, dtype=np.int64)
    retail = np.round((90000 + (pk // 10) % 20001 + 100 * (pk % 1000))
                      * 0.64) / 64
    lineitem = {"orderkey": lines, "partkey": partkey, "qty": qty,
                "price": (qty * retail[partkey - 1]).astype(np.float32)}
    part = {"partkey": pk.astype(np.int32),
            "size": rng.integers(1, 51, n_parts, dtype=np.int32)}
    return {"lineitem": lineitem, "orders": orders, "part": part}


def ref_groupby(cols, key, reducer: str):
    """The engine's aggregate semantics: per key, the float64 sum (or mean)
    of every column, cast back to the column's dtype; keys ascending.
    Keys are small non-negative ints, so a dense bincount groups them."""
    cnt = np.bincount(key)
    present = np.flatnonzero(cnt)
    out = {"key": present.astype(key.dtype)}
    for k, v in cols.items():
        acc = np.bincount(key, weights=v.astype(np.float64),
                          minlength=cnt.size)[present]
        if reducer == "mean":
            acc = acc / cnt[present]
        with np.errstate(invalid="ignore"):
            out[k] = acc.astype(v.dtype)
    return out


def ref_lookup(keys, probe):
    """Row of ``keys`` (unique, non-negative) holding each probe value;
    every probe must match, as every TPC-H foreign key does."""
    row = np.full(int(keys.max()) + 1, -1, np.int64)
    row[keys] = np.arange(keys.size)
    idx = row[probe]
    assert (idx >= 0).all(), "a foreign key has no match"
    return idx


def ref_q04(t):
    """lineitem ⋈ orders on orderkey, summed per order date."""
    li, od = t["lineitem"], t["orders"]
    idx = ref_lookup(od["orderkey"], li["orderkey"])
    cols = dict(li, r_orderkey=od["orderkey"][idx],
                custkey=od["custkey"][idx], odate=od["odate"][idx])
    return ref_groupby(cols, cols["odate"], "sum")


def ref_q17(t):
    """lineitem ⋈ part on partkey, averaged per part size."""
    li, pt = t["lineitem"], t["part"]
    idx = ref_lookup(pt["partkey"], li["partkey"])
    cols = dict(li, r_partkey=pt["partkey"][idx], size=pt["size"][idx])
    return ref_groupby(cols, cols["size"], "mean")


def ref_q18(t):
    """lineitem summed per order."""
    li = t["lineitem"]
    return ref_groupby(li, li["orderkey"], "sum")


def ref_part(t, by: str):
    pt = t["part"]
    return ref_groupby(pt, pt[by], "sum")


# ---------------------------------------------------------------------------
# Workloads beyond bench_tpch's q04/q17
# ---------------------------------------------------------------------------

def q_lineitem_by_order():
    """Q18 family: lineitem aggregated per order — no join, so a layout
    partitioned on orderkey leaves it no shuffle at all."""
    wl = lachesis.Workload("q18-like")
    li = wl.scan("lineitem")
    wl.aggregate(li, key=li["orderkey"], reducer="sum")
    return wl


def q_part_by(col: str):
    wl = lachesis.Workload(f"part-by-{col}")
    pt = wl.scan("part")
    wl.aggregate(pt, key=pt[col], reducer="sum")
    return wl


def candidate(wl, dataset: str):
    return enumerate_candidates(wl.graph, dataset)[0]


# ---------------------------------------------------------------------------
# Checks
# ---------------------------------------------------------------------------

def result_table(res):
    """The aggregate's output columns, rows sorted by key."""
    nid = next(i for i, n in res.workload.graph.nodes.items()
               if n.kind == "aggregate")
    cols = res.values[nid].columns
    order = np.argsort(cols["key"], kind="stable")
    return {k: np.ascontiguousarray(v[order]) for k, v in cols.items()}


def assert_same(got, want, what: str) -> None:
    """Same columns, dtypes, shapes and bits."""
    assert sorted(got) == sorted(want), f"{what}: {sorted(got)} vs " \
        f"{sorted(want)}"
    for k in want:
        a, b = np.asarray(got[k]), np.asarray(want[k])
        assert a.dtype == b.dtype and a.shape == b.shape, \
            f"{what}.{k}: {a.dtype}{a.shape} vs {b.dtype}{b.shape}"
        assert np.array_equal(np.ascontiguousarray(a).view(np.uint8),
                              np.ascontiguousarray(b).view(np.uint8)), \
            f"{what}.{k}: values differ"


def assert_on_tpu(ds) -> None:
    for k, v in ds.columns.items():
        assert isinstance(v, jax.Array), f"{ds.name}.{k} is host-resident"
        assert {d.platform for d in v.devices()} == {PLATFORM}, \
            f"{ds.name}.{k} is on {v.devices()}"


def run_checked(sessions, wl, ref, *, shuffles: int, elided: int,
                shuffle_bytes=None):
    """Run ``wl`` on every session; each result must equal the reference
    bit for bit (and so each other), with the expected shuffle counts.
    Returns the result tables."""
    tables = []
    for s in sessions:
        t0 = time.perf_counter()
        res = s.run(wl)
        st = res.stats
        assert (st.shuffles_performed, st.shuffles_elided) == \
            (shuffles, elided), \
            f"{wl.app_id} on {s.backend}: {st.shuffles_performed} " \
            f"shuffles, {st.shuffles_elided} elided"
        if s.backend == "device":
            assert st.device_repartitions == shuffles
        if shuffle_bytes is not None:
            assert st.shuffle_bytes == shuffle_bytes
        table = result_table(res)
        assert_same(table, ref, f"{wl.app_id} on {s.backend}")
        tables.append(table)
        log(f"{wl.app_id} backend={s.backend}: {table['key'].size} groups, "
            f"shuffles={st.shuffles_performed} elided={st.shuffles_elided} "
            f"shuffle_bytes={st.shuffle_bytes}; equal to the numpy "
            f"reference ({time.perf_counter() - t0:.2f} s)")
    return tables


def assert_plan_keys() -> None:
    """Every ShufflePlan used ran the compiled kernels."""
    keys = dr.plan_keys()
    assert keys, "no ShufflePlan was built"
    for key in keys:
        log(f"ShufflePlan {key}")
        mode, interpret, use_kernel = key[-1], key[-3], key[-2]
        assert (mode, interpret, use_kernel) == ("fused", False, True), key


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_shuffle(tables, refs):
    """The device session through the q04/q18/q17 sequence, the host
    session beside it on the partitioned layouts; returns the device
    session and its q18-like result."""
    dev = lachesis.Session(num_workers=WORKERS, backend="device")
    host = lachesis.Session(num_workers=WORKERS, backend="host")
    both = (dev, host)
    t0 = time.perf_counter()
    for name, data in tables.items():
        dev.write(name, data)
        assert_on_tpu(dev.read(name))
    rows = {n: dev.read(n).num_rows for n in tables}
    log(f"device write (round-robin) of {rows} rows "
        f"({time.perf_counter() - t0:.2f} s); every column a jax.Array "
        f"on the TPU")
    log_memory("the round-robin tables landed")

    q04, q17, q18 = q_orders_lineitem(), q_lineitem_part(), \
        q_lineitem_by_order()
    (rr_q04,) = run_checked([dev], q04, refs["q04"], shuffles=3, elided=0)

    parts = {"lineitem": candidate(q04, "lineitem"),
             "orders": candidate(q04, "orders"),
             "part": candidate(q17, "part")}
    t0 = time.perf_counter()
    for s in both:
        for name, cand in parts.items():
            s.write(name, tables[name], cand)
    for name in parts:
        assert_on_tpu(dev.read(name))
    log(f"partitioned writes on both backends "
        f"({time.perf_counter() - t0:.2f} s)")
    log_memory("the partitioned tables landed")
    # the join's inputs are co-partitioned; only the aggregate re-keys
    dev_q04, host_q04 = run_checked(both, q04, refs["q04"], shuffles=1,
                                    elided=2)
    assert_same(rr_q04, host_q04, "q04-like round-robin vs host")
    dev_q18, _ = run_checked(both, q18, refs["q18"], shuffles=0, elided=1,
                             shuffle_bytes=0)

    t0 = time.perf_counter()
    for s in both:
        s.repartition("lineitem", candidate(q17, "lineitem"))
    assert dev.store.write_log[-1]["path"] == "d2d"
    li = dev.read("lineitem")
    assert_on_tpu(li)
    assert_same(li.gather(), host.read("lineitem").gather(),
                "d2d lineitem layout")
    assert np.array_equal(li.counts, host.read("lineitem").counts)
    log(f"d2d repartition of lineitem onto partkey: layout bit-identical "
        f"to the host backend's ({time.perf_counter() - t0:.2f} s)")
    run_checked(both, q17, refs["q17"], shuffles=1, elided=2)
    log_memory("the d2d repartition")
    return dev, dev_q18


def phase_serving(dev, tables) -> None:
    wls = [q_part_by("size"), q_part_by("partkey")]
    serial = [result_table(dev.run(wl)) for wl in wls]
    for wl, table, by in zip(wls, serial, ("size", "partkey")):
        assert_same(table, ref_part(tables, by), f"{wl.app_id} serial")
    front = dev.serve(max_workers=SERVE_THREADS)
    try:
        tickets = [(i % 2, front.submit(wls[i % 2], coalesce=False))
                   for i in range(SERVE_REQUESTS)]
        for i, t in tickets:
            assert_same(result_table(t.result(timeout=900)), serial[i],
                        f"served {wls[i].app_id}")
        st = front.stats()
    finally:
        front.close()
    assert (st["completed"], st["failed"]) == (SERVE_REQUESTS, 0), st
    log(f"serving: {SERVE_REQUESTS} concurrent submits over "
        f"{SERVE_THREADS} workers, each equal to its serial result "
        f"(completed={st['completed']} failed={st['failed']})")


def phase_durable(tables, refs, want_q18) -> None:
    """Write and flush a durable device store, reopen it in a second
    Session; the partitioned consumer must move no shuffle bytes and equal
    the in-memory session's result."""
    q04, q18 = q_orders_lineitem(), q_lineitem_by_order()
    shutil.rmtree(STORE_DIR, ignore_errors=True)
    try:
        t0 = time.perf_counter()
        first = lachesis.Session(num_workers=WORKERS, backend="device",
                                 store_path=str(STORE_DIR))
        for name in ("lineitem", "orders"):
            first.write(name, tables[name], candidate(q04, name))
        first.flush()
        del first
        gc.collect()
        second = lachesis.Session(num_workers=WORKERS, backend="device",
                                  store_path=str(STORE_DIR))
        (got,) = run_checked([second], q18, refs["q18"], shuffles=0,
                             elided=1, shuffle_bytes=0)
        assert_same(got, want_q18, "q18-like after reopen")
        assert_on_tpu(second.read("lineitem"))
        log(f"durable: written, flushed, reopened in a second Session; "
            f"q18-like moved 0 shuffle bytes and equals the in-memory "
            f"session's result ({time.perf_counter() - t0:.2f} s)")
        del second
        gc.collect()
    finally:
        shutil.rmtree(STORE_DIR, ignore_errors=True)


def mesh_layout_check(one, four, mesh) -> None:
    a, b = one.read("lineitem"), four.read("lineitem")
    for k, v in b.columns.items():
        devices = {s.device for s in v.addressable_shards}
        assert devices == set(mesh.devices.flat), f"{k} on {devices}"
    assert_same(b.gather(), a.gather(), "mesh lineitem layout")
    assert np.array_equal(b.counts, a.counts)
    assert {k: np.asarray(v).shape for k, v in b.columns.items()} == \
        {k: np.asarray(v).shape for k, v in a.columns.items()}


def phase_mesh(tables, refs) -> None:
    devices = jax.devices()
    if len(devices) < 4:
        raise SystemExit(f"chip_smoke --chips 4: {len(devices)} device(s)")
    mesh = Mesh(np.array(devices[:4]), ("data",))
    q04, q17 = q_orders_lineitem(), q_lineitem_part()
    one = lachesis.Session(num_workers=WORKERS, backend="device")
    four = lachesis.Session(num_workers=WORKERS, backend="device")
    for s in (one, four):
        s.write("lineitem", tables["lineitem"])
        s.write("part", tables["part"], candidate(q17, "part"))
    assert all(len(v.devices()) == 1
               for v in four.read("lineitem").columns.values())
    for step, cand in (("orderkey", candidate(q04, "lineitem")),
                       ("partkey", candidate(q17, "lineitem"))):
        t0 = time.perf_counter()
        one.repartition("lineitem", cand)
        four.repartition("lineitem", cand, mesh=mesh)
        mesh_layout_check(one, four, mesh)
        log(f"mesh: lineitem repartitioned onto {step} over "
            f"{mesh.devices.size} devices "
            f"({sorted(d.id for d in mesh.devices.flat)}); shards on 4 "
            f"distinct devices, layout bit-identical to the one-chip "
            f"layout ({time.perf_counter() - t0:.2f} s)")
    want, got = run_checked([one, four], q17, refs["q17"], shuffles=1,
                            elided=2)
    assert_same(got, want, "q17-like mesh vs one chip")
    log("mesh: q17-like on the mesh-placed layout equals the one-chip "
        "result")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sf", type=float, default=10.0,
                    help="TPC-H scale factor (default 10: 60M lineitem rows)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh repartition path")
    args = ap.parse_args(argv)
    if not __debug__:
        raise SystemExit("chip_smoke: its checks are asserts; run it "
                         "without python -O")

    dev = require_tpu()
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"ShufflePlan defaults mode={dr.default_mode()} "
        f"use_kernel={dr.default_use_kernel()} "
        f"interpret={dr.default_interpret()}; compile cache {cache_dir}")
    assert (dr.default_mode(), dr.default_use_kernel(),
            dr.default_interpret()) == ("fused", True, False)
    log(f"setup: {time.perf_counter() - T_START:.2f} s")

    t0 = time.perf_counter()
    tables = make_tables(args.sf, args.seed)
    refs = {"q04": ref_q04(tables), "q17": ref_q17(tables),
            "q18": ref_q18(tables)}
    rows = {n: len(next(iter(t.values()))) for n, t in tables.items()}
    log(f"tables at SF {args.sf} (seed {args.seed}): {rows} rows; "
        f"generated with the numpy reference in "
        f"{time.perf_counter() - t0:.2f} s")

    if args.chips == 4:
        phase_mesh(tables, refs)
    else:
        phase_kernels(args.seed)
        dev_sess, q18 = phase_shuffle(tables, refs)
        phase_serving(dev_sess, tables)
        del dev_sess
        gc.collect()
        phase_durable(tables, refs, q18)
        assert_plan_keys()
    log(meter.line())
    log(f"total: {time.perf_counter() - T_START:.2f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
