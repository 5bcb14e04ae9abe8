"""Device-resident repartition path (DESIGN §5).

A ``Session`` with ``backend="device"`` must execute the TPC-H, Reddit,
and PageRank example workloads through the device ShufflePlans (the jnp
oracle of each kernel on CPU) **bit-identically** to the host numpy path — same values, dtypes,
and per-worker counts at every set-valued node.  No hypothesis dependency:
these run even in the bare container.
"""

import numpy as np
import pytest

from repro.api import Session
from repro.core import (Workload, author_integrator, enumerate_candidates,
                        pagerank_iteration)
from repro.core.executor import TableVal
from repro.data.device_repartition import (as_kernel_keys, device_rebucket,
                                           device_scatter_padded,
                                           device_partition_ids)
from repro.data.partition_store import PartitionStore


# -- workload builders (mirror the benchmark data, CPU-sized) -----------------

def _reddit_case(n_sub=4000, n_auth=800, seed=0):
    rng = np.random.default_rng(seed)
    subs = {"author": rng.integers(0, n_auth, n_sub).astype(np.int64),
            "score": rng.normal(size=n_sub).astype(np.float32),
            "ups": rng.integers(0, 1000, n_sub).astype(np.int32)}
    auths = {"author": np.arange(n_auth, dtype=np.int64),
             "karma": rng.normal(size=n_auth).astype(np.float32)}
    return author_integrator(), {"submissions": subs, "authors": auths}


def _pagerank_case(n=1500, fanout=4, seed=1):
    rng = np.random.default_rng(seed)
    pages = {"url": np.arange(n, dtype=np.int64),
             "neighbors": rng.integers(0, n, (n, fanout)).astype(np.int64)}
    ranks = {"url": np.arange(n, dtype=np.int64),
             "rank": np.full(n, 1.0 / n, np.float64)}
    wl = pagerank_iteration()

    def emit(cols):
        contrib = np.repeat((cols["rank"] / fanout)[:, None], fanout, 1)
        return {"url": cols["neighbors"], "contrib": contrib}
    for node in wl.graph.nodes.values():
        if node.params.get("tag") == "emit_contribs":
            node.params["fn"] = emit
    return wl, {"pages": pages, "ranks": ranks}


def _tpch_case(seed=2):
    rng = np.random.default_rng(seed)
    n_orders, n_lines = 3000, 12_000
    orders = {"orderkey": np.arange(n_orders, dtype=np.int64),
              "odate": rng.integers(0, 2556, n_orders).astype(np.int32)}
    lineitem = {"orderkey": rng.integers(0, n_orders, n_lines),
                "qty": rng.integers(1, 50, n_lines).astype(np.float32)}
    wl = Workload("q04-like")
    li = wl.scan("lineitem")
    od = wl.scan("orders")
    j = wl.join(li, od, left_key=li["orderkey"], right_key=od["orderkey"],
                tag="li_orders")
    agg = wl.aggregate(j, key=j["odate"], reducer="sum")
    wl.write(agg, "q04_out")
    return wl, {"lineitem": lineitem, "orders": orders}


CASES = {"reddit": _reddit_case, "pagerank": _pagerank_case,
         "tpch": _tpch_case}


def _run(wl, tables, backend, workers=8):
    store = PartitionStore(workers)
    for name, data in tables.items():
        store.write(name, data)           # rr ⇒ every repartition is real
    return Session(store, backend=backend).run(wl)


@pytest.mark.parametrize("case", sorted(CASES))
def test_device_backend_bit_identical(case):
    wl, tables = CASES[case]()
    vals_h, stats_h = _run(wl, tables, "host")
    wl2, tables2 = CASES[case]()
    vals_d, stats_d = _run(wl2, tables2, "device")

    assert stats_d.device_repartitions == stats_d.shuffles_performed > 0
    assert stats_h.device_repartitions == 0
    assert stats_h.shuffles_performed == stats_d.shuffles_performed
    assert stats_h.shuffle_bytes == stats_d.shuffle_bytes

    for nid, h in vals_h.items():
        if not isinstance(h, TableVal):
            continue
        d = vals_d[nid]
        np.testing.assert_array_equal(h.counts, d.counts)
        assert set(h.columns) == set(d.columns)
        for k in h.columns:
            assert h.columns[k].dtype == d.columns[k].dtype, (nid, k)
            np.testing.assert_array_equal(h.columns[k], d.columns[k],
                                          err_msg=f"node {nid} col {k}")


def test_store_roundtrip_device_repartition():
    """Round-trip a stored dataset through device repartition and compare
    exactly against the host numpy path (ISSUE satellite)."""
    wl, tables = _reddit_case()
    cand = enumerate_candidates(wl.graph, "submissions")[0]
    data = tables["submissions"]

    host = PartitionStore(8)
    dev = PartitionStore(8, backend="device")
    ds_h = host.write("submissions", data)            # round-robin first
    ds_d = dev.write("submissions", data)
    new_h, moved_h = host.repartition(ds_h, cand)
    new_d, moved_d = dev.repartition(ds_d, cand)

    assert new_d.backend == "device" and new_h.backend == "host"
    assert moved_h == moved_d
    np.testing.assert_array_equal(new_h.counts, new_d.counts)
    flat_h, flat_d = new_h.gather(), new_d.gather()
    for k in flat_h:
        assert flat_h[k].dtype == flat_d[k].dtype
        np.testing.assert_array_equal(flat_h[k], flat_d[k])
    # to_host flattens the residency split but keeps the layout
    back = new_d.to_host()
    assert back.backend == "host"
    np.testing.assert_array_equal(np.asarray(new_d.columns["score"]),
                                  back.columns["score"])


def test_device_rebucket_matches_host_rebucket():
    rng = np.random.default_rng(7)
    n, m = 3001, 13
    cols = {"k": rng.integers(0, 10_000, n).astype(np.int64),
            "v32": rng.normal(size=n).astype(np.float32),
            "v64": rng.normal(size=n),                  # stays host-side
            "mat": rng.normal(size=(n, 3)).astype(np.float32)}
    keys = cols["k"]

    from repro.core.ir import _mix_hash
    pids = np.asarray(_mix_hash(keys)).astype(np.int64) % m
    order = np.argsort(pids, kind="stable")
    want_counts = np.bincount(pids, minlength=m).astype(np.int64)

    got, counts = device_rebucket(cols, keys, m)
    np.testing.assert_array_equal(counts, want_counts)
    for k, v in cols.items():
        assert got[k].dtype == v.dtype
        np.testing.assert_array_equal(got[k], v[order])
    np.testing.assert_array_equal(got["__key__"], keys[order])


def test_device_write_layout_matches_host():
    rng = np.random.default_rng(5)
    counts = np.array([3, 0, 5, 2], np.int64)
    n = int(counts.sum())
    flat = {"a": rng.normal(size=n).astype(np.float32),
            "b": rng.integers(0, 9, n).astype(np.int64)}
    ds_h = PartitionStore(4).write_layout("d", flat, counts, None)
    ds_d = PartitionStore(4, backend="device").write_layout(
        "d", flat, counts, None)
    np.testing.assert_array_equal(ds_h.counts, ds_d.counts)
    for k in ds_h.columns:
        np.testing.assert_array_equal(ds_h.columns[k],
                                      np.asarray(ds_d.columns[k]))


def test_device_write_empty_dataset():
    """0-row hash writes must not crash the kernel path (zero-size grid)."""
    wl, _ = _reddit_case()
    cand = enumerate_candidates(wl.graph, "submissions")[0]
    empty = {"author": np.zeros(0, np.int64),
             "score": np.zeros(0, np.float32)}
    ds_h = PartitionStore(8).write("submissions", empty, cand)
    ds_d = PartitionStore(8, backend="device").write("submissions", empty,
                                                    cand)
    np.testing.assert_array_equal(ds_h.counts, ds_d.counts)
    assert ds_d.num_rows == 0 and ds_d.capacity == ds_h.capacity


def test_device_put_dataset_places_worker_axis():
    """sharding_bridge.device_put_dataset commits round-trippable columns to
    the mesh with the worker axis sharded; 64-bit columns stay host-side."""
    import jax
    from jax.sharding import Mesh
    from repro.core.sharding_bridge import device_put_dataset, sharding_for

    wl, tables = _reddit_case(n_sub=500, n_auth=100)
    cand = enumerate_candidates(wl.graph, "submissions")[0]
    ds = PartitionStore(8, backend="device").write(
        "submissions", tables["submissions"], cand)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    placed = device_put_dataset(mesh, ds)

    assert isinstance(placed.columns["score"], jax.Array)
    assert placed.columns["score"].sharding == sharding_for(mesh,
                                                            ds.partitioner)
    assert isinstance(placed.columns["author"], np.ndarray)  # int64, x64 off
    np.testing.assert_array_equal(np.asarray(placed.columns["score"]),
                                  np.asarray(ds.columns["score"]))
    # divisibility check fires before any placement, so a stub mesh works
    class TwoWideMesh:
        shape = {"data": 2}
    bad = PartitionStore(3).write("s", tables["authors"])   # m=3, extent=2
    with pytest.raises(ValueError, match="not divisible"):
        device_put_dataset(TwoWideMesh(), bad)


def test_device_rebucket_empty():
    got, counts = device_rebucket({"v": np.zeros(0, np.float32)},
                                  np.zeros(0, np.int64), 4)
    assert counts.tolist() == [0, 0, 0, 0]
    assert got["v"].size == 0 and "__key__" in got


def test_scatter_padded_matches_host_layout():
    rng = np.random.default_rng(11)
    n, m = 700, 6
    data = {"k": rng.integers(0, 50, n).astype(np.int64),
            "v": rng.normal(size=n).astype(np.float32)}
    pids, hist = device_partition_ids(data["k"], m)
    counts = np.asarray(hist).astype(np.int64)
    cols = device_scatter_padded(data, pids, counts)

    # reference: the host store write loop
    pids_np = np.asarray(pids).astype(np.int64)
    order = np.argsort(pids_np, kind="stable")
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
    cap = int(counts.max())
    for k, v in data.items():
        buf = np.zeros((m, cap) + v.shape[1:], v.dtype)
        sv = v[order]
        for w in range(m):
            c = counts[w]
            if c:
                buf[w, :c] = sv[offsets[w]:offsets[w] + c]
        got = np.asarray(cols[k])
        assert got.dtype == v.dtype
        np.testing.assert_array_equal(got, buf)


def test_kernel_key_normalization_matches_mix_hash():
    """as_kernel_keys must reproduce _mix_hash's dtype canonicalization for
    every key dtype the workloads use."""
    import jax.numpy as jnp
    from repro.core.ir import _mix_hash
    rng = np.random.default_rng(13)
    cases = [rng.integers(0, 2 ** 31 - 1, 257).astype(np.int64),
             rng.integers(0, 1000, 257).astype(np.int32),
             rng.normal(size=257).astype(np.float32),
             rng.normal(size=257),                       # float64
             rng.integers(0, 2, 257).astype(bool)]
    for keys in cases:
        pids, _ = device_partition_ids(keys, 16)
        want = np.asarray(_mix_hash(jnp.asarray(keys))).astype(np.int64) % 16
        np.testing.assert_array_equal(np.asarray(pids), want,
                                      err_msg=str(keys.dtype))
