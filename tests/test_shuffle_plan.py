"""Single-pass device shuffle: counting-sort kernels, dispatch-plan cache,
and the device-to-device repartition fast path (DESIGN §5).

No hypothesis dependency — these run even in the bare container.  The
hypothesis property sweeps live in test_shuffle_properties.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Engine, author_integrator, enumerate_candidates
from repro.core.engine import TableVal
from repro.data import device_repartition as dr
from repro.data.partition_store import (PartitionStore, _counting_sort_dest,
                                        _presorted_dest)
from repro.kernels.hash_partition.hash_partition import (hash_partition_padded,
                                                         scatter_perm)
from repro.kernels.hash_partition.ref import (hash_partition_padded_ref,
                                              hash_partition_ref,
                                              scatter_perm_ref)


# -- counting-sort kernels vs oracles ----------------------------------------

@pytest.mark.parametrize("n,m,block", [(100, 8, 64), (1000, 13, 256),
                                       (7, 4, 8), (4096, 32, 1024)])
def test_scatter_perm_matches_oracle(n, m, block):
    keys = jax.random.randint(jax.random.PRNGKey(0), (n,), 0, 2 ** 31 - 1,
                              jnp.int32)
    pids, counts = hash_partition_ref(keys, m)
    got = scatter_perm(pids, counts, block=block, interpret=True)
    want = scatter_perm_ref(pids, counts)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # a valid permutation: every destination slot hit exactly once
    assert np.array_equal(np.sort(np.asarray(got)), np.arange(n))


def test_scatter_perm_is_stable_counting_sort():
    """dest must equal the inverse of the *stable* argsort — equal pids keep
    their input order (the bit-identical guarantee hangs on this)."""
    pids = jnp.asarray(np.array([2, 0, 2, 1, 0, 2, 0], np.int32))
    counts = jnp.asarray(np.bincount(np.asarray(pids), minlength=3)
                         .astype(np.int32))
    dest = np.asarray(scatter_perm(pids, counts, block=8, interpret=True))
    order = np.argsort(np.asarray(pids), kind="stable")
    inv = np.empty_like(order)
    inv[order] = np.arange(len(order))
    np.testing.assert_array_equal(dest, inv)


@pytest.mark.parametrize("n,B,m", [(100, 128, 8), (1000, 1024, 13),
                                   (8, 8, 4), (5000, 8192, 32)])
def test_hash_partition_padded_matches_oracle(n, B, m):
    keys = jax.random.randint(jax.random.PRNGKey(1), (B,), 0, 2 ** 31 - 1,
                              jnp.int32)
    kp, kc = hash_partition_padded(keys, jnp.int32(n), m, block=256,
                                   interpret=True)
    rp, rc = hash_partition_padded_ref(keys, jnp.int32(n), m)
    np.testing.assert_array_equal(np.asarray(kp), np.asarray(rp))
    np.testing.assert_array_equal(np.asarray(kc), np.asarray(rc))
    assert int(kc[m]) == B - n                      # overflow bucket size
    assert int(kc[:m].sum()) == n


# -- host counting-sort placement (vectorized dispatch) ----------------------

def test_counting_sort_dest_matches_worker_loop():
    rng = np.random.default_rng(3)
    m, n = 7, 501
    pids = rng.integers(0, m, n)
    counts = np.bincount(pids, minlength=m)
    cap = int(counts.max())
    dest = _counting_sort_dest(pids, counts, cap)

    v = rng.normal(size=n).astype(np.float32)
    buf = np.zeros(m * cap, np.float32)
    buf[dest] = v
    # reference: per-worker copy loop (the pre-vectorization baseline)
    order = np.argsort(pids, kind="stable")
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
    want = np.zeros((m, cap), np.float32)
    sv = v[order]
    for w in range(m):
        c = counts[w]
        if c:
            want[w, :c] = sv[offsets[w]:offsets[w] + c]
    np.testing.assert_array_equal(buf.reshape(m, cap), want)


def test_presorted_dest_matches_segmented_loop():
    counts = np.array([3, 0, 5, 2], np.int64)
    cap = int(counts.max())
    dest = _presorted_dest(counts, cap)
    n = int(counts.sum())
    v = np.arange(n, dtype=np.int32)
    buf = np.zeros(4 * cap, np.int32)
    buf[dest] = v
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
    want = np.zeros((4, cap), np.int32)
    for w in range(4):
        c = counts[w]
        if c:
            want[w, :c] = v[offsets[w]:offsets[w] + c]
    np.testing.assert_array_equal(buf.reshape(4, cap), want)


# -- dispatch-plan cache: no retrace across repeated same-shape shuffles -----

def test_store_write_same_shape_traces_once():
    """Repeated PartitionStore.write calls of the same shape must trigger
    exactly one trace of the scatter plan (ISSUE 2 acceptance) — including
    writes whose key skew (and therefore capacity = counts.max()) differs,
    since capacity rides the plan as a traced scalar, not a cache key."""
    wl, _ = _reddit_like()
    cand = enumerate_candidates(wl.graph, "submissions")[0]
    dr.clear_plan_cache()
    store = PartitionStore(8, backend="device")
    rng = np.random.default_rng(0)

    def batch(seed):
        r = np.random.default_rng(seed)
        skew = 40 if seed % 2 else 60         # different counts.max() per seed
        return {"author": r.integers(0, skew, 2000).astype(np.int64),
                "score": r.normal(size=2000).astype(np.float32)}

    caps = []
    store.write("a", batch(0), cand)
    caps.append(store.read("a").capacity)
    t1 = dr.plan_cache_stats()["traces"]
    for i in range(4):
        store.write(f"b{i}", batch(i + 1), cand)
        caps.append(store.read(f"b{i}").capacity)
    stats = dr.plan_cache_stats()
    assert len(set(caps)) > 1, "test needs varying capacities to be real"
    # capacities differ but land in one output-row bucket — no retrace
    assert len({dr.shape_bucket(8 * c) for c in caps}) == 1, caps
    assert stats["traces"] == t1, f"retraced: {stats}"
    assert stats["calls"] >= 5


def test_rebucket_shape_bucket_shares_trace():
    """Different Ns inside one power-of-two bucket reuse the same plan and
    trace — the shape-bucket half of the retrace-free guarantee."""
    dr.clear_plan_cache()
    rng = np.random.default_rng(1)
    for n in (900, 1000, 1024):            # all bucket to B=1024
        assert dr.shape_bucket(n) == 1024
        cols = {"v": rng.normal(size=n).astype(np.float32)}
        keys = rng.integers(0, 10_000, n).astype(np.int64)
        got, counts = dr.device_rebucket(cols, keys, 8)
        assert int(counts.sum()) == n
    stats = dr.plan_cache_stats()
    assert stats["plans"] == 1 and stats["traces"] == 1, stats


def test_rebucket_bit_identical_inside_bucket():
    """Padding rows introduced by the shape bucket must never leak into the
    output — n=900 inside a 1024 bucket matches the host path exactly."""
    from repro.core.ir import _mix_hash
    rng = np.random.default_rng(2)
    n, m = 900, 11
    cols = {"v": rng.normal(size=n).astype(np.float32),
            "i": rng.integers(0, 9, n).astype(np.int32),
            "d": rng.normal(size=n)}                     # float64: hybrid
    keys = rng.integers(0, 5_000, n).astype(np.int64)
    got, counts = dr.device_rebucket(cols, keys, m)
    pids = np.asarray(_mix_hash(jnp.asarray(keys))).astype(np.int64) % m
    order = np.argsort(pids, kind="stable")
    np.testing.assert_array_equal(counts, np.bincount(pids, minlength=m))
    for k, v in cols.items():
        assert got[k].dtype == v.dtype
        np.testing.assert_array_equal(got[k], v[order])


@pytest.mark.parametrize("use_kernel", [True, False])
def test_fused_mode_matches_hostperm(use_kernel):
    """The TPU-default fused plan (everything in one jit, kernels in
    interpret mode on CPU) ≡ the CPU-default hostperm plan ≡ the host numpy
    path — the mode switch must never change a bit."""
    from repro.core.ir import _mix_hash
    rng = np.random.default_rng(6)
    n, m = 700, 9
    cols = {"v": rng.normal(size=n).astype(np.float32),
            "d": rng.normal(size=n),                     # float64: hybrid
            "i": rng.integers(0, 7, n).astype(np.int32)}
    keys = rng.integers(0, 3_000, n).astype(np.int64)
    got_f, counts_f = dr.device_rebucket(cols, keys, m, mode="fused",
                                         interpret=True,
                                         use_kernel=use_kernel)
    got_h, counts_h = dr.device_rebucket(cols, keys, m, mode="hostperm")
    pids = np.asarray(_mix_hash(jnp.asarray(keys))).astype(np.int64) % m
    order = np.argsort(pids, kind="stable")
    np.testing.assert_array_equal(counts_f, counts_h)
    np.testing.assert_array_equal(counts_f, np.bincount(pids, minlength=m))
    for k, v in cols.items():
        assert got_f[k].dtype == v.dtype and got_h[k].dtype == v.dtype
        np.testing.assert_array_equal(got_f[k], v[order])
        np.testing.assert_array_equal(got_h[k], v[order])

    # scatter side: same (m, cap, ...) layout from both modes
    pids_d, hist = dr.device_partition_ids(keys, m)
    counts = np.asarray(hist).astype(np.int64)
    sc_f = dr.device_scatter_padded(cols, pids_d, counts, mode="fused",
                                    interpret=True, use_kernel=use_kernel)
    sc_h = dr.device_scatter_padded(cols, pids_d, counts, mode="hostperm")
    for k in cols:
        assert np.asarray(sc_f[k]).dtype == np.asarray(sc_h[k]).dtype
        np.testing.assert_array_equal(np.asarray(sc_f[k]),
                                      np.asarray(sc_h[k]), err_msg=k)


def test_chained_rebucket_relays_fresh_key():
    """Chained device repartitions: the relayed device_columns carry the
    previous shuffle's __key__, which must never shadow the key the next
    node partitions on (regression — the stale device copy used to win)."""
    from repro.core.ir import _mix_hash
    rng = np.random.default_rng(8)
    n, m = 600, 7
    cols = {"v": rng.normal(size=n).astype(np.float32)}
    key1 = rng.integers(0, 500, n).astype(np.int32)
    key2 = rng.integers(0, 500, n).astype(np.int32)

    res1 = dr.device_rebucket_full(cols, key1, m)
    assert res1.device_columns and "__key__" in res1.device_columns
    # second shuffle on a different key, relaying the first one's flats
    key2_shuffled = key2[_stable_order(key1, m)]
    res2 = dr.device_rebucket_full(res1.columns, key2_shuffled, m,
                                   device_columns=res1.device_columns)
    order2 = _stable_order(key2_shuffled, m)
    np.testing.assert_array_equal(res2.columns["__key__"],
                                  key2_shuffled[order2])
    np.testing.assert_array_equal(res2.columns["v"],
                                  res1.columns["v"][order2])


def _stable_order(keys, m):
    from repro.core.ir import _mix_hash
    pids = np.asarray(_mix_hash(jnp.asarray(keys))).astype(np.int64) % m
    return np.argsort(pids, kind="stable")


# -- capacity validation ------------------------------------------------------

def test_hash_pids_jit_buckets_device_keys():
    """Device-resident keys are padded to the shape bucket before the
    elementwise hash jit, so varying N never retraces it (regression)."""
    before = dr._hash_pids_jit._cache_size()
    for n in (900, 950, 1000):                 # same 1024 bucket
        keys = jnp.asarray(np.arange(n, dtype=np.int32))
        pids, counts = dr.shuffle_pids(keys, 8, mode="hostperm")
        assert pids.shape == (n,) and int(counts.sum()) == n
    assert dr._hash_pids_jit._cache_size() <= before + 1


def test_empty_device_write_stays_device_backed():
    """A 0-row write to a device store must still produce a device-backed
    dataset (round-trippable dtypes), so it keeps the d2d path downstream."""
    store = PartitionStore(4, backend="device")
    ds = store.write("e", {"v": np.zeros(0, np.float32),
                           "d": np.zeros(0, np.float64)})
    assert ds.backend == "device"
    assert isinstance(ds.columns["v"], jax.Array)
    assert isinstance(ds.columns["d"], np.ndarray)     # 64-bit stays host
    assert ds.capacity == 1 and ds.num_rows == 0


def test_scatter_padded_small_capacity_raises():
    """ISSUE 2 satellite: explicit capacity < counts.max() used to silently
    clamp/drop rows inside the scatter — now it must raise."""
    rng = np.random.default_rng(4)
    n, m = 300, 4
    data = {"k": rng.integers(0, 50, n).astype(np.int64)}
    pids, hist = dr.device_partition_ids(data["k"], m)
    counts = np.asarray(hist).astype(np.int64)
    with pytest.raises(ValueError, match="capacity"):
        dr.device_scatter_padded(data, pids, counts,
                                 capacity=int(counts.max()) - 1)
    # exact capacity stays legal
    cols = dr.device_scatter_padded(data, pids, counts,
                                    capacity=int(counts.max()))
    assert np.asarray(cols["k"]).shape == (m, int(counts.max()))


# -- device-to-device repartition --------------------------------------------

def _reddit_like(n_sub=3000, n_auth=500, seed=0):
    rng = np.random.default_rng(seed)
    subs = {"author": rng.integers(0, n_auth, n_sub).astype(np.int64),
            "score": rng.normal(size=n_sub).astype(np.float32),
            "ups": rng.integers(0, 1000, n_sub).astype(np.int32)}
    return author_integrator(), {"submissions": subs}


def test_d2d_repartition_matches_host_and_skips_gather(monkeypatch):
    wl, tables = _reddit_like()
    cand = enumerate_candidates(wl.graph, "submissions")[0]
    data = tables["submissions"]

    host = PartitionStore(8)
    dev = PartitionStore(8, backend="device")
    ds_h = host.write("submissions", data)
    ds_d = dev.write("submissions", data)

    # the fast path must never call the host gather
    monkeypatch.setattr(type(ds_d), "gather",
                        _raise_gather(type(ds_d).gather), raising=True)
    new_d, moved_d = dev.repartition(ds_d, cand)
    monkeypatch.undo()
    new_h, moved_h = host.repartition(ds_h, cand)

    assert dev.write_log[-1]["path"] == "d2d"
    assert new_d.backend == "device"
    assert moved_h == moved_d
    np.testing.assert_array_equal(new_h.counts, new_d.counts)
    flat_h, flat_d = new_h.gather(), new_d.gather()
    for k in flat_h:
        assert flat_h[k].dtype == flat_d[k].dtype
        np.testing.assert_array_equal(flat_h[k], flat_d[k])


def _raise_gather(orig):
    def gather(self):
        raise AssertionError("d2d fast path must not host-gather")
    return gather


def test_d2d_repartition_stays_mesh_placed():
    from jax.sharding import Mesh
    from repro.core.sharding_bridge import sharding_for
    wl, tables = _reddit_like(n_sub=400, n_auth=64)
    cand = enumerate_candidates(wl.graph, "submissions")[0]
    dev = PartitionStore(8, backend="device")
    ds = dev.write("submissions", tables["submissions"])
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    new, _ = dev.repartition(ds, cand, mesh=mesh)
    assert isinstance(new.columns["score"], jax.Array)
    assert new.columns["score"].sharding == sharding_for(mesh,
                                                         new.partitioner)
    assert dev.read(new.name) is new        # placement persisted in the store


_MESH_PHASE = """
import sys
sys.path[:0] = [{root!r}, {src!r}]
from repro.data import device_repartition as dr
dr.default_mode = lambda: "fused"
import chip_smoke as cs
tables = cs.make_tables(0.002, 3)
cs.phase_mesh(tables, {{"q17": cs.ref_q17(tables)}})
ds = cs.lachesis.Session(num_workers=8, backend="device")
ds.write("lineitem", tables["lineitem"])
ds.repartition("lineitem", cs.candidate(cs.q_lineitem_part(), "lineitem"),
               mesh=cs.Mesh(cs.np.array(cs.jax.devices()), ("data",)))
col = ds.read("lineitem").columns["qty"]
assert len(col.sharding.device_set) == 4
assert len(dr._one_device(col).sharding.device_set) == 1
print("MESH-OK")
"""


def test_chip_smoke_mesh_phase_on_four_cpu_devices():
    """chip_smoke's --chips 4 phase on four host devices with the fused
    plan: a mesh-placed dataset is repartitioned again (gathered onto one
    device first) and the q17-like result equals the one-device run."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c",
         _MESH_PHASE.format(root=root, src=os.path.join(root, "src"))],
        capture_output=True, text=True, env=env, cwd=root, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "mesh: q17-like on the mesh-placed layout equals the one-chip" \
        in out.stdout
    assert out.stdout.rstrip().endswith("MESH-OK")


def test_flatten_dataset_matches_gather():
    wl, tables = _reddit_like(n_sub=777, n_auth=99, seed=5)
    dev = PartitionStore(6, backend="device")
    ds = dev.write("submissions", tables["submissions"])
    flat_ref = ds.gather()
    flat_dev = dr.flatten_dataset(ds)
    for k in flat_ref:
        np.testing.assert_array_equal(np.asarray(flat_dev[k]), flat_ref[k])
    dev_only = dr.device_flat_columns(ds)
    assert dev_only and all(isinstance(v, jax.Array)
                            for v in dev_only.values())
    for k, v in dev_only.items():
        np.testing.assert_array_equal(np.asarray(v), flat_ref[k])


# -- engine d2d relay ---------------------------------------------------------

def test_engine_device_store_bit_identical_and_relays_device_columns():
    """Device store + device engine ≡ host store + host engine, and the scan
    seeds the partition node with device-resident flats (the d2d relay)."""
    wl, tables = _full_reddit_case()
    host = PartitionStore(8)
    dev = PartitionStore(8, backend="device")
    for name, data in tables.items():
        host.write(name, data)
        dev.write(name, data)
    vals_h, _ = Engine(host, backend="host").run(wl)
    wl2, _ = _full_reddit_case()
    vals_d, stats_d = Engine(dev, backend="device").run(wl2)
    assert stats_d.device_repartitions > 0
    for nid, h in vals_h.items():
        if not isinstance(h, TableVal):
            continue
        d = vals_d[nid]
        np.testing.assert_array_equal(h.counts, d.counts)
        for k in h.columns:
            assert h.columns[k].dtype == d.columns[k].dtype, (nid, k)
            np.testing.assert_array_equal(h.columns[k], d.columns[k],
                                          err_msg=f"node {nid} col {k}")
    # the repartitioned tables carry device flats forward
    relayed = [v for v in vals_d.values()
               if isinstance(v, TableVal) and v.device_columns]
    assert relayed, "no TableVal carried device_columns through the run"
    for tv in relayed:
        for k, v in tv.device_columns.items():
            assert isinstance(v, jax.Array)
            np.testing.assert_array_equal(np.asarray(v), tv.columns[k])


def _full_reddit_case(n_sub=2500, n_auth=400, seed=0):
    rng = np.random.default_rng(seed)
    subs = {"author": rng.integers(0, n_auth, n_sub).astype(np.int64),
            "score": rng.normal(size=n_sub).astype(np.float32),
            "ups": rng.integers(0, 1000, n_sub).astype(np.int32)}
    auths = {"author": np.arange(n_auth, dtype=np.int64),
             "karma": rng.normal(size=n_auth).astype(np.float32)}
    return author_integrator(), {"submissions": subs, "authors": auths}
