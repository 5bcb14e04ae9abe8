"""The scan, shuffle and store spans and their transfer counters, on a
CPU Session: a query over a round-robin device store (two scans with the
device relay, two device rebuckets), a hash-keyed write and a
device-to-device repartition.  Every host↔device byte is counted once, in
the args of one span, where the copy is made; a copy not made again (a
stored column already fetched) counts nothing."""

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import Session
from repro.core import author_integrator, enumerate_candidates
from repro.data import device_repartition as dr
from repro.data.partition_store import PartitionStore
from repro.data.transfer import fetch, fetch_tree, upload


@pytest.fixture(params=["fused", "hostperm"])
def mode(request, monkeypatch):
    """Both executions of the ShufflePlans (fused runs the jnp oracle in
    place of the kernels on the CPU)."""
    monkeypatch.setattr(dr, "default_mode", lambda: request.param)
    return request.param


@pytest.fixture(autouse=True)
def _tracing():
    obs.enable("full", buffer=65536)
    obs.clear_spans()
    yield
    obs.disable()
    obs.clear_spans()


def _data(n_sub=3000, n_auth=500):
    rng = np.random.default_rng(0)
    subs = {"author": rng.integers(0, n_auth, n_sub).astype(np.int32),
            "score": rng.normal(size=n_sub).astype(np.float32),
            "wide": rng.integers(0, 9, (n_sub, 3)).astype(np.uint8),
            "big": rng.integers(0, 9, n_sub).astype(np.int64)}
    auths = {"author": np.arange(n_auth, dtype=np.int32),
             "karma": rng.normal(size=n_auth).astype(np.float32)}
    return subs, auths


def _named(name):
    return [s for s in obs.finished_spans() if s.name == name]


def _children(parent, name):
    return [s for s in obs.finished_spans()
            if s.name == name and s.parent_id == parent.span_id]


def _device_bytes(ds):
    return sum(v.nbytes for v in ds.columns.values()
               if isinstance(v, jax.Array))


def test_query_scan_and_shuffle_spans(mode):
    subs, auths = _data()
    store = PartitionStore(num_workers=8, backend="device")
    stored = {"submissions": store.write("submissions", subs),
              "authors": store.write("authors", auths)}
    sess = Session(store, backend="device")
    obs.clear_spans()
    res = sess.run(author_integrator())
    assert res.stats.device_repartitions == 2

    scans = _named("exec.scan")
    assert len(scans) == 2
    for scan in scans:
        ds = stored[scan.args["dataset"]]
        (fsp,) = _children(scan, "scan.fetch")
        (relay,) = _children(scan, "scan.relay")
        # the fetch copies every stored device column, padding included;
        # the int64 column lives on the host and is not copied
        assert fsp.args["d2h_bytes"] == _device_bytes(ds) > 0
        assert relay.args["h2d_bytes"] == 4 * ds.num_rows
        assert relay.args["columns"] == sum(
            isinstance(v, jax.Array) for v in ds.columns.values())
        assert scan.t0 <= fsp.t0 <= fsp.t1 <= relay.t0 <= scan.t1

    disp = [d for d in _named("shuffle.dispatch")
            if d.args["op"] == "rebucket"]
    assert len(disp) == 2
    for d in disp:
        assert {"rows", "m", "h2d_bytes", "d2h_bytes"} <= set(d.args)
        assert d.args["h2d_bytes"] > 0 and d.args["d2h_bytes"] > 0
        (fsp,) = _children(d, "shuffle.fetch")
        # the bytes sit on the dispatch, not on its child
        assert "d2h_bytes" not in fsp.args
        assert d.t0 <= fsp.t0 <= fsp.t1 <= d.t1

    # the stored columns were fetched once: a second query's scans copy
    # nothing, its relays upload their slot index again
    obs.clear_spans()
    sess.run(author_integrator())
    assert [s.args["d2h_bytes"] for s in _named("scan.fetch")] == [0, 0]
    assert sorted(s.args["h2d_bytes"] for s in _named("scan.relay")) == \
        sorted(4 * ds.num_rows for ds in stored.values())


def test_repeat_fetch_of_a_stored_column_counts_nothing():
    subs, _ = _data()
    store = PartitionStore(num_workers=8, backend="device")
    ds = store.write("submissions", subs)
    scans = []
    for i in range(3):
        if i == 2:           # a swapped container is fetched anew
            ds.set_column("score", ds.columns["score"] + 0)
        with obs.span("scan", d2h_bytes=0) as sp:
            flat = ds.gather()
        scans.append((sp.args["d2h_bytes"], flat))
    assert [n for n, _ in scans] == [
        _device_bytes(ds), 0, ds.columns["score"].nbytes]
    assert scans[0][0] > 0
    for _n, flat in scans:
        for k, v in flat.items():
            np.testing.assert_array_equal(v, scans[0][1][k])
    np.testing.assert_array_equal(np.sort(scans[0][1]["score"]),
                                  np.sort(subs["score"]))


def test_transfer_helpers_count_only_crossings():
    host = np.arange(10, dtype=np.int32)
    dev = jax.numpy.arange(6, dtype=jax.numpy.float32)
    with obs.span("outer") as outer:
        with obs.span("inner") as inner:
            up = upload(host)            # counted on the innermost span
            assert upload(up) is up      # already on the device
            np.testing.assert_array_equal(fetch(up), host)
            np.testing.assert_array_equal(fetch(host), host)   # no crossing
            fetch_tree((dev, {"a": dev}, host), outer)
    assert inner.args == {"h2d_bytes": 40, "d2h_bytes": 40}
    assert outer.args == {"d2h_bytes": 48}


def test_write_counts_the_key_upload_once(mode):
    subs, _ = _data()
    wl = author_integrator()
    part = enumerate_candidates(wl.graph, "submissions")[0]
    store = PartitionStore(num_workers=8, backend="device")
    obs.clear_spans()
    store.write("submissions", subs, part)
    (write,) = _named("store.write")
    (pids,) = _children(write, "store.pids")
    (disp,) = _children(write, "shuffle.dispatch")
    n = len(subs["author"])
    if mode == "fused":           # int32 keys up, the histogram back
        assert pids.args == {"h2d_bytes": 4 * n, "d2h_bytes": 4 * 8}
    else:                         # host keys hash on the host
        assert pids.args == {"h2d_bytes": 0, "d2h_bytes": 0}
    assert disp.args["op"] == "scatter" and disp.args["h2d_bytes"] > 0
    if mode == "fused":
        # the packs (f32, s32 and the three uint8 columns; the int64 stays
        # on the host), bucketed to B rows, and the int32 counts and offsets
        B = dr.shape_bucket(n)
        assert disp.args["h2d_bytes"] == B * (4 + 4 + 3) + 2 * 4 * 8
        assert disp.args["d2h_bytes"] == 4 * B    # flat_dest, for "big"
    assert "h2d_bytes" not in write.args
    assert pids.t1 <= disp.t0


def test_device_repartition_spans(mode):
    subs, _ = _data()
    wl = author_integrator()
    part = enumerate_candidates(wl.graph, "submissions")[0]
    store = PartitionStore(num_workers=8, backend="device")
    ds = store.write("submissions", subs)
    obs.clear_spans()
    new, _moved = store.repartition(ds, part, swap=True)
    (rep,) = _named("store.repartition")
    assert rep.args["path"] == "d2d"
    (flat,) = _children(rep, "store.flatten")
    (pids,) = _children(rep, "store.pids")
    (disp,) = _children(rep, "shuffle.dispatch")
    assert flat.args["h2d_bytes"] == 4 * ds.num_rows
    # the keys are projected from device columns: nothing to upload; the
    # histogram (fused) or the bucketed pids (hostperm) come back
    assert pids.args == {"h2d_bytes": 0, "d2h_bytes": 4 * (
        8 if mode == "fused" else dr.shape_bucket(ds.num_rows))}
    assert flat.t1 <= pids.t0 <= pids.t1 <= disp.t0
    assert new.num_rows == ds.num_rows


def test_spans_and_counters_cost_nothing_when_off():
    subs, _ = _data()
    obs.disable()
    wl = author_integrator()
    store = PartitionStore(num_workers=8, backend="device")
    ds = store.write("submissions", subs)
    store.write("authors", _data()[1])
    Session(store, backend="device").run(wl)
    store.repartition(ds, enumerate_candidates(wl.graph, "submissions")[0],
                      swap=True)
    assert obs.finished_spans() == []
