"""Which scans relay their stored columns to the device (DESIGN §5).

The planner binds ``PlanStep.device_relay`` per scan: true only where a
device step reads the relayed columns — a shuffled device partition of
the scanned table, or a write — through the steps that carry them (an
elided partition, an ``apply`` without a function, a write).  A scan
whose table meets a join, aggregate, filter, flatten or a map with a
function first relays nothing: no ``scan.relay`` span, ``relay=False``
on ``exec.scan``, one more ``EngineStats.scan_relays_skipped``.  Every
value stays bit-identical to the host backend's and to a run that relays
every scan."""

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import Session
from repro.core import (Workload, author_integrator, enumerate_candidates,
                        matmul_workload, pagerank_edges, pagerank_iteration)
from repro.core.dsl import reddit_loader
from repro.core import executor as ex
from repro.core import planner as planner_mod
from repro.core.executor import TableVal
from repro.data.partition_store import PartitionStore


@pytest.fixture(autouse=True)
def _tracing():
    obs.enable("full", buffer=65536)
    obs.clear_spans()
    yield
    obs.disable()
    obs.clear_spans()


# -- workloads and their tables -------------------------------------------------

def _select(*columns):
    def project(cols):
        return {k: cols[k] for k in columns}
    return project


_LINEITEM = _select("partkey", "quantity", "price")
_PART = _select("partkey", "size")
_KEEP = _select("partkey", "quantity", "price", "size")


def _tpch_like():
    """A query built as the benchmark builds its TPC-H queries: every
    scan is projected by a map, the two sides are joined, filtered and
    summed per key (q17's shape)."""
    wl = Workload("tpch-like")
    line = wl.map(wl.scan("lineitem"), fn=_LINEITEM, tag="q.lineitem")
    part = wl.map(wl.scan("part"), fn=_PART, tag="q.part")
    j = wl.join(line, part, left_key=line["partkey"],
                right_key=part["partkey"], projection=_KEEP, tag="q.join")
    f = wl.filter(j, j["size"] < 30)
    wl.aggregate(f, key=f["partkey"], reducer="sum")
    rng = np.random.default_rng(3)
    n, p = 1500, 200
    tables = {"lineitem": {"partkey": rng.integers(0, p, n).astype(np.int32),
                           "quantity": rng.integers(1, 50, n)
                           .astype(np.float32),
                           "price": rng.normal(size=n).astype(np.float32)},
              "part": {"partkey": np.arange(p, dtype=np.int32),
                       "size": rng.integers(1, 50, p).astype(np.int32)}}
    return wl, tables


def _pagerank_edges():
    rng = np.random.default_rng(4)
    n, e = 120, 900
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    tables = {"edges": {"src": src, "dst": dst},
              "ranks": {"vid": np.arange(n, dtype=np.int32),
                        "rank": np.full(n, 1.0 / n, np.float32),
                        "outdeg": np.bincount(src, minlength=n)
                        .astype(np.int32)}}
    return pagerank_edges(n), tables


def _reddit():
    rng = np.random.default_rng(5)
    n_sub, n_auth = 1200, 150
    return {"submissions": {"author": rng.integers(0, n_auth, n_sub)
                            .astype(np.int32),
                            "score": rng.normal(size=n_sub)
                            .astype(np.float32)},
            "authors": {"author": np.arange(n_auth, dtype=np.int32),
                        "karma": rng.normal(size=n_auth).astype(np.float32)}}


def _author_integrator():
    return author_integrator(), _reddit()


def _reddit_loader():
    return (reddit_loader("loader", "submissions", "parsed", "json"),
            {"submissions": _reddit()["submissions"]})


def _pagerank_iteration():
    rng = np.random.default_rng(6)
    n = 300
    return pagerank_iteration(), {
        "pages": {"url": np.arange(n, dtype=np.int32),
                  "score": rng.normal(size=n).astype(np.float32)},
        "ranks": {"url": rng.permutation(n).astype(np.int32),
                  "rank": np.full(n, 1.0 / n, np.float32)}}


def _matmul(transpose_left):
    def make():
        rng = np.random.default_rng(7)
        n = 400
        return matmul_workload(transpose_left), {
            "lhs_blocks": {"row_id": rng.integers(0, 20, n).astype(np.int32),
                           "col_id": rng.integers(0, 20, n).astype(np.int32),
                           "out_block_id": rng.integers(0, 30, n)
                           .astype(np.int32),
                           "a": rng.normal(size=n).astype(np.float32)},
            "rhs_blocks": {"row_id": np.arange(20, dtype=np.int32),
                           "b": rng.normal(size=20).astype(np.float32)}}
    return make


def _elided_partition_write():
    """scan → elided partition → write: the write reads the relay."""
    wl = Workload("rekey-write")
    subs = wl.scan("submissions")
    wl.write(wl.partition(subs["author"]), "rekeyed")
    return wl, {"submissions": _reddit()["submissions"]}


def _map_without_fn_write():
    wl = Workload("carry-write")
    wl.write(wl.map(wl.scan("submissions"), fn=None, tag="carry"), "copy")
    return wl, {"submissions": _reddit()["submissions"]}


def _flatten_partition():
    """scan → flatten → device partition: flatten drops the relay."""
    wl = Workload("flatten-shuffle")
    flat = wl.flatten(wl.scan("submissions"))
    wl.aggregate(flat, key=flat["author"], reducer="sum")
    return wl, {"submissions": _reddit()["submissions"]}


CANNED = {"author_integrator": _author_integrator,
          "reddit_loader": _reddit_loader,
          "pagerank_iteration": _pagerank_iteration,
          "pagerank_edges": _pagerank_edges,
          "matmul": _matmul(False),
          "matmul_gram": _matmul(True)}
SHAPES = {"tpch_like": _tpch_like,
          "elided_partition_write": _elided_partition_write,
          "map_without_fn_write": _map_without_fn_write,
          "flatten_partition": _flatten_partition}


def _session(make, layout, backend="device"):
    """A fresh session over a store of ``make``'s tables, each stored
    round-robin or under its first Lachesis candidate."""
    wl, tables = make()
    sess = Session(PartitionStore(num_workers=4, backend=backend),
                   backend=backend)
    for name, cols in tables.items():
        cands = enumerate_candidates(wl.graph, name)
        part = cands[0] if layout == "lachesis" and cands else None
        sess.write(name, cols, part)
    return sess, wl


def _scans(plan):
    return [s for s in plan.steps if s.kind == "scan"]


def _assert_same_values(a, b):
    assert a.keys() == b.keys()
    for nid, x in a.items():
        if not isinstance(x, TableVal):
            continue
        y = b[nid]
        np.testing.assert_array_equal(x.counts, y.counts)
        assert x.columns.keys() == y.columns.keys(), nid
        for k in x.columns:
            assert x.columns[k].dtype == y.columns[k].dtype, (nid, k)
            np.testing.assert_array_equal(x.columns[k], y.columns[k],
                                          err_msg=f"node {nid} col {k}")


def _relay_every_scan(_g, steps):
    """The binding as it was before it was made per scan."""
    for s in steps:
        if s.kind == "scan":
            s.device_relay = True


@pytest.fixture
def relay_reads(monkeypatch):
    """The identities of the ``device_columns`` the device consumers (a
    device rebucket, a layout write) were handed."""
    seen = set()
    rebucket = ex.device_rebucket_full
    write_layout = PartitionStore.write_layout

    def spy_rebucket(*a, device_columns=None, **kw):
        if device_columns is not None:
            seen.add(id(device_columns))
        return rebucket(*a, device_columns=device_columns, **kw)

    def spy_write_layout(self, *a, device_columns=None, **kw):
        if device_columns is not None:
            seen.add(id(device_columns))
        return write_layout(self, *a, device_columns=device_columns, **kw)

    monkeypatch.setattr(ex, "device_rebucket_full", spy_rebucket)
    monkeypatch.setattr(PartitionStore, "write_layout", spy_write_layout)
    return seen


# -- a relay nothing reads is left out -------------------------------------------

@pytest.mark.parametrize("case, layout", [
    ("tpch_like", "roundrobin"), ("tpch_like", "lachesis"),
    # stored round-robin, both scans feed a device shuffle: see the
    # cross-check below
    ("pagerank_edges", "lachesis")])
def test_unread_relays_are_left_out(case, layout):
    make = {**CANNED, **SHAPES}[case]
    sess, wl = _session(make, layout)
    plan = sess.plan(wl)
    assert _scans(plan) and not any(s.device_relay for s in _scans(plan))
    assert " relay" not in sess.explain(wl)
    obs.clear_spans()
    res = sess.run(wl)
    spans = obs.finished_spans()
    assert not [s for s in spans if s.name == "scan.relay"]
    scans = [s for s in spans if s.name == "exec.scan"]
    assert len(scans) == len(_scans(plan))
    assert all(s.args["relay"] is False for s in scans)
    assert res.stats.scan_relays_skipped == len(scans)
    if layout == "roundrobin":          # shuffles that re-upload instead
        assert res.stats.device_repartitions > 0

    host, wl_h = _session(make, layout, backend="host")
    res_h = host.run(wl_h)
    assert res_h.stats.scan_relays_skipped == 0     # nothing to leave out
    _assert_same_values(res_h.values, res.values)


# -- a relay that is read still happens -------------------------------------------

@pytest.mark.parametrize("case", ["elided_partition_write",
                                  "map_without_fn_write"])
def test_write_still_reads_the_relay(case, relay_reads):
    sess, wl = _session(SHAPES[case], "lachesis")
    plan = sess.plan(wl)
    (scan,) = _scans(plan)
    assert scan.device_relay
    assert " relay" in sess.explain(wl)
    obs.clear_spans()
    res = sess.run(wl)
    assert res.stats.shuffles_performed == 0
    assert res.stats.scan_relays_skipped == 0
    (span,) = [s for s in obs.finished_spans() if s.name == "exec.scan"]
    assert span.args["relay"] is True
    assert [s for s in obs.finished_spans() if s.name == "scan.relay"]
    dev = res.values[scan.nid].device_columns
    assert dev and all(isinstance(v, jax.Array) for v in dev.values())
    assert relay_reads == {id(dev)}
    (write,) = [s for s in plan.steps if s.kind == "write"]
    assert res.values[write.nid].device_columns is dev

    src = SHAPES[case]()[1]["submissions"]
    stored = sess.read(write.dataset).gather()
    order = np.lexsort((stored["score"], stored["author"]))
    want = np.lexsort((src["score"], src["author"]))
    for k in src:
        np.testing.assert_array_equal(stored[k][order], src[k][want])


# -- cross-check: the planner's relay set is the set actually read ------------------

@pytest.mark.parametrize("case, layout", [
    (case, layout) for case in sorted(CANNED) + sorted(SHAPES)
    for layout in ("roundrobin", "lachesis")
    # a flatten on the key path leaves no candidate to store under
    if (case, layout) != ("flatten_partition", "lachesis")])
def test_bound_relays_are_the_relays_read(case, layout, monkeypatch,
                                          relay_reads):
    make = {**CANNED, **SHAPES}[case]
    sess, wl = _session(make, layout)
    bound = {s.nid for s in _scans(sess.plan(wl)) if s.device_relay}
    res = sess.run(wl)

    # the same workload with every scan relaying: which relays were read?
    relay_reads.clear()
    monkeypatch.setattr(planner_mod, "_bind_relays", _relay_every_scan)
    forced, wl_f = _session(make, layout)
    scans = _scans(forced.plan(wl_f))
    res_f = forced.run(wl_f)
    read = set()
    for s in scans:
        dev = res_f.values[s.nid].device_columns
        assert dev, f"scan {s.nid} relayed nothing to check"
        if id(dev) in relay_reads:
            read.add(s.nid)
    assert bound == read
    assert res.stats.scan_relays_skipped == len(scans) - len(read)
    assert res_f.stats.scan_relays_skipped == 0
    _assert_same_values(res_f.values, res.values)
