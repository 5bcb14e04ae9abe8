"""Graphalytics PageRank over an edge list (``dsl.pagerank_edges``) on the
normal ``Session`` path: the Graph500 population's properties, every
iteration's ranks against the plain numpy reference on the host and
device backends, the dangling term, the emitted table's columns, and
the write-back layout's placement and retention."""

import importlib.util
from pathlib import Path

import numpy as np
import pytest

import lachesis
from repro.core import enumerate_candidates, pagerank_edges
from repro.core.reference import pagerank
from repro.data import device_repartition as dr
from repro.data.capacity import CapacityMap
from repro.data.partition_store import PartitionStore
from repro.obs import tracer

ROOT = Path(__file__).resolve().parents[1]
TOL = 1e-6
DAMPING = 0.85
GRAPH500 = {"scale": 8, "edgefactor": 16,
            "initiator": [0.57, 0.19, 0.19, 0.05],
            "population_seed": 20100615}


def _population():
    path = ROOT / "bench" / "populations" / "graph500.py"
    spec = importlib.util.spec_from_file_location("graph500_population",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _graph500(seed=4294967311):
    t = _population().make_tables(GRAPH500, seed, ["edges", "ranks"])
    return t["edges"]["src"], t["edges"]["dst"], len(t["ranks"]["vid"])


def _dangling_graph():
    """A directed graph in which vertices 40..49 have no out-edge and
    every vertex has an in-edge."""
    rng = np.random.default_rng(0)
    n = 50
    src = np.concatenate([rng.integers(0, 40, 300), np.arange(40),
                          np.arange(40, 50) - 40])
    dst = np.concatenate([rng.integers(0, n, 300), (np.arange(40) + 7) % n,
                          np.arange(40, 50)])
    keep = src != dst
    pairs = np.unique(np.stack([src[keep], dst[keep]], 1), axis=0)
    return pairs[:, 0].astype(np.int32), pairs[:, 1].astype(np.int32), n


def _session(backend, src, dst, n, m=4):
    wl = pagerank_edges(n, DAMPING)
    store = PartitionStore(num_workers=m, backend=backend)
    sess = lachesis.Session(store=store, backend=backend)
    sess.write("edges", {"src": src, "dst": dst},
               enumerate_candidates(wl.graph, "edges")[0])
    sess.write("ranks", {"vid": np.arange(n, dtype=np.int32),
                         "rank": np.full(n, 1.0 / n, np.float32),
                         "outdeg": np.bincount(src, minlength=n)
                         .astype(np.int32)},
               enumerate_candidates(wl.graph, "ranks")[0])
    return sess, wl


@pytest.fixture(params=["host", "device", "device-kernel"])
def backend(request, monkeypatch):
    """The host backend; the device backend with the jnp oracle of each
    kernel (the CPU default); and the device backend with the Pallas
    kernels, in interpret mode (the chip's kernel path)."""
    if request.param == "device-kernel":
        monkeypatch.setattr(dr, "default_use_kernel", lambda: True)
        return "device"
    return request.param


def _check_iterations(sess, wl, src, dst, n, iterations=3):
    want = pagerank(src, dst, n, DAMPING, iterations)
    for t in range(iterations):
        res = sess.run(wl)
        assert (res.stats.shuffles_performed, res.stats.shuffles_elided) \
            == (2, 3)
        cols = sess.read("ranks").gather()
        assert cols["rank"].dtype == np.float32
        assert np.array_equal(np.sort(cols["vid"]), np.arange(n))
        got = np.zeros(n, np.float32)
        got[cols["vid"]] = cols["rank"]
        np.testing.assert_array_equal(cols["outdeg"],
                                      np.bincount(src, minlength=n)
                                      [cols["vid"]])
        rel = np.abs(got.astype(np.float64) - want[t]) / want[t]
        assert rel.max() <= TOL, (t, rel.max())


# -- the population -----------------------------------------------------------

def test_graph500_population_is_a_clean_undirected_graph():
    src, dst, n = _graph500()
    pair = src.astype(np.int64) * n + dst
    assert np.unique(pair).size == pair.size                # no duplicates
    assert not np.any(src == dst)                           # no self-loops
    back = dst.astype(np.int64) * n + src
    assert np.array_equal(np.sort(pair), np.sort(back))     # symmetric
    assert np.bincount(src, minlength=n).min() >= 1         # no isolated
    assert src.min() >= 0 and src.max() == n - 1


def test_graph500_population_sizes_and_seeds():
    pop = _population()
    a = pop.make_tables(GRAPH500, 3, ["edges", "ranks"])
    b = pop.make_tables(GRAPH500, 2 ** 31 + 11, ["edges", "ranks"])
    c = pop.make_tables(GRAPH500, 3, ["edges", "ranks"])
    for t in ("edges", "ranks"):
        for k in a[t]:
            assert a[t][k].shape == b[t][k].shape
            assert a[t][k].dtype == b[t][k].dtype
            np.testing.assert_array_equal(a[t][k], c[t][k])
    assert not np.array_equal(a["edges"]["src"], b["edges"]["src"])
    # the degree sequence is the seed's relabelling of one graph
    np.testing.assert_array_equal(np.sort(a["ranks"]["outdeg"]),
                                  np.sort(b["ranks"]["outdeg"]))
    assert pop.make_tables(GRAPH500, 3, ["ranks"]).keys() == {"ranks"}


# -- Session against the reference --------------------------------------------

def test_pagerank_on_graph500_matches_reference(backend):
    src, dst, n = _graph500()
    sess, wl = _session(backend, src, dst, n)
    _check_iterations(sess, wl, src, dst, n)


def test_pagerank_with_dangling_vertices_matches_reference(backend):
    src, dst, n = _dangling_graph()
    outdeg = np.bincount(src, minlength=n)
    assert (outdeg == 0).sum() == 10
    assert np.bincount(dst, minlength=n).min() >= 1
    sess, wl = _session(backend, src, dst, n)
    _check_iterations(sess, wl, src, dst, n)


def test_reference_spreads_the_dangling_mass():
    src, dst, n = _dangling_graph()
    ranks = pagerank(src, dst, n, DAMPING, 5)
    # no mass is lost: the ranks keep summing to one
    for r in ranks:
        assert abs(float(r.astype(np.float64).sum()) - 1.0) < 1e-5


def test_emitted_table_carries_only_key_and_contrib():
    """The keyed aggregate sums every column but the key, so the emitted
    contributions carry nothing else (an int32 column summed in float64
    and cast back would overflow)."""
    src, dst, n = _graph500()
    sess, wl = _session("host", src, dst, n)
    res = sess.run(wl)
    g = wl.graph
    (emit,) = [i for i, nd in g.nodes.items()
               if nd.params.get("tag") == "pr_emit"]
    (agg,) = [i for i, nd in g.nodes.items() if nd.kind == "aggregate"]
    emitted = res.values[emit].columns
    assert set(emitted) == {"key", "contrib"}
    assert emitted["contrib"].dtype == np.float32
    assert len(emitted["key"]) == len(src)
    summed = res.values[agg].columns
    assert set(summed) == {"key", "contrib"}
    assert summed["contrib"].dtype == np.float32


def test_second_scan_of_ranks_is_elided():
    """Alg. 4 matches every scan of a dataset, not only the first."""
    src, dst, n = _graph500()
    sess, wl = _session("host", src, dst, n)
    plan = sess.plan(wl)
    scans = [s for s in plan.steps if s.kind == "scan"
             and s.dataset == "ranks"]
    assert len(scans) == 2
    elided = {s.dataset for s in plan.steps
              if s.kind == "partition" and s.elide}
    assert elided == {"edges", "ranks"}
    assert len(plan.elided) == 3 and len(plan.shuffled) == 2


# -- the write-back -----------------------------------------------------------

@pytest.mark.parametrize("counts", [[3, 0, 5, 2, 9], [3, 0, 5, 2, 22]],
                         ids=["padded", "full_bucket"])
@pytest.mark.parametrize("bucketed", [False, True])
def test_presorted_placement_matches_host(counts, bucketed):
    """Rows segmented per worker placed by the fused place plan ≡ the
    host placement: n inside its shape bucket (padding rows) and n
    filling it (none)."""
    counts = np.array(counts, np.int64)
    n = int(counts.sum())
    assert (dr.shape_bucket(n) == n) == (n == 32)
    rng = np.random.default_rng(2)
    flat = {"a": rng.normal(size=n).astype(np.float32),
            "b": rng.integers(0, 9, (n, 3)).astype(np.int32),
            "c": rng.integers(0, 9, n).astype(np.int64)}
    cmap = CapacityMap.from_counts(counts) if bucketed else None
    cap = int(counts.max())
    got = dr.device_place_presorted(
        flat, counts, capacity=None if bucketed else cap,
        capacity_map=cmap)
    offs = cmap.offsets if bucketed else np.arange(5) * cap
    total = cmap.total_slots if bucketed else 5 * cap
    dest = dr.host_presorted_dest(counts, cap, dest_offsets=offs)
    for k, v in flat.items():
        want = np.zeros((total,) + v.shape[1:], v.dtype)
        want[dest] = v
        if not bucketed:
            want = want.reshape((5, cap) + v.shape[1:])
        assert got[k].shape == want.shape
        np.testing.assert_array_equal(np.asarray(got[k]), want)


def test_write_layout_span_and_retention():
    """Every iteration's write-back spans ``store.write_layout`` with its
    rows, generation and retention, and the device bytes the dataset
    holds stop growing once old generations retire."""
    src, dst, n = _graph500()
    sess, wl = _session("device", src, dst, n)
    tracer.configure(mode="full")
    tracer.clear_spans()
    try:
        for _ in range(5):
            sess.run(wl)
        spans = [s for s in tracer.finished_spans()
                 if s.name == "store.write_layout"]
        dispatch = [s for s in tracer.finished_spans()
                    if s.name == "shuffle.dispatch"]
    finally:
        tracer.configure(mode="off")
    assert len(spans) == 5
    assert [s.args["generation"] for s in spans] == [1, 2, 3, 4, 5]
    assert [s.args["retired"] for s in spans] == [1, 2, 2, 2, 2]
    assert all(s.args["rows"] == n for s in spans)
    held = [s.args["held_bytes"] for s in spans]
    assert held[0] > 0 and held[2] == held[3] == held[4]
    assert held[2] == 3 * sess.read("ranks").device_bytes
    assert len(dispatch) == 10           # two rebuckets per iteration
    for s in dispatch:
        assert s.args["rows"] / s.args["m"] <= s.args["max_worker_rows"] \
            <= s.args["rows"]
