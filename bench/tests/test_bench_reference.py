"""The table generator and the numpy reference, on the CPU at tiny scale:
the system's host backend agrees with the reference on every query file,
its stored layouts agree with the reference's hash placement, and the
control's bfloat16 rounding changes what the comparison reads."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

from harness import core  # noqa: E402
from harness import reference as ref  # noqa: E402
from harness.generators import schema_of  # noqa: E402
from harness.workloads import Query  # noqa: E402

QUERIES = sorted(p.stem for p in (BENCH / "queries").glob("*.json"))
TPCH = core.load_module("populations", "tpch")
SIZE = {"scale_factor": 0.004, "population_seed": 0}
ALL = ("lineitem", "orders", "part")


@pytest.fixture(scope="module")
def tables():
    return TPCH.make_tables(SIZE, 20171, ALL)


def test_tables_match_the_configuration_files(tables):
    made = schema_of(tables)
    for path in (BENCH / "configs").glob("*.json"):
        stated = json.loads(path.read_text())
        if stated["population"] == "tpch":
            assert stated["tables"] == made, path.name
    li = tables["lineitem"]
    assert li["l_comment"].shape[1] == 44 and li["l_comment"].dtype == np.uint8
    assert len(tables["lineitem"]) == 16 and len(tables["orders"]) == 9 \
        and len(tables["part"]) == 9
    # the same seed gives the same tables
    again = TPCH.make_tables(SIZE, 20171, ALL)
    for t in tables:
        for k in tables[t]:
            assert np.array_equal(tables[t][k], again[t][k])


def test_custkeys_skip_multiples_of_three(tables):
    ck = tables["orders"]["o_custkey"]
    assert (ck % 3 != 0).all() and ck.min() >= 1


def _session(backend):
    import lachesis
    return lachesis.Session(num_workers=8, backend=backend)


@pytest.mark.parametrize("layout", ["lachesis", "roundrobin"])
@pytest.mark.parametrize("name", QUERIES)
def test_host_backend_matches_the_reference(tables, name, layout):
    q = Query(name)
    sess = _session("host")
    for ds in q.datasets:
        part = None
        if layout == "lachesis":       # as traffic/query-lachesis.json
            part = Query("q17" if ds == "part" else "q04").candidate(ds)
        sess.write(ds, tables[ds], part)
    got = ref.sort_by_key(q.result(sess.run(q.workload)))
    want = ref.run_query(q.spec, tables)
    assert want["key"].size > 0
    assert ref.mismatches(got, want) == 0


def _skewed(tables):
    """The tables with l_partkey piled onto a few keys, so that hash
    placement leaves the workers' counts far apart."""
    li = dict(tables["lineitem"])
    u = np.random.default_rng(3).random(li["l_partkey"].size)
    li["l_partkey"] = (1 + (u ** 4) * 400).astype(np.int32)
    return dict(tables, lineitem=li)


@pytest.mark.parametrize("adaptive", [False, True])
@pytest.mark.parametrize("backend", ["host", "device"])
def test_stored_layout_matches_hash_placement(tables, backend, adaptive):
    import lachesis
    from repro.data.partition_store import PartitionStore
    m = 8
    data = _skewed(tables)
    store = PartitionStore(num_workers=m, backend=backend,
                           adaptive_capacity=adaptive)
    sess = lachesis.Session(store=store, backend=backend)
    steps = [("write", "lineitem", "l_orderkey", Query("q04")),
             ("repartition", "lineitem", "l_partkey", Query("q17")),
             ("write", "orders", "o_custkey", Query("q13"))]
    order, bucketed = {}, []
    for op, ds, key, q in steps:
        if op == "write":
            stored = sess.write(ds, data[ds], q.candidate(ds))
            src = np.arange(data[ds][key].size)
        else:
            stored, _ = sess.repartition(ds, q.candidate(ds))
            src = order[ds]
        pids = ref.worker_of(data[ds][key][src], m)
        order[ds] = src[ref.placement_order(pids)]
        counts = np.bincount(pids, minlength=m)
        caps, offs, total = ref.plan_layout(counts, adaptive, 0.75)
        assert np.array_equal(stored.counts, counts), (op, ds)
        got_caps = None if stored.capacity_map is None else \
            stored.capacity_map.capacities
        assert (caps is None) == (got_caps is None)
        if caps is not None:
            assert np.array_equal(caps, got_caps)
        bucketed.append(caps is not None)
        rows, why = ref.stored_rows(
            {k: np.asarray(v) for k, v in stored.columns.items()},
            counts, offs, total, caps is None)
        assert rows is not None, why
        want = {k: v[order[ds]] for k, v in data[ds].items()}
        assert ref.mismatches(rows, want) == 0, (op, ds)
    # with adaptive capacity the skewed partkey layout took power-of-two
    # capacities; without it every layout is uniform
    assert bucketed == [False, adaptive, False]


def test_bf16_control_fails_the_comparison(tables):
    # the control's rounding changes the float columns the cells compare
    for name in ("lineitem", "orders", "part"):
        cols = tables[name]
        low = {k: ref.bf16(v) for k, v in cols.items()}
        assert ref.mismatches(low, cols) > 0, name
    # and keeps what bfloat16 holds exactly
    x = np.array([1.0, 2.5, 3.0, -0.0], np.float32)
    assert ref.mismatches({"x": ref.bf16(x)}, {"x": x}) == 0
