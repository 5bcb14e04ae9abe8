"""The PageRank cell on the CPU: it runs end to end at its tiny size and
reads every per-layer metric the program's spans and counters give; its
readers read their spans and nothing from a program without them; and a
timed path broken underneath, in the aggregate or in the write-back,
comes out not correct."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

# lachesis first: importing repro.data.partition_store before it fails on
# an import cycle in the program
import lachesis  # noqa: E402,F401
import run as bench_run  # noqa: E402
from harness import core  # noqa: E402
from harness import graph_reference as ref  # noqa: E402
from harness.core import Run, Span, Unit  # noqa: E402

CELL = "graph500-s20.pagerank-lachesis"
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = json.loads((BENCH / "configs" / "graph500-s20.json").read_text())


@pytest.fixture
def cpu_run(monkeypatch, capsys):
    """Run the cell through ``run.main`` on the CPU at its ``cpu_test``
    size; the persistent compile cache is left alone."""
    import jax
    monkeypatch.setattr(core, "enable_compile_cache", lambda: "(off)")

    def go(trace=0, seconds=0.3, seed=3000000019):
        rc = bench_run.main(["--workload", CELL, "--seed", str(seed),
                             "--seconds", str(seconds), "--trace",
                             str(trace)], devices=jax.devices(),
                            config=CONFIG["cpu_test"])
        assert rc == 0
        out = capsys.readouterr()
        return json.loads(out.out.strip().splitlines()[-1]), out.err
    return go


def test_cell_reads_every_metric_of_its_program(cpu_run):
    """Every per-layer metric the cell lists comes back from a traced run,
    but those read from the device's trace, which the CPU does not
    have."""
    res, err = cpu_run(trace=1, seconds=1.0)
    assert res["correct"] is True, res
    listed = {m["name"]: m for m in MAN["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert {"writeback_s.pagerank", "shuffle_skew.pagerank",
            "store_held_mb.pagerank"} <= set(listed)
    assert len([n for n in listed if n.endswith(".query")]) == 11
    want = {n for n, m in listed.items() if m["source"] != "device_trace"}
    assert want <= set(res["metrics"]), want - set(res["metrics"])
    assert set(res["metrics"]) <= set(listed)
    assert all(res["metrics"][n]["value"] > 0 for n in want)
    assert res["metrics"]["shuffle_skew.pagerank"]["value"] >= 1.0
    assert res["metrics"]["planning_ms.query"]["value"] > 0
    assert "warm iteration 1: shuffles=2 elided=3" in err
    assert res["checks"]["max_rel_error"]["max"] == 1e-6
    assert res["checks"]["stored_max_rel_error"]["value"] <= 1e-6
    # a window over more than one job restarts from uniform ranks
    assert res["attempted"] > CONFIG["iterations"]


def test_reference_is_the_graphalytics_equation():
    # 0 -> 1, 0 -> 2, 1 -> 2; vertex 2 dangles
    src, dst = np.array([0, 0, 1]), np.array([1, 2, 2])
    (r1,) = ref.pagerank(src, dst, 3, 0.85, 1)
    third = 1.0 / 3
    base = 0.15 / 3 + 0.85 * third / 3
    want = [base, base + 0.85 * third / 2, base + 0.85 * (third / 2 + third)]
    np.testing.assert_allclose(r1, want, rtol=1e-6)
    assert r1.dtype == np.float32


def test_relative_errors_read_a_missing_vertex_as_wrong():
    want = np.array([0.25, 0.5, 0.25])
    vid = np.array([2, 0, 1], np.int32)
    rank = np.array([0.25, 0.25, 0.5], np.float32)
    assert ref.relative_errors(vid, rank, want).max() == 0.0
    assert ref.relative_errors(vid[:2], rank[:2], want).min() == 1.0
    assert ref.relative_errors(np.array([0, 0, 1]), rank, want).min() == 1.0
    rank[0] = np.nan
    assert ref.relative_errors(vid, rank, want)[2] == 1.0


# ---------------------------------------------------------------------------
# Readers, on synthetic runs
# ---------------------------------------------------------------------------

def pagerank_run():
    run = Run(cell={}, config={}, traffic={}, seed=0, seconds=1.0,
              traced=True)
    run.window = (0.0, 10.0)
    run.units = [Unit("query.pagerank", 0.0, 5.0),
                 Unit("query.pagerank", 5.0, 10.0)]
    run.spans = [
        Span("shuffle.dispatch", 1.0, 2.0,
             {"rows": 3000, "m": 4, "max_worker_rows": 900}),
        Span("shuffle.dispatch", 2.0, 2.5,
             {"rows": 1000, "m": 4, "max_worker_rows": 500}),
        Span("store.write_layout", 4.0, 4.5,
             {"rows": 8, "generation": 1, "retired": 1,
              "held_bytes": 2_000_000}),
        Span("store.write_layout", 9.0, 9.25,
             {"rows": 8, "generation": 2, "retired": 2,
              "held_bytes": 3_000_000}),
    ]
    return run


def test_pagerank_readers():
    run = pagerank_run()
    read = bench_run.metric_reader
    assert read("writeback_s.pagerank")(run) == pytest.approx(0.375)
    # rows-weighted max/mean: (4 * 900 + 4 * 500) / 4000
    assert read("shuffle_skew.pagerank")(run) == pytest.approx(1.4)
    assert read("store_held_mb.pagerank")(run) == pytest.approx(3.0)


def test_pagerank_readers_read_nothing_from_an_older_program():
    run = pagerank_run()
    run.spans = [Span("shuffle.dispatch", 1.0, 2.0, {"rows": 3000, "m": 4})]
    for name in ("writeback_s.pagerank", "shuffle_skew.pagerank",
                 "store_held_mb.pagerank"):
        assert bench_run.metric_reader(name)(run) is None, name


# ---------------------------------------------------------------------------
# The timed path broken underneath
# ---------------------------------------------------------------------------

def test_altered_sum_comes_out_not_correct(cpu_run, monkeypatch):
    from repro.core.executor import Executor
    orig = Executor._exec_aggregate

    def agg(self, table, params):
        out = orig(self, table, params)
        v = out.columns["contrib"].copy()
        v[0] *= np.float32(1.001)
        out.columns["contrib"] = v
        return out
    monkeypatch.setattr(Executor, "_exec_aggregate", agg)
    res, _ = cpu_run()
    assert res["correct"] is False
    assert res["checks"]["mismatched_values"]["value"] > 0
    assert res["checks"]["max_rel_error"]["value"] > 1e-6


def test_lost_write_back_comes_out_not_correct(cpu_run, monkeypatch):
    """A write-back that never lands: every iteration starts again from
    the ranks of the one before the loss."""
    from repro.data.partition_store import PartitionStore
    monkeypatch.setattr(PartitionStore, "write_layout",
                        lambda self, name, *a, **kw: self.read(name))
    res, _ = cpu_run()
    assert res["correct"] is False
    assert res["checks"]["mismatched_values"]["value"] > 0


def test_corrupted_write_back_comes_out_not_correct(cpu_run, monkeypatch):
    """Every write-back stores ranks other than the ones computed: the
    ranks read back after the window are compared too."""
    from repro.data.partition_store import PartitionStore
    orig = PartitionStore.write_layout

    def write_layout(self, name, flat, counts, part, **_):
        if name == "ranks":
            flat = dict(flat, rank=flat["rank"] * np.float32(1.001))
        return orig(self, name, flat, counts, part)
    monkeypatch.setattr(PartitionStore, "write_layout", write_layout)
    res, _ = cpu_run()
    assert res["correct"] is False
    assert res["checks"]["stored_max_rel_error"]["value"] > 1e-6
