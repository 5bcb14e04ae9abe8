"""The readers of the program's spans and transfer counters, on
synthetic runs: each reads what its spans hold, and reads nothing from a
program that records no such span or argument."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

import run as bench_run  # noqa: E402
from harness import trace as tr  # noqa: E402
from harness.core import Run, Span, Unit  # noqa: E402

DEV = "/device:TPU:0"


def reader(name):
    return bench_run.metric_reader(name)


def ev(start, dur):
    return tr.DeviceEvent(name="fusion", start_s=start, dur_s=dur,
                          device=DEV)


def query_run():
    run = Run(cell={}, config={}, traffic={}, seed=0, seconds=1.0,
              traced=True)
    run.window = (0.0, 10.0)
    run.units = [Unit("query.q04", 0.0, 5.0), Unit("query.q17", 5.0, 10.0),
                 Unit("query.q18", 9.0, 9.5, error="boom")]
    run.spans = [
        Span("exec.scan", 0.0, 1.0, {}),
        Span("scan.fetch", 0.0, 0.75, {"d2h_bytes": 600_000_000}),
        Span("scan.relay", 0.75, 1.0, {"h2d_bytes": 24_000_000,
                                       "columns": 16}),
        Span("shuffle.dispatch", 2.0, 3.0,
             {"rows": 10, "m": 4, "h2d_bytes": 5_000_000,
              "d2h_bytes": 40_000_000}),
        Span("shuffle.fetch", 2.5, 3.0, {}),
        Span("exec.scan", 5.0, 5.5, {}),
        Span("scan.fetch", 5.0, 5.25, {"d2h_bytes": 20_000_000}),
        Span("shuffle.fetch", 6.0, 6.5, {}),
    ]
    return run


def ingest_run():
    run = Run(cell={}, config={}, traffic={}, seed=0, seconds=1.0,
              traced=True)
    run.window = (0.0, 9.0)
    run.units = [Unit("ingest.write.lineitem", 0.0, 3.0),
                 Unit("ingest.repartition.lineitem", 3.0, 6.0),
                 Unit("ingest.write.orders", 6.0, 9.0)]
    run.spans = [
        Span("store.write", 0.0, 2.0, {"rows": 6}),
        Span("store.pids", 0.0, 0.5, {"h2d_bytes": 24_000_000,
                                      "d2h_bytes": 128}),
        Span("shuffle.dispatch", 0.5, 1.5, {"rows": 6, "m": 4,
                                            "h2d_bytes": 600_000_000}),
        Span("store.repartition", 3.0, 5.0, {"path": "d2d"}),
        Span("store.flatten", 3.0, 3.5, {"h2d_bytes": 24_000_000}),
        Span("store.pids", 3.5, 4.0, {"h2d_bytes": 0, "d2h_bytes": 128}),
        Span("store.write", 6.0, 7.0, {"rows": 2}),
        Span("store.pids", 6.0, 6.5, {"h2d_bytes": 12_000_000,
                                      "d2h_bytes": 128}),
    ]
    return run


def test_query_span_readers():
    run = query_run()
    # two completed queries; the failed one does not count
    assert reader("scan_fetch_s.query")(run) == pytest.approx(0.5)
    assert reader("shuffle_wait_s.query")(run) == pytest.approx(0.5)
    # scan.fetch and shuffle.dispatch only: the relay uploads
    assert reader("d2h_mb.query")(run) == pytest.approx(330.0)
    # scans of columns fetched before the window copy nothing
    for s in run.spans_named("scan.fetch"):
        s.args["d2h_bytes"] = 0
    assert reader("d2h_mb.query")(run) == pytest.approx(20.0)


def test_ingest_span_readers():
    run = ingest_run()
    assert reader("store_write_s.ingest")(run) == pytest.approx(1.5)
    assert reader("repartition_s.ingest")(run) == pytest.approx(2.0)
    # every span's h2d_bytes, over the three ops
    assert reader("h2d_mb.ingest")(run) == pytest.approx(220.0)


def test_span_readers_read_nothing_from_an_older_program():
    q, i = query_run(), ingest_run()
    # spans without the new names or byte arguments
    q.spans = [Span("exec.scan", 0.0, 1.0, {}),
               Span("shuffle.dispatch", 2.0, 3.0, {"rows": 10, "m": 4})]
    i.spans = [Span("shuffle.dispatch", 0.5, 1.5, {"rows": 6, "m": 4})]
    for name in ("scan_fetch_s.query", "shuffle_wait_s.query",
                 "d2h_mb.query"):
        assert reader(name)(q) is None, name
    for name in ("store_write_s.ingest", "repartition_s.ingest",
                 "h2d_mb.ingest"):
        assert reader(name)(i) is None, name


@pytest.mark.parametrize("cell", ["query", "ingest"])
def test_idle_unspanned_share(cell):
    run = query_run() if cell == "query" else ingest_run()
    run.window = (0.0, 10.0)
    # device busy [1, 2) and [4, 5): 8 s idle
    events = [ev(1.0, 1.0), ev(4.0, 1.0)]
    # program spans on the host plane cover [0, 3) and [6, 7); the
    # harness's unit annotation over the whole window is no program span
    host = [("bench.clock@0", 0.0, 0.0), ("query.q04", 0.0, 10.0),
            (run.spans[0].name, 0.0, 3.0), (run.spans[1].name, 6.0, 7.0)]
    run.trace = tr.Trace(events=events, devices=[DEV], host_spans=host)
    # idle and unspanned: [3, 4), [5, 6), [7, 10) = 5 s of 8 s idle
    assert reader(f"idle_unspanned.{cell}")(run) == pytest.approx(62.5)
    # an older program's trace holds only the harness's annotations
    run.trace = tr.Trace(events=events, devices=[DEV], host_spans=host[:2])
    assert reader(f"idle_unspanned.{cell}")(run) is None
    # no device plane (the CPU): nothing to read
    run.trace = tr.Trace(events=[], devices=[], host_spans=host)
    assert reader(f"idle_unspanned.{cell}")(run) is None
