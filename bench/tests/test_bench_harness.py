"""The harness on the CPU: the manifest keeps the contract's form, every
cell runs end to end at a tiny scale with its chip check skipped, and a
run whose timed path is broken underneath, or computes in bfloat16 (the
control), comes out not correct."""

import json
import os
import re
import shutil
import subprocess
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
import control  # noqa: E402
import run as bench_run  # noqa: E402
from harness import core  # noqa: E402

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in MAN["workloads"]]
CONFIGS = {c["name"]: json.loads((ROOT / c["file"]).read_text())
           for c in MAN["configs"]}
CELL = {c["name"]: c for c in MAN["workloads"]}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def config_of(cell):
    return CONFIGS[CELL[cell]["config"]]


def cells_of(kind, population=None):
    """The cells whose traffic is of ``kind`` (and whose configuration's
    population is ``population``, where one is given)."""
    return [c for c in CELLS
            if core.load_traffic(CELL[c]["traffic"])["kind"] == kind
            and population in (None, config_of(c)["population"])]


# ---------------------------------------------------------------------------
# The manifest
# ---------------------------------------------------------------------------

def test_manifest_names_units_and_text_fields():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    metrics = MAN["end_to_end"] + MAN["per_layer"]
    for entry in MAN["configs"] + MAN["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    for c in MAN["workloads"]:
        assert NAME.match(c["config"]) and NAME.match(c["traffic"])
        assert c["chips"] in (1, 4)
    for c in MAN["configs"]:
        for k in c["reduced"]:
            assert NAME.match(k), k
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for text in ([c["why"] for c in MAN["configs"] + MAN["workloads"]]
                 + [c["source"] for c in MAN["configs"]]
                 + [m["layer"] for m in MAN["per_layer"]] + MAN["command"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    names = [e["name"] for e in metrics]
    assert len(names) == len(set(names)) and len(CELLS) == len(set(CELLS))
    pairs = [(c["config"], c["traffic"]) for c in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))
    for m in MAN["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert len(json.dumps(MAN)) < 64 * 1024


def test_every_traffic_file_has_its_cell():
    used = {c["traffic"] for c in MAN["workloads"]}
    assert {p.stem for p in (BENCH / "traffic").glob("*.json")} == used


def test_manifest_files_live_under_its_paths():
    assert MAN["paths"] == ["bench"]
    assert MAN["command"] == ["python3", "bench/run.py"]
    used = {c["config"] for c in MAN["workloads"]}
    files = [c["file"] for c in MAN["configs"]]
    assert used == {c["name"] for c in MAN["configs"]}
    assert len(files) == len(set(files))
    for c in MAN["configs"]:
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).exists()
        stated = json.loads((ROOT / c["file"]).read_text())
        assert stated["name"] == c["name"]
        assert sorted(stated["reduced"]) == sorted(c["reduced"])
        assert (BENCH / "populations" / f"{stated['population']}.py").exists()
        assert isinstance(stated["cpu_test"], dict)
    for c in MAN["workloads"]:
        assert (BENCH / "traffic" / f"{c['traffic']}.json").exists()
        kind = core.load_traffic(c["traffic"])["kind"]
        assert (BENCH / "kinds" / f"{kind}.py").exists(), kind
    for m in MAN["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists(), m["name"]


def test_every_cell_reports_what_its_metrics_move():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}

    def reports(metric, cell):
        return cell in metric.get("workloads", CELLS)

    for m in MAN["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert reports(e2e[m["moves"]], cell), (m["name"], cell)
    for cell in CELLS:
        mine = [n for n, m in e2e.items() if reports(m, cell)]
        assert "setup_s" in mine and len(mine) >= 2, cell
        assert any(reports(m, cell) for m in MAN["per_layer"]), cell


def test_run_seconds_fits_the_full_check():
    rs = MAN["run_seconds"]
    assert 1 <= rs <= 51
    assert 2 + 14 * 24 * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


# ---------------------------------------------------------------------------
# The chip check
# ---------------------------------------------------------------------------

def _bench_cmd(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def _result_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_run_refuses_a_cpu():
    out = _bench_cmd(ROOT)
    assert out.returncode != 0
    assert "not a TPU" in out.stderr
    assert _result_lines(out.stdout) == []


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _bench_cmd(tmp_path)
    assert out.returncode != 0
    assert _result_lines(out.stdout) == []


# ---------------------------------------------------------------------------
# Every cell, end to end at a tiny scale
# ---------------------------------------------------------------------------

@pytest.fixture
def cpu_run(monkeypatch, capsys):
    """Run a cell through ``run.main`` (or the control's ``main``) on the
    CPU at the tiny size its configuration's ``cpu_test`` states; the
    persistent compile cache is left alone."""
    import jax
    monkeypatch.setattr(core, "enable_compile_cache", lambda: "(off)")

    def go(cell, trace=0, seconds=0.3, seed=4294967311, entry=bench_run):
        rc = entry.main(["--workload", cell, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace)],
                        devices=jax.devices(),
                        config=config_of(cell)["cpu_test"])
        assert rc == 0
        out = capsys.readouterr()
        return json.loads(out.out.strip().splitlines()[-1]), out.err
    return go


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_checks_on_the_cpu(cpu_run, cell):
    res, err = cpu_run(cell)
    assert res["correct"] is True, res
    assert list(res)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(res)
    want = {m["name"] for m in MAN["end_to_end"]
            if cell in m.get("workloads", CELLS)}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert res["attempted"] >= 1 and res["failed"] == 0
    # the compared numbers are the last lines on standard error
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert all(ln.startswith("check ") for ln in tail)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_cell_reports_only_per_layer_metrics(cpu_run, cell):
    res, _ = cpu_run(cell, trace=1)
    assert res["correct"] is True
    layer = {m["name"] for m in MAN["per_layer"]}
    assert set(res["metrics"]) <= layer
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


# ---------------------------------------------------------------------------
# The timed path broken underneath: correct must come out false
# ---------------------------------------------------------------------------

def _altered_aggregate(orig):
    def agg(self, table, params):
        out = orig(self, table, params)
        for k, v in out.columns.items():
            if k != "key" and v.dtype == np.float32 and v.size:
                v = v.copy()
                v[0] += 1
                out.columns[k] = v
                break
        return out
    return agg


def _half_batch_aggregate(orig):
    def agg(self, table, params):
        from repro.core.executor import TableVal
        keep = table.counts // 2
        cols = {k: np.concatenate([table.worker_slice(w)[k][:keep[w]]
                                   for w in range(table.m)])
                for k in table.columns}
        return orig(self, TableVal(cols, keep, table.partitioner), params)
    return agg


@pytest.mark.parametrize("fault", ["altered", "half_batch"])
@pytest.mark.parametrize("cell", cells_of("closed_queries"))
def test_query_faults_come_out_not_correct(cpu_run, monkeypatch, cell,
                                           fault):
    from repro.core.executor import Executor
    orig = Executor._exec_aggregate
    wrap = {"altered": _altered_aggregate,
            "half_batch": _half_batch_aggregate}[fault]
    monkeypatch.setattr(Executor, "_exec_aggregate", wrap(orig))
    res, _ = cpu_run(cell)
    assert res["correct"] is False
    assert res["checks"]["mismatched_values"]["value"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_ingest_faults_come_out_not_correct(cpu_run, monkeypatch, fault):
    from repro.data.partition_store import PartitionStore
    if fault == "unchanged":
        # a repartition that leaves the stored state as it was
        monkeypatch.setattr(PartitionStore, "repartition",
                            lambda self, ds, part, **kw: (ds, 0))
    elif fault == "half_batch":
        orig = PartitionStore.write

        def write(self, name, data, partitioner=None, seed=0):
            n = len(next(iter(data.values()))) // 2
            return orig(self, name, {k: v[:n] for k, v in data.items()},
                        partitioner, seed)
        monkeypatch.setattr(PartitionStore, "write", write)
    else:
        orig = PartitionStore._dispatch_device

        def dispatch(self, data, partitioner, n, seed):
            # TPC-H columns: this fault runs on the tpch cells alone
            data = dict(data)
            q = np.asarray(data["l_quantity" if "l_quantity" in data
                                else "o_totalprice"]).copy()
            q[n // 2] += 1
            data["l_quantity" if "l_quantity" in data
                 else "o_totalprice"] = q
            return orig(self, data, partitioner, n, seed)
        monkeypatch.setattr(PartitionStore, "_dispatch_device", dispatch)
    cells = cells_of("closed_ingest",
                     "tpch" if fault == "altered" else None)
    assert cells
    for cell in cells:
        res, _ = cpu_run(cell)
        assert res["correct"] is False, cell
        bad = {k: v["value"] for k, v in res["checks"].items()
               if k.startswith("mismatched")}
        assert sum(bad.values()) > 0, (cell, bad)


@pytest.mark.parametrize("cell", CELLS)
def test_control_comes_out_not_correct(cpu_run, cell):
    from repro.core.executor import Executor
    from repro.data.partition_store import PartitionStore
    paths = (Executor._exec_aggregate, PartitionStore._dispatch_device)
    res, err = cpu_run(cell, entry=control)
    assert res["correct"] is False
    assert res["checks"]["mismatched_values"]["value"] > 0
    assert "check mismatched_values" in err
    # the program's own paths are back in place
    assert (Executor._exec_aggregate,
            PartitionStore._dispatch_device) == paths
