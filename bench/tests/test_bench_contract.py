"""A configuration is added as files: in a copy of the benchmark, a toy
configuration with a population, a traffic kind, a per-layer metric and
a kernel roofline reader of its own runs through the copy's ``run.main``
on the CPU, and no file that was there before changes.  A kind or a
population with no file stops the run, naming the file."""

import hashlib
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

from harness import trace as tr  # noqa: E402
from harness.core import Run, Span  # noqa: E402

ROWS = 1000                       # the toy configuration's cpu_test size
TOY_FILES = {
    "configs/toy.json": json.dumps({
        "name": "toy", "source": "a toy deployment of the harness tests",
        "population": "toy", "rows": 1 << 20, "population_seed": 5,
        "cpu_test": {"rows": ROWS}, "reduced": [],
        "store": {"num_workers": 8, "backend": "host",
                  "adaptive_capacity": False, "capacity_threshold": 0.75,
                  "max_retired_generations": 2},
        "tables": {"items": {"k": ["int32", 1], "v": ["float32", 1]}}}),
    "configs/nopop.json": json.dumps({
        "name": "nopop", "source": "a configuration whose population has "
        "no file", "population": "nosuch", "cpu_test": {}, "reduced": []}),
    "populations/toy.py": '''
import numpy as np


def make_tables(config, seed, want):
    rng = np.random.default_rng(seed)
    n = config["rows"]
    items = {"k": rng.permutation(n).astype(np.int32),
             "v": rng.random(n, dtype=np.float32)}
    return {t: {"items": items}[t] for t in want}
''',
    "kinds/toy_write.py": '''
from harness.core import Unit
from harness.generators import Generator, ready


class ToyWrite(Generator):
    """One producer writes the dataset round-robin, again and again."""

    def setup(self):
        self.ds = self.traffic["dataset"]
        self.make_tables([self.ds])
        self.open_session()

    def _write(self, unit):
        stored = self.session.write(self.ds, self.tables[self.ds], None)
        ready(stored)
        unit.rows = int(stored.num_rows)

    def warm(self):
        self._write(Unit("warm", 0.0))

    def window(self):
        self.rotations([("toy.write", self._write)])

    def end_to_end(self):
        rows = sum(u.rows for u in self.run.units if u.error is None)
        return {"toy_rows_per_s": rows / self.run.window_s}

    def check(self):
        n = len(self.tables[self.ds]["k"])
        self.run.check("mismatched_rows", sum(
            u.rows != n for u in self.run.units if u.error is None), max=0)
        self.run.check("failed_writes", self.failed(), max=0)


GENERATOR = ToyWrite
''',
    "traffic/toy-write.json": json.dumps({"kind": "toy_write",
                                          "dataset": "items"}),
    "traffic/nokind.json": json.dumps({"kind": "nosuch"}),
    "metrics/rows_per_op.toy.py": '''
from harness.readers import done


def read(run):
    ops = done(run, "toy.")
    return sum(u.rows for u in ops) / len(ops) if ops else None
''',
    "metrics/toy_kernel_roofline.toy.py": '''
from harness.readers import roofline_pct


def toy_kernel_bytes(args):
    """A key in and a slot out (int32) per row."""
    return 8 * int(args["rows"])


def read(run):
    return roofline_pct(run, ("toy_kernel",), toy_kernel_bytes,
                        "toy.dispatch")
''',
}
ENTRIES = {
    "configs": [
        {"name": "toy", "source": "a toy deployment of the harness tests",
         "file": "bench/configs/toy.json", "reduced": [],
         "why": "proves a configuration is added as files"},
        {"name": "nopop", "source": "a population with no file",
         "file": "bench/configs/nopop.json", "reduced": [],
         "why": "a population with no file"}],
    "workloads": [
        {"name": "toy.write", "config": "toy", "traffic": "toy-write",
         "chips": 1, "why": "round-robin writes of a toy table"},
        {"name": "toy.nokind", "config": "toy", "traffic": "nokind",
         "chips": 1, "why": "a kind with no file"},
        {"name": "nopop.write", "config": "nopop", "traffic": "toy-write",
         "chips": 1, "why": "a population with no file"}],
    "end_to_end": [
        {"name": "toy_rows_per_s", "unit": "rows/s", "better": "higher",
         "bound": 0.05, "source": "host_clock",
         "workloads": ["toy.write"]}],
    "per_layer": [
        {"name": "rows_per_op.toy", "unit": "rows", "better": "higher",
         "source": "program_counter", "layer": "store",
         "moves": "toy_rows_per_s", "workloads": ["toy.write"]},
        {"name": "toy_kernel_roofline.toy", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "kernels",
         "moves": "toy_rows_per_s", "workloads": ["toy.write"]}],
}

# runs the copy's run.main with the chip check skipped and the cell's
# configuration at its cpu_test size
DRIVER = """
import sys
sys.path.insert(0, sys.argv[1])
import lachesis  # noqa: F401  (before repro.data: an import cycle)
import jax
import run
from harness import core
core.enable_compile_cache = lambda: "(off)"
_cell, _entry, stated = core.find_cell(core.manifest(), sys.argv[3])
sys.exit(run.main(sys.argv[2:], devices=jax.devices(),
                  config=stated["cpu_test"]))
"""


def digests(root: Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A copy of the benchmark with the toy's files and entries added,
    and the digests of what was there before."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digests(root)
    del before[Path("BENCHMARK.json")]
    for rel, text in TOY_FILES.items():
        path = root / "bench" / rel
        assert not path.exists(), rel
        path.write_text(text)
    man = json.loads((root / "BENCHMARK.json").read_text())
    for key, entries in ENTRIES.items():
        man[key] += entries
    (root / "BENCHMARK.json").write_text(json.dumps(man, indent=1))
    return root, before


def run_copy(root: Path, cell: str, trace: int = 0):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run(
        [sys.executable, "-c", DRIVER, str(root / "bench"),
         "--workload", cell, "--seed", "4294967311", "--seconds", "0.3",
         "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    return out, json.loads(lines[-1]) if lines else None


def test_toy_configuration_runs_from_its_own_files(copy):
    root, before = copy
    out, res = run_copy(root, "toy.write")
    assert out.returncode == 0, out.stderr[-4000:]
    assert res["correct"] is True, res
    assert set(res["metrics"]) == {"toy_rows_per_s", "setup_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["checks"]["mismatched_rows"]["value"] == 0
    after = digests(root)
    assert {p: after[p] for p in before} == before


def test_toy_configuration_reads_its_own_metric(copy):
    root, _ = copy
    out, res = run_copy(root, "toy.write", trace=1)
    assert out.returncode == 0, out.stderr[-4000:]
    assert res["correct"] is True, res
    assert res["metrics"]["rows_per_op.toy"]["value"] == ROWS
    # the CPU's trace has no device events: the roofline is not read
    assert "toy_kernel_roofline.toy" not in res["metrics"]


def test_toy_kernel_roofline_reader_carries_its_own_count(copy):
    root, _ = copy
    spec = importlib.util.spec_from_file_location(
        "toy_kernel_roofline",
        root / "bench" / "metrics" / "toy_kernel_roofline.toy.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    n = 1 << 20
    bound_s = 8 * n / 819e9               # the toy's bytes at 819 GB/s
    run = Run(cell={}, config={}, traffic={}, seed=0, seconds=1.0,
              traced=True, device_kind="TPU v5 lite")
    run.window = (0.0, 10.0)
    run.spans = [Span("toy.dispatch", 1.0 + i, 1.5 + i, {"rows": n})
                 for i in range(3)]
    dev = "/device:TPU:0"
    # each call takes 2x its bound: 50% of the roofline; the other
    # kernel's and the fusion's events are not the toy's
    events = [tr.DeviceEvent(f"%toy_kernel.{i} = s32[{n}]{{0}} custom-call("
                             f"s32[{n}]{{0}} %p)", 1.1 + i, 2 * bound_s, dev)
              for i in range(3)]
    events += [tr.DeviceEvent("_kernel_padded", 1.2, 1.0, dev),
               tr.DeviceEvent("fusion.1", 1.3, 1.0, dev)]
    run.trace = tr.Trace(events=events, devices=[dev], host_spans=[])
    assert reader.read(run) == pytest.approx(50.0)
    run.spans = run.spans[:2]             # a call without its span
    assert reader.read(run) is None


@pytest.mark.parametrize("cell, missing", [
    ("toy.nokind", "bench/kinds/nosuch.py"),
    ("nopop.write", "bench/populations/nosuch.py")])
def test_a_name_with_no_file_stops_the_run_naming_it(copy, cell, missing):
    root, _ = copy
    out, res = run_copy(root, cell)
    assert out.returncode != 0 and res is None
    assert missing in out.stderr, out.stderr[-2000:]
