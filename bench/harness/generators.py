"""The general generators, one per kind of traffic file.

A traffic file (``bench/traffic/<name>.json``) names its ``kind`` and
holds the parameters; the class registered here under that kind drives
the system with them:

* ``closed_queries``: one client runs the named queries in rotation over
  layouts stored under the named queries' candidates (or round-robin);
* ``closed_ingest``: one producer repeats a cycle of store writes and
  repartitions.

Each generator sets up (tables from the seed, the stored layouts), warms up
every shape of its own traffic, runs the window and then checks what the
window produced against ``reference.py``.
"""

from __future__ import annotations

import gc
import time
from typing import Dict

import numpy as np

from . import reference as ref
from .core import Run, Unit, log
from .tables import make_tables, schema_of
from .workloads import Query


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _ready(ds) -> None:
    import jax
    jax.block_until_ready([v for v in ds.columns.values()
                           if isinstance(v, jax.Array)])


class Generator:
    """Set-up shared by every kind: the tables and the session."""

    def __init__(self, run: Run):
        self.run = run
        self.cfg = run.config
        self.traffic = run.traffic
        self.session = None

    def make_tables(self, want) -> None:
        cfg = self.cfg
        t0 = time.perf_counter()
        self.tables = make_tables(cfg["scale_factor"], self.run.seed,
                                  want=want,
                                  population_seed=cfg["population_seed"])
        stated = {t: cfg["tables"][t] for t in want}
        made = schema_of(self.tables)
        if made != stated:
            raise SystemExit(f"generated schema {made} is not the "
                             f"configuration's {stated}")
        rows = {t: len(next(iter(c.values())))
                for t, c in self.tables.items()}
        log(f"tables at SF {cfg['scale_factor']} (seed {self.run.seed}): "
            f"{rows} rows in {time.perf_counter() - t0:.2f} s")

    def open_session(self):
        import lachesis
        from repro.data.partition_store import PartitionStore
        st = self.cfg["store"]
        store = PartitionStore(
            num_workers=st["num_workers"], backend=st["backend"],
            adaptive_capacity=st["adaptive_capacity"],
            capacity_threshold=st["capacity_threshold"],
            max_retired_generations=st["max_retired_generations"])
        self.session = lachesis.Session(store=store, backend=st["backend"])
        return self.session

    def layout(self, spec: str, dataset: str):
        """A layout name from a traffic file: ``roundrobin``, or a query
        whose candidate for ``dataset`` Lachesis stores it under."""
        return None if spec == "roundrobin" else Query(spec).candidate(
            dataset)

    def release(self) -> None:
        """Drop the program's state before the reference runs."""
        self.session = None
        gc.collect()

    def failed(self) -> int:
        return sum(u.error is not None for u in self.run.units)

    def _run_unit(self, unit: Unit, fn) -> None:
        try:
            with _annotate(unit.name):
                fn(unit)
        except Exception as e:                      # noqa: BLE001
            unit.error = f"{type(e).__name__}: {e}"
            log(f"{unit.name} failed: {unit.error}")
        unit.t1 = time.perf_counter()

    def rotations(self, steps) -> None:
        """Run whole rotations of ``steps`` (``(name, fn(unit))`` pairs) and
        close the window at the rotation boundary nearest to
        ``--seconds``, taking the next rotation to last as long as the
        last one did.  Every seed then runs whole rotations of the same
        work, and the window's length stays near ``--seconds``."""
        t_end = self.run.window[0] + self.run.seconds
        while True:
            t_rot = time.perf_counter()
            for name, fn in steps:
                unit = Unit(name=name, t0=time.perf_counter())
                self._run_unit(unit, fn)
                self.run.units.append(unit)
            now = time.perf_counter()
            if now + (now - t_rot) / 2 >= t_end:
                return


# ---------------------------------------------------------------------------
# Closed-loop consumer queries
# ---------------------------------------------------------------------------

class ClosedQueries(Generator):
    """One client; the queries in rotation, starting at an offset the
    seed picks; the window holds whole rotations."""

    def setup(self) -> None:
        self.queries = [Query(n) for n in self.traffic["queries"]]
        want = sorted({d for q in self.queries for d in q.datasets})
        self.make_tables(want)
        sess = self.open_session()
        t0 = time.perf_counter()
        for ds in want:
            stored = sess.write(ds, self.tables[ds],
                                self.layout(self.traffic["layout"][ds], ds))
            _ready(stored)
        log(f"stored {want} under {self.traffic['layout']} in "
            f"{time.perf_counter() - t0:.2f} s")

    def warm(self) -> None:
        for q in self.queries:
            t0 = time.perf_counter()
            st = self.session.run(q.workload).stats
            log(f"warm {q.name}: shuffles={st.shuffles_performed} "
                f"elided={st.shuffles_elided} "
                f"device_repartitions={st.device_repartitions} "
                f"({time.perf_counter() - t0:.2f} s)")

    def window(self) -> None:
        start = self.run.seed % len(self.queries)
        order = self.queries[start:] + self.queries[:start]

        def step(q):
            def go(u):
                res = self.session.run(q.workload)
                u.stats = res.stats
                u.extra["result"] = q.result(res)
                u.extra["query"] = q.name
            return f"query.{q.name}", go
        self.rotations([step(q) for q in order])

    def end_to_end(self) -> Dict[str, float]:
        return {"query_s": self.run.window_s / len(self.run.units)}

    def check(self) -> None:
        want = {q.name: ref.run_query(q.spec, self.tables)
                for q in self.queries}
        bad = 0
        for u in self.run.units:
            got = u.extra.pop("result", None)
            if got is not None:
                bad += ref.mismatches(ref.sort_by_key(got),
                                      want[u.extra["query"]])
        self.run.check("mismatched_values", bad, max=0)
        self.run.check("failed_queries", self.failed(), max=0)
        self.run.check("queries_checked",
                       sum(u.error is None for u in self.run.units), min=1)


# ---------------------------------------------------------------------------
# Closed-loop producer: store writes and repartitions
# ---------------------------------------------------------------------------

class ClosedIngest(Generator):
    """One producer repeating a cycle of ops, each blocked until its
    stored columns are ready; the window holds whole cycles."""

    def setup(self) -> None:
        self.ops = self.traffic["ops"]
        want = sorted({op["dataset"] for op in self.ops})
        self.make_tables(want)
        self.open_session()
        self.parts = [self.layout(op["layout"], op["dataset"])
                      for op in self.ops]

    def _expected(self) -> None:
        """The reference's counts, layout and row order after each op of
        the cycle (the cycle repeats on the same data, so op ``j`` always
        leaves the same layout)."""
        st = self.cfg["store"]
        m = st["num_workers"]
        order: Dict[str, np.ndarray] = {}
        self.expect = []
        for op in self.ops:
            ds, key = op["dataset"], self.tables[op["dataset"]][op["key"]]
            if op["op"] == "write":
                src = np.arange(key.size)
            elif op["op"] == "repartition":
                src = order[ds]
            else:
                raise SystemExit(f"unknown ingest op {op['op']!r}")
            pids = ref.worker_of(key[src], m)
            order[ds] = src[ref.placement_order(pids)]
            counts = np.bincount(pids, minlength=m)
            caps, offs, total = ref.plan_layout(
                counts, st["adaptive_capacity"], st["capacity_threshold"])
            self.expect.append({"counts": counts, "caps": caps,
                                "offsets": offs, "total": total,
                                "order": order[ds]})

    def _op(self, j: int, unit: Unit) -> None:
        op, sess = self.ops[j], self.session
        if op["op"] == "write":
            ds = sess.write(op["dataset"], self.tables[op["dataset"]],
                            self.parts[j])
        else:
            ds, _moved = sess.repartition(op["dataset"], self.parts[j])
        _ready(ds)
        unit.rows = int(ds.num_rows)
        cmap = ds.capacity_map
        unit.extra.update(op=j, counts=np.asarray(ds.counts).copy(),
                          caps=None if cmap is None
                          else np.asarray(cmap.capacities).copy())
        self.last[j] = ds

    def warm(self) -> None:
        self.last: Dict[int, object] = {}
        for j, op in enumerate(self.ops):
            t0 = time.perf_counter()
            unit = Unit(name="warm", t0=t0)
            self._op(j, unit)
            log(f"warm {op['op']} {op['dataset']} on {op['key']}: "
                f"{unit.rows} rows, bucketed={unit.extra['caps'] is not None}"
                f" ({time.perf_counter() - t0:.2f} s)")

    def window(self) -> None:
        store = self.session.store
        log_start = store.write_totals["entries"]
        self.rotations([(f"ingest.{op['op']}.{op['dataset']}",
                         lambda u, j=j: self._op(j, u))
                        for j, op in enumerate(self.ops)])
        new = store.write_totals["entries"] - log_start
        self.run.write_log = list(store.write_log[-new:]) if new else []

    def end_to_end(self) -> Dict[str, float]:
        rows = sum(u.rows for u in self.run.units if u.error is None)
        return {"ingest_rows_per_s": rows / self.run.window_s}

    def release(self) -> None:
        # the newest layout each op left, copied to the host first
        self.final = {}
        for j, ds in self.last.items():
            self.final[j] = {k: np.asarray(v) for k, v in ds.columns.items()}
        self.last = {}
        super().release()

    def check(self) -> None:
        self._expected()
        bad_counts = bad_layout = 0
        for u in self.run.units:
            if u.error is not None:
                continue
            e = self.expect[u.extra["op"]]
            bad_counts += int(np.count_nonzero(u.extra["counts"]
                                               != e["counts"]))
            caps = u.extra["caps"]
            if (caps is None) != (e["caps"] is None) or (
                    caps is not None and not np.array_equal(caps, e["caps"])):
                bad_layout += 1
        bad_values = 0
        for j, cols in self.final.items():
            e, ds = self.expect[j], self.ops[j]["dataset"]
            got, why = ref.stored_rows(cols, e["counts"], e["offsets"],
                                       e["total"], e["caps"] is None)
            table = self.tables[ds]
            want = {k: v[e["order"]] for k, v in table.items()}
            if got is None:
                log(f"op {j}: {why}")
                bad_values += sum(v.size for v in want.values())
            else:
                bad_values += ref.mismatches(got, want)
        self.run.check("mismatched_counts", bad_counts, max=0)
        self.run.check("mismatched_capacity_maps", bad_layout, max=0)
        self.run.check("mismatched_values", bad_values, max=0)
        self.run.check("failed_ops", self.failed(), max=0)
        self.run.check("ops_checked",
                       sum(u.error is None for u in self.run.units), min=1)


GENERATORS = {"closed_queries": ClosedQueries, "closed_ingest": ClosedIngest}

