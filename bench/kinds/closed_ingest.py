"""The ``closed_ingest`` kind: one producer repeats the traffic file's
cycle of ``ops``, each a store ``write`` or ``repartition`` of a dataset
on a key, under a layout as ``closed_queries`` names them.  Every op's
counts and capacity map, and the newest layout each op left, are
compared with ``harness/reference.py``'s placement."""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

from harness import reference as ref
from harness.core import Unit, log
from harness.generators import Generator, ready


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
        ready(ds)
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


GENERATOR = ClosedIngest
