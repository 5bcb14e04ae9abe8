"""The ``closed_queries`` kind: one client runs the traffic file's
``queries`` (``bench/queries/<name>.json``) in rotation over the
datasets stored under its ``layout``, one entry per dataset: a query
whose candidate for the dataset Lachesis stores it under, or
``roundrobin``.  Every answer of the window is compared with
``harness/reference.py``'s."""

from __future__ import annotations

import time
from typing import Dict

from harness import reference as ref
from harness.core import log
from harness.generators import Generator, ready
from harness.workloads import Query


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
            ready(stored)
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


GENERATOR = ClosedQueries
