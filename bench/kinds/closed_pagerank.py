"""The ``closed_pagerank`` kind: one client runs an iterative job, LDBC
Graphalytics PageRank over the configuration's ``edges``
(``repro.core.dsl.pagerank_edges``), one iteration per unit.  Each
iteration is one ``Session.run`` that scans ``edges`` and ``ranks`` and
writes ``ranks`` back, so the next one plans against a new generation.
A job is the configuration's ``iterations`` iterations from uniform
ranks; the unit after the last one first writes uniform ranks again.

``edges`` and ``ranks`` are stored under the workflow's own candidate
for each, what Lachesis stores them under.  Every iteration's ranks in
the window, and the ranks stored after its last one, are compared with
``harness/graph_reference.py``'s at the same iteration."""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

from harness import graph_reference as ref
from harness.core import Unit, log
from harness.generators import Generator, ready

#: the largest relative error of a rank, about 16 float32 ulps.  The
#: program's keyed sums are float64 sums of the same float32 terms as the
#: reference's, in another order, so each iteration's sum may differ by
#: an ulp once cast to float32, and damping shrinks what earlier
#: iterations left by 0.85 each time.  bfloat16 arithmetic errs by about
#: 4e-3.
TOLERANCE = 1e-6


class ClosedPageRank(Generator):
    """One client; iteration after iteration of the job, restarted from
    uniform ranks after the last; the window holds whole iterations."""

    def setup(self) -> None:
        from repro.core import enumerate_candidates, pagerank_edges
        self.make_tables(["edges", "ranks"])
        self.n = len(self.tables["ranks"]["vid"])
        self.iterations = int(self.cfg["iterations"])
        self.workflow = pagerank_edges(self.n, float(self.cfg["damping"]))
        g = self.workflow.graph
        (self.write_nid,) = [i for i, nd in g.nodes.items()
                             if nd.kind == "write"]
        sess = self.open_session()
        self.parts = {ds: enumerate_candidates(g, ds)[0]
                      for ds in ("edges", "ranks")}
        t0 = time.perf_counter()
        for ds in ("edges", "ranks"):
            ready(sess.write(ds, self.tables[ds], self.parts[ds]))
        log(f"stored edges and ranks under {self.parts} in "
            f"{time.perf_counter() - t0:.2f} s; |V| = {self.n}, "
            f"{len(self.tables['edges']['src'])} edge rows")
        self.done = 0              # iterations of the current job
        self.stored = None         # the ranks stored after the window

    def _restart(self) -> None:
        ready(self.session.write("ranks", self.tables["ranks"],
                                 self.parts["ranks"]))
        self.done = 0

    def _iterate(self, unit: Unit) -> None:
        if self.done == self.iterations:
            self._restart()
        res = self.session.run(self.workflow)
        ready(self.session.read("ranks"))
        self.done += 1
        new = res.values[self.write_nid].columns
        unit.stats = res.stats
        unit.rows = len(new["vid"])
        unit.extra.update(iteration=self.done, vid=new["vid"],
                          rank=new["rank"])

    def warm(self) -> None:
        """One iteration and a restart compile every program: the
        write-back stores a layout of the same shapes as the restart."""
        t0 = time.perf_counter()
        unit = Unit(name="warm", t0=t0)
        self._iterate(unit)
        st = unit.stats
        log(f"warm iteration {self.done}: "
            f"shuffles={st.shuffles_performed} "
            f"elided={st.shuffles_elided} "
            f"device_repartitions={st.device_repartitions} "
            f"plan_cache_hit={st.plan_cache_hit} "
            f"({time.perf_counter() - t0:.2f} s)")
        t0 = time.perf_counter()
        self._restart()
        log(f"warm restart from uniform ranks "
            f"({time.perf_counter() - t0:.2f} s)")

    def window(self) -> None:
        self.rotations([("query.pagerank", self._iterate)])

    def end_to_end(self) -> Dict[str, float]:
        return {"query_s": self.run.window_s / len(self.run.units)}

    def release(self) -> None:
        """Read the stored ranks back once, outside the window, so that
        the last write-back is checked too."""
        if self.done:
            ds = self.session.read("ranks")
            self.stored = (self.done, ds.gather())
        super().release()

    def check(self) -> None:
        edges = self.tables["edges"]
        want = ref.pagerank(edges["src"], edges["dst"], self.n,
                            float(self.cfg["damping"]), self.iterations)
        stored = 1.0
        if self.stored is not None:
            done, cols = self.stored
            stored = float(ref.relative_errors(
                cols["vid"], cols["rank"], want[done - 1]).max())
        self.run.check("stored_max_rel_error", stored, max=TOLERANCE)
        worst, bad = 0.0, 0
        for u in self.run.units:
            vid, rank = u.extra.pop("vid", None), u.extra.pop("rank", None)
            if u.error is not None:
                continue
            err = ref.relative_errors(vid, rank, want[u.extra["iteration"]
                                                      - 1])
            worst = max(worst, float(err.max()))
            bad += int(np.count_nonzero(err > TOLERANCE))
        self.run.check("max_rel_error", worst, max=TOLERANCE)
        self.run.check("mismatched_values", bad, max=0)
        self.run.check("failed_queries", self.failed(), max=0)
        self.run.check("queries_checked",
                       sum(u.error is None for u in self.run.units), min=1)


GENERATOR = ClosedPageRank
