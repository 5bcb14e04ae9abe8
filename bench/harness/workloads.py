"""Build the system's workloads from the query files.

A query file (``bench/queries/<name>.json``) is data; this module turns
it into a ``lachesis.Workload`` in the DSL, and ``reference.run_query``
reads the same file to compute the answer.  Each input is scanned and
projected by a map to the columns its TPC-H counterpart reads, so every
key path is ``scan -> apply -> attr -> partition``: a layout stored under
one query's candidate realizes the same key in every other query.
"""

from __future__ import annotations

import json
import operator
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

QUERIES = Path(__file__).resolve().parents[1] / "queries"

_OPS = {"<": operator.lt, "<=": operator.le, ">": operator.gt,
        ">=": operator.ge, "==": operator.eq, "!=": operator.ne}


def load_query(name: str) -> dict:
    with open(QUERIES / f"{name}.json") as f:
        spec = json.load(f)
    if spec.get("name") != name:
        raise ValueError(f"{name}.json names itself {spec.get('name')!r}")
    return spec


def _select(columns: List[str], count: Optional[str]):
    cols_t = tuple(columns)

    def project(cols):
        out = {k: cols[k] for k in cols_t}
        if count:
            out[count] = np.ones(len(cols[cols_t[0]]), np.float32)
        return out
    return project


def _keep(columns: List[str]):
    cols_t = tuple(columns)

    def keep(seg):
        return {k: seg[k] for k in cols_t}
    return keep


class Query:
    """One query file built into a Workload.  The workload object and its
    functions are built once and reused, so every run after the first is
    a plan-cache hit."""

    def __init__(self, name: str):
        import lachesis
        self.name = name
        self.spec = load_query(name)
        spec = self.spec
        wl = lachesis.Workload(name)
        rel = {}
        for alias, inp in spec["inputs"].items():
            scan = wl.scan(inp.get("dataset", alias))
            rel[alias] = wl.map(scan, fn=_select(inp["columns"],
                                                 inp.get("count")),
                                tag=f"{name}.{alias}")
        join = spec.get("join")
        if join:
            left, right = rel[join["left"]], rel[join["right"]]
            cur = wl.join(left, right, left_key=left[join["left_key"]],
                          right_key=right[join["right_key"]],
                          projection=_keep(join["keep"]), tag=f"{name}.join")
        else:
            (cur,) = rel.values()
        for col, op, rhs in spec.get("filters", ()):
            other = cur[rhs["col"]] if isinstance(rhs, dict) else rhs
            cur = wl.filter(cur, _OPS[op](cur[col], other))
        proj = spec.get("project")
        if proj:
            cur = wl.map(cur, fn=_select(proj["columns"], proj.get("count")),
                         tag=f"{name}.project")
        agg = spec["aggregate"]
        out = wl.aggregate(cur, key=cur[agg["key"]],
                           reducer=agg.get("reducer", "sum"))
        self.workload = wl
        self.result_nid = out._nid

    @property
    def datasets(self) -> List[str]:
        return sorted({inp.get("dataset", a)
                       for a, inp in self.spec["inputs"].items()})

    def candidate(self, dataset: str):
        """The partitioner this query's plan would shuffle ``dataset`` on
        first (Lachesis's storage-time choice for it)."""
        from repro.core import enumerate_candidates
        cands = enumerate_candidates(self.workload.graph, dataset)
        if not cands:
            raise ValueError(f"{self.name} has no candidate for {dataset}")
        return cands[0]

    def result(self, run_result) -> Dict[str, np.ndarray]:
        """The aggregate's columns, as the run produced them."""
        return dict(run_result.values[self.result_nid].columns)
