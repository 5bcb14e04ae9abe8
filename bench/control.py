"""The control of a cell's comparison: the program with its float
columns computed in bfloat16, the nearest precision below the float32
the configuration states, driven through the cell's own timed path.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s> \
        --trace 0

runs ``run.py``'s ``main`` with the same arguments after two changes to
the program: the executor's aggregate rounds its float inputs and results
to bfloat16 (what the query cells compare), and the store rounds every
float column it dispatches to bfloat16 (what the ingest cell compares).
Its result line has to read ``"correct": false``, or the comparison could
not tell the two precisions apart.  The benchmark's own runs do not run
this.
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

import run as bench_run  # noqa: E402
from harness import reference as ref  # noqa: E402


def _bf16(cols):
    return {k: ref.bf16(np.asarray(v)) for k, v in cols.items()}


def install():
    """Put the bfloat16 paths in the program's place; returns the undo."""
    from repro.core.executor import Executor, TableVal
    from repro.data.partition_store import PartitionStore
    aggregate, dispatch = (Executor._exec_aggregate,
                           PartitionStore._dispatch_device)

    def low_aggregate(self, table, params):
        out = aggregate(self, TableVal(_bf16(table.columns), table.counts,
                                       table.partitioner), params)
        out.columns.update(_bf16(out.columns))
        return out

    def low_dispatch(self, data, partitioner, n, seed):
        return dispatch(self, _bf16(data), partitioner, n, seed)

    Executor._exec_aggregate = low_aggregate
    PartitionStore._dispatch_device = low_dispatch

    def undo():
        Executor._exec_aggregate = aggregate
        PartitionStore._dispatch_device = dispatch
    return undo


def main(argv=None, **kw) -> int:
    """``run.main`` with the control in place; ``kw`` as ``run.main``'s."""
    undo = install()
    try:
        return bench_run.main(argv, **kw)
    finally:
        undo()


if __name__ == "__main__":
    sys.exit(main())
