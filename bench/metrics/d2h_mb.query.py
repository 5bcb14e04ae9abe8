"""Executor scan and shuffle: megabytes copied device-to-host per query,
the ``d2h_bytes`` of the ``scan.fetch`` and ``shuffle.dispatch`` spans
(a stored column counts once, at its first scan)."""
from harness.program_spans import mb_per_unit


def read(run):
    return mb_per_unit(run, "query.", "d2h_bytes", "scan.fetch",
                       "shuffle.dispatch")
