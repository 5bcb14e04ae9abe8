"""Executor host operators: seconds per query in the program's
``exec.join``, ``exec.aggregate`` and ``exec.filter`` spans."""
from harness.readers import span_s_per_unit


def read(run):
    return span_s_per_unit(run, "query.", "exec.join", "exec.aggregate",
                           "exec.filter")
