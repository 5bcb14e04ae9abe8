"""Executor scan, the fetch: seconds per query in the program's
``scan.fetch`` spans (``StoredDataset.gather``: the host mask take over
every stored column, and the columns' copy to the host the first time a
generation is scanned)."""
from harness.readers import span_s_per_unit


def read(run):
    if not run.spans_named("scan.fetch"):
        return None          # a program without the span
    return span_s_per_unit(run, "query.", "scan.fetch")
