"""Store: seconds per repartition op in the program's
``store.repartition`` spans (flatten, pids, scatter and install)."""
from harness.readers import span_s_per_unit


def read(run):
    if not run.spans_named("store.repartition"):
        return None          # a program without the span
    return span_s_per_unit(run, "ingest.repartition.", "store.repartition")
