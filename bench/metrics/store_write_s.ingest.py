"""Store: seconds per write op in the program's ``store.write`` spans
(pids, dispatch and the device scatter's enqueue)."""
from harness.readers import span_s_per_unit


def read(run):
    if not run.spans_named("store.write"):
        return None          # a program without the span
    return span_s_per_unit(run, "ingest.write.", "store.write")
