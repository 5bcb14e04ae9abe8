"""Store, the write-back: seconds per iteration in the program's
``store.write_layout`` spans (the placement of the new ranks in their
stored layout, and the flip to the new generation)."""
from harness.readers import span_s_per_unit


def read(run):
    if not run.spans_named("store.write_layout"):
        return None          # a program without the span
    return span_s_per_unit(run, "query.", "store.write_layout")
