"""Shuffle dispatch, the fetch: seconds per query in the program's
``shuffle.fetch`` spans (the rebucket's ``jax.device_get``: the wait for
the device and the copy of its result to the host)."""
from harness.readers import span_s_per_unit


def read(run):
    if not run.spans_named("shuffle.fetch"):
        return None          # a program without the span
    return span_s_per_unit(run, "query.", "shuffle.fetch")
