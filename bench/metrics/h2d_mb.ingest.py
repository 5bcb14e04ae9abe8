"""Store: megabytes copied host-to-device per op, the ``h2d_bytes`` of
every program span (each byte is counted on the innermost span that
moved it)."""
from harness.program_spans import mb_per_unit


def read(run):
    return mb_per_unit(run, "ingest.", "h2d_bytes")
