"""Device: the share of the window's device-idle time in which no
program span is open, in percent (program spans read from the trace's
host plane, where the tracer mirrors them)."""
from harness.program_spans import idle_unspanned_pct


def read(run):
    return idle_unspanned_pct(run)
