"""Executor scan (store to host gather and the device relay): seconds
per query in the program's ``exec.scan`` spans."""
from harness.readers import span_s_per_unit


def read(run):
    return span_s_per_unit(run, "query.", "exec.scan")
