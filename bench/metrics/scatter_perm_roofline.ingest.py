"""Kernel ``scatter_perm``: its share of the bandwidth roofline over the
traced window (``harness/roofline.py`` counts the algorithm's bytes)."""
from harness.readers import roofline_pct


def read(run):
    return roofline_pct(run, "scatter_perm")
