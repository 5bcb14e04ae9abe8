"""Kernel ``scatter_perm``: its share of the bandwidth roofline over the
traced window, one call per ``shuffle.dispatch`` span, whose bytes
``roofline.scatter_perm_bytes`` counts."""
from harness import roofline
from harness.readers import roofline_pct
from harness.trace import KERNELS


def read(run):
    return roofline_pct(run, KERNELS["scatter_perm"],
                        roofline.scatter_perm_bytes, "shuffle.dispatch")
