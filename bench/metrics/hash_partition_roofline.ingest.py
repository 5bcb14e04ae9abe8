"""Kernel ``hash_partition``: its share of the bandwidth roofline over the
traced window, one call per ``shuffle.dispatch`` span, whose bytes
``roofline.hash_partition_bytes`` counts."""
from harness import roofline
from harness.readers import roofline_pct
from harness.trace import KERNELS


def read(run):
    return roofline_pct(run, KERNELS["hash_partition"],
                        roofline.hash_partition_bytes, "shuffle.dispatch")
