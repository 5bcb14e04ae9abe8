"""Chip peaks and the bytes each dispatch kernel's algorithm must move.

A kernel's roofline share is the least time the chip could take for the
work the algorithm needs (its bytes over the peak bandwidth: these
kernels do a few integer operations per key, far under any compute
peak) divided by the device time its events took.  The bytes count what
the algorithm must read and write for ``n`` valid rows over ``m``
partitions, not what an implementation pads, re-reads or scratches.
"""

from __future__ import annotations

from typing import Dict

#: Google Cloud documentation, "TPU v5e": per chip
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table of ``device_kind``; an unknown chip is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add its "
                       f"published numbers to PEAKS") from None


def hash_partition_bytes(n: int, m: int) -> int:
    """Keys in (int32), a partition id out per key (int32), and the
    ``m``-bin histogram out (int32)."""
    return 4 * n + 4 * n + 4 * m


def scatter_perm_bytes(n: int, m: int) -> int:
    """Partition ids in (int32), the ``m`` base offsets in (int32), a
    destination slot out per row (int32)."""
    return 4 * n + 4 * m + 4 * n


BYTES = {"hash_partition": hash_partition_bytes,
         "scatter_perm": scatter_perm_bytes}
