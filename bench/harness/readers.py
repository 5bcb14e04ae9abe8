"""Helpers the per-layer metric readers share.

A reader returns ``None`` when its run holds nothing to read (no unit of
its kind, no traced event), and the harness leaves the metric out."""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence

from . import roofline
from . import trace as tr
from .core import Run, Unit, log


def done(run: Run, prefix: str) -> List[Unit]:
    """The window's units of one kind that completed."""
    return [u for u in run.units
            if u.name.startswith(prefix) and u.error is None]


def span_s_per_unit(run: Run, prefix: str, *names: str) -> Optional[float]:
    """Seconds in the program's spans ``names``, per completed unit."""
    units = done(run, prefix)
    if not units or not run.spans:
        return None
    return sum(s.t1 - s.t0 for s in run.spans_named(*names)) / len(units)


def idle_pct(run: Run) -> Optional[float]:
    """Share of the traced window in which no operation ran on the
    device, in percent."""
    if run.trace is None or not run.trace.devices:
        return None
    lo, hi = run.window
    return 100.0 * (1.0 - tr.busy_s(run.trace, lo, hi) / (hi - lo))


def roofline_pct(run: Run, events: Sequence[str],
                 nbytes: Callable[[dict], int], span: str
                 ) -> Optional[float]:
    """A kernel's share of its bandwidth roofline over the window: the
    bytes its algorithm needs for every call of the window at the chip's
    peak bandwidth, over the device time of its events.

    ``events`` are the names the kernel's device events go by
    (``trace.kernel_name``).  Each program span ``span`` launches one
    call, which needs ``nbytes(span.args)`` bytes, so the i-th event goes
    with the i-th span; a window whose counts differ is not read."""
    if run.trace is None:
        return None
    lo, hi = run.window
    found = tr.kernel_events(run.trace, events, lo, hi)
    calls = sorted(run.spans_named(span), key=lambda s: s.t0)
    if not found:
        return None
    if len(found) != len(calls):
        log(f"{events[0]}: {len(found)} kernel events but {len(calls)} "
            f"{span} spans in the window; roofline not read")
        return None
    total = sum(nbytes(s.args) for s in calls)
    device_s = sum(e.dur_s for e in found)
    bw = roofline.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (total / bw) / device_s
