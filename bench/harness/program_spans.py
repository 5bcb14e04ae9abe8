"""Readers of the program's transfer counters and of its spans on the
trace's host plane (the seconds in a span are read by
``readers.span_s_per_unit``).

The program's tracer mirrors each span it records into the profiler
(``repro.obs.tracer``), so a traced run holds the spans twice: in
``Run.spans`` from the tracer, and on the trace's host plane next to the
device's operations.  A program that records no such span, or no such
argument, gives nothing to read: each reader then returns ``None``."""

from __future__ import annotations

from typing import Optional

from . import trace as tr
from .core import Run
from .readers import done


def mb_per_unit(run: Run, prefix: str, arg: str, *names: str
                ) -> Optional[float]:
    """Megabytes (1e6 bytes) of the span argument ``arg`` per completed
    unit of ``prefix``, over the spans ``names`` (every span when none is
    named); ``None`` when no span carries the argument."""
    units = done(run, prefix)
    spans = run.spans_named(*names) if names else run.spans
    counted = [s.args[arg] for s in spans if arg in s.args]
    if not units or not counted:
        return None
    return sum(counted) / len(units) / 1e6


def idle_unspanned_pct(run: Run) -> Optional[float]:
    """Share of the window's device-idle time in which no program span is
    open, in percent.  Program spans are the host-plane events whose names
    the tracer recorded in ``run.spans``; the harness's annotations are
    not among them."""
    if run.trace is None or not run.trace.devices or not run.spans:
        return None
    names = {s.name for s in run.spans}
    spans = [(a, b) for n, a, b in run.trace.host_spans if n in names]
    if not spans:
        return None
    lo, hi = run.window
    idle = unspanned = 0.0
    for dev in run.trace.devices:
        busy = [(e.start_s, e.end_s) for e in run.trace.events
                if e.device == dev]
        idle += (hi - lo) - tr.union_length(busy, lo, hi)
        unspanned += (hi - lo) - tr.union_length(busy + spans, lo, hi)
    if idle <= 0.0:
        return None
    return 100.0 * unspanned / idle
