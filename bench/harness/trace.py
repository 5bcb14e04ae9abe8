"""From a profiler trace to device busy time, kernel time and idle gaps.

``jax.profiler`` writes an ``.xplane.pb``; ``ProfileData`` reads it.  A
device plane (``/device:TPU:<i>``) holds a line of XLA operations, one
event per operation with its device start and duration.  The host plane
holds the ``TraceAnnotation`` spans the harness opens, one of which,
``bench.clock``, ties the trace's clock to ``time.perf_counter`` so that
the program's own spans can be laid over the device's timeline.

The reduction here is the one every pull request is measured with:

* busy: the union of the device's operation intervals inside the window;
* kernel time: the summed device duration of a kernel's events.  On a
  TPU an operation's event is named by its HLO text
  (``%scatter_permutation.1 = s32[...] custom-call(...)``); a kernel's
  events are the custom calls whose instruction name, less its ``.N``
  suffix, is one of the names its reader gives (``KERNELS`` names the
  families the breakdown labels);
* idle gaps: the stretches of the window with no operation on the
  device, each named by the innermost host span open at its midpoint.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

CLOCK_ANNOTATION = "bench.clock"

#: names of each kernel family's events: the jitted wrappers the custom
#: calls are named after today, the Pallas kernel functions, and the
#: family names a ``name=`` on the pallas_call would give them
KERNELS: Dict[str, Tuple[str, ...]] = {
    "hash_partition": ("hash_partition", "hash_partition_padded",
                       "partition_ids", "padded_partition_ids",
                       "_kernel", "_kernel_padded"),
    "scatter_perm": ("scatter_perm", "scatter_permutation", "_perm_kernel"),
}

#: the line of a device plane that holds one event per executed operation,
#: and the line of whole programs (jitted modules)
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"

_INSTR = re.compile(r"^%?([\w.\-]+) = ")
_OPCODE = re.compile(r" ([\w\-]+)\(")

Interval = Tuple[float, float]          # (start_s, end_s) on the host clock


@dataclass
class DeviceEvent:
    name: str
    start_s: float                      # host perf_counter seconds
    dur_s: float
    device: str

    @property
    def end_s(self) -> float:
        return self.start_s + self.dur_s


@dataclass
class Trace:
    """A reduced trace: device events on the host's perf_counter clock."""
    events: List[DeviceEvent]
    devices: List[str]
    host_spans: List[Tuple[str, float, float]]   # annotations, perf clock
    modules: List[DeviceEvent] = field(default_factory=list)


def hlo_parts(text: str) -> Optional[Tuple[str, str, str]]:
    """(instruction, result type without layout, opcode) of an HLO
    event name, or None when the name is not HLO text."""
    m = _INSTR.match(text)
    if m is None:
        return None
    rest, depth = text[m.end():], 0
    for i, ch in enumerate(rest):        # the result type may be a tuple
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if ch == " " and depth == 0:
            break
    else:
        return None
    op = _OPCODE.match(rest[i:])
    if op is None:
        return None
    return m.group(1), re.sub(r"\{[^}]*\}", "", rest[:i]), op.group(1)


def union_length(intervals: Iterable[Interval], lo: float, hi: float
                 ) -> float:
    """Length of the union of ``intervals`` clipped to ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of ``[lo, hi]`` that no interval covers, in order."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def kernel_family(name: str) -> Optional[str]:
    for fam, names in KERNELS.items():
        if name in names:
            return fam
    return None


def kernel_name(ev: DeviceEvent) -> Optional[str]:
    """The name a device event goes by as a kernel: a custom call's
    instruction name less its ``.N`` suffix, or the event's own name where
    it is not HLO text; ``None`` for any other operation."""
    parts = hlo_parts(ev.name)
    if parts is None:
        return ev.name
    instr, _type, opcode = parts
    if opcode != "custom-call":
        return None
    return re.sub(r"\.\d+$", "", instr)


def event_kernel(ev: DeviceEvent) -> Optional[str]:
    """The kernel family (``KERNELS``) of a device event, for labels."""
    name = kernel_name(ev)
    return None if name is None else kernel_family(name)


def op_label(ev: DeviceEvent, module: str = "") -> str:
    """A short name for a device operation: its program, instruction and
    result type (``jit_fn/fusion.2 u8[16777217,44]``), or its kernel
    family."""
    fam = event_kernel(ev)
    if fam is not None:
        return fam
    parts = hlo_parts(ev.name)
    label = ev.name if parts is None else f"{parts[0]} {parts[1]}"
    return f"{module}/{label}"[:120] if module else label[:120]


def read_xplane(path: str) -> Trace:
    """Reduce one ``.xplane.pb`` to device events and host annotations,
    both on the perf_counter clock of the process that recorded it."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    host: List[Tuple[str, float, float]] = []
    raw_dev: List[Tuple[str, float, float, str]] = []
    raw_mod: List[Tuple[str, float, float, str]] = []
    devices: List[str] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            devices.append(plane.name)
            for line in plane.lines:
                into = {OP_LINE: raw_dev, MODULE_LINE: raw_mod}.get(
                    line.name)
                if into is None:
                    continue
                for ev in line.events:
                    into.append((ev.name, float(ev.start_ns),
                                 float(ev.duration_ns), plane.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.name, float(ev.start_ns),
                                 float(ev.start_ns) + float(ev.duration_ns)))
    return _on_perf_clock(raw_dev, raw_mod, host, devices)


def _on_perf_clock(raw_dev, raw_mod, host, devices) -> Trace:
    """Move every timestamp to the host's perf_counter clock.

    The annotation ``bench.clock@<perf_counter ns>`` was opened just
    after that stamp was read: its start in the trace fixes the offset."""
    stamped = [(n, s) for n, s, _e in host
               if n.startswith(CLOCK_ANNOTATION + "@")]
    if not stamped:
        raise ValueError(f"no {CLOCK_ANNOTATION}@<ns> annotation in the "
                         "trace")
    name, start = stamped[0]
    offset = start - float(name.split("@", 1)[1])
    def moved(raw):
        return [DeviceEvent(n, (s - offset) / 1e9, d / 1e9, dev)
                for n, s, d, dev in raw]
    spans = [(n, (s - offset) / 1e9, (e - offset) / 1e9)
             for n, s, e in host]
    return Trace(events=moved(raw_dev), devices=sorted(set(devices)),
                 host_spans=spans, modules=moved(raw_mod))


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


# ---------------------------------------------------------------------------
# Reductions over a window
# ---------------------------------------------------------------------------

def busy_s(trace: Trace, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` in which an operation ran, averaged over
    the devices that appear in the trace."""
    if not trace.devices:
        return 0.0
    per = []
    for dev in trace.devices:
        per.append(union_length(((e.start_s, e.end_s) for e in trace.events
                                 if e.device == dev), lo, hi))
    return sum(per) / len(per)


def kernel_events(trace: Trace, names: Sequence[str], lo: float, hi: float
                  ) -> List[DeviceEvent]:
    """The window's events of the kernel whose events go by ``names``
    (``kernel_name``), in order of start."""
    return sorted((e for e in trace.events
                   if lo <= e.start_s < hi and kernel_name(e) in names),
                  key=lambda e: e.start_s)


def module_names(trace: Trace):
    """A function from a device event to the program (jitted module) it
    ran in, the fingerprint in parentheses dropped."""
    mods = sorted(trace.modules, key=lambda e: e.start_s)
    starts = [m.start_s for m in mods]

    def name_of(ev: DeviceEvent) -> str:
        i = bisect.bisect_right(starts, ev.start_s) - 1
        if i >= 0 and mods[i].device == ev.device and \
                ev.start_s < mods[i].end_s:
            return mods[i].name.split("(", 1)[0]
        return ""
    return name_of


def top_ops(trace: Trace, lo: float, hi: float, k: int = 10
            ) -> List[List]:
    """The ``k`` device operations that took most time in the window,
    summed by short name (``op_label``): ``[[name, seconds], ...]``."""
    module = module_names(trace)
    tot: Dict[str, float] = {}
    for e in trace.events:
        if lo <= e.start_s < hi:
            name = op_label(e, module(e))
            tot[name] = tot.get(name, 0.0) + e.dur_s
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def idle_by_host(trace: Trace, lo: float, hi: float,
                 spans: Sequence[Tuple[str, float, float]], k: int = 10
                 ) -> List[List]:
    """Idle seconds of the window summed by what the host was doing: the
    innermost span of ``spans`` (name, start, end on the perf clock) open
    at each gap's midpoint, or ``host.other``.  The ``k`` largest."""
    busy = [(e.start_s, e.end_s) for e in trace.events]
    tot: Dict[str, float] = {}
    ordered = sorted(spans, key=lambda s: s[1])
    for s, e in gaps(busy, lo, hi):
        mid = 0.5 * (s + e)
        name, width = "host.other", float("inf")
        for n, a, b in ordered:
            if a > mid:
                break
            if b >= mid and b - a < width:
                name, width = n, b - a
        tot[name] = tot.get(name, 0.0) + (e - s)
    return [[n, v] for n, v in sorted(tot.items(), key=lambda x: -x[1])[:k]]
