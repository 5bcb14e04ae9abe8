"""What every cell's run shares: the manifest, the run record, the
compile meter and the result line."""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import resource
import statistics
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find_cell(man: dict, name: str) -> Tuple[dict, dict, dict]:
    """(cell, configuration entry, configuration file) of cell ``name``."""
    cells = {c["name"]: c for c in man["workloads"]}
    if name not in cells:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json "
                         f"(cells: {sorted(cells)})")
    cell = cells[name]
    entry = {c["name"]: c for c in man["configs"]}[cell["config"]]
    return cell, entry, load_json(ROOT / entry["file"])


def load_traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def load_module(folder: str, name: str):
    """The module ``bench/<folder>/<name>.py``.  This is how the harness
    finds what a name in ``BENCHMARK.json`` or in a configuration or
    traffic file stands for: a traffic kind (``kinds``), a population
    (``populations``) or a metric's reader (``metrics``).  A name with no
    file exits, naming the file."""
    path = BENCH / folder / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"bench: no file {path.relative_to(ROOT)} for "
                         f"{name!r} in {folder}/")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileMeter:
    """Backend compiles (a persistent-cache hit counts its retrieval) and
    the persistent cache's hits and misses, from JAX's monitoring
    events."""

    def __init__(self):
        import jax
        self.seconds, self.programs, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_kw):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.programs += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self) -> str:
        return (f"{self.programs} programs in {self.seconds:.2f} s; "
                f"persistent cache hits {self.hits}, misses {self.misses}")


class HostMeter:
    """The process's CPU seconds, page faults, context switches and time
    in Python's garbage collector from ``start()`` on: where the host's
    time went in a window the host bounds."""

    def __init__(self):
        self._gc_t0 = None
        gc.callbacks.append(self._gc)
        self.start()

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0
            self.gc_runs[info["generation"]] += 1
            self._gc_t0 = None

    def start(self) -> None:
        self.gc_s, self.gc_runs = 0.0, [0, 0, 0]
        self.r0 = resource.getrusage(resource.RUSAGE_SELF)

    def line(self, wall_s: float) -> str:
        r, r0 = resource.getrusage(resource.RUSAGE_SELF), self.r0
        return (f"host in the window: cpu user {r.ru_utime - r0.ru_utime:.2f}"
                f" s, sys {r.ru_stime - r0.ru_stime:.2f} s over "
                f"{wall_s:.2f} s; gc {self.gc_s:.3f} s in "
                f"{sum(self.gc_runs)} collections (generation 2: "
                f"{self.gc_runs[2]}); page faults minor "
                f"{r.ru_minflt - r0.ru_minflt}, major "
                f"{r.ru_majflt - r0.ru_majflt}; context switches voluntary "
                f"{r.ru_nvcsw - r0.ru_nvcsw}, involuntary "
                f"{r.ru_nivcsw - r0.ru_nivcsw}; load average "
                f"{os.getloadavg()[0]:.2f}")


def unit_times(units) -> str:
    """Median, least and most seconds of each kind of unit."""
    by: Dict[str, List[float]] = {}
    for u in units:
        if u.t1 is not None:
            by.setdefault(u.name, []).append(u.t1 - u.t0)
    return "; ".join(f"{k} x{len(v)} median {statistics.median(v):.3f} s "
                     f"[{min(v):.3f}, {max(v):.3f}]"
                     for k, v in sorted(by.items()))


@dataclass
class Unit:
    """One unit of work in the window: a query, a store op or a request."""
    name: str
    t0: float                      # perf_counter when due / started
    t1: Optional[float] = None     # perf_counter when complete
    rows: int = 0
    stats: Any = None              # EngineStats of a query
    error: Optional[str] = None
    extra: Dict[str, Any] = field(default_factory=dict)


@dataclass
class Span:
    name: str
    t0: float
    t1: float
    args: Dict[str, Any]


@dataclass
class Run:
    """Everything a run records, for the generators' checks and the metric
    readers (``bench/metrics/<metric>.py``)."""
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    traced: bool
    device_kind: str = ""
    units: List[Unit] = field(default_factory=list)
    window: Tuple[float, float] = (0.0, 0.0)
    spans: List[Span] = field(default_factory=list)   # program's, traced
    trace: Any = None                                  # trace.Trace
    write_log: List[dict] = field(default_factory=list)
    checks: Dict[str, dict] = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def spans_named(self, *names: str) -> List[Span]:
        return [s for s in self.spans if s.name in names]

    def check(self, name: str, value, *, max=None, min=None) -> None:
        """Record a compared number with its limit."""
        entry = {"value": value}
        if max is not None:
            entry["max"] = max
        if min is not None:
            entry["min"] = min
        self.checks[name] = entry

    @property
    def correct(self) -> bool:
        ok = bool(self.checks)
        for c in self.checks.values():
            if "max" in c and not c["value"] <= c["max"]:
                ok = False
            if "min" in c and not c["value"] >= c["min"]:
                ok = False
        return ok


def compile_cache_dir() -> str:
    """JAX_COMPILATION_CACHE_DIR when set, else ``<checkout>/.jax_cache``:
    a fixed path, so a later run of the checkout finds its programs."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")


def enable_compile_cache() -> str:
    import jax
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however quick to compile, so that set-up after
    # the first run of a checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def result_line(run: Run, metrics: Dict[str, Tuple[float, str]],
                attempted: int, failed: int, device: dict,
                breakdown: Optional[dict] = None) -> str:
    out = {"correct": run.correct, "attempted": attempted, "failed": failed,
           "metrics": {k: {"value": v, "unit": u}
                       for k, (v, u) in metrics.items()},
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = run.checks
    return json.dumps(out)
