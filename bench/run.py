"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout, on a machine whose first JAX device is a TPU
with as many chips as the cell asks for; anything else exits nonzero
before any work.  A run:

1. sets up: tables from ``--seed``, the stored layouts, every shape of
   the cell's own traffic warmed (compiles count here, in ``setup_s``);
2. measures for ``--seconds`` (with ``--trace 1`` under the profiler and
   the program's tracer, for the per-layer metrics);
3. frees the program's state and checks what the window produced
   against the numpy reference;
4. prints its notes and every compared number on standard error, and
   one JSON result line last on standard output.

Everything a cell is made of is found by its name in ``BENCHMARK.json``
or in the files it names, so a configuration, a population, a traffic
kind and a metric are added as new files and new entries, with no file
of the harness changed (``core.load_module`` finds them; a name with no
file exits nonzero, naming the file):

* a configuration is ``bench/configs/<name>.json`` (its ``file`` entry):
  the sizes as run, its ``population``, the ``store`` settings, the
  ``tables`` it holds as ``{table: {column: [dtype, width]}}`` (checked
  against what the population made), and ``cpu_test``, the keys the CPU
  tests override to run it at a tiny size;
* a population is ``bench/populations/<population>.py``, defining
  ``make_tables(config, seed, want)``: the tables named in ``want``, as
  ``{table: {column: numpy array}}``, the same for the same seed;
* a traffic mix is ``bench/traffic/<name>.json``, data whose ``kind``
  names ``bench/kinds/<kind>.py``, which defines ``GENERATOR``: a
  subclass of ``harness.generators.Generator`` with ``setup``, ``warm``,
  ``window``, ``end_to_end`` (the cell's end-to-end metrics by name),
  ``check`` (``Run.check`` for each compared number) and, where it keeps
  more state, ``release``;
* a per-layer metric is ``bench/metrics/<metric>.py``, defining
  ``read(run)``: the metric from the run's units, spans, counters or
  trace, or ``None`` where the run holds nothing to read.  A kernel's
  roofline reader passes ``harness.readers.roofline_pct`` the names of
  the kernel's device events, the span that launches one call and its
  own function of that span's arguments to the bytes a call needs.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

from harness import core  # noqa: E402
from harness import trace as tr  # noqa: E402
from harness.core import Run, Span, log  # noqa: E402

TRACER_BUFFER = 1 << 24      # spans; a traced window must drop none


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(n: int):
    """JAX's devices, which must be TPUs and at least ``n`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX's first device is "
                         f"{devs[0].platform!r}, not a TPU; run on a "
                         f"machine with a TPU chip")
    if len(devs) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:n]


def metric_reader(name: str):
    return core.load_module("metrics", name).read


def cell_metrics(man: dict, cell: str, kind: str):
    """The manifest's metrics of ``kind`` that cell ``cell`` reports."""
    return [m for m in man[kind]
            if cell in m.get("workloads", [cell])]


def start_profiler(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    stamp = time.perf_counter_ns()
    with jax.profiler.TraceAnnotation(f"{tr.CLOCK_ANNOTATION}@{stamp}"):
        pass


def main(argv=None, *, devices=None, config=None) -> int:
    """``devices`` stands in for the chip check and ``config`` overrides
    keys of the cell's configuration (tests on the CPU, at tiny sizes)."""
    args = parse(argv)
    man = core.manifest()
    cell, _entry, stated = core.find_cell(man, args.workload)
    config = dict(stated, **(config or {}))
    traffic = core.load_traffic(cell["traffic"])
    generator = core.load_module("kinds", traffic["kind"]).GENERATOR

    import jax
    devs = devices if devices is not None else require_chips(cell["chips"])
    cache = core.enable_compile_cache()
    meter = core.CompileMeter()
    from repro.data import device_repartition as dr
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"ShufflePlan defaults mode={dr.default_mode()} "
        f"use_kernel={dr.default_use_kernel()} "
        f"interpret={dr.default_interpret()}; compile cache {cache}")

    run = Run(cell=cell, config=config, traffic=traffic, seed=args.seed,
              seconds=args.seconds, traced=bool(args.trace),
              device_kind=devs[0].device_kind)
    gen = generator(run)
    gen.setup()
    gen.warm()
    setup_s = time.perf_counter() - T_START
    log(f"setup: {setup_s:.2f} s; compile {meter.line()}")

    from repro.obs import tracer
    trace_dir = None
    if run.traced:
        tracer.configure(mode="full", buffer=TRACER_BUFFER)
        tracer.clear_spans()
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        start_profiler(trace_dir)
    programs0, traces0 = meter.programs, dr.plan_cache_stats()["traces"]
    gc.collect()
    host = core.HostMeter()
    run.window = (time.perf_counter(), 0.0)
    gen.window()
    if run.window[1] == 0.0:
        run.window = (run.window[0], time.perf_counter())
    log(host.line(run.window_s))
    log(f"unit seconds: {core.unit_times(run.units)}")
    compiles = meter.programs - programs0
    retraces = dr.plan_cache_stats()["traces"] - traces0
    if run.traced:
        jax.profiler.stop_trace()
        tst = tracer.TRACER.stats()
        run.spans = [Span(s.name, s.t0, s.t1, dict(s.args))
                     for s in tracer.finished_spans()
                     if s.t1 is not None and s.t0 >= run.window[0]]
        tracer.configure(mode="off")
        log(f"program spans in the window: {len(run.spans)}, dropped "
            f"{tst['dropped']}")
        if tst["dropped"]:
            raise SystemExit("the tracer dropped spans: raise its buffer")
    log(f"window: {run.window_s:.3f} s, {len(run.units)} units, "
        f"{gen.failed()} failed; compiles in the window {compiles}, "
        f"ShufflePlan retraces {retraces}")
    stats = devs[0].memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    log(f"device memory: bytes_in_use={stats.get('bytes_in_use')} "
        f"peak_bytes_in_use={peak}")

    e2e = gen.end_to_end()
    e2e["setup_s"] = setup_s
    gen.release()
    t0 = time.perf_counter()
    gen.check()
    run.check("compiles_in_window", compiles, max=0)
    log(f"reference check: {time.perf_counter() - t0:.2f} s")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    breakdown = None
    if run.traced:
        run.trace = tr.read_xplane(tr.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = run.window
        device["busy_s"] = tr.busy_s(run.trace, lo, hi)
        device["window_s"] = run.window_s
        host = [(s.name, s.t0, s.t1) for s in run.spans] + [
            (u.name, u.t0, u.t1) for u in run.units]
        breakdown = {"device_ops": tr.top_ops(run.trace, lo, hi),
                     "idle_gaps": tr.idle_by_host(run.trace, lo, hi, host)}
        metrics = {}
        for m in cell_metrics(man, cell["name"], "per_layer"):
            v = metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = (float(v), m["unit"])
    else:
        metrics = {m["name"]: (float(e2e[m["name"]]), m["unit"])
                   for m in cell_metrics(man, cell["name"], "end_to_end")}
    for name, (v, unit) in metrics.items():
        log(f"metric {name} = {v!r} {unit}")
    for name, c in run.checks.items():
        lim = " ".join(f"{k} {c[k]}" for k in ("max", "min") if k in c)
        log(f"check {name} = {c['value']} ({lim})")
    print(core.result_line(run, metrics, len(run.units), gen.failed(),
                           device, breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
