"""Benchmark suite entrypoint: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Pass ``--json PATH`` (by
convention ``BENCH_<tag>.json``) to additionally snapshot the emitted rows
(collected in ``common.ROWS``) — see benchmarks/README.md for the
methodology.  The dry-run/roofline cells
(which need the 512-device env flag) run via ``repro.launch.dryrun`` as a
separate process — see EXPERIMENTS.md §Dry-run.
"""

from __future__ import annotations

import json
import sys
import time
import traceback


def main() -> None:
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (bench_reddit, bench_pagerank, bench_linear_algebra,
                   bench_tpch, bench_overhead, bench_drl_training,
                   bench_history, bench_kernels, bench_autopilot,
                   bench_storage, bench_serving, bench_cluster)
    argv = sys.argv[1:]
    json_path = None
    if "--json" in argv:
        i = argv.index("--json") + 1
        if i >= len(argv):
            sys.exit("usage: python -m benchmarks.run [--json BENCH_<tag>.json]")
        json_path = argv[i]
    suites = [
        ("reddit(Fig5,Tab3)", bench_reddit.main),
        ("pagerank(Fig6)", bench_pagerank.main),
        ("linear_algebra(Fig7-9)", bench_linear_algebra.main),
        ("tpch(Fig10)", bench_tpch.main),
        ("overhead(Tab2,Fig11)", bench_overhead.main),
        ("drl_training(Fig12)", bench_drl_training.main),
        ("history(Fig13)", bench_history.main),
        ("kernels(Pallas)", bench_kernels.main),
        ("autopilot(service)", bench_autopilot.main),
        ("storage(durable)", bench_storage.main),
        ("serving(tier)", bench_serving.main),
        ("cluster(tier)", bench_cluster.main),
    ]
    from .common import ROWS
    print("name,us_per_call,derived")
    failures = []
    timings = {}
    try:
        for name, fn in suites:
            t0 = time.time()
            try:
                fn()
                timings[name] = time.time() - t0
                print(f"# {name} done in {timings[name]:.1f}s",
                      file=sys.stderr)
            except Exception:
                traceback.print_exc()
                failures.append(name)
    finally:
        if json_path:
            with open(json_path, "w") as f:
                json.dump({"rows": ROWS, "suite_seconds": timings,
                           "failures": failures}, f, indent=1)
            print(f"# wrote {json_path}", file=sys.stderr)
    if failures:
        print(f"# FAILED: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()
