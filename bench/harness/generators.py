"""What every kind of traffic shares.

A traffic file (``bench/traffic/<name>.json``) names its ``kind`` and
holds the parameters; the kind is the file ``bench/kinds/<kind>.py``,
whose ``GENERATOR``, a subclass of ``Generator``, drives the system with
them.  A generator sets up (tables from the seed, the stored layouts),
warms up every shape of its own traffic, runs the window and then checks
what the window produced against a plain reference.
"""

from __future__ import annotations

import gc
import time
from typing import Dict

import numpy as np

from .core import Run, Unit, load_module, log
from .workloads import Query


def schema_of(tables) -> Dict[str, Dict[str, list]]:
    """``{table: {column: [dtype, width]}}``: what a configuration file
    states, to check the generated tables against."""
    return {t: {k: [str(v.dtype), int(np.prod(v.shape[1:]))]
                for k, v in cols.items()}
            for t, cols in tables.items()}


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def ready(ds) -> None:
    """Wait until a stored dataset's device columns are computed."""
    import jax
    jax.block_until_ready([v for v in ds.columns.values()
                           if isinstance(v, jax.Array)])


class Generator:
    """Set-up shared by every kind: the tables and the session."""

    def __init__(self, run: Run):
        self.run = run
        self.cfg = run.config
        self.traffic = run.traffic
        self.session = None

    def make_tables(self, want) -> None:
        """The tables ``want`` of the configuration's ``population``
        (``bench/populations/<population>.py``), made from the seed and
        checked against the schema the configuration states."""
        cfg = self.cfg
        t0 = time.perf_counter()
        population = load_module("populations", cfg["population"])
        self.tables = population.make_tables(cfg, self.run.seed, want)
        stated = {t: cfg["tables"][t] for t in want}
        made = schema_of(self.tables)
        if made != stated:
            raise SystemExit(f"generated schema {made} is not the "
                             f"configuration's {stated}")
        rows = {t: len(next(iter(c.values())))
                for t, c in self.tables.items()}
        log(f"tables of population {cfg['population']} (seed "
            f"{self.run.seed}): {rows} rows in "
            f"{time.perf_counter() - t0:.2f} s")

    def open_session(self):
        import lachesis
        from repro.data.partition_store import PartitionStore
        st = self.cfg["store"]
        store = PartitionStore(
            num_workers=st["num_workers"], backend=st["backend"],
            adaptive_capacity=st["adaptive_capacity"],
            capacity_threshold=st["capacity_threshold"],
            max_retired_generations=st["max_retired_generations"])
        self.session = lachesis.Session(store=store, backend=st["backend"])
        return self.session

    def layout(self, spec: str, dataset: str):
        """A layout name from a traffic file: ``roundrobin``, or a query
        whose candidate for ``dataset`` Lachesis stores it under."""
        return None if spec == "roundrobin" else Query(spec).candidate(
            dataset)

    def release(self) -> None:
        """Drop the program's state before the reference runs."""
        self.session = None
        gc.collect()

    def failed(self) -> int:
        return sum(u.error is not None for u in self.run.units)

    def _run_unit(self, unit: Unit, fn) -> None:
        try:
            with _annotate(unit.name):
                fn(unit)
        except Exception as e:                      # noqa: BLE001
            unit.error = f"{type(e).__name__}: {e}"
            log(f"{unit.name} failed: {unit.error}")
        unit.t1 = time.perf_counter()

    def rotations(self, steps) -> None:
        """Run whole rotations of ``steps`` (``(name, fn(unit))`` pairs) and
        close the window at the rotation boundary nearest to
        ``--seconds``, taking the next rotation to last as long as the
        last one did.  Every seed then runs whole rotations of the same
        work, and the window's length stays near ``--seconds``."""
        t_end = self.run.window[0] + self.run.seconds
        while True:
            t_rot = time.perf_counter()
            for name, fn in steps:
                unit = Unit(name=name, t0=time.perf_counter())
                self._run_unit(unit, fn)
                self.run.units.append(unit)
            now = time.perf_counter()
            if now + (now - t_rot) / 2 >= t_end:
                return
