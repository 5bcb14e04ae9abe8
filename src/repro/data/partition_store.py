"""Persistent partitioned storage — the Pangea-storage analogue (paper §4).

A :class:`PartitionStore` holds named columnar datasets laid out across ``m``
logical workers.  The layout is the *persistent partitioning*: column arrays
are shaped ``(m, capacity, ...)`` with a per-worker ``counts`` vector, so a
consumer whose desired partitioner matches the stored one operates strictly
worker-locally (no shuffle).  On a TPU pod the leading axis maps onto the
mesh via ``NamedSharding(mesh, P("data"))`` — see core/sharding_bridge.

TPU adaptation (DESIGN §2): objects → fixed-capacity padded rows; skew shows
up as padding waste, penalized by the ``key_distribution`` feature.

Backends (DESIGN §5): ``backend="host"`` (default) dispatches with numpy
(one vectorized counting-sort placement per write, no per-worker Python
loop); ``backend="device"`` holds columns device-resident (jnp) behind the
same ``(m, capacity)`` layout, dispatching through the cached single-pass
shuffle plans (hash → counting-sort → packed scatter) and repartitioning
device-to-device when the source dataset is device-backed.

Durability (DESIGN §10): pass ``root=`` to back the store with the
:mod:`~repro.data.storage` tier — every published generation is written as
per-column segment files (already in the padded layout, so reopening is a
zero-copy ``np.memmap``) under a crash-safe manifest; a fresh process
reattaches with :meth:`PartitionStore.open` (or
``lachesis.Session(store_path=...)``) and consumers elide their shuffles
against layouts a previous application paid for.  ``memory_budget_bytes``
turns on the eviction loop: cold datasets spill to their segments, reads
lazily rehydrate, and a device-resident store prefetches host→device.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..core.partitioner import (HASH, PartitionerCandidate, RANDOM,
                                ROUND_ROBIN)
from ..obs.tracer import span as _span
from .capacity import CapacityMap, plan_capacity_map, valid_slot_index
from .device_repartition import (device_place_presorted,
                                 device_repartition_dataset,
                                 device_scatter_padded, dtype_roundtrips,
                                 flatten_dataset, host_counting_sort_dest,
                                 host_presorted_dest, shuffle_pids)
from .transfer import fetch


Columns = Dict[str, np.ndarray]

#: kept for backward compatibility; the authoritative list lives in the
#: BackendRegistry (repro.core.backends.REGISTRY)
BACKENDS = ("host", "device")

#: write_log entries retained verbatim; older entries fold into the
#: monotone ``write_totals`` aggregates (satellite of DESIGN §10)
DEFAULT_WRITE_LOG_CAP = 256


class RetiredGenerationError(KeyError):
    """A specific, still-retained generation was requested but has left
    the bounded retention window (``max_retired_generations``).  Distinct
    from a plain ``KeyError`` (unknown dataset name) so callers that pin
    generations — the planner — can retry on exactly this condition."""

# one vectorized counting-sort placement shared by all columns, replacing
# the per-worker Python copy loop (lives in device_repartition beside the
# device plans it is the reference of)
_counting_sort_dest = host_counting_sort_dest


@dataclass
class StoredDataset:
    """One immutable generation of a named dataset.

    Column arrays are never mutated in place after construction; a layout
    change installs a NEW StoredDataset and atomically flips the store's
    name → generation pointer (DESIGN §8).  A reader holding this object
    therefore always sees one consistent generation, never a half-shuffled
    table, even while a background repartition swaps the pointer.

    (The eviction loop may swap a column's *container* — in-RAM ndarray ⇄
    read-only memmap of its persisted segment — which is bit-identical by
    construction, so the immutable-values contract holds for readers.)

    Layouts: with ``capacity_map=None`` (the default), columns are the
    uniform padded ``(m, capacity, ...)`` grid.  With a
    :class:`~repro.data.capacity.CapacityMap`, columns are *flat*
    ``(total_slots, ...)`` and partition ``i`` occupies the slot range
    ``[offsets[i], offsets[i] + capacities[i])`` — the skew-adaptive
    layout (DESIGN §12).  ``gather()`` produces the identical row order
    for both."""
    name: str
    columns: Columns                   # (m, capacity, ...) or (slots, ...)
    counts: np.ndarray                 # (m,) valid rows per worker
    partitioner: Optional[PartitionerCandidate]
    num_rows: int
    nbytes: int
    created_at: float = field(default_factory=time.time)
    generation: int = 0
    capacity_map: Optional[CapacityMap] = None
    # name → (device column, its host copy): each device column crosses to
    # the host once per generation, however often it is scanned
    _host: Dict[str, Tuple[Any, np.ndarray]] = field(
        default_factory=dict, init=False, repr=False, compare=False)

    @property
    def num_workers(self) -> int:
        return int(self.counts.shape[0])

    @property
    def capacity(self) -> int:
        if self.capacity_map is not None:
            caps = self.capacity_map.capacities
            return int(caps.max()) if caps.size else 0
        return int(next(iter(self.columns.values())).shape[1])

    def slot_capacities(self) -> np.ndarray:
        """(m,) per-partition slot capacities (uniform ⇒ all equal)."""
        if self.capacity_map is not None:
            return self.capacity_map.capacities
        return np.full(self.num_workers, self.capacity, dtype=np.int64)

    def slot_offsets(self) -> np.ndarray:
        """(m,) flat-slot base offset of each partition."""
        if self.capacity_map is not None:
            return self.capacity_map.offsets
        return np.arange(self.num_workers, dtype=np.int64) * self.capacity

    @property
    def total_slots(self) -> int:
        if self.capacity_map is not None:
            return self.capacity_map.total_slots
        return self.num_workers * self.capacity

    @property
    def padded_bytes(self) -> int:
        """Bytes actually occupied by the padded layout (incl. padding)."""
        return int(sum(int(v.nbytes) for v in self.columns.values()))

    @property
    def valid_bytes(self) -> int:
        """Bytes of real rows inside the padded layout."""
        slots = self.total_slots
        if slots <= 0:
            return 0
        return int(self.padded_bytes * (self.num_rows / slots))

    @property
    def device_bytes(self) -> int:
        """Bytes of the columns held in device memory."""
        return int(sum(int(v.nbytes) for v in self.columns.values()
                       if isinstance(v, jax.Array)))

    def padding_waste(self) -> int:
        """Bytes spent on padding alone — what skew costs this layout."""
        return max(self.padded_bytes - self.valid_bytes, 0)

    def skew(self) -> float:
        """max/mean partition fill — load-balance diagnostic."""
        mean = max(self.counts.mean(), 1e-9)
        return float(self.counts.max() / mean)

    @property
    def backend(self) -> str:
        """"device" when any column is device-resident (a jax array)."""
        return "device" if any(isinstance(v, jax.Array)
                               for v in self.columns.values()) else "host"

    @property
    def spilled(self) -> bool:
        """True when every column is a disk-backed memmap view (the
        eviction loop's cold state — reads page in lazily).  Zero-size
        columns hold no memory and cannot be memmapped, so they don't
        count against the cold state."""
        cols = [v for v in self.columns.values() if v.size]
        return bool(self.columns) and all(isinstance(v, np.memmap)
                                          for v in cols)

    def gather(self) -> Columns:
        """Materialize back to flat rows (host-side, used by shuffles):
        one boolean-mask take over the padded layout per column — the
        row-major (worker-major) mask reproduces the per-worker
        concatenation order exactly.  A bucketed layout takes the same
        worker-major rows through its slot-offset index, so the output is
        bit-identical across layouts."""
        counts = np.asarray(self.counts)
        cols = self.host_columns()
        if self.capacity_map is not None:
            idx = valid_slot_index(counts, self.capacity_map.offsets)
            return {k: v[idx] for k, v in cols.items()}
        m, cap = self.num_workers, self.capacity
        mask = (np.arange(cap) < counts[:, None]).reshape(-1)
        return {k: v.reshape((m * cap,) + v.shape[2:])[mask]
                for k, v in cols.items()}

    def host_columns(self) -> Columns:
        """Every column as a host array, layout unchanged.  A device column
        is fetched on first use and its copy kept with it (a swapped
        column container is fetched anew)."""
        out: Columns = {}
        for k, v in self.columns.items():
            if isinstance(v, jax.Array):
                src, host = self._host.get(k, (None, None))
                if src is not v:
                    host = fetch(v)
                    self._host[k] = (v, host)
                v = host
            out[k] = np.asarray(v)
        return out

    def set_column(self, name: str, v) -> None:
        """Swap column ``name``'s container (same values), dropping the
        host copy of the container it replaces."""
        self.columns[name] = v
        self._host.pop(name, None)

    def to_host(self) -> "StoredDataset":
        """Copy with every column materialized as numpy (layout unchanged)."""
        cols = self.host_columns()
        return StoredDataset(name=self.name, columns=cols,
                             counts=self.counts, partitioner=self.partitioner,
                             num_rows=self.num_rows, nbytes=self.nbytes,
                             created_at=self.created_at,
                             generation=self.generation,
                             capacity_map=self.capacity_map)


class PartitionStore:
    def __init__(self, num_workers: int = 8, backend: str = "host",
                 max_retired_generations: int = 2,
                 registry=None,
                 root: Optional[str] = None,
                 memory_budget_bytes: Optional[int] = None,
                 autoflush: bool = True,
                 write_log_cap: int = DEFAULT_WRITE_LOG_CAP,
                 adaptive_capacity: bool = False,
                 capacity_threshold: float = 0.75,
                 cluster=None):
        from ..core.backends import resolve_backend
        # UnknownBackendError on typos; `registry` (default: the global
        # one) lets a Session thread its own registry through, so custom
        # backends registered there resolve here too
        b = resolve_backend(backend, registry)
        self.backend = b.name
        # capability, not name: a registered custom backend with
        # device_resident=True gets device-resident columns too
        self._device_resident = b.device_resident
        self._storage_prefetch = b.storage_prefetch
        # skew-adaptive layout (DESIGN §12): opt-in — when on, writes whose
        # histogram is skewed enough get a bucketed CapacityMap layout
        # instead of the uniform worst-case capacity
        self.adaptive_capacity = bool(adaptive_capacity)
        self.capacity_threshold = float(capacity_threshold)
        self.datasets: Dict[str, StoredDataset] = {}
        self.write_log: List[Dict[str, Any]] = []
        self.write_log_cap = int(write_log_cap)
        #: monotone aggregates over ALL writes (including entries evicted
        #: from the bounded write_log) — benchmarks read these
        self.write_totals: Dict[str, float] = {
            "entries": 0, "rows": 0, "bytes": 0, "latency_s": 0.0,
            "evicted": 0, "padded_bytes": 0, "valid_bytes": 0,
            "max_skew": 0.0}
        # generation machinery (DESIGN §8): `datasets` maps each name to its
        # CURRENT generation; superseded generations are retained (bounded)
        # so in-flight readers and audits can still resolve them by number.
        self.max_retired_generations = max_retired_generations
        self._retired: Dict[str, List[StoredDataset]] = {}
        # Concurrency contract (DESIGN §11): the name→StoredDataset pointer
        # flip is one dict assignment, so READS ARE LOCK-FREE — a reader
        # resolves the current generation with a plain dict lookup and then
        # owns an immutable object.  ``_swap_lock`` is the writer side: it
        # serializes pointer flips, retired-list maintenance and container
        # swaps (spill/prefetch) so writers never interleave, while readers
        # never wait.
        self._swap_lock = threading.Lock()
        self._install_locks: Dict[str, threading.Lock] = {}
        self._log_lock = threading.Lock()
        self._evict_lock = threading.Lock()
        # test-only injectable sync points (tests/test_serving_races.py):
        # named callables invoked at the store's sharp edges so races are
        # reproduced deterministically with events, not sleeps.  Empty dict
        # in production — one dict lookup per crossing, no locking.
        self._sync_points: Dict[str, Callable[[], None]] = {}
        # durable tier (DESIGN §10)
        self.autoflush = autoflush
        self.memory_budget_bytes = memory_budget_bytes
        self._dirty: set = set()
        self._last_access: Dict[str, int] = {}
        self._access_clock = itertools.count(1)
        self.durable = None
        # cluster tier (DESIGN §14): health tracking + the rebalance path
        # exist only when the durable tier is a ClusterDurableStore
        self.health = None
        # durable-only observability (DESIGN §15): per-run telemetry
        # history and the regression watchdog reading it
        self.telemetry = None
        self.watchdog = None
        if cluster is not None and root is None:
            raise ValueError("cluster=ClusterConfig(...) needs root= "
                             "(nodes are directories under the store root)")
        if root is not None:
            from .storage.durable import DurableStore
            if cluster is not None or os.path.exists(
                    os.path.join(root, "cluster.json")):
                if memory_budget_bytes is not None:
                    raise ValueError(
                        "a cluster store does not support "
                        "memory_budget_bytes: columns are reassembled "
                        "in RAM from per-node parts and cannot be "
                        "memmap-swapped to a single local segment")
                from ..cluster.control import ClusterHealth
                from ..cluster.node import ClusterDurableStore
                self.durable = ClusterDurableStore(
                    root, num_workers=num_workers,
                    max_retired_generations=max_retired_generations,
                    cluster=cluster)
                # health watches the LIVE membership (directory epoch),
                # not the bootstrap config; wired before _attach so the
                # very first reads feed the straggler detector
                self.health = ClusterHealth(self.durable.directory.nodes)
                self.durable.health = self.health
            else:
                self.durable = DurableStore(
                    root, num_workers=num_workers,
                    max_retired_generations=max_retired_generations)
            # an existing catalog is authoritative for the worker count —
            # segment layouts are (m, capacity) and cannot be re-bucketed
            # on open without a shuffle
            if self.durable.num_workers is not None:
                num_workers = self.durable.num_workers
            # durable telemetry history + regression watchdog (DESIGN
            # §15) live under the same root, so profiles and baselines
            # survive restarts with the data they describe
            from ..obs.telemetry import TelemetryStore
            from ..obs.watchdog import RegressionDetector
            self.telemetry = TelemetryStore(root)
            self.watchdog = RegressionDetector(self.telemetry)
            self._attach()
        self.m = num_workers

    @classmethod
    def open(cls, root: str, **kwargs) -> "PartitionStore":
        """Reattach to a durable store directory written by a previous
        process.  Worker count and dataset layouts come from the on-disk
        catalog; ``backend=`` etc. are this process's choices."""
        return cls(root=root, **kwargs)

    @property
    def is_durable(self) -> bool:
        return self.durable is not None

    @property
    def root(self) -> Optional[str]:
        return self.durable.root if self.durable is not None else None

    # -- cluster tier (DESIGN §14) -------------------------------------------
    @property
    def is_cluster(self) -> bool:
        return getattr(self.durable, "is_cluster", False)

    @property
    def directory(self):
        """Current :class:`~repro.cluster.directory.PartitionDirectory`
        epoch (None on a non-cluster store)."""
        return self.durable.directory if self.is_cluster else None

    @property
    def cluster_config(self):
        return self.durable.cluster if self.is_cluster else None

    @property
    def placement_epoch(self) -> int:
        """Placement generation the planner pins into PlanKeys: a
        rebalance bumps it, invalidating exactly the plans compiled
        against the old placement.  -1 on non-cluster stores (one value
        for every single-host store, so their keys are unaffected)."""
        return self.durable.directory.epoch if self.is_cluster else -1

    def plan_rebalance(self, **kwargs):
        """Plan (without applying) an incremental placement change —
        see :meth:`repro.cluster.rebalancer.Rebalancer.plan`."""
        from ..cluster.rebalancer import Rebalancer
        return Rebalancer(self).plan(**kwargs)

    def rebalance(self, plan=None, *, abort_after: Optional[int] = None,
                  on_abort=None, **kwargs):
        """Apply a placement change: ``plan`` from :meth:`plan_rebalance`,
        or plan-and-apply in one step (kwargs as for plan_rebalance).
        Returns a :class:`~repro.cluster.rebalancer.RebalanceResult`."""
        from ..cluster.rebalancer import Rebalancer
        r = Rebalancer(self)
        if plan is None:
            plan = r.plan(**kwargs)
        return r.apply(plan, abort_after=abort_after, on_abort=on_abort)

    def _attach(self) -> None:
        """Load every dataset's newest consistent generation as memmap
        views (zero-copy; nothing is paged in until first touch)."""
        for name, ds in self.durable.load_all().items():
            self.datasets[name] = ds

    def _log_write(self, entry: Dict[str, Any]) -> None:
        """Append a write_log row, folding overflow into the monotone
        aggregates so the log stays bounded under sustained traffic.
        Serialized: concurrent writers (the serving tier) must not lose
        counter increments to read-modify-write races."""
        with self._log_lock:
            self.write_log.append(entry)
            t = self.write_totals
            t["entries"] += 1
            t["rows"] += int(entry.get("rows", 0))
            t["bytes"] += int(entry.get("bytes", 0))
            t["latency_s"] += float(entry.get("latency", 0.0))
            t["padded_bytes"] += int(entry.get("padded_bytes", 0))
            t["valid_bytes"] += int(entry.get("valid_bytes", 0))
            t["max_skew"] = max(t["max_skew"],
                                float(entry.get("skew", 0.0)))
            while len(self.write_log) > self.write_log_cap:
                self.write_log.pop(0)
                t["evicted"] += 1

    def write_stats(self) -> Dict[str, float]:
        """Cumulative write counters (monotone across write_log eviction)."""
        with self._log_lock:
            return dict(self.write_totals)

    def register_metrics(self, registry) -> None:
        """Expose this store's cumulative stats through a
        :class:`~repro.obs.metrics.MetricsRegistry` (idempotent per
        registry).  The internal representations stay authoritative —
        ``write_totals`` folds evicted log rows, ``io_snapshot`` lives in
        the durable tier — so they are contributed as snapshot-time
        callbacks rather than migrated to registry counters."""
        marker = id(registry)
        regs = getattr(self, "_metric_registries", None)
        if regs is None:
            regs = self._metric_registries = set()
        if marker in regs:
            return
        regs.add(marker)
        registry.register_callback(self, PartitionStore._metric_samples)
        # the watchdog's coalesce-rate series reads serving counters out
        # of whichever registry the session exports through
        if self.watchdog is not None and self.watchdog.registry is None:
            self.watchdog.registry = registry

    def _metric_samples(self):
        for k, v in self.write_stats().items():
            yield f"store_write_{k}", {}, float(v)
        for k, v in self.io_snapshot().items():
            yield f"store_io_{k}", {}, float(v)
        yield "store_datasets", {}, float(len(self.datasets))
        yield "store_resident_bytes", {}, float(self.resident_bytes())
        if self.telemetry is not None:
            st = self.telemetry.stats()
            yield "telemetry_records", {}, float(st["records"])
            yield "telemetry_appends_total", {}, float(st["appends"])
            yield "telemetry_compactions_total", {}, float(st["compactions"])
        if self.watchdog is not None:
            yield ("watchdog_perf_regressions_total", {},
                   float(self.watchdog.raised_total))
            yield "watchdog_checks_total", {}, float(self.watchdog.checks)
        if self.is_cluster:
            for k, v in self.durable.cluster_snapshot().items():
                yield f"cluster_{k}", {}, float(v)
            d = self.durable.directory
            yield "cluster_epoch", {}, float(d.epoch)
            yield "cluster_directory_lookups_total", {}, float(d.lookups)
            yield "cluster_nodes", {}, float(len(d.nodes))
            if self.health is not None:
                yield ("cluster_heartbeat_misses_total", {},
                       float(self.health.heartbeat_misses))
                yield ("cluster_straggler_reissues_total", {},
                       float(self.health.straggler_reissues))
                yield ("cluster_nodes_alive", {},
                       float(len(self.health.alive_nodes())))

    # -- test-only race instrumentation (DESIGN §11) -------------------------
    def set_sync_point(self, point: str,
                       fn: Optional[Callable[[], None]]) -> None:
        """Install (or with ``None`` remove) a callable invoked when store
        internals cross ``point`` — e.g. ``install:pre_flip``,
        ``spill:column`` — so concurrency tests reproduce interleavings
        deterministically with :class:`threading.Event` barriers instead of
        sleeps.  Production stores never set these."""
        if fn is None:
            self._sync_points.pop(point, None)
        else:
            self._sync_points[point] = fn

    def _sync(self, point: str) -> None:
        fn = self._sync_points.get(point)
        if fn is not None:
            fn()

    def _name_lock(self, name: str) -> threading.Lock:
        with self._swap_lock:
            return self._install_locks.setdefault(name, threading.Lock())

    def _install(self, name: str, ds: StoredDataset,
                 persist: Optional[Callable[[StoredDataset], Any]] = None
                 ) -> StoredDataset:
        """Atomically make ``ds`` the current generation of ``name``.

        The flip is a single dict assignment under the (global) swap lock;
        readers that already hold the previous StoredDataset keep reading
        it unchanged (generations are immutable).  On a durable store with
        autoflush the generation is persisted (segments → manifest →
        CURRENT) *before* the in-memory flip, so the disk pointer never
        runs ahead of a generation that fully exists.  The fsync-bound
        persist runs under a per-NAME lock only (it serializes the
        generation sequence of this dataset), so a slow background
        repartition of one dataset never blocks writers of another.

        ``persist`` overrides the default durable publication for this
        install (always invoked, regardless of autoflush) — the
        Rebalancer passes one that republishes under a NEW placement
        epoch, keeping the flip semantics identical for MVCC readers."""
        with _span("store.install", "store", dataset=name) as sp:
            with self._name_lock(name):
                prev = self.datasets.get(name)
                if prev is not None:
                    ds.generation = prev.generation + 1
                if self.durable is not None:
                    if persist is not None:
                        persist(ds)
                        self._dirty.discard(name)
                    elif self.autoflush:
                        self.durable.persist(ds)
                        self._dirty.discard(name)
                    else:
                        self._dirty.add(name)
                self._sync("install:pre_flip")
                with self._swap_lock:
                    if prev is not None:
                        retired = self._retired.setdefault(name, [])
                        retired.append(prev)
                        if len(retired) > self.max_retired_generations:
                            del retired[:len(retired)
                                        - self.max_retired_generations]
                    self.datasets[name] = ds
                self._sync("install:post_flip")
            sp.set(generation=ds.generation)
        self._touch(name)
        self._maybe_evict()
        return ds

    def generation_of(self, name: str) -> int:
        return self.datasets[name].generation

    # -- durability (DESIGN §10) ---------------------------------------------
    def flush(self, name: Optional[str] = None) -> int:
        """Persist pending generations to the durable tier (all datasets,
        or just ``name``).  Returns the number of generations published.
        No-op (0) on a memory-only store."""
        if self.durable is None:
            return 0
        names = [name] if name is not None else sorted(list(self.datasets))
        published = 0
        for n in names:
            ds = self.datasets.get(n)
            if ds is None:
                continue
            if n in self._dirty or not self.durable.has_generation(
                    n, ds.generation):
                self.durable.persist(ds)
                self._dirty.discard(n)
                published += 1
        return published

    def io_snapshot(self) -> Dict[str, float]:
        """Copy of the durable tier's I/O counters (zeros when memory-only).
        The executor diffs this around a run to attribute storage I/O."""
        if self.durable is None:
            return {}
        return self.durable.io_snapshot()

    # -- eviction loop ---------------------------------------------------------
    def _touch(self, name: str) -> None:
        # itertools.count is a single C-level op — atomic under the GIL, so
        # concurrent readers never lose a tick (LRU stays consistent)
        self._last_access[name] = next(self._access_clock)

    def resident_bytes(self) -> int:
        """Bytes of column data currently held in RAM/device memory (spilled
        memmap views count as 0 — they are disk-backed).  Retired-but-
        retained generations count too: they hold real memory until their
        retention window closes."""
        with self._swap_lock:
            # snapshot under the writer lock: a concurrent install/retire
            # must not resize these containers mid-iteration
            live = list(self.datasets.values())
            retired = [d for lst in self._retired.values() for d in lst]
        total = 0
        for ds in live + retired:
            for v in list(ds.columns.values()):
                if not isinstance(v, np.memmap):
                    total += int(v.nbytes)
        return total

    def namespace_bytes(self, prefix: str = "") -> int:
        """Logical bytes of every current-generation dataset whose name
        starts with ``prefix`` — the serving tier's per-tenant accounting
        (tenants own disjoint name prefixes, DESIGN §11)."""
        with self._swap_lock:
            live = [d for n, d in self.datasets.items()
                    if n.startswith(prefix)]
        return int(sum(d.nbytes for d in live))

    def is_spilled(self, name: str) -> bool:
        return self.datasets[name].spilled

    def spill(self, name: str) -> bool:
        """Evict ``name``'s current generation to its segment files: columns
        become read-only memmap views (bit-identical by construction).
        Persists first if the generation isn't durable yet.  Returns False
        on a memory-only store, and on a cluster store (assembled columns
        span per-node parts — no single local segment to memmap)."""
        if self.durable is None or self.is_cluster:
            return False
        # the per-name lock serializes spill against a concurrent _install
        # of the same dataset (the generation sequence stays linear); other
        # datasets' writers are unaffected
        with _span("store.spill", "store", dataset=name) as sp:
            with self._name_lock(name):
                ds = self.datasets[name]
                if ds.spilled:
                    return True
                self.flush(name)
                man = self.durable.load_manifest(name, ds.generation)
                if man is None:          # validation failed — keep resident
                    sp.set(ok=False)
                    return False
                sp.set(generation=ds.generation)
                return self._swap_to_segments(ds, man)

    def _swap_to_segments(self, ds: StoredDataset, man) -> bool:
        """Replace ``ds``'s column containers with memmap views of their
        persisted segments (same bits, shared by every reader).

        Each column flips under the writer lock individually; a reader
        mid-``gather()`` may observe some columns in RAM and some as
        memmap views — bit-identical by construction, so the immutable-
        values contract holds (the ``spill:column`` sync point lets the
        race tests freeze exactly that mixed state)."""
        freed = sum(int(v.nbytes) for v in list(ds.columns.values())
                    if not isinstance(v, np.memmap))
        cols = self.durable.open_columns(ds.name, man)
        for k in list(ds.columns):
            self._sync("spill:column")
            with self._swap_lock:
                ds.set_column(k, cols[k])
        self._sync("spill:post_swap")
        self.durable.io_add(spills=1, spilled_bytes=freed)
        return True

    def _spill_retired(self) -> int:
        """Evict retired-but-retained generations first: they hold real
        memory, are never read on the hot path, and the durable tier
        retains the same generation window on disk."""
        spilled = 0
        for name, lst in self._retired.items():
            for old in lst:
                if old.spilled:
                    continue
                if not self.durable.has_generation(name, old.generation):
                    # segments + manifest only: CURRENT must never move
                    # backwards to a superseded generation
                    self.durable.persist(old, publish_current=False)
                man = self.durable.load_manifest(name, old.generation)
                if man is not None and self._swap_to_segments(old, man):
                    spilled += 1
        return spilled

    def prefetch(self, name: str) -> bool:
        """Promote a spilled dataset back to residency: in-RAM copies on a
        host store, device arrays (host→device prefetch) on a
        device-resident one.  Returns True when the dataset is resident."""
        with _span("store.prefetch", "store", dataset=name) as psp:
            with self._name_lock(name):
                ds = self.datasets[name]
                if not ds.spilled:
                    return True
                t0 = time.perf_counter()
                loaded = 0
                promoted: Columns = {}
                for k, v in list(ds.columns.items()):
                    arr = np.array(v)    # one sequential segment read
                    loaded += int(arr.nbytes)
                    if self._storage_prefetch:
                        promoted[k] = jax.numpy.asarray(arr) \
                            if dtype_roundtrips(arr.dtype) else arr
                    else:
                        promoted[k] = arr
                self._sync("prefetch:pre_swap")
                with self._swap_lock:
                    for k in list(ds.columns):
                        ds.set_column(k, promoted[k])
                if self.durable is not None:
                    self.durable.io_add(bytes_read=loaded,
                                        read_s=time.perf_counter() - t0,
                                        rehydrations=1,
                                        rehydrated_bytes=loaded)
                psp.set(bytes=loaded)
        self._touch(name)
        self._maybe_evict(exclude=name)
        return True

    def _maybe_evict(self, exclude: Optional[str] = None) -> int:
        """Enforce ``memory_budget_bytes``: spill coldest-first (LRU by
        last read/install) until resident bytes fit.  Requires the durable
        tier; a memory-only store never spills."""
        if self.memory_budget_bytes is None or self.durable is None:
            return 0
        # one evictor at a time: concurrent budget-crossers skip instead of
        # queueing up to spill the same victims (the holder restores the
        # invariant for everyone)
        if not self._evict_lock.acquire(blocking=False):
            return 0
        try:
            spilled = 0
            if self.resident_bytes() > self.memory_budget_bytes:
                spilled += self._spill_retired()
            while self.resident_bytes() > self.memory_budget_bytes:
                before = self.resident_bytes()
                with self._swap_lock:
                    candidates = [(n, d.spilled)
                                  for n, d in self.datasets.items()]
                victims = sorted(
                    (n for n, is_spilled in candidates
                     if not is_spilled and n != exclude),
                    key=lambda n: self._last_access.get(n, 0))
                if not victims:
                    break
                if not self.spill(victims[0]):
                    break
                spilled += 1
                if self.resident_bytes() >= before:
                    break                # no progress (e.g. 0-size columns)
            return spilled
        finally:
            self._evict_lock.release()

    # -- write path (storage-time partitioning) ------------------------------
    def write(self, name: str, data: Columns,
              partitioner: Optional[PartitionerCandidate] = None,
              seed: int = 0) -> StoredDataset:
        """Dispatch each row to a worker via ``g(d_i)`` and persist."""
        t0 = time.perf_counter()
        n = len(next(iter(data.values())))
        if partitioner is None:
            partitioner = PartitionerCandidate(graph=None, strategy=ROUND_ROBIN)

        with _span("store.write", "store", dataset=name, rows=n,
                   strategy=partitioner.strategy):
            if self._device_resident:
                columns, counts, cmap = self._dispatch_device(
                    data, partitioner, n, seed)
            else:
                columns, counts, cmap = self._dispatch_host(
                    data, partitioner, n, seed)

        nbytes = int(sum(np.asarray(v).nbytes for v in data.values()))
        ds = StoredDataset(name=name, columns=columns,
                           counts=counts.astype(np.int64),
                           partitioner=partitioner, num_rows=n, nbytes=nbytes,
                           capacity_map=cmap)
        self._install(name, ds)
        self._log_write({
            "name": name, "rows": n, "bytes": nbytes,
            "strategy": partitioner.strategy,
            "latency": time.perf_counter() - t0,
            "skew": ds.skew(),
            "padded_bytes": ds.padded_bytes,
            "valid_bytes": ds.valid_bytes,
            "bucketed": cmap is not None,
            "generation": ds.generation,
        })
        return ds

    def _plan_cmap(self, counts) -> Optional[CapacityMap]:
        """Counts → bucketed CapacityMap when adaptive capacity is on and
        the re-layout saves enough padding; None ⇒ stay uniform."""
        if not self.adaptive_capacity:
            return None
        return plan_capacity_map(counts, threshold=self.capacity_threshold)

    # -- dispatch backends ---------------------------------------------------
    def _host_pids(self, data: Columns, partitioner: PartitionerCandidate,
                   n: int, seed: int) -> np.ndarray:
        pids = np.asarray(partitioner.partition_ids(data, self.m)) \
            if partitioner.strategy != RANDOM else \
            np.random.default_rng(seed).integers(0, self.m, size=n)
        return np.asarray(pids, np.int64)

    def _dispatch_host(self, data, partitioner, n, seed):
        """Host-side numpy dispatch: one counting-sort placement, then a
        single vectorized scatter per column (no per-worker Python loop)."""
        pids = self._host_pids(data, partitioner, n, seed)
        counts = np.bincount(pids, minlength=self.m)
        cmap = self._plan_cmap(counts)
        if cmap is not None:
            dest = _counting_sort_dest(pids, counts, 0,
                                       dest_offsets=cmap.offsets)
            total = cmap.total_slots
            columns: Columns = {}
            for k, v in data.items():
                v = np.asarray(v)
                buf = np.zeros((total,) + v.shape[1:], v.dtype)
                buf[dest] = v
                columns[k] = buf
            return columns, counts, cmap
        cap = int(counts.max()) if n else 1
        dest = _counting_sort_dest(pids, counts, cap)
        columns = {}
        for k, v in data.items():
            v = np.asarray(v)
            buf = np.zeros((self.m * cap,) + v.shape[1:], v.dtype)
            buf[dest] = v
            columns[k] = buf.reshape((self.m, cap) + v.shape[1:])
        return columns, counts, None

    def _dispatch_device(self, data, partitioner, n, seed):
        """Device dispatch (DESIGN §5): hash keys through the Pallas kernel,
        re-bucket with a jax scatter consuming its (pids, histogram) output.
        Keyless/range strategies — and partitioners that opt out of kernel
        dispatch (SaltedPartitioner's pid math is not the plain key hash) —
        keep their host pid computation but still scatter on device, so the
        stored columns are device-resident."""
        with _span("store.pids", "store", h2d_bytes=0, d2h_bytes=0):
            if (partitioner.strategy == HASH and partitioner.graph is not None
                    and getattr(partitioner, "kernel_dispatchable", True)):
                keys = partitioner.key_fn()(data)
                pids, counts = shuffle_pids(keys, self.m)
            else:
                pids = self._host_pids(data, partitioner, n, seed)
                counts = np.bincount(pids, minlength=self.m).astype(np.int64)
        cmap = self._plan_cmap(counts)
        columns = device_scatter_padded(data, pids, counts,
                                        capacity_map=cmap)
        return columns, counts, cmap

    def write_layout(self, name: str, flat_columns: Columns,
                     counts: np.ndarray,
                     partitioner: Optional[PartitionerCandidate],
                     device_columns: Optional[Columns] = None
                     ) -> StoredDataset:
        """Persist an ALREADY-partitioned table (flat columns segmented per
        worker by ``counts``) without re-dispatching — used when a workload
        materializes an output whose layout was produced by its own
        partition nodes (e.g. iterative PageRank writing updated ranks).

        ``device_columns`` — device-resident flats from an upstream device
        shuffle (engine d2d chain); the device placement consumes them in
        place of re-uploading the matching host columns.

        Spanned as ``store.write_layout``: the rows, the new generation,
        the generations retained (``retired``) and the device bytes the
        live and retained generations hold (``held_bytes``)."""
        counts = np.asarray(counts, np.int64)
        n = int(counts.sum())
        with _span("store.write_layout", "store", dataset=name, rows=n,
                   h2d_bytes=0) as sp:
            cmap = self._plan_cmap(counts)
            columns = self._materialize_layout(flat_columns, counts, cmap,
                                               device_columns=device_columns)
            nbytes = int(sum(np.asarray(v).nbytes
                             for v in flat_columns.values()))
            ds = StoredDataset(name=name, columns=columns, counts=counts,
                               partitioner=partitioner, num_rows=n,
                               nbytes=nbytes, capacity_map=cmap)
            ds = self._install(name, ds)
            if sp.recording:
                retired = self.retired_generations(name)
                sp.set(generation=ds.generation, retired=len(retired),
                       held_bytes=sum(d.device_bytes
                                      for d in [ds] + retired))
        return ds

    def retired_generations(self, name: str) -> List[StoredDataset]:
        """The superseded generations of ``name`` the store still holds,
        oldest first."""
        with self._swap_lock:
            return list(self._retired.get(name, ()))

    def _materialize_layout(self, flat_columns: Columns, counts: np.ndarray,
                            cmap: Optional[CapacityMap],
                            device_columns: Optional[Columns] = None
                            ) -> Columns:
        """Rows already segmented per worker (pids implied by ``counts``) →
        padded columns: uniform ``(m, cap, ...)`` when ``cmap`` is None,
        flat bucketed ``(total_slots, ...)`` otherwise.  Shared by
        write_layout and rebucket."""
        n = int(counts.sum())
        cap = int(counts.max()) if n else 1
        if self._device_resident:
            return device_place_presorted(
                flat_columns, counts,
                capacity=None if cmap is not None else cap,
                capacity_map=cmap, device_columns=device_columns)
        if cmap is not None:
            dest = host_presorted_dest(counts, 0, dest_offsets=cmap.offsets)
            total = cmap.total_slots
            columns: Columns = {}
            for k, v in flat_columns.items():
                v = np.asarray(v)
                buf = np.zeros((total,) + v.shape[1:], v.dtype)
                buf[dest] = v
                columns[k] = buf
            return columns
        dest = host_presorted_dest(counts, cap)
        columns = {}
        for k, v in flat_columns.items():
            v = np.asarray(v)
            buf = np.zeros((self.m * cap,) + v.shape[1:], v.dtype)
            buf[dest] = v
            columns[k] = buf.reshape((self.m, cap) + v.shape[1:])
        return columns

    def rebucket(self, name: str) -> Tuple[StoredDataset, int]:
        """Re-layout ``name``'s current generation under a fresh
        :class:`CapacityMap` planned from its live histogram — SAME
        partitioner, so consumer elisions survive and no rows cross the
        network (a local rewrite, not a shuffle).  Publishes the result as
        a new generation via the usual atomic flip; returns
        ``(new ds, 0 bytes moved)``.  A no-op (current ds, 0) when the
        planned layout equals the current one."""
        t0 = time.perf_counter()
        with _span("store.rebucket", "store", dataset=name) as sp:
            ds = self.read(name)
            counts = np.asarray(ds.counts, np.int64)
            cmap = plan_capacity_map(counts,
                                     threshold=self.capacity_threshold)
            if cmap == ds.capacity_map:
                sp.set(noop=True)
                return ds, 0
            flat = flatten_dataset(ds)
            new = StoredDataset(name=name,
                                columns=self._materialize_layout(
                                    flat, counts, cmap),
                                counts=counts, partitioner=ds.partitioner,
                                num_rows=ds.num_rows, nbytes=ds.nbytes,
                                capacity_map=cmap)
            self._install(name, new)
            sp.set(generation=new.generation, bucketed=cmap is not None)
        self._log_write({
            "name": name, "rows": new.num_rows, "bytes": new.nbytes,
            "strategy": ds.partitioner.strategy if ds.partitioner else None,
            "latency": time.perf_counter() - t0,
            "skew": new.skew(),
            "padded_bytes": new.padded_bytes,
            "valid_bytes": new.valid_bytes,
            "bucketed": cmap is not None,
            "path": "rebucket",
            "generation": new.generation,
        })
        return new, 0

    # -- read path -------------------------------------------------------------
    def read(self, name: str,
             generation: Optional[int] = None) -> StoredDataset:
        """Current generation of ``name``; pass ``generation`` to resolve a
        specific (possibly superseded, still-retained) one.

        On a device-resident durable store, reading a spilled dataset
        prefetches it host→device first (DESIGN §10); a host store reads
        straight through the memmap views (lazy page-in).

        Thread-safety (DESIGN §11): the current-generation hot path is
        LOCK-FREE — one dict lookup resolves an immutable StoredDataset,
        and a concurrent ``_install`` pointer flip is invisible to a reader
        that already resolved (generations are never mutated in place).
        Only the retired-generation fallback briefly takes the writer lock
        to snapshot the retention list."""
        ds = self.datasets[name]
        if generation is None or ds.generation == generation:
            self._touch(name)
            if self._storage_prefetch and ds.spilled:
                self.prefetch(name)
                return self.datasets.get(name, ds)
            return ds
        with self._swap_lock:
            retained = list(self._retired.get(name, ()))
        for old in reversed(retained):
            if old.generation == generation:
                return old
        if self.durable is not None:
            # a fresh process retains no in-memory retired generations, but
            # the durable tier keeps the same retention window on disk
            old = self.durable.load(name, generation)
            if old is not None:
                return old
        raise RetiredGenerationError(
            f"{name}@gen{generation} not found "
            f"(current gen {ds.generation}, retains last "
            f"{self.max_retired_generations})")

    def stored_partitioners(self) -> Dict[str, Optional[PartitionerCandidate]]:
        with self._swap_lock:
            return {n: d.partitioner for n, d in self.datasets.items()}

    # -- shuffle (the operation Lachesis exists to avoid) ------------------------
    def repartition(self, ds: StoredDataset,
                    partitioner: PartitionerCandidate,
                    name: Optional[str] = None,
                    mesh=None, swap: bool = False) -> Tuple[StoredDataset, int]:
        """Full shuffle.  Returns (new ds, bytes moved).

        Bytes moved = (m-1)/m of the dataset on average (every row whose new
        worker differs from its current one crosses the network).

        Device-to-device fast path (DESIGN §5): when both the store and the
        dataset are device-backed and the target is a keyed hash
        partitioner, the shuffle runs entirely on device — flatten by a
        device gather, hash with the compiled key projection, counting-sort
        scatter into the new layout — with no host ``gather()``/concatenate.
        Pass ``mesh`` to commit the result back onto the mesh
        (``sharding_bridge.device_put_dataset``) so repartitioned datasets
        stay mesh-placed.

        ``swap=True`` (DESIGN §8) rewrites the dataset *in place* as a new
        generation under its own name: the whole shuffle materializes off
        to the side, then one atomic pointer flip publishes it.  Concurrent
        readers holding the previous generation keep a consistent view."""
        t0 = time.perf_counter()
        moved = int(ds.nbytes * (self.m - 1) / self.m)
        name = name or (ds.name if swap else ds.name + "@reparted")
        with _span("store.repartition", "store", dataset=name,
                   bytes_moved=moved, swap=swap) as rsp:
            return self._repartition(ds, partitioner, name, mesh, swap,
                                     moved, t0, rsp)

    def _repartition(self, ds, partitioner, name, mesh, swap, moved, t0,
                     rsp) -> Tuple[StoredDataset, int]:
        if mesh is not None:
            from ..core.sharding_bridge import device_put_dataset
        if (self._device_resident and ds.backend == "device"
                and partitioner.strategy == HASH
                and partitioner.graph is not None
                and getattr(partitioner, "kernel_dispatchable", True)):
            rsp.set(path="d2d")
            columns, counts, cmap = device_repartition_dataset(
                ds, partitioner, self.m, plan_capacity=self._plan_cmap)
            new = StoredDataset(name=name, columns=columns, counts=counts,
                                partitioner=partitioner,
                                num_rows=int(counts.sum()),
                                nbytes=ds.nbytes, capacity_map=cmap)
            if mesh is not None:
                new = device_put_dataset(mesh, new)
            self._install(name, new)
            self._log_write({
                "name": name, "rows": new.num_rows, "bytes": new.nbytes,
                "strategy": partitioner.strategy,
                "latency": time.perf_counter() - t0,
                "skew": new.skew(), "path": "d2d",
                "padded_bytes": new.padded_bytes,
                "valid_bytes": new.valid_bytes,
                "bucketed": cmap is not None,
                "generation": new.generation,
            })
        else:
            rsp.set(path="host")
            flat = ds.gather()
            new = self.write(name, flat, partitioner)
            if mesh is not None:
                # same generation, mesh-placed columns — re-publish only if
                # no newer generation landed while we were placing (CAS)
                new = device_put_dataset(mesh, new)
                with self._swap_lock:
                    cur = self.datasets.get(name)
                    if cur is not None and cur.generation == new.generation:
                        self.datasets[name] = new
        return new, moved
