"""Device-resident repartition path (DESIGN §5).

The paper's dispatch hot spot — hash the partition key, histogram the
destinations, re-bucket every column — runs here as a **single-pass device
shuffle**: one jitted pipeline per shape bucket that hashes, counting-sorts
and permutes/scatters without ever leaving the device.  Three consumers:

* the :class:`~repro.data.partition_store.PartitionStore` device write path
  (:func:`device_scatter_padded` — scatter flat rows into the persistent
  ``(m, capacity, ...)`` layout),
* the engine's repartition node (:func:`device_rebucket` /
  :func:`device_rebucket_full` — re-bucket a flat intermediate into worker
  segments), and
* :func:`device_repartition_dataset` — the device-to-device fast path that
  reshuffles a device-resident ``StoredDataset`` into a new layout without
  a host ``gather()``.

**Dispatch plans.**  A :class:`ShufflePlan` is the jitted
hash → counting-sort → permute/scatter pipeline for one
``(shape-bucket, dtype-set, m, capacity)`` key.  Row counts are padded up to
a power-of-two bucket and the valid count rides along as a traced scalar
(scalar-prefetched into the kernel), so repeated shuffles of any N in the
bucket reuse one trace — ``plan_cache_stats()`` exposes the trace counter
the no-retrace tests assert on.  Same-dtype round-trippable columns are
packed into a single ``(B, C)`` matrix, so K columns cost one gather/scatter
and one host sync, not K.

**Counting sort, not argsort.**  Each row's destination is its stable
counting-sort position: per-partition base offsets from an exclusive prefix
sum over the histogram plus a running stable rank — an O(N) placement
replacing the O(N log N) ``jnp.argsort`` + per-column eager gather the old
path paid.  Two executions of the same math, picked per backend
(``mode``):

* ``"fused"`` (TPU default) — everything inside one jit: the
  ``hash_partition_padded`` kernel emits pids with padding routed to an
  overflow partition ``m``, ``scatter_perm`` computes the permutation (a
  stable rank on the MXU over prefix-summed base offsets), and the packed
  gather/scatter rides the same
  trace.  One device dispatch per shuffle.
* ``"hostperm"`` (CPU default) — XLA-on-CPU sorts/scatters are an order of
  magnitude slower than numpy, so the permutation is computed host-side
  (numpy radix sort over small-int pids: O(N)) and only the packed
  gather — the part XLA-CPU is actually good at — stays jitted.  Plans are
  still cached and traced exactly once per bucket.

Bit-identical guarantee: both modes apply the same Wang hash as
``core.ir._mix_hash`` and reproduce the stable-sort order exactly — no
arithmetic touches the payload — so device results match the host numpy
path bit-for-bit (asserted by the kernel, plan, and property tests).  With
jax's default x64-disabled config, 64-bit payload columns cannot round-trip
through jnp; those are gathered host-side by the same permutation (hybrid
gather), preserving exact bits and dtypes either way.
"""

from __future__ import annotations

import threading

from collections import OrderedDict
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.hash_partition.ops import (padded_partition_ids,
                                          partition_ids, scatter_permutation)
from ..obs.tracer import span as _span
from .capacity import CapacityMap, bucket_capacity, valid_slot_index
from .transfer import fetch, fetch_tree, upload

Columns = Dict[str, Any]

MODES = ("fused", "hostperm")


def default_interpret() -> bool:
    """Pallas kernels need interpret mode anywhere but a real TPU."""
    return jax.default_backend() != "tpu"


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else interpret


def default_use_kernel() -> bool:
    """Kernels compile on TPU; elsewhere the jitted jnp oracle is the
    bit-identical stand-in (interpret-mode kernels are correctness coverage,
    exercised explicitly by the kernel tests)."""
    return jax.default_backend() == "tpu"


def _resolve_use_kernel(use_kernel: Optional[bool]) -> bool:
    return default_use_kernel() if use_kernel is None else use_kernel


def default_mode() -> str:
    return "fused" if jax.default_backend() == "tpu" else "hostperm"


def _resolve_mode(mode: Optional[str]) -> str:
    mode = default_mode() if mode is None else mode
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}")
    return mode


def dtype_roundtrips(dtype) -> bool:
    """True if jnp.asarray preserves this dtype under the active jax config
    (x64-disabled canonicalizes int64/float64 down — those columns must stay
    host-side to keep the bit-identical guarantee)."""
    return jnp.asarray(np.empty(0, dtype)).dtype == np.dtype(dtype)


def as_kernel_keys(keys) -> jax.Array:
    """Normalize a key column for the hash kernel.

    Mirrors ``core.ir._mix_hash``'s dtype handling exactly (float32 bits are
    reinterpreted, everything else is cast to int32 with jnp's canonical
    truncation) so kernel pids equal host pids bit-for-bit.  Device-resident
    keys are normalized with jnp ops — no host round-trip.
    """
    if isinstance(keys, jax.Array):
        k = keys.reshape(-1)
        if jnp.issubdtype(k.dtype, jnp.integer):
            return k.astype(jnp.int32)
        if k.dtype == jnp.float32:
            return k.view(jnp.int32)
        return k.astype(jnp.int32)
    k = np.asarray(keys).reshape(-1)
    if np.issubdtype(k.dtype, np.integer):
        return upload(k.astype(np.int32))
    if k.dtype == np.float64:                     # jnp canonicalizes f64→f32
        k = k.astype(np.float32)
    if k.dtype == np.float32:
        return upload(k.view(np.int32))
    return upload(k.astype(np.int32))


def _host_kernel_keys(keys) -> np.ndarray:
    """Host-side twin of :func:`as_kernel_keys` (int32, same truncation)."""
    k = np.asarray(keys).reshape(-1)
    if np.issubdtype(k.dtype, np.integer) or k.dtype == np.bool_:
        return k.astype(np.int32)
    if k.dtype == np.float64:
        k = k.astype(np.float32)
    if k.dtype == np.float32:
        return k.view(np.int32)
    return k.astype(np.int32)


def _host_wang(x: np.ndarray) -> np.ndarray:
    """Numpy twin of ref.wang_hash — identical uint32 arithmetic."""
    with np.errstate(over="ignore"):
        x = x.astype(np.uint32)
        x = (x ^ np.uint32(61)) ^ (x >> np.uint32(16))
        x = x * np.uint32(9)
        x = x ^ (x >> np.uint32(4))
        x = x * np.uint32(0x27D4EB2D)
        x = x ^ (x >> np.uint32(15))
    return x


@partial(jax.jit, static_argnames=("num_partitions",))
def _hash_pids_jit(keys, num_partitions: int) -> jax.Array:
    """Elementwise hash → pid, no histogram (the histogram is cheaper on
    the host when the permutation is computed there anyway)."""
    from ..kernels.hash_partition.ref import wang_hash
    return (wang_hash(keys) % jnp.uint32(num_partitions)).astype(jnp.int32)


def device_partition_ids(keys, num_partitions: int, *,
                         interpret: Optional[bool] = None,
                         use_kernel: Optional[bool] = None
                         ) -> Tuple[jax.Array, jax.Array]:
    """Kernel dispatch: keys → (pids (N,) int32, histogram (m,) int32)."""
    keys = as_kernel_keys(keys)
    if keys.shape[0] == 0:           # zero-size grids crash pallas_call
        return (jnp.zeros(0, jnp.int32),
                jnp.zeros(num_partitions, jnp.int32))
    return partition_ids(keys, num_partitions,
                         interpret=_resolve_interpret(interpret),
                         use_kernel=_resolve_use_kernel(use_kernel))


def shuffle_pids(keys, num_partitions: int, *,
                 interpret: Optional[bool] = None,
                 use_kernel: Optional[bool] = None,
                 mode: Optional[str] = None
                 ) -> Tuple[Any, np.ndarray]:
    """Mode-matched pid computation: ``(pids, counts (m,) np.int64)``.

    fused → kernel/oracle hash+histogram on device (pids stay device);
    hostperm → device keys hash through a tiny jitted elementwise pass, host
    keys hash with the numpy Wang twin; histogram via np.bincount.
    """
    mode = _resolve_mode(mode)
    if mode == "fused":
        pids, hist = device_partition_ids(keys, num_partitions,
                                          interpret=interpret,
                                          use_kernel=use_kernel)
        return pids, fetch(hist).astype(np.int64)
    if isinstance(keys, jax.Array):
        # bucket the key length so the elementwise jit never retraces per N
        k = as_kernel_keys(keys)
        n = int(k.shape[0])
        B = shape_bucket(n)
        k_p = k if n == B else jnp.zeros(B, jnp.int32).at[:n].set(k)
        pids = fetch(_hash_pids_jit(k_p, num_partitions))[:n]
    else:
        pids = (_host_wang(_host_kernel_keys(keys))
                % np.uint32(num_partitions)).astype(np.int32)
    counts = np.bincount(pids, minlength=num_partitions).astype(np.int64)
    return pids, counts


# ---------------------------------------------------------------------------
# Host counting-sort placement (shared with the store's host dispatch)
# ---------------------------------------------------------------------------

def host_counting_order(pids: np.ndarray) -> np.ndarray:
    """Stable order of rows grouped by pid — numpy radix sort (O(N)) when
    the pids fit in int16, stable mergesort otherwise.  Identical output to
    ``np.argsort(pids, kind="stable")`` either way."""
    if pids.size and pids.max(initial=0) < np.iinfo(np.int16).max:
        return np.argsort(pids.astype(np.int16), kind="stable")
    return np.argsort(pids, kind="stable")


def host_counting_sort_dest(pids: np.ndarray, counts: np.ndarray,
                            cap: int,
                            dest_offsets: Optional[np.ndarray] = None
                            ) -> np.ndarray:
    """Flat destination slot (partition base + stable rank-within-pid) of
    every row — one vectorized counting-sort placement shared by all
    columns.  The uniform layout's base is ``pid * cap``; a bucketed layout
    passes its own per-partition ``dest_offsets``."""
    n = pids.shape[0]
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
    order = host_counting_order(pids)
    rank = np.empty(n, np.int64)
    rank[order] = np.arange(n, dtype=np.int64) - offsets[pids[order]]
    if dest_offsets is None:
        return pids * cap + rank
    return np.asarray(dest_offsets, dtype=np.int64)[pids] + rank


def host_presorted_dest(counts: np.ndarray, cap: int,
                        dest_offsets: Optional[np.ndarray] = None
                        ) -> np.ndarray:
    """The same placement for rows already segmented per worker (a
    ``write_layout``): no sort, the worker of each row is implied by the
    segmentation.  A bucketed layout passes its ``dest_offsets``."""
    m = counts.shape[0]
    pids = np.repeat(np.arange(m, dtype=np.int64), counts)
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
    rank = np.arange(pids.shape[0], dtype=np.int64) - offsets[pids]
    if dest_offsets is None:
        return pids * cap + rank
    return np.asarray(dest_offsets, dtype=np.int64)[pids] + rank


# ---------------------------------------------------------------------------
# Shape buckets and column packing
# ---------------------------------------------------------------------------

def shape_bucket(n: int) -> int:
    """Pad row counts up to a power of two so nearby Ns share one trace."""
    return max(8, 1 << (int(n) - 1).bit_length())


@dataclass
class _Pack:
    """Same-dtype round-trippable columns flattened into one (rows, C)
    matrix — one upload + one gather/scatter + one download per dtype."""
    dtype: np.dtype
    width: int                                   # C = sum of member widths
    members: List[Tuple[str, Tuple[int, ...], int, int]]  # name, trail, c0, c1
    data: Any = None                             # (rows, C) np or jax array


def _split_columns(columns: Columns,
                   device_columns: Optional[Columns] = None
                   ) -> Tuple[List[Tuple[str, Any]], List[Tuple[str, Any]]]:
    """(device-eligible cols, host-only cols); device-resident copies from
    ``device_columns`` are preferred so an upstream device stage's output
    feeds the next shuffle without re-uploading."""
    dev, host = [], []
    for k, v in columns.items():
        src = v
        if device_columns is not None and k in device_columns:
            src = device_columns[k]
        dt = src.dtype if isinstance(src, jax.Array) else np.asarray(v).dtype
        if dtype_roundtrips(dt):
            dev.append((k, src))
        else:
            host.append((k, np.asarray(v)))
    return dev, host


def _build_packs(dev_cols: List[Tuple[str, Any]], n: int,
                 rows: int) -> List[_Pack]:
    """Group device-eligible columns by dtype into (rows, C) pack matrices;
    rows beyond n are zero padding (never read back)."""
    groups: Dict[str, _Pack] = {}
    for name, v in dev_cols:
        dt = np.dtype(str(v.dtype))
        trail = tuple(v.shape[1:])
        w = int(np.prod(trail)) if trail else 1
        p = groups.setdefault(str(dt), _Pack(dtype=dt, width=0, members=[]))
        p.members.append((name, trail, p.width, p.width + w))
        p.width += w
    packs = sorted(groups.values(), key=lambda p: str(p.dtype))
    by_name = dict(dev_cols)
    for p in packs:
        on_device = any(isinstance(by_name[nm], jax.Array)
                        for nm, *_ in p.members)
        if on_device:         # keep the pack on device — no host round-trip
            flat = [upload(by_name[nm]).reshape(n, -1)
                    for nm, *_ in p.members]
            cat = flat[0] if len(flat) == 1 else jnp.concatenate(flat, axis=1)
            p.data = jnp.zeros((rows, p.width), p.dtype).at[:n].set(cat)
        else:
            buf = np.zeros((rows, p.width), p.dtype)
            for nm, _trail, c0, c1 in p.members:
                buf[:n, c0:c1] = np.asarray(by_name[nm]).reshape(n, -1)
            p.data = buf                     # one jnp upload at call time
    return packs


def _pack_spec(packs: List[_Pack]) -> Tuple[Tuple[str, int], ...]:
    return tuple((str(p.dtype), p.width) for p in packs)


# ---------------------------------------------------------------------------
# ShufflePlan: the jitted permute/scatter pipelines, cached per shape bucket
# ---------------------------------------------------------------------------

@dataclass
class ShufflePlan:
    """One compiled dispatch plan, keyed on
    (kind, shape-bucket, dtype-set, m, capacity, mode)."""
    key: Tuple
    fn: Callable = None
    traces: int = 0          # bumped inside the traced body — retrace counter
    calls: int = 0


# LRU-bounded plan cache.  A long-lived optimizer service shuffles many
# (shape-bucket, dtype-set, m, capacity) keys over its lifetime; an unbounded
# dict would pin every jitted executable it ever traced.  Least-recently-used
# plans are evicted past the capacity; their trace/call counters fold into
# ``_RETIRED`` so ``plan_cache_stats()`` totals stay monotone across
# evictions (the no-retrace assertions keep working).
_PLANS: "OrderedDict[Tuple, ShufflePlan]" = OrderedDict()
_PLAN_CACHE_CAPACITY = 64
_RETIRED = {"plans": 0, "traces": 0, "calls": 0}
# Guards _PLANS/_RETIRED: the serving tier dispatches shuffles from many
# threads (DESIGN §11); an unguarded OrderedDict corrupts under concurrent
# get/move_to_end/popitem.  Cheap — plan *lookup* is a dict hit; the jit
# trace itself happens lazily at first call, outside this lock.
_PLANS_LOCK = threading.RLock()


def plan_cache_stats() -> Dict[str, int]:
    """(plans, traces, calls, evictions) across the process — ``plans`` is
    the live-plan count; ``traces``/``calls`` include evicted plans so a flat
    ``traces`` across repeated same-shape shuffles stays the no-retrace
    guarantee even after LRU turnover."""
    with _PLANS_LOCK:
        return {"plans": len(_PLANS),
                "traces": sum(p.traces for p in _PLANS.values())
                + _RETIRED["traces"],
                "calls": sum(p.calls for p in _PLANS.values())
                + _RETIRED["calls"],
                "evictions": _RETIRED["plans"]}


def plan_keys() -> List[Tuple]:
    """Keys of the live ShufflePlans, least recently used first."""
    with _PLANS_LOCK:
        return list(_PLANS)


def reset_plan_cache_stats() -> None:
    """Zero the trace/call counters without dropping any compiled plan —
    the companion to :func:`plan_cache_stats` for a long-lived service that
    wants per-window "did anything retrace?" checks."""
    with _PLANS_LOCK:
        for p in _PLANS.values():
            p.traces = 0
            p.calls = 0
        _RETIRED.update(plans=0, traces=0, calls=0)


def clear_plan_cache() -> None:
    """Drop every plan and all counters (tests start from a clean slate)."""
    with _PLANS_LOCK:
        _PLANS.clear()
        _RETIRED.update(plans=0, traces=0, calls=0)


def set_plan_cache_capacity(capacity: int) -> None:
    """Bound the live-plan count; evicts LRU plans immediately if needed."""
    global _PLAN_CACHE_CAPACITY
    if capacity < 1:
        raise ValueError("plan cache capacity must be >= 1")
    with _PLANS_LOCK:
        _PLAN_CACHE_CAPACITY = capacity
        _evict_to_capacity()


def plan_cache_capacity() -> int:
    return _PLAN_CACHE_CAPACITY


def _evict_to_capacity() -> None:
    # caller holds _PLANS_LOCK
    while len(_PLANS) > _PLAN_CACHE_CAPACITY:
        _key, plan = _PLANS.popitem(last=False)
        _RETIRED["plans"] += 1
        _RETIRED["traces"] += plan.traces
        _RETIRED["calls"] += plan.calls


def _get_plan(key: Tuple, build: Callable[[ShufflePlan], Callable]
              ) -> ShufflePlan:
    with _PLANS_LOCK:
        plan = _PLANS.get(key)
        if plan is None:
            # building the wrapper is cheap (jax.jit is lazy); the actual
            # trace happens at first call, outside the lock — concurrent
            # first calls of one plan serialize inside jax, trace once
            plan = ShufflePlan(key=key)
            plan.fn = jax.jit(build(plan))
            _PLANS[key] = plan
            _evict_to_capacity()
        else:
            _PLANS.move_to_end(key)
        return plan


def _fused_rebucket_plan(m: int, B: int, spec: Tuple, interpret: bool,
                         use_kernel: bool) -> ShufflePlan:
    """keys + dynamic n + packs → (order, counts, gathered packs), one jit:
    hash kernel (padding → overflow partition m) → counting-sort kernel →
    permutation inversion → packed gather."""
    key = ("rebucket", m, B, spec, interpret, use_kernel, "fused")

    def build(plan: ShufflePlan):
        def shuffle_rebucket(keys, n, packs):
            plan.traces += 1
            pids, counts_full = padded_partition_ids(
                keys, n, m, interpret=interpret, use_kernel=use_kernel)
            dest = scatter_permutation(pids, counts_full,
                                       interpret=interpret,
                                       use_kernel=use_kernel)
            # invert the counting-sort placement → gather permutation
            order = jnp.zeros(B, jnp.int32).at[dest].set(
                jnp.arange(B, dtype=jnp.int32))
            outs = tuple(jnp.take(p, order, axis=0) for p in packs)
            return order, counts_full[:m], outs
        return shuffle_rebucket

    return _get_plan(key, build)


def _hostperm_rebucket_plan(m: int, B: int, spec: Tuple) -> ShufflePlan:
    """host-computed counting-sort order + packs → gathered packs (the one
    stage XLA-on-CPU is fast at stays jitted and retrace-free)."""
    key = ("rebucket", m, B, spec, "hostperm")

    def build(plan: ShufflePlan):
        def shuffle_rebucket_hostperm(order, packs):
            plan.traces += 1
            return tuple(jnp.take(p, order, axis=0) for p in packs)
        return shuffle_rebucket_hostperm

    return _get_plan(key, build)


def _fused_scatter_plan(m: int, B: int, R: int, spec: Tuple,
                        interpret: bool, use_kernel: bool) -> ShufflePlan:
    """pids + counts + dynamic (n, slot offsets) + packs → flat (R, C) packs.

    Counting-sort kernel → each row's slot ``flat_dest`` → one 1-D int32
    scatter that inverts it into ``inv`` (the source row of every slot) →
    one packed gather per dtype pack, as the rebucket and ``hostperm``
    plans do: on the TPU a gather of wide rows is far cheaper than a
    scatter of them.  The per-partition destination base offsets ride
    along as a traced ``(m,)`` array and the output rows are bucketed to
    ``R ≥ total slots`` (+1 trash slot), so same-shape writes with
    different key skew — and uniform vs bucketed :class:`CapacityMap`
    layouts alike — reuse one trace; the caller slices ``[:total]`` eagerly
    outside the jit.  The uniform layout simply passes
    ``offsets = arange(m) * cap``."""
    key = ("scatter", m, B, R, spec, interpret, use_kernel, "fused")

    def build(plan: ShufflePlan):
        def store_scatter(pids, counts, n, slot_offs, packs):
            plan.traces += 1
            counts_full = jnp.concatenate(
                [counts.astype(jnp.int32),
                 (jnp.int32(B) - n.astype(jnp.int32)).reshape(1)])
            dest = scatter_permutation(pids, counts_full,
                                       interpret=interpret,
                                       use_kernel=use_kernel)
            offs = jnp.cumsum(counts_full) - counts_full
            rank = dest - offs[pids]
            # real rows → partition base + rank; padding rows (pid == m) →
            # the trash slot R (the clamped take is discarded by the where)
            base = jnp.take(slot_offs, jnp.minimum(pids, m - 1))
            flat_dest = jnp.where(pids < m, base + rank, R)
            # invert once, in 1-D: the source row of every output slot.
            # Empty slots keep the out-of-range row B and gather zeros.
            inv = jnp.full(R + 1, B, jnp.int32).at[flat_dest].set(
                jnp.arange(B, dtype=jnp.int32))[:R]
            outs = tuple(jnp.take(p, inv, axis=0, mode="fill", fill_value=0)
                         for p in packs)
            return flat_dest, outs
        return store_scatter

    return _get_plan(key, build)


def _hostperm_scatter_plan(m: int, B: int, R: int,
                           spec: Tuple) -> ShufflePlan:
    """Gather-formulated padded scatter: ``inv`` maps every (worker, slot)
    to its source row (B = the all-zeros trash row for empty slots), so the
    layout materializes as one packed gather — XLA-CPU scatters are slow,
    its gathers are not.  Output rows are bucketed to ``R ≥ m * cap`` so
    different capacities share one trace."""
    key = ("scatter", m, B, R, spec, "hostperm")

    def build(plan: ShufflePlan):
        def store_scatter_hostperm(inv, packs):
            plan.traces += 1
            return tuple(jnp.take(p, inv, axis=0) for p in packs)
        return store_scatter_hostperm

    return _get_plan(key, build)


def _fused_place_plan(m: int, B: int, R: int, spec: Tuple) -> ShufflePlan:
    """counts + slot offsets + packs → flat (R, C) packs, for rows already
    segmented per worker: slot ``r`` of partition ``w`` takes row
    ``offsets[w] + (r - slot_offs[w])`` while that is under ``counts[w]``,
    so the layout is one packed gather per dtype, with no counting sort
    and no inversion.  Keyed like the scatter plan: the offsets are
    traced, the output rows bucketed to ``R``."""
    key = ("place", m, B, R, spec, "fused")

    def build(plan: ShufflePlan):
        def store_place(counts, slot_offs, packs):
            plan.traces += 1
            slot = jnp.arange(R, dtype=jnp.int32)
            w = jnp.searchsorted(slot_offs, slot, side="right") - 1
            rank = slot - jnp.take(slot_offs, w)
            offs = jnp.cumsum(counts) - counts
            src = jnp.where(rank < jnp.take(counts, w),
                            jnp.take(offs, w) + rank, B)
            return tuple(jnp.take(p, src, axis=0, mode="fill", fill_value=0)
                         for p in packs)
        return store_place

    return _get_plan(key, build)


# ---------------------------------------------------------------------------
# Re-bucket (engine repartition node)
# ---------------------------------------------------------------------------

@dataclass
class ShuffleResult:
    """Output of a device shuffle: host-materialized columns for the
    engine's columnar compute plus the device-resident flats so a chained
    device stage (store write, next shuffle) skips the re-upload."""
    columns: Columns                     # np columns incl "__key__"
    counts: np.ndarray                   # (m,) int64
    device_columns: Optional[Columns] = None    # flat jax arrays (subset)


def device_rebucket_full(columns: Columns, key_vals, num_partitions: int, *,
                         interpret: Optional[bool] = None,
                         use_kernel: Optional[bool] = None,
                         mode: Optional[str] = None,
                         device_columns: Optional[Columns] = None
                         ) -> ShuffleResult:
    """Re-bucket flat columns by hash(key) % m through one cached plan.

    Single-pass shuffle (hash → histogram → counting-sort permutation →
    packed gather); K same-dtype columns cost one gather and one host sync.
    ``device_columns`` (flat jax arrays from an upstream device stage) are
    consumed in place of re-uploading the matching host columns.
    """
    interpret = _resolve_interpret(interpret)
    use_kernel = _resolve_use_kernel(use_kernel)
    mode = _resolve_mode(mode)
    key_arr = key_vals if isinstance(key_vals, jax.Array) \
        else np.asarray(key_vals).reshape(-1)
    n = int(key_arr.shape[0])
    m = int(num_partitions)
    if n == 0:
        out = {k: np.asarray(v).copy() for k, v in columns.items()}
        out["__key__"] = np.asarray(key_arr)
        return ShuffleResult(out, np.zeros(m, np.int64), None)

    cols = dict(columns)
    cols["__key__"] = key_arr
    if device_columns:
        # a relayed "__key__" is the *previous* shuffle's key — never let it
        # shadow the key this node is partitioning on
        device_columns = {k: v for k, v in device_columns.items()
                          if k != "__key__"}
        if isinstance(key_arr, jax.Array):
            device_columns["__key__"] = key_arr
    dev_cols, host_cols = _split_columns(cols, device_columns)
    B = shape_bucket(n)
    packs = _build_packs(dev_cols, n, B)
    spec = _pack_spec(packs)

    with _span("shuffle.dispatch", "shuffle", op="rebucket", rows=n, m=m,
               bucket=B, mode=mode, h2d_bytes=0, d2h_bytes=0) as sp:
        if mode == "fused":
            keys_p = jnp.zeros(B, jnp.int32).at[:n].set(
                as_kernel_keys(key_arr))
            plan = _fused_rebucket_plan(m, B, spec, interpret, use_kernel)
            plan.calls += 1
            order_d, counts_d, outs_d = plan.fn(
                keys_p, jnp.int32(n), tuple(upload(p.data) for p in packs))
            # one transfer for everything the host needs; its bytes count
            # on the dispatch
            with _span("shuffle.fetch", "shuffle"):
                order_np, counts_np, outs_np = fetch_tree(
                    (order_d, counts_d, outs_d), sp)
            order_valid = order_np[:n]
            counts_np = counts_np.astype(np.int64)
        else:
            pids_np, counts_np = shuffle_pids(key_arr, m, mode="hostperm")
            order_valid = host_counting_order(pids_np)
            order_p = np.concatenate(
                [order_valid, np.arange(n, B)]).astype(np.int32)
            plan = _hostperm_rebucket_plan(m, B, spec)
            plan.calls += 1
            outs_d = plan.fn(upload(order_p),
                             tuple(upload(p.data) for p in packs))
            with _span("shuffle.fetch", "shuffle"):
                outs_np = fetch_tree(outs_d, sp)
        # the worker that holds most rows, from the fetched histogram
        sp.set(max_worker_rows=int(counts_np.max()))

    out: Columns = {}
    device_out: Columns = {}
    for p, mat_d, mat_np in zip(packs, outs_d, outs_np):
        for name, trail, c0, c1 in p.members:
            out[name] = np.ascontiguousarray(
                mat_np[:n, c0:c1]).reshape((n,) + trail)
            device_out[name] = mat_d[:n, c0:c1].reshape((n,) + trail)
    for name, v in host_cols:
        out[name] = v[order_valid]
    return ShuffleResult(out, counts_np, device_out or None)


def device_rebucket(columns: Columns, key_vals, num_partitions: int, *,
                    interpret: Optional[bool] = None,
                    use_kernel: Optional[bool] = None,
                    mode: Optional[str] = None
                    ) -> Tuple[Columns, np.ndarray]:
    """Compatibility wrapper: ``(new_columns incl "__key__", counts)`` —
    the same contract as the engine's host-side shuffle."""
    res = device_rebucket_full(columns, key_vals, num_partitions,
                               interpret=interpret, use_kernel=use_kernel,
                               mode=mode)
    return res.columns, res.counts


# ---------------------------------------------------------------------------
# Padded scatter (store write path)
# ---------------------------------------------------------------------------

def _check_overflow(counts_np: np.ndarray, capacities: np.ndarray) -> None:
    """Raise a diagnosable error when any partition outgrows its capacity
    (the scatter would silently clamp/drop the overflowing rows)."""
    over = np.flatnonzero(counts_np > capacities)
    if over.size:
        pid = int(over[int(np.argmax((counts_np - capacities)[over]))])
        need = int(counts_np[pid])
        have = int(capacities[pid])
        raise ValueError(
            f"partition {pid} has {need} rows but capacity {have}: the "
            f"scatter would silently drop/clamp overflowing rows "
            f"(suggest overflow bucket capacity {bucket_capacity(need)} "
            f"for partition {pid}, e.g. via CapacityMap.from_counts)")


@dataclass
class _Layout:
    """Where a padded write puts its rows: partition ``i`` holds
    ``counts[i]`` rows from flat slot ``offsets[i]`` on, in ``total``
    slots, shaped ``(m, cap, ...)`` (uniform) or flat ``(total, ...)``
    (under a :class:`CapacityMap`)."""
    counts: np.ndarray              # (m,) int64
    offsets: np.ndarray             # (m,) int64
    total: int
    cap: int
    bucketed: bool

    @property
    def m(self) -> int:
        return int(self.counts.shape[0])

    @property
    def n(self) -> int:
        return int(self.counts.sum())

    def shape(self, trail: Tuple[int, ...]) -> Tuple[int, ...]:
        if self.bucketed:
            return (self.total,) + trail
        return (self.m, self.cap) + trail


def _plan_layout(counts, capacity: Optional[int],
                 capacity_map: Optional[CapacityMap]) -> _Layout:
    """The layout of a padded write, checked against its counts."""
    counts_np = np.asarray(counts).astype(np.int64)
    m = int(counts_np.shape[0])
    max_count = int(counts_np.max()) if counts_np.sum() else 0
    if capacity_map is not None:
        if capacity is not None:
            raise ValueError("pass capacity or capacity_map, not both")
        if capacity_map.num_partitions != m:
            raise ValueError(
                f"capacity_map covers {capacity_map.num_partitions} "
                f"partitions, counts cover {m}")
        _check_overflow(counts_np, capacity_map.capacities)
        return _Layout(counts_np, capacity_map.offsets.astype(np.int64),
                       capacity_map.total_slots, 0, True)
    if capacity is not None and int(capacity) < max_count:
        _check_overflow(counts_np, np.full(m, int(capacity), dtype=np.int64))
    cap = int(capacity) if capacity is not None else max_count
    if not max_count:
        cap = cap or 1
    return _Layout(counts_np, np.arange(m, dtype=np.int64) * cap, m * cap,
                   cap, False)


def _empty_layout(flat_columns: Columns, lay: _Layout) -> Columns:
    """Zero-filled columns of an empty layout, device-backed where the
    dtype round-trips."""
    out: Columns = {}
    for k, v in flat_columns.items():
        v = np.asarray(v)
        if dtype_roundtrips(v.dtype):
            out[k] = jnp.zeros(lay.shape(v.shape[1:]), v.dtype)
        else:
            out[k] = np.zeros(lay.shape(v.shape[1:]), v.dtype)
    return out


def _assemble(lay: _Layout, packs: List[_Pack], outs,
              host_cols: List[Tuple[str, Any]],
              flat_dest_np: Optional[np.ndarray]) -> Columns:
    """The plan's flat (R, C) packs cut down to the layout's columns, and
    the host-only columns placed at ``flat_dest_np`` on the host."""
    columns: Columns = {}
    total = lay.total
    for p, mat in zip(packs, outs):
        # eager slice from the row bucket down to the real layout
        if lay.bucketed:
            flat = mat[:total]
            for name, trail, c0, c1 in p.members:
                columns[name] = flat[:, c0:c1].reshape((total,) + trail)
        else:
            grid = mat[:total].reshape(lay.m, lay.cap, p.width)
            for name, trail, c0, c1 in p.members:
                columns[name] = grid[:, :, c0:c1].reshape(
                    (lay.m, lay.cap) + trail)
    for name, v in host_cols:
        buf = np.zeros((total + 1,) + v.shape[1:], v.dtype)
        buf[flat_dest_np] = v
        columns[name] = buf[:total].reshape(lay.shape(v.shape[1:]))
    return columns


def device_scatter_padded(flat_columns: Columns, pids, counts, *,
                          capacity: Optional[int] = None,
                          capacity_map: Optional[CapacityMap] = None,
                          interpret: Optional[bool] = None,
                          use_kernel: Optional[bool] = None,
                          mode: Optional[str] = None,
                          device_columns: Optional[Columns] = None
                          ) -> Columns:
    """Scatter flat rows into the persistent padded layout.

    Uniform layout (default): ``(m, capacity, ...)`` columns.  With a
    ``capacity_map``, each partition gets its own slot range and columns
    come back *flat* as ``(total_slots, ...)`` — partition ``i`` occupies
    ``[offsets[i], offsets[i] + capacities[i])``.  Both shapes ride the
    same cached plan: the per-partition base offsets are a traced array, so
    switching skew levels (or uniform ↔ bucketed within one output-row
    bucket) never retraces.

    One cached counting-sort plan per (bucket, dtype-set, m, row-bucket):
    destination slot of row i is ``base[pids[i]] + rank-of-i-within-its-
    partition``, inverted into each slot's source row and materialized per
    dtype *pack* — K same-dtype columns cost one gather.  Round-trippable
    columns come back device-resident (jax arrays); 64-bit columns are
    scattered host-side (hybrid).

    A ``capacity`` (or capacity-map bucket) smaller than its partition's
    row count would silently clamp/drop rows inside the scatter, so it
    raises instead, naming the offending partition.
    """
    interpret = _resolve_interpret(interpret)
    use_kernel = _resolve_use_kernel(use_kernel)
    mode = _resolve_mode(mode)
    lay = _plan_layout(counts, capacity, capacity_map)
    m, n = lay.m, lay.n
    if n == 0:
        return _empty_layout(flat_columns, lay)

    dev_cols, host_cols = _split_columns(flat_columns, device_columns)
    B = shape_bucket(n)
    R = shape_bucket(lay.total)  # output-row bucket: offsets traced, not keyed

    with _span("shuffle.dispatch", "shuffle", op="scatter", rows=n, m=m,
               max_worker_rows=int(lay.counts.max()), bucket=B, mode=mode,
               h2d_bytes=0, d2h_bytes=0):
        if mode == "fused":
            packs = _build_packs(dev_cols, n, B)
            if isinstance(pids, jax.Array):
                pids_p = jnp.full(B, m, jnp.int32).at[:n].set(
                    pids.astype(jnp.int32))
            else:
                buf = np.full(B, m, np.int32)
                buf[:n] = np.asarray(pids).astype(np.int32)
                pids_p = upload(buf)
            plan = _fused_scatter_plan(m, B, R, _pack_spec(packs), interpret,
                                       use_kernel)
            plan.calls += 1
            flat_dest_d, outs = plan.fn(
                pids_p, upload(lay.counts.astype(np.int32)), jnp.int32(n),
                upload(lay.offsets.astype(np.int32)),
                tuple(upload(p.data) for p in packs))
            flat_dest_np = None
            if host_cols:
                flat_dest_np = fetch(flat_dest_d)[:n]
        else:
            # rows [n:B] of each pack are zeros; row B is the explicit trash
            # source every empty (worker, slot) cell gathers from
            packs = _build_packs(dev_cols, n, B + 1)
            pids_np = fetch(pids).astype(np.int64)
            flat_dest_np = host_counting_sort_dest(
                pids_np, lay.counts, lay.cap, dest_offsets=lay.offsets)
            inv = np.full(R, B, np.int32)
            inv[flat_dest_np] = np.arange(n, dtype=np.int32)
            plan = _hostperm_scatter_plan(m, B, R, _pack_spec(packs))
            plan.calls += 1
            outs = plan.fn(upload(inv), tuple(upload(p.data) for p in packs))
    return _assemble(lay, packs, outs, host_cols, flat_dest_np)


def device_place_presorted(flat_columns: Columns, counts, *,
                           capacity: Optional[int] = None,
                           capacity_map: Optional[CapacityMap] = None,
                           mode: Optional[str] = None,
                           device_columns: Optional[Columns] = None
                           ) -> Columns:
    """Rows already segmented per worker by ``counts`` (worker 0's rows
    first) → the persistent padded layout, as :func:`device_scatter_padded`
    lays it out and returns it.  Each worker's rows keep their order, so
    the slot of every row is known without a sort: one cached gather per
    dtype pack (``fused``), or a host placement and the same gather
    (``hostperm``).  No kernel runs and no rows change worker, so this is
    no shuffle dispatch: its transfers count on the caller's span."""
    mode = _resolve_mode(mode)
    lay = _plan_layout(counts, capacity, capacity_map)
    n = lay.n
    if n == 0:
        return _empty_layout(flat_columns, lay)
    dev_cols, host_cols = _split_columns(flat_columns, device_columns)
    B = shape_bucket(n)
    R = shape_bucket(lay.total)
    flat_dest_np = None
    if mode == "fused":
        packs = _build_packs(dev_cols, n, B)
        plan = _fused_place_plan(lay.m, B, R, _pack_spec(packs))
        plan.calls += 1
        outs = plan.fn(upload(lay.counts.astype(np.int32)),
                       upload(lay.offsets.astype(np.int32)),
                       tuple(upload(p.data) for p in packs))
    else:
        packs = _build_packs(dev_cols, n, B + 1)
        flat_dest_np = host_presorted_dest(lay.counts, lay.cap, lay.offsets)
        inv = np.full(R, B, np.int32)
        inv[flat_dest_np] = np.arange(n, dtype=np.int32)
        plan = _hostperm_scatter_plan(lay.m, B, R, _pack_spec(packs))
        plan.calls += 1
        outs = plan.fn(upload(inv), tuple(upload(p.data) for p in packs))
    if host_cols and flat_dest_np is None:
        flat_dest_np = host_presorted_dest(lay.counts, lay.cap, lay.offsets)
    return _assemble(lay, packs, outs, host_cols, flat_dest_np)


# ---------------------------------------------------------------------------
# Device-to-device dataset repartition (store fast path)
# ---------------------------------------------------------------------------

def _valid_slot_index(ds) -> np.ndarray:
    """Flat indices of the valid slots of a padded layout in worker-major
    order — the exact row order ``StoredDataset.gather()`` produces.
    Single source of truth for every flatten below (the bit-identical
    guarantee hangs on this ordering).  Uniform layouts use base offsets
    ``w * capacity``; bucketed layouts use their :class:`CapacityMap`
    offsets — the enumerated row order is identical either way.
    """
    counts = np.asarray(ds.counts)
    cm = getattr(ds, "capacity_map", None)
    if cm is not None:
        offs = cm.offsets
    else:
        offs = np.arange(ds.num_workers, dtype=np.int64) * ds.capacity
    return valid_slot_index(counts, offs)


def _flat_slots(ds, v):
    """A column viewed as flat slots: bucketed columns already are
    ``(total_slots, ...)``; uniform ``(m, capacity, ...)`` columns
    reshape."""
    if getattr(ds, "capacity_map", None) is not None:
        return v
    return v.reshape((ds.num_workers * ds.capacity,) + v.shape[2:])


def _one_device(v: jax.Array) -> jax.Array:
    """A mesh-placed column gathered onto its lowest-id device.  XLA cannot
    partition a Mosaic kernel, so a ShufflePlan fed a sharded input fails to
    compile on TPU; the shuffle itself runs on one chip."""
    if len(v.sharding.device_set) == 1:
        return v
    dev = min(v.sharding.device_set, key=lambda d: d.id)
    return jax.device_put(v, jax.sharding.SingleDeviceSharding(dev))


def flatten_dataset(ds, device_only: bool = False) -> Columns:
    """Flatten a StoredDataset's padded columns back to flat rows *without*
    a host round-trip: device-resident columns are gathered with a device
    permutation over :func:`_valid_slot_index`; host columns take the numpy
    path (skipped entirely under ``device_only``).
    """
    idx = _valid_slot_index(ds)
    idx_dev = None
    out: Columns = {}
    for k, v in ds.columns.items():
        if isinstance(v, jax.Array):
            if idx_dev is None:
                idx_dev = upload(idx.astype(np.int32))
            out[k] = jnp.take(_flat_slots(ds, _one_device(v)), idx_dev,
                              axis=0)
        elif not device_only:
            out[k] = _flat_slots(ds, np.asarray(v))[idx]
    return out


def device_flat_columns(ds) -> Optional[Columns]:
    """The device-resident subset of :func:`flatten_dataset` (engine scan
    seeds its d2d chain with these), computed without touching host cols."""
    return flatten_dataset(ds, device_only=True) or None


def device_repartition_dataset(ds, partitioner, num_partitions: int, *,
                               interpret: Optional[bool] = None,
                               use_kernel: Optional[bool] = None,
                               mode: Optional[str] = None,
                               plan_capacity: Optional[Callable] = None
                               ) -> Tuple[Columns, np.ndarray,
                                          Optional[CapacityMap]]:
    """Device-to-device repartition: device-resident StoredDataset → new
    padded device layout, no host gather/concatenate.

    Valid rows are gathered on device, the partition key is evaluated with
    the candidate's compiled key projection (jnp — stays on device), and the
    cached plan scatters straight into the new padded layout.  Only the
    pids/histogram cross to the host (the histogram sizes the capacity).
    64-bit columns ride the hybrid path as usual.

    ``plan_capacity`` (counts → Optional[CapacityMap]) lets the store
    choose a bucketed layout from the fresh histogram; returns the map it
    used (None ⇒ uniform ``(m, capacity, ...)``).
    """
    with _span("store.flatten", "store", h2d_bytes=0):
        flat = flatten_dataset(ds)
    with _span("store.pids", "store", h2d_bytes=0, d2h_bytes=0):
        keys = partitioner.key_fn()(flat)
        pids, counts = shuffle_pids(keys, num_partitions, interpret=interpret,
                                    use_kernel=use_kernel, mode=mode)
    cmap = plan_capacity(counts) if plan_capacity is not None else None
    columns = device_scatter_padded(flat, pids, counts, capacity_map=cmap,
                                    interpret=interpret,
                                    use_kernel=use_kernel, mode=mode)
    return columns, counts, cmap
