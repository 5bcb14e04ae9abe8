"""The plain reference: the same operations on the same tables, in numpy.

It imports nothing of the system under test.  It states the semantics
that the benchmark holds the system to:

* a query (``bench/queries/<name>.json``) projects each input, joins on
  a key (each left row with the one right row of equal key; rows with no
  match drop out), filters, projects again and aggregates per key: the
  float64 sum (or mean) of every remaining column, cast back to the
  column's dtype, one row per distinct key;
* storage places row ``i`` on worker ``wang(key_i) % m``, in the order of
  its input within each worker; a layout is uniform (every worker holds
  ``max(count)`` slots) unless adaptive capacity is on and the
  power-of-two capacity of each worker's count needs at most
  ``threshold`` of the uniform power-of-two total.

Every float column is exact to a binary fraction (``tables.py``), so the
sums are exact and the comparison is bit for bit.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

Cols = Dict[str, np.ndarray]

_OPS = {"<": np.less, "<=": np.less_equal, ">": np.greater,
        ">=": np.greater_equal, "==": np.equal, "!=": np.not_equal}


# ---------------------------------------------------------------------------
# Queries
# ---------------------------------------------------------------------------

def select(cols: Cols, columns: List[str], count: Optional[str]) -> Cols:
    out = {k: cols[k] for k in columns}
    if count:
        out[count] = np.ones(len(cols[columns[0]]), np.float32)
    return out


def lookup(keys: np.ndarray, probe: np.ndarray) -> Tuple[np.ndarray,
                                                          np.ndarray]:
    """For each probe value, the row of ``keys`` that holds it, and
    whether one does.  ``keys`` must be unique and non-negative."""
    top = int(max(keys.max(initial=0), probe.max(initial=0))) + 1
    row = np.full(top, -1, np.int64)
    row[keys] = np.arange(keys.size)
    if np.unique(keys).size != keys.size:
        raise ValueError("join keys on the right side are not unique")
    idx = row[probe]
    return idx, idx >= 0


def groupby(cols: Cols, key: np.ndarray, reducer: str) -> Cols:
    """Per distinct key (ascending): the float64 sum or mean of every
    column, cast back to the column's dtype; the key under ``"key"``."""
    if key.size and key.min() < 0:
        raise ValueError("group keys must be non-negative")
    cnt = np.bincount(key)
    present = np.flatnonzero(cnt)
    out = {"key": present.astype(key.dtype)}
    for k, v in cols.items():
        if v.ndim != 1:
            raise ValueError(f"cannot aggregate the {v.ndim}-D column {k}")
        acc = np.bincount(key, weights=v.astype(np.float64),
                          minlength=cnt.size)[present]
        if reducer == "mean":
            acc = acc / cnt[present]
        elif reducer != "sum":
            raise ValueError(f"unknown reducer {reducer!r}")
        out[k] = acc.astype(v.dtype)
    return out


def run_query(spec: dict, tables: Dict[str, Cols]) -> Cols:
    """The answer to ``spec`` over ``tables``, rows ascending by key."""
    rel: Dict[str, Cols] = {}
    for alias, inp in spec["inputs"].items():
        rel[alias] = select(tables[inp.get("dataset", alias)],
                            inp["columns"], inp.get("count"))
    join = spec.get("join")
    if join:
        left, right = rel[join["left"]], rel[join["right"]]
        idx, hit = lookup(right[join["right_key"]], left[join["left_key"]])
        both = {k: v[hit] for k, v in left.items()}
        both.update({k: v[idx[hit]] for k, v in right.items()
                     if k not in both})
        cur = {k: both[k] for k in join["keep"]}
    else:
        (cur,) = rel.values()
    for col, op, rhs in spec.get("filters", ()):
        other = cur[rhs["col"]] if isinstance(rhs, dict) else rhs
        mask = _OPS[op](cur[col], other)
        cur = {k: v[mask] for k, v in cur.items()}
    proj = spec.get("project")
    if proj:
        cur = select(cur, proj["columns"], proj.get("count"))
    agg = spec["aggregate"]
    return groupby(cur, cur[agg["key"]], agg.get("reducer", "sum"))


def bf16(v: np.ndarray) -> np.ndarray:
    """float32 values rounded to the nearest bfloat16 (half to even),
    held as float32; other dtypes unchanged."""
    if v.dtype != np.float32:
        return v
    bits = np.ascontiguousarray(v).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32)


# ---------------------------------------------------------------------------
# Comparison
# ---------------------------------------------------------------------------

def as_bits(v: np.ndarray) -> np.ndarray:
    """Elementwise bit pattern (so NaN == NaN and -0 != +0)."""
    v = np.ascontiguousarray(v)
    if v.dtype.kind == "f":
        return v.view({2: np.uint16, 4: np.uint32, 8: np.uint64}
                      [v.dtype.itemsize])
    return v


def mismatches(got: Cols, want: Cols) -> int:
    """Values of ``want`` that ``got`` does not hold bit for bit, in the
    same column, row and dtype.  A column that is missing or of another
    shape or dtype counts all its values."""
    bad = 0
    for k in set(got) | set(want):
        a, b = got.get(k), want.get(k)
        if a is None or b is None:
            bad += (a if b is None else b).size
            continue
        a, b = np.asarray(a), np.asarray(b)
        if a.shape != b.shape or a.dtype != b.dtype:
            bad += max(a.size, b.size)
            continue
        bad += int(np.count_nonzero(as_bits(a) != as_bits(b)))
    return bad


def sort_by_key(cols: Cols) -> Cols:
    order = np.argsort(cols["key"], kind="stable")
    return {k: np.asarray(v)[order] for k, v in cols.items()}


# ---------------------------------------------------------------------------
# Storage placement
# ---------------------------------------------------------------------------

def wang(keys: np.ndarray) -> np.ndarray:
    """Thomas Wang's 32-bit integer mix, over the key's int32 bits."""
    with np.errstate(over="ignore"):
        x = keys.astype(np.int32).view(np.uint32).astype(np.uint32)
        x = (x ^ np.uint32(61)) ^ (x >> np.uint32(16))
        x = x * np.uint32(9)
        x = x ^ (x >> np.uint32(4))
        x = x * np.uint32(0x27D4EB2D)
        x = x ^ (x >> np.uint32(15))
    return x


def worker_of(keys: np.ndarray, m: int) -> np.ndarray:
    return (wang(keys) % np.uint32(m)).astype(np.int64)


def pow2_ceil(counts: np.ndarray) -> np.ndarray:
    """Each count rounded up to a power of two (0 stays 0)."""
    return np.array([0 if c <= 0 else 1 << (int(c) - 1).bit_length()
                     for c in np.asarray(counts)], np.int64)


def plan_layout(counts: np.ndarray, adaptive: bool, threshold: float
                ) -> Tuple[Optional[np.ndarray], np.ndarray, int]:
    """(per-worker capacities or None for uniform, slot offsets, total
    slots) of the layout that holds ``counts``."""
    m = counts.size
    if adaptive and counts.sum() > 0:
        caps = pow2_ceil(counts)
        uniform_total = m * int(pow2_ceil(np.array([counts.max()]))[0])
        if caps.sum() <= threshold * uniform_total:
            offs = np.concatenate([[0], np.cumsum(caps)[:-1]])
            return caps, offs.astype(np.int64), int(caps.sum())
    cap = int(counts.max()) if counts.sum() else 1
    return None, np.arange(m, dtype=np.int64) * cap, m * cap


def placement_order(pids: np.ndarray) -> np.ndarray:
    """Input rows in stored order: grouped by worker, stable within."""
    return np.argsort(pids, kind="stable")


def stored_rows(columns: Dict[str, np.ndarray], counts: np.ndarray,
                offsets: np.ndarray, total: int, uniform: bool
                ) -> Tuple[Optional[Cols], str]:
    """The valid rows of a stored layout in worker order, read with the
    reference's own slot offsets; (None, why) when a column's shape is
    not the layout's."""
    m = counts.size
    idx = np.repeat(offsets, counts) + (
        np.arange(int(counts.sum())) - np.repeat(np.cumsum(counts) - counts,
                                                 counts))
    out = {}
    for k, v in columns.items():
        v = np.asarray(v)
        lead = (m, total // m) if uniform else (total,)
        if v.shape[:len(lead)] != lead:
            return None, f"{k} has shape {v.shape}, the layout {lead}"
        out[k] = v.reshape((total,) + v.shape[len(lead):])[idx]
    return out, ""
