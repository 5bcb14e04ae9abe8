"""TPC-H tables at the specification's full row width, made in numpy.

Population follows TPC-H clause 4.2.3 (row counts, key rules, value
ranges), with three encodings that the store needs: every key and date
is ``int32`` (dates are days since 1992-01-01), every low-cardinality
CHAR column is dictionary-coded to ``int32``, and every comment or name
is a fixed-width ``uint8`` column at its maximum length, zero-padded.
Prices are kept to exact binary fractions (retail and extended price to
1/64, total price to 1/16), so every float64 sum of them is exact and a
result does not depend on the order of summation.

The population is fixed, as dbgen's is for a scale factor: it comes
from the configuration's ``population_seed``.  A run's ``--seed`` permutes
the rows of every table, so every seed stores and queries the same rows
(the same sizes at every step, hence the same compiled programs) in
another order, and the same seed gives the same tables.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

Table = Dict[str, np.ndarray]

# TPC-H clause 4.2.3 dates, as days since STARTDATE = 1992-01-01
ENDDATE = 2556            # 1998-12-31
CURRENTDATE = 1263        # 1995-06-17
ORDERDATE_MAX = ENDDATE - 151

# dictionary sizes of the coded CHAR columns (clause 4.2.2.13 / 4.2.3)
RETURNFLAGS = 3           # A, N, R
LINESTATUSES = 2          # F, O
ORDERSTATUSES = 3         # F, O, P
SHIPINSTRUCTS = 4
SHIPMODES = 7
PRIORITIES = 5
MFGRS = 5
BRANDS = 25
TYPES = 150
CONTAINERS = 40

# fixed widths of the text columns (their maximum lengths)
L_COMMENT, O_COMMENT, P_NAME, P_COMMENT = 44, 79, 55, 23

_TEXT = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz ", np.uint8)


_BYTE_TO_TEXT = np.resize(_TEXT, 256)
TEXT_POOL = 1 << 16       # distinct strings per text column


def _text(rng, n: int, width: int, lo: int, hi: int) -> np.ndarray:
    """``n`` strings of ``lo``..``hi`` random letters, zero-padded to
    ``width`` bytes, drawn from a pool of ``TEXT_POOL`` such strings (as
    dbgen's comments come from a small grammar)."""
    k = min(n, TEXT_POOL)
    raw = np.frombuffer(rng.bytes(k * width), np.uint8).reshape(k, width)
    pool = _BYTE_TO_TEXT[raw]
    length = rng.integers(lo, hi + 1, k)
    pool *= np.arange(width, dtype=np.int32)[None, :] < length[:, None]
    return pool[rng.integers(0, k, n)]


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE (clause 4.2.3) to the nearest 1/64, as float64."""
    pk = partkey.astype(np.int64)
    cents = 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)
    return np.round(cents * 0.64) / 64


def _custkeys(rng, n: int, n_cust: int) -> np.ndarray:
    """O_CUSTKEY: uniform over the customers whose key is not a multiple
    of 3 (clause 4.2.3)."""
    k = rng.integers(0, n_cust - n_cust // 3, n)
    return (3 * (k // 2) + 1 + k % 2).astype(np.int32)


def make_tables(sf: float, seed: int, want=("lineitem", "orders", "part"),
                population_seed: int = 0) -> Dict[str, Table]:
    """The population of ``population(...)``, each table's rows permuted
    by ``seed``."""
    tables = population(sf, want, population_seed)
    rng = np.random.default_rng(seed)
    for name in sorted(tables):
        t = tables[name]
        perm = rng.permutation(len(next(iter(t.values()))))
        tables[name] = {k: v[perm] for k, v in t.items()}
    return tables


def population(sf: float, want=("lineitem", "orders", "part"),
               population_seed: int = 0) -> Dict[str, Table]:
    """lineitem (16 columns), orders (9) and part (9) at scale ``sf``.

    ``want`` names the tables to return (lineitem needs orders and part
    to exist, so they are always drawn)."""
    rng = np.random.default_rng(population_seed)
    n_orders = int(1_500_000 * sf)
    n_parts = int(200_000 * sf)
    n_supp = int(10_000 * sf)
    n_cust = int(150_000 * sf)
    n_clerk = max(int(1_000 * sf), 1)

    # ---- part ------------------------------------------------------------
    pk = np.arange(1, n_parts + 1, dtype=np.int32)
    mfgr = rng.integers(0, MFGRS, n_parts).astype(np.int32)
    part = {
        "p_partkey": pk,
        "p_name": _text(rng, n_parts, P_NAME, 20, P_NAME),
        "p_mfgr": mfgr,
        "p_brand": (mfgr * 5 + rng.integers(0, 5, n_parts)).astype(np.int32),
        "p_type": rng.integers(0, TYPES, n_parts).astype(np.int32),
        "p_size": rng.integers(1, 51, n_parts).astype(np.int32),
        "p_container": rng.integers(0, CONTAINERS, n_parts).astype(np.int32),
        "p_retailprice": retail_price(pk).astype(np.float32),
        "p_comment": _text(rng, n_parts, P_COMMENT, 5, P_COMMENT - 1),
    }

    # ---- orders (keys and dates; totals and status follow the lines) -----
    i = np.arange(n_orders, dtype=np.int64)
    orderkey = ((i // 8) * 32 + i % 8 + 1).astype(np.int32)   # sparse keys
    odate = rng.integers(0, ORDERDATE_MAX + 1, n_orders).astype(np.int32)
    custkey = _custkeys(rng, n_orders, n_cust)

    # ---- lineitem ------------------------------------------------------------
    nlines = rng.integers(1, 8, n_orders)
    n = int(nlines.sum())
    owner = np.repeat(np.arange(n_orders), nlines)
    first = np.cumsum(nlines) - nlines
    linenumber = (np.arange(n) - first[owner] + 1).astype(np.int32)
    partkey = rng.integers(1, n_parts + 1, n).astype(np.int32)
    pk64 = partkey.astype(np.int64)
    supp_i = rng.integers(0, 4, n)
    suppkey = ((pk64 + supp_i * (n_supp // 4 + (pk64 - 1) // n_supp))
               % n_supp + 1).astype(np.int32)
    qty = rng.integers(1, 51, n).astype(np.float32)
    extprice = (qty * retail_price(pk64)).astype(np.float32)
    discount = (rng.integers(0, 11, n) / 100).astype(np.float32)
    tax = (rng.integers(0, 9, n) / 100).astype(np.float32)
    od = odate[owner]
    shipdate = (od + rng.integers(1, 122, n)).astype(np.int32)
    commitdate = (od + rng.integers(30, 91, n)).astype(np.int32)
    receiptdate = (shipdate + rng.integers(1, 31, n)).astype(np.int32)
    returned = receiptdate <= CURRENTDATE
    returnflag = np.where(returned, rng.integers(0, 2, n) * 2, 1)
    linestatus = (shipdate > CURRENTDATE).astype(np.int32)
    lineitem = {
        "l_orderkey": orderkey[owner],
        "l_partkey": partkey,
        "l_suppkey": suppkey,
        "l_linenumber": linenumber,
        "l_quantity": qty,
        "l_extendedprice": extprice,
        "l_discount": discount,
        "l_tax": tax,
        "l_returnflag": returnflag.astype(np.int32),
        "l_linestatus": linestatus,
        "l_shipdate": shipdate,
        "l_commitdate": commitdate,
        "l_receiptdate": receiptdate,
        "l_shipinstruct": rng.integers(0, SHIPINSTRUCTS, n).astype(np.int32),
        "l_shipmode": rng.integers(0, SHIPMODES, n).astype(np.int32),
        "l_comment": _text(rng, n, L_COMMENT, 10, L_COMMENT - 1),
    }

    # O_TOTALPRICE: sum of extprice*(1+tax)*(1-discount), to 1/16
    charge = (extprice.astype(np.float64) * (1 + tax.astype(np.float64))
              * (1 - discount.astype(np.float64)))
    total = np.bincount(owner, weights=charge, minlength=n_orders)
    n_open = np.bincount(owner, weights=linestatus, minlength=n_orders)
    status = np.where(n_open == 0, 0, np.where(n_open == nlines, 1, 2))
    orders = {
        "o_orderkey": orderkey,
        "o_custkey": custkey,
        "o_orderstatus": status.astype(np.int32),
        "o_totalprice": (np.round(total * 16) / 16).astype(np.float32),
        "o_orderdate": odate,
        "o_orderpriority": rng.integers(0, PRIORITIES,
                                        n_orders).astype(np.int32),
        "o_clerk": rng.integers(1, n_clerk + 1, n_orders).astype(np.int32),
        "o_shippriority": np.zeros(n_orders, np.int32),
        "o_comment": _text(rng, n_orders, O_COMMENT, 19, O_COMMENT - 1),
    }
    tables = {"lineitem": lineitem, "orders": orders, "part": part}
    return {k: tables[k] for k in want}


def schema_of(tables: Dict[str, Table]) -> Dict[str, Dict[str, list]]:
    """``{table: {column: [dtype, width]}}`` — what a configuration file
    states, to check the generated tables against."""
    return {t: {k: [str(v.dtype), int(np.prod(v.shape[1:]))]
                for k, v in cols.items()}
            for t, cols in tables.items()}


def row_bytes(table: Table) -> int:
    return int(sum(v.itemsize * int(np.prod(v.shape[1:]))
                   for v in table.values()))
