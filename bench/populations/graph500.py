"""The ``graph500`` population: a Graph500 Kronecker graph as an edge
list, and the uniform ranks an LDBC Graphalytics PageRank job starts
from.

The generator is the Graph500 specification's (section 3, its reference
``kronecker_generator``): ``edgefactor * 2**scale`` edges, each placed by
``scale`` independent choices of a quadrant of the adjacency matrix with
the initiator's probabilities A, B, C, D.  The graph is then cleaned up
as Graphalytics datasets are: self-loops and duplicate edges dropped,
made undirected (both directions stored as rows of ``edges``, as the
specification's kernel 1 builds it), vertices with no edge dropped and
the rest numbered densely.  So every kept vertex has an out-edge, and
PageRank's dangling term is zero by construction (asserted here).

The graph is fixed by the configuration's ``scale``, ``edgefactor``,
``initiator`` and ``population_seed``.  A run's ``--seed`` permutes the
vertex labels, as Graph500 permutes them, and the order of the rows of
both tables: every seed has the same |V| and |E| (the same shapes at
every step, hence the same compiled programs), and the same seed gives
the same tables.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

Table = Dict[str, np.ndarray]


def kronecker_edges(scale: int, edgefactor: int, initiator, seed: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """The Graph500 reference generator's ``(start, end)`` vertex of each
    of ``edgefactor * 2**scale`` edges, before its label permutation."""
    a, b, c, _d = (float(x) for x in initiator)
    m = edgefactor << scale
    rng = np.random.default_rng(seed)
    ab = a + b
    c_norm = np.float32(c / (1.0 - ab))
    a_norm = np.float32(a / ab)
    ii = np.zeros(m, np.int32)
    jj = np.zeros(m, np.int32)
    for level in range(scale):
        ii_bit = rng.random(m, dtype=np.float32) > ab
        jj_bit = rng.random(m, dtype=np.float32) > np.where(ii_bit, c_norm,
                                                            a_norm)
        ii |= ii_bit.astype(np.int32) << level
        jj |= jj_bit.astype(np.int32) << level
    return ii, jj


def clean_undirected(ii: np.ndarray, jj: np.ndarray, scale: int
                     ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Self-loops and duplicates dropped, both directions of each
    remaining edge, isolated vertices dropped and the rest numbered
    ``0 .. |V| - 1`` in label order: ``(src, dst, |V|)``."""
    keep = ii != jj
    lo = np.minimum(ii, jj)[keep].astype(np.int64)
    hi = np.maximum(ii, jj)[keep].astype(np.int64)
    pair = np.unique((lo << scale) | hi)
    lo = (pair >> scale).astype(np.int32)
    hi = (pair & ((1 << scale) - 1)).astype(np.int32)
    present = np.zeros(1 << scale, bool)
    present[lo] = True
    present[hi] = True
    dense = (np.cumsum(present, dtype=np.int64) - 1).astype(np.int32)
    lo, hi = dense[lo], dense[hi]
    return (np.concatenate([lo, hi]), np.concatenate([hi, lo]),
            int(present.sum()))


def make_tables(config: dict, seed: int, want) -> Dict[str, Table]:
    scale = int(config["scale"])
    src, dst, n = clean_undirected(
        *kronecker_edges(scale, int(config["edgefactor"]),
                         config["initiator"], int(config["population_seed"])),
        scale)
    rng = np.random.default_rng(seed)
    label = rng.permutation(n).astype(np.int32)
    rows = rng.permutation(src.size)
    src, dst = label[src[rows]], label[dst[rows]]
    vid = rng.permutation(n).astype(np.int32)
    outdeg = np.bincount(src, minlength=n)[vid].astype(np.int32)
    if outdeg.min() < 1:
        raise AssertionError("a kept vertex has no out-edge")
    tables = {
        "edges": {"src": src, "dst": dst},
        "ranks": {"vid": vid,
                  "rank": np.full(n, 1.0 / n, np.float32),
                  "outdeg": outdeg},
    }
    return {t: tables[t] for t in want}
