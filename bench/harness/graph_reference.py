"""The plain reference of the PageRank cells: LDBC Graphalytics PageRank
in numpy, over the edge list as the population made it.  It imports
nothing of the system under test.

PR_0(v) = 1/|V|, and PR_t+1(v) = (1 - d)/|V| + d * sum over edges u -> v
of PR_t(u)/|out(u)| + (d/|V|) * sum over dangling w of PR_t(w).  Ranks
are float32; each ``rank/outdeg`` is a float32 division, and the sums
are float64 ``np.bincount`` sums cast to float32.
"""

from __future__ import annotations

from typing import List

import numpy as np


def pagerank(src: np.ndarray, dst: np.ndarray, num_vertices: int,
             damping: float, iterations: int) -> List[np.ndarray]:
    """The ranks after each of ``iterations`` iterations, indexed by
    vertex ``0 .. num_vertices - 1``."""
    n = int(num_vertices)
    outdeg = np.bincount(src, minlength=n)
    dangling = outdeg == 0
    rank = np.full(n, 1.0 / n, np.float32)
    out = []
    for _ in range(iterations):
        contrib = rank[src] / outdeg[src].astype(np.float32)
        summed = np.bincount(dst, weights=contrib.astype(np.float64),
                             minlength=n).astype(np.float32)
        spread = float(rank[dangling].astype(np.float64).sum())
        base = np.float32((1.0 - damping) / n + damping * spread / n)
        rank = base + np.float32(damping) * summed
        out.append(rank)
    return out


def relative_errors(vid: np.ndarray, rank: np.ndarray,
                    want: np.ndarray) -> np.ndarray:
    """Each vertex's ``|rank - want| / want``, the ranks placed by
    ``vid``.  A run that does not give every vertex exactly once reads 1
    for every vertex, and a rank that is not finite reads 1."""
    n = want.size
    if vid.size != n or vid.min(initial=0) < 0 or vid.max(initial=0) >= n \
            or np.unique(vid).size != n:
        return np.ones(n)
    got = np.zeros(n, np.float64)
    got[vid] = rank
    err = np.abs(got - want) / want
    return np.where(np.isfinite(err), err, 1.0)
