"""Plain numpy references of canned workloads, independent of the
planner, the executor and the store: the tests hold ``Session`` runs to
them."""

from __future__ import annotations

from typing import List

import numpy as np


def pagerank(src: np.ndarray, dst: np.ndarray, num_vertices: int,
             damping: float, iterations: int) -> List[np.ndarray]:
    """LDBC Graphalytics PageRank over the directed edges ``src → dst`` of
    vertices ``0 .. num_vertices - 1``: the float32 ranks after each of
    ``iterations`` iterations, indexed by vertex.

    PR_0(v) = 1/|V|; PR_t+1(v) = (1 - d)/|V| + d * sum over edges u → v of
    PR_t(u)/|out(u)| + (d/|V|) * sum over dangling w of PR_t(w).  Each
    ``rank/outdeg`` is a float32 division; the sums are float64
    ``np.bincount`` sums cast to float32."""
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
