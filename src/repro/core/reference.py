"""Plain NumPy references for the engine's traversals and PageRank.

Independent of the engine: each function walks the CSR with NumPy and
Python loops only, so tests and ``chip_smoke.py`` can hold the engine's
answers (any placement, the Pallas kernel path included) against the same
semantics computed another way.

* :func:`bfs_levels` — level-synchronous BFS; exact.
* :func:`dijkstra` — Dijkstra with every sum taken in float32, the way the
  engine relaxes ``dist[u] + w``.  With non-negative weights f32 addition is
  monotone, so any relaxation schedule (the engine's delta-stepping, its
  kernel path) reaches this same least fixpoint: compared exactly.
* :func:`pagerank` / :func:`ppr` — power iteration in float64 (the engine's
  f32 ``add`` reductions reassociate, so these are compared with a tolerance).
"""
from __future__ import annotations

import heapq

import numpy as np

__all__ = ["bfs_levels", "dijkstra", "pagerank", "ppr"]


def bfs_levels(indptr, indices, src: int) -> np.ndarray:
    """(n,) int64 BFS level from ``src``; unreachable = -1."""
    n = indptr.shape[0] - 1
    level = -np.ones(n, np.int64)
    level[src] = 0
    frontier, d = [src], 0
    while frontier:
        nxt = []
        for u in frontier:
            for v in indices[indptr[u]:indptr[u + 1]]:
                if level[v] < 0:
                    level[v] = d + 1
                    nxt.append(v)
        frontier, d = nxt, d + 1
    return level


def dijkstra(csr, src: int) -> np.ndarray:
    """(n,) float32 shortest distances from ``src`` (unit weights when the
    CSR has no values); unreachable = +inf."""
    indptr = np.asarray(csr.indptr)
    indices = np.asarray(csr.indices)
    w = (np.asarray(csr.values, np.float32) if csr.values is not None
         else np.ones(indices.shape, np.float32))
    n = indptr.shape[0] - 1
    dist = np.full(n, np.inf, np.float32)
    dist[src] = 0.0
    pq = [(np.float32(0.0), src)]
    while pq:
        d, u = heapq.heappop(pq)
        if d > dist[u]:
            continue
        for e in range(indptr[u], indptr[u + 1]):
            v, nd = indices[e], d + w[e]        # float32 + float32
            if nd < dist[v]:
                dist[v] = nd
                heapq.heappush(pq, (nd, v))
    return dist


def _push_operator(csr):
    indptr = np.asarray(csr.indptr, np.int64)
    rows = np.repeat(np.arange(indptr.shape[0] - 1), np.diff(indptr))
    cols = np.asarray(csr.indices, np.int64)
    deg = np.diff(indptr).astype(np.float64)
    return rows, cols, deg


def pagerank(csr, damping: float = 0.85, iters: int = 20) -> np.ndarray:
    """(n,) float64 PageRank; sink mass is spread uniformly."""
    rows, cols, deg = _push_operator(csr)
    n = deg.shape[0]
    x = np.full(n, 1.0 / n)
    for _ in range(iters):
        push = np.where(deg[rows] > 0, x[rows] / np.maximum(deg[rows], 1), 0.0)
        y = np.bincount(cols, weights=push, minlength=n)
        x = (1 - damping) / n + damping * (y + x[deg == 0].sum() / n)
    return x


def ppr(csr, src: int, damping: float = 0.85, iters: int = 20) -> np.ndarray:
    """(n,) float64 personalized PageRank from ``src``: restarts and sink
    mass both return to ``src``."""
    rows, cols, deg = _push_operator(csr)
    n = deg.shape[0]
    r = np.zeros(n)
    r[src] = 1.0
    x = r.copy()
    for _ in range(iters):
        push = np.where(deg[rows] > 0, x[rows] / np.maximum(deg[rows], 1), 0.0)
        y = np.bincount(cols, weights=push, minlength=n)
        x = (1 - damping) * r + damping * (y + x[deg == 0].sum() * r)
    return x
