"""Plain references for the benchmark's comparisons, vectorised with NumPy.

They take the edge list the benchmark generated (``rows``, ``cols``,
``w``, sorted by row) and nothing the program made.

* :func:`bfs_levels` — level-synchronous BFS; exact.
* :func:`sssp_f32` — frontier Bellman-Ford with every sum taken in float32.
  With non-negative weights a float32 relaxation ``min(d[v], d[u] + w)`` is
  monotone, so every schedule of relaxations from the same start — this one,
  Dijkstra's, delta-stepping's — stops at the same fixpoint: the program's
  distances are compared with it exactly.
* :func:`ppr` — personalized PageRank by power iteration in float64, for
  many sources at once (the program sums in float32 in another order, so it
  is compared by a limit).

``precision="bfloat16"`` computes :func:`sssp_f32` and :func:`ppr` with every
stored value rounded to bfloat16: the control that the comparison has to
fail.  ``levels_short_by`` stops :func:`bfs_levels` early: the control of the
BFS cell, whose levels carry no floating-point precision to lower.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse

__all__ = ["Graph", "bfs_levels", "sssp_f32", "ppr"]


class Graph:
    """CSR arrays of the generated edge list, built here with NumPy."""

    def __init__(self, n: int, rows, cols, w):
        self.n = int(n)
        self.indices = np.asarray(cols, np.int64)
        self.w = np.asarray(w, np.float32)
        self.rows = np.asarray(rows, np.int64)
        self.deg = np.bincount(self.rows, minlength=self.n).astype(np.int64)
        self.indptr = np.concatenate([[0], np.cumsum(self.deg)])

    def out_edges(self, verts: np.ndarray) -> np.ndarray:
        """Edge ids of every out-edge of ``verts``, grouped by vertex."""
        starts = self.indptr[verts]
        lens = self.deg[verts]
        total = int(lens.sum())
        if total == 0:
            return np.zeros(0, np.int64)
        offs = np.repeat(starts - np.concatenate([[0], np.cumsum(lens)[:-1]]),
                         lens)
        return offs + np.arange(total, dtype=np.int64)


def _bf16(x: np.ndarray) -> np.ndarray:
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def bfs_levels(g: Graph, src: int, levels_short_by: int = 0) -> np.ndarray:
    """(n,) int32 hop level from ``src``; unreachable = -1."""
    level = np.full(g.n, -1, np.int32)
    level[src] = 0
    frontier = np.array([src], np.int64)
    depth = 0
    while frontier.size:
        nbrs = g.indices[g.out_edges(frontier)]
        mark = np.zeros(g.n, bool)
        mark[nbrs] = True
        mark &= level < 0
        frontier = np.flatnonzero(mark)
        depth += 1
        level[frontier] = depth
    if levels_short_by:
        level[level > level.max() - levels_short_by] = -1
    return level


def sssp_f32(g: Graph, src: int, precision: str = "float32") -> np.ndarray:
    """(n,) float32 distances from ``src``; unreachable = +inf."""
    rnd = _bf16 if precision == "bfloat16" else (lambda x: x)
    w = rnd(g.w)
    dist = np.full(g.n, np.inf, np.float32)
    dist[src] = 0.0
    active = np.array([src], np.int64)
    while active.size:
        e = g.out_edges(active)
        u = np.repeat(active, g.deg[active])
        v = g.indices[e]
        cand = rnd(dist[u] + w[e])
        better = cand < dist[v]
        v, cand = v[better], cand[better]
        if not v.size:
            break
        np.minimum.at(dist, v, cand)
        active = np.unique(v)
    return dist


def ppr(g: Graph, srcs, damping: float, iters: int,
        precision: str = "float64") -> np.ndarray:
    """(n, len(srcs)) personalized PageRank, a column per source: restarts
    and the mass of vertices without out-edges both return to the source."""
    rnd = _bf16 if precision == "bfloat16" else (lambda x: x)
    srcs = np.asarray(srcs, np.int64)
    # into[v, u] = 1 for each edge u -> v: every out-edge of u carries the
    # same share of u's score, so the push is rounded once per vertex
    into = scipy.sparse.csr_matrix(
        (np.ones(g.rows.size), (g.indices, g.rows)), shape=(g.n, g.n))
    inv = np.where(g.deg > 0, 1.0 / np.maximum(g.deg, 1), 0.0)[:, None]
    sink = g.deg == 0
    r = np.zeros((g.n, srcs.size))
    r[srcs, np.arange(srcs.size)] = 1.0
    x = r.copy()
    for _ in range(iters):
        y = rnd(into @ rnd(x * inv))
        x = rnd((1 - damping) * r + damping * (y + x[sink].sum(0) * r))
    return x
