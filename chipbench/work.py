"""The work of one search, from the graph and the reached set alone.

Both counts depend on what a search must do, not on how the program does
it, so they read the same whether XLA or a Pallas kernel runs the search.

* :func:`traversed_edges` — Graph500's count: the undirected edges with a
  reached endpoint.  The graph is symmetric and deduplicated, so that is the
  sum of the reached vertices' degrees over two.
* :func:`least_bytes` — the fewest bytes a search must move through HBM:
  each reached vertex's adjacency list is read once (4 B per column index,
  and 4 B per weight for SSSP), its two row pointers (8 B) and its state
  (4 B).  The operations per byte are near 0, so HBM bandwidth bounds the
  search, and least bytes over peak bandwidth is its least time.
"""
from __future__ import annotations

import numpy as np

__all__ = ["traversed_edges", "least_bytes"]

INDEX_BYTES = 4
WEIGHT_BYTES = 4
ROW_POINTER_BYTES = 8
STATE_BYTES = 4


def traversed_edges(deg: np.ndarray, reached: np.ndarray) -> int:
    return int(deg[reached].sum()) // 2


def least_bytes(deg: np.ndarray, reached: np.ndarray, weighted: bool) -> int:
    per_entry = INDEX_BYTES + (WEIGHT_BYTES if weighted else 0)
    return (per_entry * int(deg[reached].sum())
            + (ROW_POINTER_BYTES + STATE_BYTES) * int(reached.sum()))
