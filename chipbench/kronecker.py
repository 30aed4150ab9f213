"""The Graph500 Kronecker generator, kept with the benchmark.

A copy of the generator in the Graph500 specification (section 3, the
Octave reference ``kronecker_generator``): ``edge_factor * 2**scale`` edges,
each placed bit by bit into one quadrant of the adjacency matrix with
probabilities A, B, C and D = 1 - A - B - C; the vertex labels are then
permuted at random.  Weights are drawn uniform in [0, 1) as float32, one per
generated edge, as the specification's SSSP kernel asks.

What the graph then is follows the configuration:

* ``directed: false`` — Graph500's own graph: every edge goes both ways with
  one weight, self-loops are dropped, and of parallel edges the lightest is
  kept, so both directions of a pair carry the same weight.
* ``directed: true`` — the generated edges as they are, with self-loops
  dropped and the lightest of parallel edges kept.

The edge list is sorted by (row, column) and carries no duplicates.  It is a
function of the configuration alone; :func:`load_edges` caches it in the
checkout, keyed by the parameters, so later runs of a cell read it back.
"""
from __future__ import annotations

import hashlib
import json
import os

import numpy as np

__all__ = ["kronecker_edges", "finish_edges", "load_edges", "GRAPH_KEYS"]

# the configuration keys that decide the graph
GRAPH_KEYS = ("generator", "scale", "edge_factor", "A", "B", "C",
              "graph_seed", "directed", "weights")


def kronecker_edges(scale: int, edge_factor: int, a: float, b: float,
                    c: float, seed: int):
    """(src, dst, weight) of the raw generated edges, int64/int64/float32."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = edge_factor * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ii = np.zeros(m, np.int64)
    jj = np.zeros(m, np.int64)
    for bit in range(scale):
        ii_bit = rng.random(m) > ab
        jj_bit = rng.random(m) > np.where(ii_bit, c_norm, a_norm)
        ii |= ii_bit.astype(np.int64) << bit
        jj |= jj_bit.astype(np.int64) << bit
    perm = rng.permutation(n)
    w = rng.random(m, dtype=np.float32)
    return perm[ii], perm[jj], w


def _lightest(key: np.ndarray, w: np.ndarray):
    """Unique keys (sorted) and the least weight of each."""
    order = np.lexsort((w, key))
    key, w = key[order], w[order]
    first = np.ones(key.shape[0], bool)
    first[1:] = key[1:] != key[:-1]
    return key[first], w[first]


def finish_edges(src, dst, w, n: int, directed: bool):
    """Drop self-loops, keep the lightest of parallel edges and, undirected,
    add the reverse of every edge; (rows, cols, weights) sorted by row, then
    column, as int32/int32/float32."""
    keep = src != dst
    src, dst, w = src[keep], dst[keep], w[keep]
    if directed:
        key, w = _lightest(src * n + dst, w)
        rows, cols = key // n, key % n
    else:
        lo, hi = np.minimum(src, dst), np.maximum(src, dst)
        key, w = _lightest(lo * n + hi, w)
        lo, hi = key // n, key % n
        rows = np.concatenate([lo, hi])
        cols = np.concatenate([hi, lo])
        w = np.concatenate([w, w])
        order = np.lexsort((cols, rows))
        rows, cols, w = rows[order], cols[order], w[order]
    return rows.astype(np.int32), cols.astype(np.int32), w.astype(np.float32)


def _params(config: dict) -> dict:
    missing = [k for k in GRAPH_KEYS if k not in config]
    if missing:
        raise KeyError(f"configuration {config.get('name')!r} lacks {missing}")
    if config["generator"] != "graph500_kronecker":
        raise ValueError(f"unknown generator {config['generator']!r}")
    if config["weights"] != "uniform_0_1_f32":
        raise ValueError(f"unknown weights {config['weights']!r}")
    return {k: config[k] for k in GRAPH_KEYS}


def build_edges(config: dict):
    p = _params(config)
    src, dst, w = kronecker_edges(p["scale"], p["edge_factor"], p["A"],
                                  p["B"], p["C"], p["graph_seed"])
    return finish_edges(src, dst, w, 1 << p["scale"], p["directed"])


def load_edges(config: dict, cache_dir: str):
    """The configuration's edge list, from ``cache_dir`` when a run made it
    before, else generated and written there.  Returns (n, rows, cols, w)."""
    p = _params(config)
    digest = hashlib.sha256(json.dumps(p, sort_keys=True).encode())
    path = os.path.join(cache_dir, f"{config['name']}-"
                        f"{digest.hexdigest()[:16]}.npz")
    n = 1 << p["scale"]
    try:
        with np.load(path) as z:
            return n, z["rows"], z["cols"], z["w"]
    except (OSError, KeyError, ValueError):
        pass
    rows, cols, w = build_edges(config)
    os.makedirs(cache_dir, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp.npz"
    np.savez(tmp, rows=rows, cols=cols, w=w)
    os.replace(tmp, path)
    return n, rows, cols, w
