"""Builds the system under test from a configuration file.

The benchmark takes from the program only what it measures: ``CSR.from_coo``
builds the graph from the benchmark's edge list (set-up), and
``GraphService`` or the public ``bfs`` / ``sssp`` serve the traffic.
"""
from __future__ import annotations

import os

import numpy as np

from . import kronecker

__all__ = ["edges", "degrees", "csr_of", "service_of", "to_query"]


def edges(config: dict, work_dir: str):
    """(n, rows, cols, w) of the configuration's graph (cached on disk)."""
    return kronecker.load_edges(config, os.path.join(work_dir, "graphs"))


def csr_of(n: int, rows, cols, w):
    from repro.core.graph import CSR
    return CSR.from_coo(rows, cols, w, n, n)


def service_of(config: dict, csr):
    """``GraphService`` as the configuration states it, on one device."""
    from repro.core import GraphService
    s = config["service"]
    return GraphService(csr, batch_budget=s["batch_budget"],
                        cache_capacity=s["cache_capacity"],
                        ppr_iters=s["ppr_iters"], damping=s["damping"],
                        placement=s["placement"])


def to_query(q):
    """The service's query object for a generated ``(kind, a, b)``."""
    from repro.core import Distance, PPRTopK, Reachability
    kind, a, b = q
    if kind == "reach":
        return Reachability(a, b)
    if kind == "dist":
        return Distance(a, b)
    if kind == "ppr":
        return PPRTopK(a, k=b)
    raise ValueError(f"unknown query kind {kind!r}")


def degrees(n: int, rows) -> np.ndarray:
    return np.bincount(np.asarray(rows), minlength=n).astype(np.int64)
