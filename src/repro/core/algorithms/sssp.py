"""Single-source shortest paths — delta-stepping-style, on the frontier engine.

The vertex program is the (min, +) semiring: active vertices emit their
tentative distance, every edge adds its weight, destinations keep the min.
On top of that the update rule implements delta-stepping's bucket discipline:
only *pending* vertices (improved since last expanded) whose distance falls
inside the current bucket ``[0, bound)`` join the frontier; when the bucket
drains, ``bound`` advances to ``min(pending dist) + delta``.  ``delta=inf``
degenerates to Bellman–Ford with a frontier (every pending vertex active),
small ``delta`` approaches Dijkstra's settled order — the classic knob
between work-efficiency and parallelism.

Distributed, the relaxations are PIUMA remote atomic *min* ops at the owner
(`offload.remote_scatter_combine`), with the bucket bound agreed globally via
a collective-engine reduction.

Weights must be non-negative (as delta-stepping requires); an unweighted
graph relaxes with unit weights (== BFS distances).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

from .. import engine
from ..dgas import ATT
from ..graph import CSR
from .distgraph import ShardedGraph

__all__ = ["sssp", "sssp_distributed", "sssp_program", "auto_delta",
           "sssp_batched", "sssp_batched_distributed"]

_INF = jnp.float32(jnp.inf)


def sssp_program(delta: float, *, global_min=None) -> engine.VertexProgram:
    """(min, +) relaxation with bucketed frontier admission.

    global_min: optional f(x)->x reduction so the distributed engine agrees
      on `min(pending dist)` across shards (identity for the local engine).
    """
    gmin = global_min if global_min is not None else (lambda x: x)

    def msg_fn(state, frontier):
        return jnp.where(frontier > 0, state["dist"], _INF)

    def update_fn(state, acc, frontier, it):
        dist, pending, bound = state["dist"], state["pending"], state["bound"]
        relaxed = acc < dist
        dist = jnp.minimum(dist, acc)
        pending = (pending & (frontier == 0)) | relaxed
        in_bucket = pending & (dist <= bound)
        minpend = gmin(jnp.min(jnp.where(pending, dist, _INF)))
        # global-min of (0 if in bucket else 1) is 0 iff ANY shard has one
        bucket_empty = gmin(jnp.min(jnp.where(in_bucket, 0.0, 1.0))) > 0.5
        bound = jnp.where(jnp.isfinite(minpend) & bucket_empty,
                          minpend + delta, bound)
        new_frontier = pending & (dist <= bound)
        return ({"dist": dist, "pending": pending, "bound": bound},
                new_frontier.astype(jnp.int32))

    return engine.VertexProgram(edge_op="add", combine="min",
                                msg_fn=msg_fn, update_fn=update_fn)


def auto_delta(csr: CSR, *, bins: int = 64, light_edges_per_vertex: float = 4.0,
               scaled: bool = True) -> float:
    """Delta from the weight histogram (DESIGN.md §8).

    Pick delta at the weight quantile where the expected number of sub-delta
    ("light") edges per vertex reaches ``light_edges_per_vertex``:
    delta ≈ quantile(w, q) with q = min(1, target / avg_degree), read off a
    ``bins``-bin histogram CDF (the histogram, not a full sort, is what a
    PIUMA-side autotuner would keep as a running statistic).  Small targets
    degenerate toward Dijkstra's serial bucket order (many near-empty
    expansions); very large ones re-relax heavy chains Bellman-Ford-style.
    The default target 4.0 comes from the `bench_engine.py --sweep-delta`
    sweep on RMAT and uniform-weight graphs (DESIGN.md §8): on this
    bulk-synchronous engine, iteration count dominates, and the 4-light-edge
    quantile sits within ~10% of the best fixed delta on both graph classes
    while keeping the bucket discipline that bounds re-relaxation work.

    scaled: multiply the histogram quantile by the tuned ``sssp.delta_scale``
    for this backend and graph scale (``repro.tune``, DESIGN.md §18) — the
    autotuner sweeps the multiplier by measured iteration count and passes
    ``scaled=False`` to read the raw quantile it scales.  Unweighted (and
    empty) graphs always return exactly 1.0: unit-weight distances are
    integers, one BFS level per bucket, and there is no quantile to scale.
    """
    if csr.values is None:
        return 1.0
    w = np.asarray(csr.values)
    if w.size == 0:
        return 1.0
    from ... import tune
    mul = (tune.resolve("sssp.delta_scale", n=csr.n_rows) if scaled else 1.0)
    avg_deg = max(1.0, csr.nnz / max(1, csr.n_rows))
    hist, edges = np.histogram(w, bins=bins)
    cdf = np.cumsum(hist) / max(1, w.size)
    q = min(1.0, light_edges_per_vertex / avg_deg)
    return float(max(edges[min(int(np.searchsorted(cdf, q)) + 1,
                               len(edges) - 1)], 1e-6)) * mul


def sssp(csr: CSR, source: int, *, delta: Optional[float] = None,
         max_iters: Optional[int] = None, mode: str = "auto",
         kernel_bb=None, return_stats: bool = False, trace: bool = False,
         trace_len: Optional[int] = None):
    """Returns (n,) float32 distances; unreachable = +inf.

    delta: bucket width; None auto-tunes from the weight histogram
      (:func:`auto_delta`).
    kernel_bb: optional weighted BBCSR of A^T (``engine.build_pull_operand``)
      to run the relaxations on the Pallas masked-select min combine.
    trace: with return_stats, record the per-level engine trace into
      ``stats['trace']`` (obs.decode_level_trace reads it back).
    """
    n = csr.n_rows
    delta = delta if delta is not None else auto_delta(csr)
    max_iters = max_iters if max_iters is not None else 4 * n
    state0 = {
        "dist": jnp.full((n,), _INF).at[source].set(0.0),
        "pending": jnp.zeros((n,), bool).at[source].set(True),
        "bound": jnp.float32(delta),
    }
    frontier0 = jnp.zeros((n,), jnp.int32).at[source].set(1)
    out = engine.run(csr, sssp_program(delta), state0, frontier0,
                     max_iters=max_iters, mode=mode, kernel_bb=kernel_bb,
                     return_stats=return_stats, trace=trace,
                     trace_len=trace_len)
    if return_stats:
        state, stats = out
        return state["dist"], stats
    return out["dist"]


def sssp_batched(csr: CSR, sources, *, delta: Optional[float] = None,
                 max_iters: Optional[int] = None, mode: str = "auto",
                 kernel_bb=None, return_stats: bool = False,
                 trace: bool = False, trace_len: Optional[int] = None):
    """Distances (B, n) float32 for B concurrent single-source runs.

    The *same* ``sssp_program`` drives every lane (the engine vmaps it), so
    row b is bit-identical to ``sssp(csr, sources[b], delta=delta)`` — each
    lane keeps its own bucket bound and drains independently while the
    (min, +) relaxations of all lanes ride one shared edge scan.  ``delta``
    must be shared across the batch (it is a graph-level constant under
    :func:`auto_delta` anyway — the service layer's compatibility rule).
    kernel_bb: optional weighted BBCSR of A^T (``engine.build_pull_operand``)
      to run the relaxations on the Pallas masked-select min combine.
    """
    n = csr.n_rows
    src = jnp.asarray(sources, jnp.int32)
    B = int(src.shape[0])
    delta = delta if delta is not None else auto_delta(csr)
    max_iters = max_iters if max_iters is not None else 4 * n
    lanes = jnp.arange(B)
    state0 = {
        "dist": jnp.full((B, n), _INF).at[lanes, src].set(0.0),
        "pending": jnp.zeros((B, n), bool).at[lanes, src].set(True),
        "bound": jnp.full((B,), delta, jnp.float32),
    }
    frontier0 = jnp.zeros((B, n), jnp.int32).at[lanes, src].set(1)
    out = engine.run_batched(csr, sssp_program(delta), state0, frontier0,
                             max_iters=max_iters, mode=mode,
                             kernel_bb=kernel_bb, return_stats=return_stats,
                             trace=trace, trace_len=trace_len)
    if return_stats:
        state, stats = out
        return state["dist"], stats
    return out["dist"]


def sssp_batched_distributed(g: ShardedGraph, att: ATT, sources, mesh: Mesh,
                             *, axis=None, delta: float = 1.0,
                             max_iters: int = 256,
                             return_stats: bool = False,
                             placement: str = "sync",
                             sync_interval: Optional[int] = None,
                             trace: bool = False,
                             trace_len: Optional[int] = None):
    """Batched distances stacked (S, B, per_shard) under `att`; slice
    ``[:, b, :]`` matches ``sssp_distributed(g, att, sources[b], mesh,
    delta=delta)`` — all B lanes' remote atomic-min relaxations share each
    level's compacted exchange, and the per-lane bucket bounds are agreed
    with one (lane-batched) collective min.  ``return_stats`` adds the
    engine's {'iters', 'pushes', 'pulls', 'fallbacks'} trace (the service
    layer's route-byte model input)."""
    axis = axis if axis is not None else mesh.axis_names[0]
    ax = axis if isinstance(axis, str) else tuple(axis)
    S, per = att.n_shards, att.per_shard
    src = jnp.asarray(sources, jnp.int32)
    B = int(src.shape[0])
    owner = att.owner(src)
    local = att.local(src)
    lanes = jnp.arange(B)
    # async: per-shard bucket pacing — each shard advances its own bound
    # from its local pending set (exactly the local engine's rule); the
    # (min, +) fixpoint is schedule-independent, so distances still match
    # the sync placement bit-for-bit while the two pmin collectives per
    # level disappear from the micro-stepped path.
    prog = sssp_program(delta) if placement == "async" else \
        sssp_program(delta, global_min=lambda x: lax.pmin(x, ax))
    state0 = {
        "dist": jnp.full((S, B, per), _INF).at[owner, lanes, local].set(0.0),
        "pending": jnp.zeros((S, B, per), bool).at[owner, lanes, local].set(True),
        "bound": jnp.full((S, B), delta, jnp.float32),
    }
    frontier0 = jnp.zeros((S, B, per), jnp.int32).at[owner, lanes, local].set(1)
    out = engine.run_batched_distributed(g, att, mesh, prog, state0,
                                         frontier0, axis=axis,
                                         max_iters=max_iters,
                                         return_stats=return_stats,
                                         placement=placement,
                                         sync_interval=sync_interval,
                                         trace=trace, trace_len=trace_len)
    if return_stats:
        state, stats = out
        return state["dist"], stats
    return out["dist"]


def sssp_distributed(g: ShardedGraph, att: ATT, source: int, mesh: Mesh, *,
                     axis=None, delta: float = 1.0, max_iters: int = 256,
                     placement: str = "sync",
                     sync_interval: Optional[int] = None) -> jnp.ndarray:
    """Distances stacked (S, per_shard) under `att`; remote atomic-min push.

    placement='async': bounded-staleness pacing with per-shard bucket
    bounds (local gmin — PIUMA's own per-block bucket model); the (min, +)
    fixpoint is schedule-independent so distances match 'sync' exactly.
    """
    axis = axis if axis is not None else mesh.axis_names[0]
    ax = axis if isinstance(axis, str) else tuple(axis)
    S, per = att.n_shards, att.per_shard
    owner = int(att.owner(jnp.asarray(source)))
    local = int(att.local(jnp.asarray(source)))

    prog = sssp_program(delta) if placement == "async" else \
        sssp_program(delta, global_min=lambda x: lax.pmin(x, ax))
    state0 = {
        "dist": jnp.full((S, per), _INF).at[owner, local].set(0.0),
        "pending": jnp.zeros((S, per), bool).at[owner, local].set(True),
        "bound": jnp.full((S, 1), delta, jnp.float32),
    }
    frontier0 = jnp.zeros((S, per), jnp.int32).at[owner, local].set(1)
    state = engine.run_distributed(g, att, mesh, prog, state0, frontier0,
                                   axis=axis, max_iters=max_iters, mode="push",
                                   placement=placement,
                                   sync_interval=sync_interval)
    return state["dist"]
