"""PageRank — push-style along out-edges (the paper's Fig. 1 motivating
pattern), as a dense-frontier :mod:`repro.core.engine` vertex program.

The frontier never shrinks (every vertex pushes mass every iteration), so the
engine runs the dense direction throughout; what PageRank gains from the
engine is the shared machinery: locally the edge-parallel segment reduction,
distributed the shard_map wiring with every push a PIUMA *remote atomic add*
at the owner of the destination vertex.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from ...compat import shard_map
from .. import engine
from ..dgas import ATT
from ..graph import BBCSR, CSR
from .. import offload
from .distgraph import ShardedGraph

__all__ = ["pagerank", "pagerank_distributed", "ppr", "ppr_batched",
           "ppr_topk", "ppr_program"]


def pagerank(csr: CSR, *, damping: float = 0.85, iters: int = 20,
             kernel_bb: Optional[BBCSR] = None) -> jnp.ndarray:
    """(n,) float32 PageRank.  kernel_bb: optional unit-valued BBCSR of A^T
    (``engine.build_pull_operand(csr, unit_values=True)``) to run each
    iteration on the Pallas SpMV kernel."""
    n = csr.n_rows
    deg = csr.degrees().astype(jnp.float32)
    inv_deg = jnp.where(deg > 0, 1.0 / jnp.maximum(deg, 1), 0.0)

    def msg_fn(state, frontier):
        return state["x"] * inv_deg

    def update_fn(state, acc, frontier, it):
        x = state["x"]
        dangling = jnp.sum(jnp.where(deg > 0, 0.0, x))  # redistribute sinks
        x = (1 - damping) / n + damping * (acc + dangling / n)
        return {"x": x}, frontier

    prog = engine.VertexProgram(edge_op="copy", combine="add",
                                msg_fn=msg_fn, update_fn=update_fn)
    state0 = {"x": jnp.full((n,), 1.0 / n, jnp.float32)}
    frontier0 = jnp.ones((n,), jnp.int32)
    return engine.run(csr, prog, state0, frontier0, max_iters=iters,
                      mode="pull", kernel_bb=kernel_bb)["x"]


def ppr_program(csr: CSR, damping: float) -> engine.VertexProgram:
    """Personalized PageRank: the restart vector rides in ``state['r']`` (so
    the batched engine's lane vmap personalizes it per source); dangling mass
    also restarts to r — the random surfer teleports home, not uniformly."""
    deg = csr.degrees().astype(jnp.float32)
    inv_deg = jnp.where(deg > 0, 1.0 / jnp.maximum(deg, 1), 0.0)

    def msg_fn(state, frontier):
        return state["x"] * inv_deg

    def update_fn(state, acc, frontier, it):
        x, r = state["x"], state["r"]
        dangling = jnp.sum(jnp.where(deg > 0, 0.0, x))
        x = (1 - damping) * r + damping * (acc + dangling * r)
        return {"x": x, "r": r}, frontier

    return engine.VertexProgram(edge_op="copy", combine="add",
                                msg_fn=msg_fn, update_fn=update_fn)


def ppr(csr: CSR, source: int, *, damping: float = 0.85,
        iters: int = 20) -> jnp.ndarray:
    """Personalized PageRank from one source; (n,) float32 scores."""
    n = csr.n_rows
    r = jnp.zeros((n,), jnp.float32).at[source].set(1.0)
    state0 = {"x": r, "r": r}
    frontier0 = jnp.ones((n,), jnp.int32)
    return engine.run(csr, ppr_program(csr, damping), state0, frontier0,
                      max_iters=iters, mode="pull")["x"]


def ppr_batched(csr: CSR, sources, *, damping: float = 0.85,
                iters: int = 20, return_stats: bool = False,
                trace: bool = False, trace_len=None):
    """Personalized PageRank for B sources in one engine pass; (B, n) f32.

    Row b is bit-identical to ``ppr(csr, sources[b])``: the vmapped lanes
    share each dense edge scan (PageRank never leaves the pull regime) but
    personalize the restart vector per lane via the state.  ``return_stats``
    adds the ExecutionCore's {'iters', 'pushes', 'pulls'} trace.
    """
    n = csr.n_rows
    src = jnp.asarray(sources, jnp.int32)
    B = int(src.shape[0])
    r = jnp.zeros((B, n), jnp.float32).at[jnp.arange(B), src].set(1.0)
    state0 = {"x": r, "r": r}
    frontier0 = jnp.ones((B, n), jnp.int32)
    out = engine.run_batched(csr, ppr_program(csr, damping), state0,
                             frontier0, max_iters=iters, mode="pull",
                             return_stats=return_stats,
                             trace=trace, trace_len=trace_len)
    if return_stats:
        state, stats = out
        return state["x"], stats
    return out["x"]


def ppr_topk(csr: CSR, sources, k: int, *, damping: float = 0.85,
             iters: int = 20,
             return_stats: bool = False,
             trace: bool = False, trace_len=None):
    """Top-k PPR per source: (scores (B, k), vertex ids (B, k)) — the
    service layer's PPR query shape.  ``return_stats`` appends the
    ExecutionCore's level trace (all pulls: PPR never leaves the dense
    regime), so the serving ledger can price PPR batches from the measured
    run like the traversal kinds."""
    out = ppr_batched(csr, sources, damping=damping, iters=iters,
                      return_stats=return_stats,
                      trace=trace, trace_len=trace_len)
    x, stats = out if return_stats else (out, None)
    vals, idx = lax.top_k(x, k)
    if return_stats:
        return vals, idx.astype(jnp.int32), stats
    return vals, idx.astype(jnp.int32)


def pagerank_distributed(g: ShardedGraph, att: ATT, mesh: Mesh, *, axis=None,
                         damping: float = 0.85, iters: int = 20) -> jnp.ndarray:
    """x sharded by `att` (same rule owns vertex data and src rows).

    Returns stacked (S, per_shard) pagerank vector.
    """
    axis = axis if axis is not None else mesh.axis_names[0]
    spec = P(axis) if isinstance(axis, str) else P(tuple(axis))
    n, S, per = att.n_global, att.n_shards, att.per_shard
    axes = [axis] if isinstance(axis, str) else list(axis)

    # degrees, sharded by att
    def _deg_shard(src, *, att, axis):
        d = jnp.zeros((att.per_shard,), jnp.float32)
        ones = jnp.where(src[0] >= 0, 1.0, 0.0)
        return offload.remote_scatter_add(d, src[0], ones, att, axis,
                                          capacity=src.shape[1])[None]

    deg = shard_map(partial(_deg_shard, att=att, axis=axis), mesh=mesh,
                    in_specs=(spec,), out_specs=spec)(g.src)
    inv_deg = jnp.where(deg > 0, 1.0 / jnp.maximum(deg, 1.0), 0.0)

    def msg_fn(state, frontier):
        return state["x"] * state["inv_deg"]

    def update_fn(state, acc, frontier, it):
        x, dg = state["x"], state["deg"]
        dangling = offload.hierarchical_psum(
            jnp.sum(jnp.where(dg > 0, 0.0, x)), axes)
        x = (1 - damping) / n + damping * (acc + dangling / n)
        return {"x": x, "inv_deg": state["inv_deg"], "deg": dg}, frontier

    prog = engine.VertexProgram(edge_op="copy", combine="add",
                                msg_fn=msg_fn, update_fn=update_fn)

    # mask padded vertex slots out of the initial mass
    x = jnp.full((S, per), 1.0 / n, jnp.float32)
    # zero out padding slots (local ids beyond the shard's span)
    spans = jnp.asarray(
        [min(per, max(0, att.shard_slice(s)[1])) if att.kind != "interleave"
         else (n - s + S - 1) // S for s in range(S)], jnp.int32)
    x = jnp.where(jnp.arange(per)[None, :] < spans[:, None], x, 0.0)

    state0 = {"x": x, "inv_deg": inv_deg, "deg": deg}
    frontier0 = jnp.ones((S, per), jnp.int32)
    state = engine.run_distributed(g, att, mesh, prog, state0, frontier0,
                                   axis=axis, max_iters=iters, mode="push")
    return state["x"]
