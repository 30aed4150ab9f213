"""Graph query service: micro-batched multi-source traversal serving.

PIUMA's concurrency story is *many traversals in flight at once* — the
single-query engine reproduces the memory/network story (DESIGN.md §3–§7),
this module reproduces the serving story on top of the batched engine: a
typed query API, an admission queue that micro-batches compatible queries
into one batched engine pass, an LRU result cache keyed by (graph epoch,
query), and a stats ledger (queries/sec, batch occupancy, cache hit rate,
latency percentiles, deadline-miss rate, route bytes/query).

Placement follows the ExecutionCore grid (DESIGN.md §14): constructed with a
``mesh``, the service serves traversal queries from the **sharded** engine —
`engine.run_batched_distributed` via ``msbfs_distributed`` /
``sssp_batched_distributed`` — so one compacted owner-routed exchange per
level carries every lane of the batch; without a mesh it serves from the
local batched engine exactly as before.  ``placement='async'`` serves the
traversal kinds under the engine's bounded-staleness placement instead —
``sync_interval`` collective-free micro-steps between global checks, same
results (the traversal programs are monotone), ~K× fewer global reductions
per query — and ``cost_seed='auto'`` warms the deadline cost EWMA from the
last committed bench doc.  PPR and neighbor-sample queries
stay on the local placement either way (PPR is a dense-regime program with
no batched-distributed port yet; sampling is one compacted gather).

Queries and their results
-------------------------

=====================  =============================  =====================
query                  engine pass                    result
=====================  =============================  =====================
:class:`Reachability`  bit-packed MS-BFS lane         bool
:class:`Distance`      batched delta-stepping lane    float (inf = no path)
:class:`PPRTopK`       vmapped personalized-PR lane   (ids (k,), scores (k,))
:class:`NeighborSample` keyed one-hop sample slots    ids (fanout,)
=====================  =============================  =====================

Micro-batching policy (DESIGN.md §13/§14): the admission queue preserves
submission order *within* a kind, and each round picks the next kind
**round-robin** over the kinds with pending queries — a burst of one kind
can therefore no longer starve the others (the pre-PR-5 policy served the
oldest query's kind first, so head-of-line bursts monopolized the engine).
Queries of the round's kind are collected in submission order until the
batch budget of lanes is full.  Traversal queries occupying the same source
share a lane (dedup), sample queries occupy ``fanout`` slots.  Batches are
padded to the full budget so each (kind, budget) pair compiles exactly once;
padding lanes replay lane 0 and are discarded.

Deadline-aware admission (DESIGN.md §14): ``submit(q, deadline=s)`` attaches
a latency SLO (seconds from submission).  The micro-batcher then flushes not
only on demand but the moment the oldest admitted deadline's *slack* —
deadline minus now minus the kind's estimated batch cost (an EWMA of
measured executions) — is exhausted, or as soon as a kind's pending lane
demand fills the budget: a deadline query waits for batch-fill only while
waiting is free.  ``poll()`` is the client-driven tick between submissions.
The deadline never changes *what* is computed — only when the batch is cut —
so it stays out of the cache key.

Graph mutation (DESIGN.md §16): the service's graph currency is an
epoch-versioned :class:`~repro.core.graph.GraphHandle` — CSR + epoch +
delta log + per-partition mutation stamps, with all epoch bookkeeping in
``graph.py`` (machine-enforced by the `mutable-handle` repro-lint rule).
``apply_updates(inserts, deletes)`` splices an edge-update batch through
``GraphHandle.apply`` and invalidates the cache **partition-scoped**: each
cached entry records which partitions its computation touched (the
traversal's reached set, mapped to block partitions), and an update evicts
only the entries whose touched set intersects the mutated partitions.
That is sound because an edge change at (u, v) can alter a traversal's
result only if the traversal reached u's (or, symmetrically priced, v's)
partition — an entry that never touched them never saw the edge.  The
legacy ``update_graph(csr)`` whole-swap survives as a deprecated shim over
``GraphHandle.replace`` (every partition stamped, so everything evicts).

Cache keying rule: the frozen query dataclass itself — epochs no longer
live in the key because invalidation is eager: a mutation evicts exactly
the entries it could have changed, and what survives is still correct.
Sampled results are cached too (a repeated NeighborSample query returns
the *same* draw until evicted or its partition is mutated — the draw is
keyed by (seed, epoch, query), not by batch composition).
"""
from __future__ import annotations

import collections
import dataclasses
import logging
import os
import time
import warnings
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from . import engine, traffic
from .. import tune as _tune
from .dgas import block_rule
from .graph import CSR, GraphHandle, UpdateReport
from ..obs import (Histogram, Observability, decode_level_trace,
                   get_registry, span)
from .algorithms.bfs import msbfs, msbfs_distributed
from .algorithms.distgraph import shard_graph, update_shards
from .algorithms.pagerank import ppr_topk
from .algorithms.sssp import auto_delta, sssp_batched, sssp_batched_distributed

__all__ = [
    "Reachability", "Distance", "PPRTopK", "NeighborSample",
    "ServiceStats", "GraphService", "load_cost_priors",
]

_log = logging.getLogger("repro.streaming")


# trace-safe: host-side bench-doc discovery at service construction —
# repro-lint: disable=host-sync
def load_cost_priors(*, distributed: bool = False, budget: int = 32,
                     bench_dir: Optional[str] = None) -> Dict[str, float]:
    """Per-kind batch-cost priors (seconds) from the newest committed bench
    doc (``BENCH_pr<N>.json``, highest N wins, searched in ``bench_dir`` or
    the working directory).

    The deadline-slack estimate subtracts the kind's EWMA batch cost, but the
    EWMA starts empty — the first observation is the compile-inflated cold
    run, so early deadlines either fire pessimistically or (before any batch)
    not at all.  Seeding from the last bench run gives admission a
    steady-state prior from the first submit; the EWMA still converges to
    this deployment's true cost.  Returns {} when no usable doc exists (the
    pre-seed behavior), so construction never fails on a missing file.
    """
    import glob
    import json
    import re
    pat = os.path.join(bench_dir or os.getcwd(), "BENCH_pr*.json")
    best, best_n = None, -1
    for p in glob.glob(pat):
        m = re.match(r"BENCH_pr(\d+)\.json$", os.path.basename(p))
        if m and int(m.group(1)) > best_n:
            best, best_n = p, int(m.group(1))
    if best is None:
        return {}
    try:
        with open(best) as f:
            doc = json.load(f)
        section = doc["service_distributed" if distributed else "service"]
        row = section["budgets"][str(budget)]
        if distributed:
            cost = float(row["latency_p50_ms"]) / 1e3
        else:
            cost = float(budget) / float(row["qps"])
    except (KeyError, TypeError, ValueError, OSError):
        return {}
    if not (cost > 0.0 and np.isfinite(cost)):
        return {}
    # one coarse per-batch prior for every kind — the EWMA refines per kind
    return {k: cost for k in _KIND_ROTATION}


# ---------------------------------------------------------------------------
# Typed queries (frozen => hashable => cache keys)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Reachability:
    """Is `target` reachable from `source`?  Served by an MS-BFS lane."""

    source: int
    target: int


@dataclasses.dataclass(frozen=True)
class Distance:
    """Shortest weighted distance source -> target (inf if unreachable).
    Served by a batched delta-stepping lane (the graph-level `auto_delta`)."""

    source: int
    target: int


@dataclasses.dataclass(frozen=True)
class PPRTopK:
    """Top-k personalized-PageRank neighborhood of `source`.  k may vary per
    query up to the service's ``ppr_k_max``; every batch computes
    ``ppr_k_max`` candidates and slices each query's k (one compile per
    (kind, budget))."""

    source: int
    k: int = 8


@dataclasses.dataclass(frozen=True)
class NeighborSample:
    """`fanout` independent one-hop neighbor draws from `vertex` (uniform
    over out-edges; sinks return the vertex itself).  `seed` salts the draw
    so distinct queries on one vertex stay independent."""

    vertex: int
    fanout: int = 1
    seed: int = 0


_KIND = {Reachability: "reach", Distance: "dist", PPRTopK: "ppr",
         NeighborSample: "sample"}
# fixed rotation for the round-robin batch-kind selection
_KIND_ROTATION = ("reach", "dist", "ppr", "sample")


# ---------------------------------------------------------------------------
# Stats ledger
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServiceStats:
    """Counters over a service's lifetime (or since `reset_stats`).

    route_bytes is the §7/§13 *model* of what a distributed deployment moves:
    per batched push level one compacted exchange at the derived capacity
    whose items carry all B lanes (`traffic.batched_payload_bytes`) — levels
    the engine reports as capacity-overflow fallbacks are charged at the full
    partition instead — per dense level a full-partition gather of the lane
    payloads.  Under a mesh the trace comes from the *real* distributed run
    (`run_batched_distributed(return_stats=True)`), so the ledger prices what
    actually executed; n_model_shards is then the mesh size.

    Latency is recorded per query (submit -> result stored), and every query
    submitted with a deadline counts toward ``deadline_miss_rate`` — a miss
    is a result that lands after its absolute deadline.

    Two sums split a served query's time in the service, on the service's
    clock: ``batch_wait_s`` adds, per query a batch served, the time from
    its ``submit`` to the start of that batch's dispatch; ``result_hold_s``
    adds the time from the end of the batch's extraction (its answer
    stored) to the return of the ``flush`` that ran the batch — answers
    held while the flush runs its other batches.  A cache hit runs no batch
    and adds zero to both.
    """

    budget: int
    n_model_shards: int = 8
    queries: int = 0
    cache_hits: int = 0
    batches: int = 0
    lanes_used: int = 0
    busy_s: float = 0.0
    route_bytes: int = 0
    push_levels: int = 0
    pull_levels: int = 0
    deadline_queries: int = 0
    deadline_misses: int = 0
    updates: int = 0            # apply_updates batches ingested
    update_edges: int = 0       # edges changed across those batches
    cache_evicted: int = 0      # entries evicted by partition-scoped purges
    batch_wait_s: float = 0.0   # sum of submit -> batch dispatch, see above
    result_hold_s: float = 0.0  # sum of extracted -> flush return, see above
    # log-bucketed latency sketch (repro.obs): O(buckets) retention no
    # matter how many queries are served, percentiles within one bucket
    # width (12%) of exact — replaces the raw 65536-deep sample deque
    latency_hist: Histogram = dataclasses.field(
        default_factory=lambda: Histogram("service.latency_s"))

    @property
    def qps(self) -> float:
        return self.queries / self.busy_s if self.busy_s > 0 else 0.0

    @property
    def occupancy(self) -> float:
        """Mean fraction of the lane budget a batch actually fills."""
        return self.lanes_used / (self.batches * self.budget) \
            if self.batches else 0.0

    @property
    def hit_rate(self) -> float:
        return self.cache_hits / self.queries if self.queries else 0.0

    @property
    def route_bytes_per_query(self) -> float:
        return self.route_bytes / self.queries if self.queries else 0.0

    def _latency_pct(self, pct: float) -> float:
        return self.latency_hist.percentile(pct)

    @property
    def latency_p50_ms(self) -> float:
        return 1e3 * self._latency_pct(50)

    @property
    def latency_p95_ms(self) -> float:
        return 1e3 * self._latency_pct(95)

    @property
    def deadline_miss_rate(self) -> float:
        return self.deadline_misses / self.deadline_queries \
            if self.deadline_queries else 0.0

    def as_dict(self) -> Dict[str, float]:
        return {
            "queries": self.queries, "cache_hits": self.cache_hits,
            "batches": self.batches, "lanes_used": self.lanes_used,
            "busy_s": self.busy_s, "route_bytes": self.route_bytes,
            "push_levels": self.push_levels, "pull_levels": self.pull_levels,
            "qps": self.qps, "occupancy": self.occupancy,
            "hit_rate": self.hit_rate,
            "route_bytes_per_query": self.route_bytes_per_query,
            "latency_p50_ms": self.latency_p50_ms,
            "latency_p95_ms": self.latency_p95_ms,
            "deadline_queries": self.deadline_queries,
            "deadline_misses": self.deadline_misses,
            "deadline_miss_rate": self.deadline_miss_rate,
            "updates": self.updates, "update_edges": self.update_edges,
            "cache_evicted": self.cache_evicted,
            "batch_wait_s": self.batch_wait_s,
            "result_hold_s": self.result_hold_s,
        }

    def __str__(self) -> str:
        return (f"ServiceStats(queries={self.queries}, qps={self.qps:.1f}, "
                f"occupancy={self.occupancy:.2f}, "
                f"hit_rate={self.hit_rate:.2f}, "
                f"p50={self.latency_p50_ms:.1f}ms, "
                f"p95={self.latency_p95_ms:.1f}ms, "
                f"miss_rate={self.deadline_miss_rate:.3f}, "
                f"route_B/query={self.route_bytes_per_query:.0f}, "
                f"batches={self.batches})")


# ---------------------------------------------------------------------------
# The service
# ---------------------------------------------------------------------------

class GraphService:
    """Serve typed graph queries from one (mutable-by-epoch) graph.

    batch_budget: lanes per micro-batch — the B the batched engine runs at
      (None = the tuned lane budget for this backend/scale, repro.tune).
    cache_capacity: LRU entries; 0 disables caching.
    results_capacity: completed-but-unclaimed results kept for
      :meth:`result`; the oldest are dropped beyond this (a fire-and-forget
      client must not leak the service's memory).
    ppr_iters / damping / mode / ppr_k_max: engine knobs shared by every
      query (part of the compatibility rule: everything but the
      source/k/fanout is service-level, so same-kind queries always batch —
      every PPR batch computes ``ppr_k_max`` candidates and slices each
      query's k, keeping one compile per (kind, budget)).
    mesh: optional jax Mesh — serve traversal kinds from the sharded engine
      (`run_batched_distributed`); the graph is block-sharded over the mesh's
      first axis and the route-byte ledger prices the *measured* level trace.
    n_model_shards: width of the route-byte model when no mesh is given
      (with a mesh the real shard count is used).
    clock: injectable monotonic time source (seconds) — deadlines, latency
      percentiles and the EWMA batch-cost estimate all read it, so tests can
      drive admission deterministically with a fake clock.
    deadline_safety: slack margin in seconds — a deadline is considered
      "about to expire" once slack <= this margin, so a client that polls at
      least once per ``deadline_safety`` window is never served late while
      the engine is idle (the §14 property the hypothesis suite asserts).
    placement: 'sync' (default) or 'async'.  With a mesh, traversal kinds
      then run under the engine's bounded-staleness placement —
      ``sync_interval`` collective-free local micro-steps between global
      convergence checks — which returns identical results (the traversal
      programs are monotone) with ~``sync_interval``× fewer global
      reductions.  Ignored without a mesh (the local engine has no barrier
      to relax).
    sync_interval: micro-steps per global check under placement='async'
      (default 8); 1 reproduces the sync schedule exactly.
    cost_seed: optional per-kind batch-cost priors in seconds ({kind: s}),
      or 'auto' to read the newest committed bench doc
      (:func:`load_cost_priors`) — deadline admission then starts from a
      steady-state estimate instead of learning from the compile-inflated
      first batch.
    obs: optional :class:`repro.obs.Observability`.  The service's spans
      (DESIGN.md §17) — ``svc.flush``, then per batch ``svc.collect``,
      ``svc.dispatch`` (runner, pad, upload, call), ``svc.sync`` (the wait
      on the device), ``svc.extract`` (per-query answers, partitions,
      cache) and ``svc.charge`` (ledger, cost estimate, latency), each
      batch span with ``kind`` / ``lanes`` / ``queries`` / ``budget`` —
      always reach a running ``jax.profiler`` trace; an attached
      ``Observability`` also keeps them, and the traversal runners then
      compile with ``trace=True`` and each batch's decoded levels become an
      arg of its ``svc.sync`` span.  Degradation counters (push-capacity fallback,
      cache invalidations) land in ``obs.metrics`` when attached, else the
      process-wide registry.
    """

    #: EWMA weight for the per-kind batch-cost estimate the deadline slack
    #: subtracts; ~0.3 tracks warmup -> steady-state within a few batches.
    COST_EWMA_ALPHA = 0.3

    def __init__(self, csr, *, batch_budget: Optional[int] = None,
                 cache_capacity: int = 4096, results_capacity: int = 65536,
                 ppr_iters: int = 20, damping: float = 0.85,
                 mode: str = "auto", ppr_k_max: int = 64,
                 mesh=None, n_model_shards: int = 8, seed: int = 0,
                 clock=time.perf_counter, deadline_safety: float = 0.0,
                 placement: str = "sync",
                 sync_interval: Optional[int] = None,
                 cost_seed=None, obs: Optional[Observability] = None):
        # tuned-config funnel (DESIGN.md §18): explicit batch_budget wins,
        # None takes the tuned lane budget for this backend and graph scale
        _n_rows = (csr.csr if isinstance(csr, GraphHandle) else csr).n_rows
        batch_budget = int(_tune.resolve("service.batch_budget",
                                         explicit=batch_budget, n=_n_rows))
        if batch_budget < 1:
            raise ValueError("batch_budget must be >= 1")
        if placement not in ("sync", "async"):
            raise ValueError(f"placement must be 'sync' or 'async', "
                             f"got {placement!r}")
        self.budget = int(batch_budget)
        self.cache_capacity = int(cache_capacity)
        self.results_capacity = int(results_capacity)
        self.ppr_k_max = int(ppr_k_max)
        self.ppr_iters = ppr_iters
        self.damping = damping
        self.mode = mode
        self.seed = seed
        self.mesh = mesh
        self.placement = placement
        self.sync_interval = int(sync_interval) if sync_interval is not None \
            else (8 if placement == "async" else 1)
        self._clock = clock
        self.obs = obs
        self._spans = obs.spans if obs is not None else None
        self._metrics = obs.metrics if obs is not None else get_registry()
        self._trace = obs is not None
        self.deadline_safety = float(deadline_safety)
        if mesh is not None:
            n_model_shards = 1
            for a in mesh.axis_names:
                n_model_shards *= int(mesh.shape[a])
        self.stats = ServiceStats(budget=self.budget,
                                  n_model_shards=n_model_shards)
        self._cache: "collections.OrderedDict[Tuple, Any]" = \
            collections.OrderedDict()
        # (ticket, query, absolute deadline or None, submit time)
        self._queue: "collections.deque[Tuple[int, Any, Optional[float], float]]" = \
            collections.deque()
        self._results: "collections.OrderedDict[int, Any]" = \
            collections.OrderedDict()
        self._next_ticket = 0
        self._rr = 0                      # round-robin rotation cursor
        self._n_deadlines = 0             # queued entries carrying a deadline
        self._cost_ewma: Dict[str, float] = {}
        if cost_seed == "auto":
            cost_seed = load_cost_priors(distributed=mesh is not None,
                                         budget=self.budget)
        self._cost_ewma.update({k: float(v)
                                for k, v in (cost_seed or {}).items()})
        handle = csr if isinstance(csr, GraphHandle) else \
            GraphHandle.wrap(csr, n_partitions=n_model_shards)
        self._att = self._gsh = None
        self._set_graph(handle)

    # -- graph epoch (GraphHandle is the currency; see graph.py) -----------

    @property
    def epoch(self) -> int:
        """The served graph's epoch — read-only handle bookkeeping."""
        return self.handle.epoch

    @property
    def csr(self) -> CSR:
        """The served graph's CSR (the handle's current effective graph)."""
        return self.handle.csr

    # trace-safe: host-side graph installation — concrete handle/ATT
    # arithmetic before any runner is (re)compiled —
    # repro-lint: disable=host-sync
    def _set_graph(self, handle: GraphHandle,
                   report: Optional[UpdateReport] = None) -> None:
        self.handle = handle
        csr = handle.csr
        self.delta = auto_delta(csr)
        self._ppr_k = min(self.ppr_k_max, csr.n_rows)
        # the runners take the graph as an argument (a closed-over graph
        # would be baked into every program as constants, which more than
        # doubles each compiled program); the recorded max degree and
        # destination-sorted edge stream keep the engine's gather budgets
        # static and its packed pull step free of a sort under the trace.
        # Their compiled shapes are the old graph's: drop them.
        self._graph = csr.for_jit()
        self._runners: Dict[Tuple, Any] = {}
        if self.mesh is not None:
            S = self.stats.n_model_shards
            gsh = None
            if report is not None and self._gsh is not None \
                    and not report.compacted:
                # incremental reshard: only shards owning a changed SOURCE
                # row moved edges (the stacked layout is source-partitioned)
                shards = np.unique(self._att.owner(
                    np.asarray(report.changed_sources, np.int64)))
                gsh = update_shards(self._gsh, csr, self._att, shards)
                if gsh is None:
                    _log.info("epoch %d: shard padding overflow — full "
                              "reshard", handle.epoch)
            if gsh is None:           # cold start / compaction / overflow
                self._att = block_rule(csr.n_rows, S)
                gsh, _ = shard_graph(csr, S, row_att=self._att)
            # resident on the mesh: shard s's edge lists live on device s
            self._gsh = jax.device_put(gsh, NamedSharding(
                self.mesh, PartitionSpec(tuple(self.mesh.axis_names))))
            m_per = self._gsh.edges_per_shard
        else:
            self._att = self._gsh = None
            m_per = -(-csr.nnz // self.stats.n_model_shards)
        self._edge_cap = engine.frontier_edge_capacity(
            m_per, _tune.resolve("engine.switch_frac", n=csr.n_rows),
            n=csr.n_rows)
        self._m_per_shard = m_per

    # trace-safe: host-side ingest driver — the report's concrete partition
    # counts feed the ledger, nothing here is traced —
    # repro-lint: disable=host-sync
    def apply_updates(self, inserts=None, deletes=None) -> UpdateReport:
        """Ingest one edge-update batch (DESIGN.md §16).

        inserts: (rows, cols) or (rows, cols, vals); deletes: (rows, cols)
        — ``GraphHandle.apply`` semantics (deletes first, duplicate inserts
        last-wins, upserts replace weights).  Bumps the epoch, reshards only
        the touched partitions under a mesh, and invalidates the cache
        partition-scoped: entries whose recorded touched-partition set is
        disjoint from the mutation survive.  Pending queries were admitted
        against the old graph, so they flush against it first.  Returns the
        :class:`~repro.core.graph.UpdateReport` (the repair seed for
        ``algorithms.incremental``).
        """
        if self._queue:
            self.flush()
        handle, report = self.handle.apply(inserts, deletes)
        self._set_graph(handle, report=report)
        evicted = self._invalidate_partitions(report.touched_partitions)
        # route-byte model: a deployment reships the touched partitions'
        # edge lists (every partition on compaction), one contract-payload
        # item per surviving edge — the §9 contract_level pricing
        counts = handle.partition_edge_counts()
        self._charge_ingest(int(counts.sum()) if report.compacted
                            else int(counts[report.touched_partitions].sum()))
        st = self.stats
        st.updates += 1
        st.update_edges += report.n_changed
        st.cache_evicted += evicted
        return report

    def update_graph(self, csr: CSR) -> int:
        """Deprecated whole-graph swap — a thin shim over
        ``GraphHandle.replace`` (every partition is stamped, so the
        partition-scoped invalidation evicts everything).  Use
        :meth:`apply_updates` for streaming deltas.  Pending queries were
        *admitted* (and bounds-validated) against the old graph, so they are
        flushed against it first — a query never executes on a different
        graph than the one it was accepted for."""
        warnings.warn(
            "GraphService.update_graph(csr) is deprecated; use "
            "apply_updates(inserts, deletes) for streaming edge deltas, or "
            "rebuild the service from GraphHandle.replace(csr) for a "
            "whole-graph swap", DeprecationWarning, stacklevel=2)
        if self._queue:
            self.flush()
        self._set_graph(self.handle.replace(csr))
        self._invalidate_partitions(range(self.handle.n_partitions))
        self._charge_ingest(self.csr.nnz)
        return self.epoch

    def reset_stats(self) -> None:
        self.stats = ServiceStats(budget=self.budget,
                                  n_model_shards=self.stats.n_model_shards)

    # -- cache -------------------------------------------------------------
    # entries are q -> (value, touched_parts): `touched_parts` is the
    # frozenset of block partitions the computation read (None = all, the
    # conservative default), recorded so apply_updates can evict exactly the
    # entries a mutation could have changed (module docstring soundness
    # argument) instead of purging the world.

    def _cache_get(self, q) -> Tuple[bool, Any]:
        if q in self._cache:
            self._cache.move_to_end(q)
            return True, self._cache[q][0]
        return False, None

    def _cache_put(self, q, value, parts: Optional[frozenset] = None) -> None:
        if self.cache_capacity <= 0:
            return
        self._cache[q] = (value, parts)
        self._cache.move_to_end(q)
        while len(self._cache) > self.cache_capacity:
            self._cache.popitem(last=False)

    # trace-safe: host-side cache sweep over concrete partition ids —
    # repro-lint: disable=host-sync
    def _invalidate_partitions(self, parts) -> int:
        """Evict entries whose touched-partition set intersects `parts`
        (entries with no recorded set count as touching everything).
        Returns the number evicted."""
        ps = {int(p) for p in np.asarray(list(parts)).reshape(-1)}
        evict = [k for k, (_, ent) in self._cache.items()
                 if ent is None or ent & ps]
        for k in evict:
            del self._cache[k]
        if evict:
            self._metrics.counter("service.cache_invalidations").inc(
                len(evict))
        return len(evict)

    def _charge_ingest(self, n_edges: int) -> None:
        """Price a reshard of `n_edges` surviving edges in the route-byte
        ledger — contract-payload items (src, dst, weight), §9 pricing."""
        ctr = traffic.RouteByteCounter(self.stats.n_model_shards)
        ctr.contract_level(int(n_edges))
        self.stats.route_bytes += ctr.total_bytes

    # partition attribution of results: block partitions are contiguous
    # vertex ranges (GraphHandle arithmetic), so a traversal's touched set
    # is the owners of its reached vertices — computed from the result
    # arrays the executors already pulled to host.

    # trace-safe: partition attribution over result arrays the executors
    # already pulled to host — repro-lint: disable=host-sync
    def _parts_of_mask(self, reached: np.ndarray) -> frozenset:
        """Touched partitions of one lane's (n,) reached mask."""
        idx = np.nonzero(reached)[0]
        return frozenset(
            int(p) for p in np.unique(self.handle.partition_of(idx)))

    # trace-safe: same host-side attribution, per-shard variant —
    # repro-lint: disable=host-sync
    def _parts_of_shard_mask(self, shard_mask: np.ndarray) -> frozenset:
        """Touched partitions from a per-shard reached indicator (S,) —
        shards are contiguous global ranges under the block ATT, so each
        reached shard maps to the partition range covering it."""
        per = self._att.per_shard
        n = self.csr.n_rows
        parts = set()
        for s in np.nonzero(shard_mask)[0]:
            lo, hi = int(s) * per, min(n, (int(s) + 1) * per) - 1
            if hi >= lo:
                parts.update(range(int(self.handle.partition_of(lo)),
                                   int(self.handle.partition_of(hi)) + 1))
        return frozenset(parts)

    # -- admission ---------------------------------------------------------

    def submit(self, q, deadline: Optional[float] = None) -> int:
        """Enqueue a query; returns a ticket for :meth:`result`.

        deadline: optional latency SLO in seconds from now.  Deadline-aware
        admission then arms: the service flushes as soon as the oldest
        admitted deadline's slack (deadline - now - the kind's estimated
        batch cost) runs out, or a kind's pending lane demand fills the
        budget — instead of waiting for an explicit :meth:`flush`.
        """
        if type(q) not in _KIND:
            raise TypeError(f"unknown query type {type(q).__name__}")
        if deadline is not None and deadline < 0:
            raise ValueError(f"deadline must be >= 0, got {deadline}")
        if isinstance(q, NeighborSample) and not 0 < q.fanout <= self.budget:
            raise ValueError(f"fanout {q.fanout} outside [1, {self.budget}] "
                             "(one batch slot per draw)")
        n = self.csr.n_rows
        for field in ("source", "target", "vertex"):
            v = getattr(q, field, None)
            if v is not None and not 0 <= v < n:
                raise ValueError(f"{type(q).__name__}.{field}={v} outside "
                                 f"[0, {n})")
        if isinstance(q, PPRTopK) and not 0 < q.k <= self._ppr_k:
            raise ValueError(f"PPRTopK.k={q.k} outside [1, {self._ppr_k}] "
                             "(raise ppr_k_max to serve larger k)")
        t = self._next_ticket
        self._next_ticket += 1
        now = self._clock()
        self._queue.append((t, q, None if deadline is None else now + deadline,
                            now))
        if deadline is not None:
            self._n_deadlines += 1
        if self._deadline_armed() and (self._deadline_due()
                                       or self._some_kind_full()):
            self.flush()
        return t

    def poll(self) -> List[int]:
        """The client-driven admission tick: flush iff some admitted query's
        deadline slack is exhausted (a no-op otherwise).  Call between
        submissions; returns the tickets served, like :meth:`flush`."""
        if self._deadline_armed() and self._deadline_due():
            return self.flush()
        return []

    def _deadline_armed(self) -> bool:
        # O(1): deadline-free streams pay nothing for the admission checks
        # (the counter resets when flush drains the queue)
        return self._n_deadlines > 0

    def _est_cost(self, kind: str) -> float:
        """EWMA estimate of one batch execution of this kind (0 until the
        first measured batch — an unknown cost must not hold a deadline)."""
        return self._cost_ewma.get(kind, 0.0)

    def _deadline_due(self) -> bool:
        """True iff some admitted deadline is about to expire: its slack
        (deadline - now - estimated batch cost) is within the safety margin,
        so serving any later could land past the deadline."""
        now = self._clock()
        return any(dl is not None
                   and now >= dl - self._est_cost(_KIND[type(q)])
                   - self.deadline_safety
                   for _, q, dl, _ in self._queue)

    def _some_kind_full(self) -> bool:
        """True iff some kind's head batch is as packed as it can ever get,
        by replaying `_collect`'s exact accounting: cache hits occupy no
        lane, traversal sources dedupe, and the sample batch cuts at the
        first query whose fanout no longer fits (FIFO within the kind, so a
        later small query could never join that batch anyway)."""
        lanes: Dict[str, Any] = {k: set() for k in _KIND_ROTATION}
        slots = 0
        for _, q, _, _ in self._queue:
            if q in self._cache:
                continue            # will be served from cache, takes no lane
            kind = _KIND[type(q)]
            if kind == "sample":
                if slots + q.fanout > self.budget:
                    return True     # _collect would cut the batch here
                slots += q.fanout
                if slots == self.budget:
                    return True
            else:
                lanes[kind].add(q.source)
                if len(lanes[kind]) >= self.budget:
                    return True
        return False

    def result(self, ticket: int):
        if ticket not in self._results:
            if 0 <= ticket < self._next_ticket and \
                    not any(t == ticket for t, *_ in self._queue):
                raise KeyError(f"ticket {ticket} was claimed already or "
                               "evicted (results_capacity bounds unclaimed "
                               "results)")
            raise KeyError(f"ticket {ticket} has no result (flush pending "
                           "queries first)")
        return self._results.pop(ticket)

    def query(self, q, deadline: Optional[float] = None):
        """Submit + flush + return: the synchronous convenience path."""
        t = self.submit(q, deadline=deadline)
        self.flush()
        return self.result(t)

    def flush(self) -> List[int]:
        """Drain the admission queue; returns the processed tickets in
        submission order.  Each round micro-batches one kind — chosen
        round-robin over the kinds with pending queries, FIFO within the
        kind — up to the lane budget."""
        done: List[int] = []
        extracted: List[Tuple[int, float]] = []   # (queries, answers stored)
        t0 = self._clock()
        with self._span("svc.flush"):
            while self._queue:
                kind = self._next_kind()
                with self._span("svc.collect", kind=kind,
                                budget=self.budget) as args:
                    batch, lanes = self._collect(kind, done)
                    args.update(self._batch_tags(kind, batch, lanes))
                done.extend(t for t, *_ in batch)
                if batch:
                    extracted.append(
                        (len(batch), self._execute(kind, batch, lanes)))
                    self.stats.batches += 1
            self._n_deadlines = 0           # queue drained: nothing armed
            t1 = self._clock()
            self.stats.result_hold_s += sum(nq * (t1 - te)
                                            for nq, te in extracted)
            self.stats.busy_s += t1 - t0
        return sorted(done)

    def _span(self, name: str, **args):
        """The service's spans, through the one ``repro.obs`` span path."""
        return span(name, self._spans, **args)

    def _batch_tags(self, kind: str, batch, lanes: List[int]) -> Dict:
        """The args every span of one batch carries."""
        n_lanes = sum(q.fanout for _, q, *_ in batch) if kind == "sample" \
            else len(lanes)
        return {"kind": kind, "lanes": n_lanes, "queries": len(batch),
                "budget": self.budget}

    def _next_kind(self) -> str:
        """Round-robin across kinds with pending queries (the PR-5 fix for
        FIFO head-of-line blocking: a burst of one kind no longer starves
        the others — each kind gets a batch per rotation)."""
        pending = {_KIND[type(q)] for _, q, *_ in self._queue}
        K = len(_KIND_ROTATION)
        for i in range(K):
            kind = _KIND_ROTATION[(self._rr + i) % K]
            if kind in pending:
                self._rr = (_KIND_ROTATION.index(kind) + 1) % K
                return kind
        raise AssertionError("flush loop entered with an empty queue")

    def _collect(self, kind: str, done: List[int]):
        """Pull same-kind queries from the queue (submission order) until the
        lane budget fills.  Returns ([(ticket, query, deadline, t_submit)],
        ordered lane keys) — traversal queries dedupe on source, sample
        queries take fanout slots."""
        batch: List[Tuple] = []
        lanes: List[int] = []
        slots = 0
        keep: List[Tuple] = []
        while self._queue:
            entry = self._queue.popleft()
            t, q, dl, ts = entry
            if _KIND[type(q)] != kind:
                keep.append(entry)
                continue
            hit, val = self._cache_get(q)
            if hit:
                self._store_result(t, val)
                done.append(t)
                self.stats.queries += 1
                self.stats.cache_hits += 1
                self._account_latency(dl, ts)
                continue
            if kind == "sample":
                need = q.fanout
                if slots + need > self.budget and slots > 0:
                    keep.append(entry)
                    break
                slots += min(need, self.budget)
            else:
                src = q.source
                if src not in lanes:
                    if len(lanes) >= self.budget:
                        keep.append(entry)
                        break
                    lanes.append(src)
            batch.append(entry)
        self._queue.extendleft(reversed(keep))
        return batch, lanes

    # -- execution ---------------------------------------------------------

    def _pad(self, xs: List[int]) -> np.ndarray:
        out = np.zeros((self.budget,), np.int32)
        out[: len(xs)] = xs
        if xs:
            out[len(xs):] = xs[0]
        return out

    def _runner(self, key, build):
        fn = self._runners.get(key)
        if fn is None:
            fn = self._runners[key] = build()
        return fn

    def program(self, kind: str, trace: bool = False):
        """The jitted program that serves one batch of ``kind`` queries
        ('reach', 'dist', 'ppr' or 'sample'), and the shapes of its
        arguments: the served graph, then the batch's arrays of
        ``budget`` lanes.  Reach and dist run sharded under a mesh."""
        distributed = self.mesh is not None and kind in ("reach", "dist")
        graph = self._gsh if distributed else self._graph
        lanes = jax.ShapeDtypeStruct((self.budget,), jnp.int32)
        shapes = (jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), graph), lanes)
        if kind == "reach" and distributed:
            fn = lambda g, s: msbfs_distributed(
                g, self._att, s, self.mesh, max_levels=self.csr.n_rows,
                return_stats=True, placement=self.placement,
                sync_interval=self.sync_interval, trace=trace)
        elif kind == "reach":
            fn = lambda g, s: msbfs(
                g, s, mode=self.mode, return_stats=True, trace=trace)
        elif kind == "dist" and distributed:
            fn = lambda g, s: sssp_batched_distributed(
                g, self._att, s, self.mesh, delta=self.delta,
                max_iters=4 * self.csr.n_rows, return_stats=True,
                placement=self.placement, sync_interval=self.sync_interval,
                trace=trace)
        elif kind == "dist":
            fn = lambda g, s: sssp_batched(
                g, s, delta=self.delta, mode=self.mode, return_stats=True,
                trace=trace)
        elif kind == "ppr":
            # every batch computes ppr_k_max candidates and slices per
            # query: compiles stay one per (kind, budget), not per observed k
            k = self._ppr_k
            fn = lambda g, s: ppr_topk(
                g, s, k, damping=self.damping, iters=self.ppr_iters,
                return_stats=True, trace=trace)
        elif kind == "sample":
            base = jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                      self.epoch)

            def fn(g, v, s):
                keys = jax.vmap(lambda si: jax.random.fold_in(base, si))(s)
                return jax.vmap(
                    lambda kk, vv: engine.sample_neighbors(
                        g, vv[None], kk)[0])(keys, v)
            shapes += (jax.ShapeDtypeStruct((self.budget,), jnp.uint32),)
        else:
            raise ValueError(f"unknown query kind {kind!r}")
        return jax.jit(fn), shapes

    def _account_latency(self, dl: Optional[float], ts: float) -> None:
        now = self._clock()
        self.stats.latency_hist.observe(now - ts)
        if dl is not None:
            self.stats.deadline_queries += 1
            if now > dl:
                self.stats.deadline_misses += 1

    def _update_cost(self, kind: str, seconds: float) -> None:
        prev = self._cost_ewma.get(kind)
        a = self.COST_EWMA_ALPHA
        self._cost_ewma[kind] = seconds if prev is None \
            else (1 - a) * prev + a * seconds

    def _charge(self, n_lanes: int, pushes: int, pulls: int, *,
                packed: bool, fallbacks: int = 0) -> None:
        """Route-byte model of the batch (see ServiceStats).  Push levels
        move routed items (index + validity header + lanes) at the compacted
        capacity — except measured capacity-overflow fallbacks, which routed
        the full partition; dense pull levels gather the bare lane payload
        for the full edge partition — no routing header."""
        st = self.stats
        item = traffic.batched_payload_bytes(n_lanes, packed=packed)
        lane_bytes = item - (4 + 1)
        ctr = traffic.RouteByteCounter(st.n_model_shards)
        fallbacks = min(int(fallbacks), int(pushes))
        for _ in range(int(pushes) - fallbacks):
            ctr.push_level(self._edge_cap, payload_bytes=item)
        for _ in range(fallbacks):
            ctr.push_level(self._m_per_shard, payload_bytes=item)
        for _ in range(int(pulls)):
            ctr.pull_level(self._m_per_shard * lane_bytes)
        st.route_bytes += ctr.total_bytes
        st.push_levels += int(pushes)
        st.pull_levels += int(pulls)

    def _execute(self, kind: str, batch, lanes: List[int]) -> float:
        """Serve one non-empty batch; returns when its answers were stored
        (the end of ``svc.extract``), on the service clock."""
        tags = self._batch_tags(kind, batch, lanes)
        t_exec = self._clock()
        if kind == "sample":
            stats = self._execute_sample(batch, tags)
        else:
            stats = self._execute_traversal(kind, batch, lanes, tags)
        t_stored = self._clock()
        with self._span("svc.charge", **tags) as args:
            args["route_bytes"] = self._charge_batch(kind, stats)
            self.stats.lanes_used += tags["lanes"]
            self.stats.queries += len(batch)
            self._update_cost(kind, self._clock() - t_exec)
            for _, _, dl, ts in batch:
                self._account_latency(dl, ts)
        return t_stored

    def _charge_batch(self, kind: str, stats) -> int:
        """Price one batch in the route-byte ledger; returns its bytes."""
        rb0 = self.stats.route_bytes
        if kind == "sample":
            ctr = traffic.RouteByteCounter(self.stats.n_model_shards)
            ctr.push_level(self.budget,
                           payload_bytes=traffic.ROUTE_PAYLOAD_BYTES)
            self.stats.route_bytes += ctr.total_bytes
            self.stats.push_levels += 1
        else:
            self._charge_traversal(
                stats, packed=kind == "reach",
                distributed=self.mesh is not None and kind in ("reach",
                                                               "dist"))
        return self.stats.route_bytes - rb0

    def _dispatched(self, batch) -> None:
        """Count the batch's queries' wait from submit to this dispatch."""
        now = self._clock()
        self.stats.batch_wait_s += sum(now - ts for *_, ts in batch)

    # trace-safe: host executor — readbacks AFTER the jitted runner return
    # are the service's product — repro-lint: disable=host-sync
    def _execute_traversal(self, kind: str, batch, lanes: List[int], tags):
        """Dispatch, wait on and extract one traversal batch; returns the
        engine's stats for the ledger."""
        lane_of = {s: i for i, s in enumerate(lanes)}
        distributed = self.mesh is not None and kind in ("reach", "dist")
        lane_parts: Dict[int, frozenset] = {}
        trace = self._trace

        def parts_of(ln: int, reached) -> frozenset:
            # reached: (n,) lane mask locally, (S, per) stacked distributed —
            # memoised per lane (dedup'd queries share the computation)
            if ln not in lane_parts:
                lane_parts[ln] = (
                    self._parts_of_shard_mask(reached.any(axis=1))
                    if distributed else self._parts_of_mask(reached))
            return lane_parts[ln]

        graph = self._gsh if distributed else self._graph
        with self._span("svc.dispatch", **tags):
            self._dispatched(batch)
            run = self._runner((kind, self.budget, trace),
                               lambda: self.program(kind, trace)[0])
            *outs, stats = run(graph, jnp.asarray(self._pad(lanes)))
        with self._span("svc.sync", **tags) as args:
            outs = [np.asarray(o) for o in outs]
            if trace:
                args["levels"] = [lv.as_dict()
                                  for lv in decode_level_trace(stats)]
        with self._span("svc.extract", **tags):
            if kind == "ppr":
                vals, ids = outs
                for t, q, *_ in batch:
                    ln = lane_of[q.source]
                    # PPR iterates dense over the whole graph: parts=None
                    # means "touched everything", so any mutation evicts it
                    self._finish(t, q, (ids[ln, : q.k].copy(),
                                        vals[ln, : q.k].copy()))
            else:
                res, = outs
                reached = (lambda r: r >= 0) if kind == "reach" \
                    else np.isfinite
                answer = (lambda v: bool(v >= 0)) if kind == "reach" \
                    else float
                if distributed:
                    # each target's (owner, local) slot in the stacked
                    # (S, B, per) result, on the host: no batch size
                    # compiles a device op
                    tgt = np.asarray([q.target for _, q, *_ in batch],
                                     np.int64)
                    own, loc = self._att.owner(tgt), self._att.local(tgt)
                    for (t, q, *_), o, l in zip(batch, own, loc):
                        ln = lane_of[q.source]
                        self._finish(t, q, answer(res[o, ln, l]),
                                     parts=parts_of(ln, reached(
                                         res[:, ln, :])))
                else:
                    for t, q, *_ in batch:
                        ln = lane_of[q.source]
                        self._finish(t, q, answer(res[ln, q.target]),
                                     parts=parts_of(ln, reached(res[ln])))
        return stats

    # trace-safe: ledger accounting over concrete returned stats —
    # repro-lint: disable=host-sync
    def _charge_traversal(self, stats, *, packed: bool,
                          distributed: bool) -> None:
        """Feed the ledger the run's level trace — stacked (S,) and globally
        identical under the distributed placement, scalar locally.

        Async placement: the engine reports buffered flushes in 'pushes'
        (micro-steps move no network traffic), so the ledger prices each as
        one dense outbox exchange — `traffic.flush_route_bytes` at the
        resident partition width with the batch's lane payload — instead of
        compacted push levels."""
        def first(x):
            a = np.asarray(x)
            return int(a.reshape(-1)[0])
        fallbacks = first(stats["fallbacks"]) if distributed else 0
        if fallbacks:
            self._metrics.counter("service.push_capacity_fallback").inc(
                fallbacks)
        if distributed and self.placement == "async":
            st = self.stats
            flushes = first(stats["pushes"])
            ctr = traffic.RouteByteCounter(st.n_model_shards)
            for _ in range(flushes):
                ctr.flush_level(self._att.per_shard,
                                elem_bytes=4 * self.budget)
            st.route_bytes += ctr.total_bytes
            st.push_levels += flushes
            return
        self._charge(self.budget, first(stats["pushes"]),
                     first(stats["pulls"]), packed=packed,
                     fallbacks=fallbacks)

    # trace-safe: host executor, readback after the jitted sampler returns —
    # repro-lint: disable=host-sync
    def _execute_sample(self, batch, tags) -> None:
        with self._span("svc.dispatch", **tags):
            self._dispatched(batch)
            run = self._runner(("sample", self.budget),
                               lambda: self.program("sample")[0])
            verts = np.zeros((self.budget,), np.int32)
            salts = np.zeros((self.budget,), np.uint32)
            spans: List[Tuple[int, int]] = []
            pos = 0
            for t, q, *_ in batch:
                take = q.fanout
                # _collect's slot accounting and submit's fanout bound
                # guarantee the batch fits; fail loudly (not by
                # truncating-and-caching a wrong-shaped result) if that
                # invariant ever regresses
                assert pos + take <= self.budget, (pos, take, self.budget)
                verts[pos: pos + take] = q.vertex
                # the draw is keyed by (epoch, query, slot) — batch-
                # composition independent, so cached and recomputed
                # answers agree
                qh = np.uint32(hash((q.vertex, q.fanout, q.seed)) & 0x7FFFFFFF)
                salts[pos: pos + take] = qh + np.arange(take, dtype=np.uint32)
                spans.append((pos, take))
                pos += take
            out = run(self._graph, jnp.asarray(verts), jnp.asarray(salts))
        with self._span("svc.sync", **tags):
            nbrs = np.asarray(out)
        with self._span("svc.extract", **tags):
            for (t, q, *_), (s, take) in zip(batch, spans):
                # a one-hop draw reads only the vertex's own out-edge list,
                # which lives in its source partition
                self._finish(t, q, nbrs[s: s + take].copy(),
                             parts=frozenset(
                                 {int(self.handle.partition_of(q.vertex))}))

    def _store_result(self, ticket: int, value) -> None:
        self._results[ticket] = value
        while len(self._results) > self.results_capacity:
            self._results.popitem(last=False)  # oldest unclaimed ticket

    def _finish(self, ticket: int, q, value,
                parts: Optional[frozenset] = None) -> None:
        self._store_result(ticket, value)
        self._cache_put(q, value, parts)
