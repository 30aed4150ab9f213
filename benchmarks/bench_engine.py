"""Direction-optimizing engine sweep on RMAT graphs.

Times level-synchronous BFS in three engine modes on the same graph:

* ``push`` — every level expands the frontier sparsely (nonzero-compaction +
  the active rows' out-edges laid flat into an edge budget, nnz slots);
* ``pull`` — every level is one dense edge-parallel pass (work ∝ |E|);
* ``auto`` — the engine's switch: push while the frontier population count is
  under n/32 and its out-edges fit a budget of about nnz/14 slots, pull
  otherwise (Beamer's heuristic).

On RMAT the frontier explodes after 2-3 hops, so always-push pays the
nnz-slot budget on every level and always-pull pays |E| work on the tiny
first/last levels; the switch takes the cheaper side of each.  SSSP
(delta-stepping buckets), connected components (min-label propagation) and
multi-level Louvain (gain-gated sweeps + contraction, DESIGN.md §11) run on
the same engine to show the abstraction generalizes — one machinery, five
workloads, and Louvain is the first with a *quality* metric (modularity)
rather than output equivalence.

Also reported:

* the distributed push *byte model* (`core/traffic.py`): routed bytes per
  sparse level under full-capacity routing vs the engine's compacted
  frontier-proportional capacity (`engine.frontier_edge_capacity`);
* the compacted-push **overflow fallback rate** on a skewed RMAT graph
  (DESIGN.md §7): per BFS level, would any shard's active-edge count
  overflow the derived capacity;
* with >= 8 devices (the CI bench lane exports
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``): distributed
  multi-level Louvain — partition equivalence vs single-device, contraction
  route bytes, and the *measured* fallback count from
  `engine.run_distributed(return_stats=True)`;
* the **streaming** section (PR 8, fixed RMAT-12, DESIGN.md §16):
  incremental repair (``bfs_repair``/``sssp_repair`` warm-started from the
  previous fixpoint) vs from-scratch per ≤1%-of-edges insert epoch — gated
  ≥ 3x for SSSP, never-slower for BFS; update-ingest throughput through
  ``GraphService.apply_updates``; and the partition-scoped cache survival
  fraction across a one-partition update — gated ≥ 0.5;
* the **obs** section (PR 9, fixed RMAT-12, DESIGN.md §17): one B=32 batch
  served through a span/trace/metrics-instrumented service — gated on ≥ 90%
  of the batch wall clock attributed to named spans and on the exported
  Chrome trace validating; with ``--json`` the trace itself is written next
  to the bench document as ``TRACE_*.json``;
* ``--sweep-delta`` — delta-stepping bucket-width sweep on RMAT and
  uniform-weight graphs against the histogram auto-tune (DESIGN.md §8);
* the **graph query service** section (always at RMAT-12, whatever
  ``--scale``): the MS-BFS amortization ratio (per-query time at B=256 vs a
  sequential bfs — the PR-4 acceptance bar is < 0.15) and, per batch budget
  B ∈ {1, 32, 256}, serving queries/sec, batch occupancy, modeled route
  bytes per query, and the cache hit rate on a resubmitted stream
  (DESIGN.md §13);
* the **distributed service** section (PR 5, also fixed RMAT-12, needs >= 8
  devices): the same budgets served through `run_batched_distributed`
  behind the facade, with latency p50/p95 and the deadline-miss rate under
  a 60 s SLO — gated = 0 at B=32 (DESIGN.md §14); since PR 7 the B=1 lane
  serves under ``placement='async'`` (larger budgets stay sync — the dense
  micro-step work dominates there) with the cost EWMA seeded from the last
  bench doc;
* the **async placement** section (PR 7, fixed RMAT-12, needs >= 8
  devices): MS-BFS and batched delta-stepping at B ∈ {1, 32} under the
  level-synchronous vs the bounded-staleness placement (sync_interval=8) —
  latency p50/p95 and the measured global-reduction counts, gated on
  bit-identical results and a >= 4x (sssp) / >= 2x (bfs) reduction ratio.

Run:  PYTHONPATH=src python benchmarks/bench_engine.py [--scale 12]
      PYTHONPATH=src python benchmarks/bench_engine.py --scale 7 --smoke \
          --json BENCH_pr4.json --baseline auto
      PYTHONPATH=src python benchmarks/bench_engine.py --sweep-delta

``--smoke`` (the `scripts/ci.sh bench` lane) checks the outputs for NaN and
for regression markers (modes disagreeing, byte model not shrinking,
modularity not beating a single LPA sweep) and exits nonzero on failure.
``--json`` writes the machine-readable result document (the repo's persisted
``BENCH_*.json`` trajectory); ``--baseline auto`` compares against the
newest committed ``BENCH_*.json`` and fails on NaN or a >25% regression.
"""
import argparse
import glob
import json
import math
import os
import platform
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dgas, engine, rmat, uniform_random_graph, traffic
from repro.core.algorithms import (auto_delta, bfs, bfs_program,
                                   connected_components, label_propagation,
                                   modularity, multilevel, pagerank, sssp)


def _t(fn, reps=3):
    jax.block_until_ready(fn())  # compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e3  # ms


def routed_bytes_report(n, m, pushes, n_shards=8, switch_frac=1 / 32):
    """Byte model for the distributed push levels of this run: full-capacity
    routing vs the engine's compacted frontier-proportional capacity."""
    m_per_shard = -(-m // n_shards)
    edge_cap = engine.frontier_edge_capacity(m_per_shard, switch_frac)
    full = traffic.RouteByteCounter(n_shards)
    compact = traffic.RouteByteCounter(n_shards)
    for _ in range(max(pushes, 1)):
        full.push_level(m_per_shard)
        compact.push_level(edge_cap)
    reduction = full.total_bytes / max(1, compact.total_bytes)
    print(f"\nrouted bytes / sparse level (model, S={n_shards}): "
          f"full={traffic.push_level_route_bytes(n_shards, m_per_shard):,} B  "
          f"compact={traffic.push_level_route_bytes(n_shards, edge_cap):,} B  "
          f"(capacity {m_per_shard} -> {edge_cap})")
    print(f"sparse-phase total over {max(pushes, 1)} push levels: "
          f"{full.total_bytes:,} B -> {compact.total_bytes:,} B "
          f"({reduction:.1f}x less)")
    return {"full_bytes": full.total_bytes, "compact_bytes": compact.total_bytes,
            "reduction": reduction}


def fallback_report(scale, edge_factor=8, n_shards=8, switch_frac=1 / 32):
    """Compacted-push overflow fallback rate on a *skewed* RMAT graph.

    Replays BFS levels under the distributed engine's capacity rule (block
    vertex rule, ``frontier_edge_capacity`` per-peer budget): a push level
    falls back to full-capacity routing when any shard's active-edge count
    overflows.  This is the analytical counterpart of the runtime counter in
    ``run_distributed(return_stats=True)`` — same decision rule, no mesh
    needed — measured on a=0.7 RMAT where degree skew concentrates active
    edges on few shards (DESIGN.md §7 records the number).
    """
    g = rmat(scale, edge_factor, a=0.7, b=0.12, c=0.12, seed=1)
    n, m = g.n_rows, g.nnz
    lv = np.asarray(bfs(g, 0))
    att = dgas.block_rule(n, n_shards)
    rows = np.asarray(g.row_ids())
    owner = np.asarray(att.owner(jnp.asarray(rows)))
    m_per_shard = int(np.bincount(owner, minlength=n_shards).max())
    edge_cap = engine.frontier_edge_capacity(m_per_shard, switch_frac)
    switch_count = max(1, int(n * switch_frac))
    push_levels = fallbacks = 0
    for d in range(int(lv.max()) + 1 if lv.max() >= 0 else 0):
        frontier = lv == d
        if not frontier.any() or int(frontier.sum()) > switch_count:
            continue  # dense regime: the engine pulls, no routing capacity
        push_levels += 1
        active_per_shard = np.bincount(owner[frontier[rows]],
                                       minlength=n_shards)
        if active_per_shard.max() > edge_cap:
            fallbacks += 1
    rate = fallbacks / push_levels if push_levels else 0.0
    print(f"\ncompacted-push fallback on skewed RMAT-{scale} (a=0.7, S={n_shards}): "
          f"{fallbacks}/{push_levels} push levels overflow cap {edge_cap} "
          f"(rate {rate:.2f})")
    return {"scale": scale, "push_levels": push_levels, "fallbacks": fallbacks,
            "rate": rate, "edge_cap": edge_cap}


def louvain_report(g, smoke_failures):
    """Multi-level Louvain quality + wall time (the repo's first quality
    metric: modularity, not output equivalence)."""
    q_single = float(modularity(g, label_propagation(g, iters=1)))
    labels, scores = multilevel(g)  # cold run: correctness + jit warmup
    ms = float("inf")
    # best-of-8, not the usual best-of-3: this section is hundreds of small
    # dispatches, so its min needs more samples to converge under host load
    # (measured: best-of-3 straddles the 25% baseline gate, best-of-8 is
    # stable to a few percent)
    for _ in range(8):
        t0 = time.perf_counter()
        multilevel(g)  # warm: level shapes repeat, so compiles are cached
        ms = min(ms, (time.perf_counter() - t0) * 1e3)
    q_multi = scores[-1] if scores else float(modularity(g, labels))
    n_comm = int(np.unique(np.asarray(labels)).size)
    print(f"\nlouvain: single LPA sweep Q={q_single:.5f}  multilevel "
          f"Q={q_multi:.5f} over {len(scores)} levels ({n_comm} communities, "
          f"{ms:.0f} ms)")
    if not scores:
        smoke_failures.append("REGRESSION: multilevel accepted no level")
    elif not all(b > a for a, b in zip(scores, scores[1:])):
        smoke_failures.append("REGRESSION: multilevel scores not increasing")
    if not np.isfinite(q_multi) or q_multi <= q_single:
        smoke_failures.append(
            "REGRESSION: multilevel Q does not beat a single LPA sweep")
    return {"single_sweep": q_single, "multilevel": q_multi,
            "levels": len(scores), "n_communities": n_comm, "ms": ms}


def distributed_report(scale, smoke_failures, n_shards=8):
    """Distributed lane (runs when the host exposes >= n_shards devices, as
    the CI bench lane does via XLA_FLAGS): distributed multi-level Louvain
    equivalence + contraction route bytes, and the measured compacted-push
    fallback counter from the engine's runtime stats."""
    if len(jax.devices()) < n_shards:
        print(f"\ndistributed lane skipped ({len(jax.devices())} devices < "
              f"{n_shards}; CI sets XLA_FLAGS=--xla_force_host_platform_"
              f"device_count={n_shards})")
        return None
    from repro.core.algorithms import multilevel_distributed
    from repro.core.algorithms.louvain import partition_equal
    from repro.core.algorithms.distgraph import shard_graph, unshard_vertex_array
    from repro.launch.mesh import make_cores_mesh

    mesh = make_cores_mesh(n_shards)
    g = rmat(scale, 8, seed=1)
    lab_l, scores_l = multilevel(g)
    ctr = traffic.RouteByteCounter(n_shards,
                                   payload_bytes=traffic.CONTRACT_PAYLOAD_BYTES)
    # cold run: correctness + route-byte counter + jit warmup; the reported
    # time is best-of-3 warm (louvain_report's idiom) — the cold wall clock
    # is compile-dominated (~20 s at smoke scale) and gated it measured the
    # XLA frontend, not the engine
    lab_d, scores_d = multilevel_distributed(g, mesh, counter=ctr)
    ms = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        multilevel_distributed(g, mesh)
        ms = min(ms, (time.perf_counter() - t0) * 1e3)
    match = partition_equal(lab_l, lab_d)
    # measured fallback counter on a skewed graph (engine runtime stats);
    # mode='auto' so only genuine push-regime levels count, matching
    # fallback_report's analytical replay of the same decision rule
    gs = rmat(scale, 8, a=0.7, b=0.12, c=0.12, seed=1)
    att = dgas.block_rule(gs.n_rows, n_shards)
    gsh, _ = shard_graph(gs, n_shards, row_att=att)
    g_rev = engine.reverse_graph(gs, att)
    o0, l0 = int(att.owner(jnp.asarray(0))), int(att.local(jnp.asarray(0)))
    st0 = {"level": jnp.full((n_shards, att.per_shard), -1,
                             jnp.int32).at[o0, l0].set(0)}
    f0 = jnp.zeros((n_shards, att.per_shard), jnp.int32).at[o0, l0].set(1)
    _, stats = engine.run_distributed(gsh, att, mesh, bfs_program(), st0, f0,
                                      axis="cores", max_iters=gs.n_rows,
                                      mode="auto", g_rev=g_rev,
                                      return_stats=True)
    stats = {k: int(np.asarray(v)[0]) for k, v in stats.items()}
    print(f"\ndistributed louvain (S={n_shards}): Q levels "
          f"{[round(s, 5) for s in scores_d]} ({ms:.0f} ms), partition match "
          f"with single-device: {match}")
    print(f"contraction routing: {ctr.total_bytes:,} B over {ctr.levels} "
          f"levels; measured push fallbacks on skewed RMAT-{scale}: "
          f"{stats['fallbacks']}/{stats['pushes']}")
    if not match:
        smoke_failures.append(
            "REGRESSION: distributed multilevel diverges from single-device")
    if scores_d and scores_l and abs(scores_d[-1] - scores_l[-1]) > 1e-3:
        smoke_failures.append("REGRESSION: distributed multilevel Q diverges")
    return {"q_levels": scores_d, "partition_match": bool(match),
            "contract_bytes": ctr.total_bytes, "contract_levels": ctr.levels,
            "ms": ms, "measured_fallbacks": stats["fallbacks"],
            "measured_pushes": stats["pushes"]}


def service_report(smoke_failures, budgets=(1, 32, 256), scale=12,
                   edge_factor=8):
    """Graph query service throughput + the MS-BFS amortization ratio.

    Runs at a *fixed* RMAT-12 regardless of ``--scale`` so the trajectory
    point (and the PR-4 acceptance ratio: batched per-query time at B=256
    must be < 0.15x one sequential bfs) stays comparable across lanes.
    Per budget B: queries/sec over a fresh random reachability stream
    (compiled runner pre-warmed — qps measures serving, not compilation),
    batch occupancy, route bytes per query (batched §13 byte model), and the
    cache hit rate when the same stream is resubmitted.
    """
    from repro.core import GraphService, Reachability
    from repro.core.algorithms import msbfs

    g = rmat(scale, edge_factor, seed=0)
    n = g.n_rows
    B = 256
    srcs = np.arange(B, dtype=np.int32) % n
    # sequential per-query cost = mean over a sample of the *actual* batch
    # sources (source 0 alone is the densest hub on RMAT — using only it
    # would flatter the ratio); one compile, source as a traced argument
    bfs_one = jax.jit(lambda s: bfs(g, s))
    sample = srcs[:: max(1, B // 16)]
    jax.block_until_ready(bfs_one(int(sample[0])))  # compile
    t0 = time.perf_counter()
    for s in sample:
        jax.block_until_ready(bfs_one(int(s)))
    t1 = (time.perf_counter() - t0) * 1e3 / len(sample)
    tB = _t(jax.jit(lambda: msbfs(g, srcs)))
    ratio = (tB / B) / t1
    print(f"\nservice (RMAT-{scale}): bfs {t1:.2f} ms/query sequential "
          f"(mean of {len(sample)} sources), msbfs B={B} {tB:.2f} ms total "
          f"= {tB / B:.4f} ms/query (amortization ratio {ratio:.4f}, "
          f"target < 0.15)")
    if not np.isfinite(ratio) or ratio >= 0.15:
        smoke_failures.append(
            f"REGRESSION: msbfs amortization ratio {ratio:.3f} >= 0.15")
    doc = {"scale": scale, "bfs_ms_per_query": t1, "msbfs_b256_ms": tB,
           "amortization_ratio": ratio, "budgets": {}}
    rng = np.random.default_rng(0)
    for budget in budgets:
        n_q = min(512, max(64, 2 * budget))
        svc = GraphService(g, batch_budget=budget, cache_capacity=4 * n_q)
        svc.query(Reachability(0, 1))   # compile the (kind, budget) runner
        svc.reset_stats()
        stream = [Reachability(int(s), int(t))
                  for s, t in zip(rng.integers(0, n, n_q),
                                  rng.integers(0, n, n_q))]
        for q in stream:
            svc.submit(q)
        svc.flush()
        cold = svc.stats.as_dict()
        svc.reset_stats()               # isolate the resubmission pass
        for q in stream:                # resubmission: pure cache hits
            svc.submit(q)
        svc.flush()
        warm = svc.stats.as_dict()
        row = {"n_queries": n_q, "qps": cold["qps"],
               "occupancy": cold["occupancy"],
               "route_bytes_per_query": cold["route_bytes_per_query"],
               "hit_rate_resubmit": warm["hit_rate"]}
        doc["budgets"][str(budget)] = row
        print(f"  B={budget:<4d} {cold['qps']:>9.1f} q/s  occupancy "
              f"{cold['occupancy']:.2f}  {cold['route_bytes_per_query']:>9.0f}"
              f" route B/q  resubmit hit rate {warm['hit_rate']:.2f}")
        if not (np.isfinite(cold["qps"]) and cold["qps"] > 0):
            smoke_failures.append(f"REGRESSION: service qps at B={budget} "
                                  "not positive")
        if not 0 < cold["occupancy"] <= 1:
            smoke_failures.append(f"REGRESSION: service occupancy at "
                                  f"B={budget} out of range")
        # second pass re-submits the identical stream: every query must hit
        # (capacity 4 * n_q rules out evictions)
        if warm["hit_rate"] < 0.999:
            smoke_failures.append(f"REGRESSION: resubmitted stream hit rate "
                                  f"{warm['hit_rate']:.2f} < 1.0 at B={budget}")
    return doc


def service_distributed_report(smoke_failures, budgets=(1, 32, 256), scale=12,
                               edge_factor=8, n_shards=8):
    """The query service on the *sharded* engine (PR 5, DESIGN §14): with a
    mesh the service serves reach/dist through `run_batched_distributed`, so
    this section measures end-to-end distributed serving — qps, occupancy,
    route bytes/query now priced from the *measured* level trace (incl.
    capacity-overflow fallbacks), and the deadline SLO accounting.  Runs when
    the host exposes >= n_shards devices (the CI bench lane forces 8); fixed
    RMAT-12 like `service_report` so the trajectory stays comparable.

    Gates: qps positive at every budget, and the PR-5 acceptance bar —
    **deadline-miss rate = 0 at B=32** under a generous (60 s) SLO on the
    pre-warmed runners.

    Since PR 7 the **B=1 lane serves under ``placement='async'``**
    (sync_interval=8 — identical results, one buffered flush + one
    termination psum per global check instead of five collectives per
    level), with the deadline cost EWMA seeded from the last committed
    bench doc (``cost_seed='auto'``); the baseline gate compares p50
    same-host.  Larger budgets stay level-synchronous: at B>=32 the dense
    per-lane micro-step work dominates the saved barriers on the forced
    host mesh (async p50 measured ~1.4-2x sync there — see the `async`
    section), so async is the small-batch latency lever, not a throughput
    one.  Each budget row records its placement.
    """
    if len(jax.devices()) < n_shards:
        print(f"\ndistributed service lane skipped ({len(jax.devices())} "
              f"devices < {n_shards})")
        return None
    from repro.core import GraphService, Reachability
    from repro.launch.mesh import make_cores_mesh

    mesh = make_cores_mesh(n_shards)
    g = rmat(scale, edge_factor, seed=0)
    n = g.n_rows
    rng = np.random.default_rng(1)
    doc = {"scale": scale, "n_shards": n_shards,
           "placement": "async@B=1, sync@B>=32", "sync_interval": 8,
           "budgets": {}}
    print(f"\ndistributed service (RMAT-{scale}, S={n_shards}, "
          f"run_batched_distributed behind the facade, async at B=1):")
    for budget in budgets:
        n_q = min(512, max(32, 2 * budget))
        placement = "async" if budget == 1 else "sync"
        svc = GraphService(g, batch_budget=budget, mesh=mesh,
                           cache_capacity=4 * n_q, placement=placement,
                           sync_interval=8, cost_seed="auto")
        svc.query(Reachability(0, 1))   # compile the (kind, budget) runner
        svc.reset_stats()
        stream = [Reachability(int(s), int(t))
                  for s, t in zip(rng.integers(0, n, n_q),
                                  rng.integers(0, n, n_q))]
        for q in stream:                # 60 s SLO: misses mean a real stall
            svc.submit(q, deadline=60.0)
        svc.flush()
        st = svc.stats.as_dict()
        row = {"n_queries": n_q, "placement": placement, "qps": st["qps"],
               "occupancy": st["occupancy"],
               "route_bytes_per_query": st["route_bytes_per_query"],
               "latency_p50_ms": st["latency_p50_ms"],
               "latency_p95_ms": st["latency_p95_ms"],
               "deadline_miss_rate": st["deadline_miss_rate"]}
        doc["budgets"][str(budget)] = row
        print(f"  B={budget:<4d} [{placement:>5s}] {st['qps']:>9.1f} q/s  occupancy "
              f"{st['occupancy']:.2f}  {st['route_bytes_per_query']:>11.0f}"
              f" route B/q  p50/p95 {st['latency_p50_ms']:.0f}/"
              f"{st['latency_p95_ms']:.0f} ms  miss rate "
              f"{st['deadline_miss_rate']:.3f}")
        if not (np.isfinite(st["qps"]) and st["qps"] > 0):
            smoke_failures.append(f"REGRESSION: distributed service qps at "
                                  f"B={budget} not positive")
        if budget == 32 and st["deadline_miss_rate"] != 0.0:
            smoke_failures.append(
                f"REGRESSION: deadline-miss rate "
                f"{st['deadline_miss_rate']:.3f} != 0 at B=32 (acceptance "
                "bar: the idle sharded engine must meet a 60 s SLO)")
    return doc


def async_report(smoke_failures, scale=12, edge_factor=8, n_shards=8,
                 budgets=(1, 32), sync_interval=8, reps=5):
    """Bounded-staleness placement vs the level-synchronous baseline (PR 7).

    Fixed RMAT-12 (like the service sections) on the >= 8-device lane: for
    B ∈ ``budgets`` lanes, runs multi-source BFS and batched delta-stepping
    under placement='sync' and placement='async' (``sync_interval`` local
    micro-steps per global check), reporting per-run latency p50/p95 over
    ``reps`` warm repetitions and the **global-reduction count** — the
    engine's measured level/flush trace priced by
    `traffic.level_collectives` (sync: overflow psum + 3 routing exchanges +
    termination psum per compacted push level, + 2 bucket pmins for sssp;
    async: one buffered flush + one termination psum per global check).

    Gates: async must return bit-identical results to sync (the programs are
    monotone — staleness cannot change the fixpoint), and at
    ``sync_interval=8`` the sssp reduction ratio must stay >= 4x (the PR-7
    acceptance bar: 7 collectives per delta-stepping level vs 2 per check,
    with local bucket-bound advances absorbing expansions between flushes)
    while bfs must stay >= 2x (a frontier hop crosses shards only at a
    flush, so its ratio comes from the per-check collective count, 5 -> 2;
    measured ~3x on RMAT).
    """
    if len(jax.devices()) < n_shards:
        print(f"\nasync placement lane skipped ({len(jax.devices())} "
              f"devices < {n_shards})")
        return None
    from repro.core.algorithms import msbfs_distributed, sssp_batched_distributed
    from repro.core.algorithms.distgraph import shard_graph
    from repro.launch.mesh import make_cores_mesh

    mesh = make_cores_mesh(n_shards)
    g = rmat(scale, edge_factor, seed=0)
    n = g.n_rows
    att = dgas.block_rule(n, n_shards)
    gsh, _ = shard_graph(g, n_shards, row_att=att)
    delta = auto_delta(g)
    doc = {"scale": scale, "n_shards": n_shards,
           "sync_interval": sync_interval, "budgets": {}}
    print(f"\nasync placement (RMAT-{scale}, S={n_shards}, "
          f"sync_interval={sync_interval}; reductions = measured trace x "
          f"traffic.level_collectives):")
    for budget in budgets:
        srcs = np.arange(budget, dtype=np.int32) % n
        row = {}
        results = {}
        for name, coll_sync, make in (
            ("bfs", traffic.level_collectives(placement="sync"),
             lambda p: jax.jit(lambda s: msbfs_distributed(
                 gsh, att, s, mesh, max_levels=n, return_stats=True,
                 placement=p, sync_interval=sync_interval))),
            ("sssp", traffic.level_collectives(placement="sync",
                                               program_collectives=2),
             lambda p: jax.jit(lambda s: sssp_batched_distributed(
                 gsh, att, s, mesh, delta=delta, max_iters=4 * n,
                 return_stats=True, placement=p,
                 sync_interval=sync_interval))),
        ):
            for placement in ("sync", "async"):
                fn = make(placement)
                out, stats = jax.block_until_ready(fn(srcs))  # compile
                results[(name, placement)] = np.asarray(out)
                lats = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(srcs))
                    lats.append((time.perf_counter() - t0) * 1e3)
                first = lambda x: int(np.asarray(x).reshape(-1)[0])
                if placement == "async":
                    checks = first(stats["pushes"])  # flushes
                    reductions = checks * traffic.level_collectives(
                        placement="async")
                else:
                    checks = first(stats["iters"])   # levels
                    reductions = checks * coll_sync
                row[f"{name}_{placement}"] = {
                    "p50_ms": float(np.percentile(lats, 50)),
                    "p95_ms": float(np.percentile(lats, 95)),
                    "global_checks": checks,
                    "global_reductions": reductions,
                }
            sy, an = row[f"{name}_sync"], row[f"{name}_async"]
            ratio = sy["global_reductions"] / max(1, an["global_reductions"])
            row[f"{name}_reduction_ratio"] = ratio
            match = np.array_equal(results[(name, "sync")],
                                   results[(name, "async")])
            print(f"  B={budget:<3d} {name:<5} sync  p50 {sy['p50_ms']:8.1f} "
                  f"ms  {sy['global_reductions']:4d} reductions "
                  f"({sy['global_checks']} levels)")
            print(f"  B={budget:<3d} {name:<5} async p50 {an['p50_ms']:8.1f} "
                  f"ms  {an['global_reductions']:4d} reductions "
                  f"({an['global_checks']} flushes)  {ratio:.1f}x fewer, "
                  f"identical: {match}")
            if not match:
                smoke_failures.append(
                    f"REGRESSION: async {name} diverges from sync at "
                    f"B={budget}")
            bar = 4.0 if name == "sssp" else 2.0
            if ratio < bar:
                smoke_failures.append(
                    f"REGRESSION: async {name} reduction ratio {ratio:.1f}x "
                    f"< {bar:.0f}x at B={budget}, "
                    f"sync_interval={sync_interval}")
        doc["budgets"][str(budget)] = row
    return doc


def streaming_report(smoke_failures, scale=12, edge_factor=8, n_epochs=5):
    """Streaming-graph section (PR 8, DESIGN.md §16), fixed RMAT-12 like the
    service sections so the trajectory point stays comparable:

    * **repair vs scratch**: per epoch of a ≤1%-of-edges insert batch, warm
      best-of-3 time of incremental ``bfs_repair`` / ``sssp_repair`` (old
      fixpoint + changed-endpoint frontier) against the from-scratch run on
      the updated graph — gated ≥ 3x for SSSP (the acceptance bar: scratch
      delta-stepping pays ~15 bucket expansions, the repair wave converges
      in a couple; results are bit-identical, pinned by
      tests/test_streaming.py).  BFS is reported but gated only at
      "never slower than scratch": at RMAT-12 its wall clock is
      dispatch-floor-bound (~4 ms for even a one-level run vs ~12 ms for
      the full six), so the iteration ratio caps it near 2x no matter how
      small the repair cone is;
    * **ingest throughput**: edges/s through ``GraphService.apply_updates``
      (splice + runner reset + partition-scoped invalidation + ledger);
    * **cache survival**: fraction of cached entries still live after an
      update touching ONE partition — gated ≥ 0.5 (partition-scoped
      invalidation; an epoch-keyed cache would score 0 here).

    The update stream is pure edge growth: endpoint pairs are rejection-
    sampled against the current edge set so every insert is a genuinely new
    edge (always monotone-safe), with weights from the generator's own
    U[0,1) — near-zero weights would make every insert a global shortcut
    and turn "repair" into a worst-case full rewrite.
    """
    from repro.core import (GraphHandle, GraphService, NeighborSample,
                            Reachability)
    from repro.core.algorithms import bfs_repair, sssp_repair

    g = rmat(scale, edge_factor, seed=0)
    n, m = g.n_rows, g.nnz
    rng = np.random.default_rng(2)
    batch = max(1, min(m // 100, 256))          # <= 1% of edges per epoch

    def make_batch(cur):
        # new-only endpoints: reject pairs already present in `cur` (and
        # in-batch duplicates) so the batch is inserts, never upserts
        have = np.repeat(np.arange(n, dtype=np.int64),
                         np.diff(np.asarray(cur.indptr))) * n \
            + np.asarray(cur.indices, np.int64)
        keys = np.empty(0, np.int64)
        while keys.size < batch:
            cand = rng.integers(0, n, 2 * batch) * n + rng.integers(0, n, 2 * batch)
            cand = cand[~np.isin(cand, have)]
            keys = np.unique(np.concatenate([keys, cand]))
        keys = rng.permutation(keys)[:batch]
        return (keys // n, keys % n, rng.random(batch).astype(np.float32))

    # --- ingest throughput through the service -----------------------------
    svc = GraphService(g, batch_budget=8)
    ingest_s = 0.0                      # batch generation stays off the clock
    for _ in range(n_epochs):
        ins = make_batch(svc.csr)
        t0 = time.perf_counter()
        svc.apply_updates(inserts=ins)
        ingest_s += time.perf_counter() - t0
    ingest_eps = n_epochs * batch / ingest_s

    # --- repair vs scratch, warm best-of-3 per epoch -----------------------
    handle = GraphHandle.wrap(g, n_partitions=8)
    prev_bfs = bfs(handle.csr, 0)
    # scratch runs pin the UNSCALED histogram delta: this section gates the
    # repair machinery's speedup, and the 3x bar was calibrated against
    # delta_scale=1 scratch — letting the tuned multiplier (DESIGN.md §18)
    # speed up the denominator would flap the gate without any repair change
    # (sssp_repair itself is delta-free: bound = inf)
    prev_sssp = sssp(handle.csr, 0,
                     delta=auto_delta(handle.csr, scaled=False))
    speedups = {"bfs": [], "sssp": []}
    print(f"\nstreaming (RMAT-{scale}, batch={batch} edges "
          f"= {100 * batch / m:.2f}% of m):")
    for e in range(n_epochs):
        handle, rep = handle.apply(make_batch(handle.csr))
        if not rep.monotone_safe:
            smoke_failures.append(
                "REGRESSION: new-edge insert batch classified unsafe")
        csr, ch = handle.csr, rep.changed_sources
        ms = {}
        for name, scratch_fn, repair_fn, prev in (
            ("bfs", lambda: bfs(csr, 0),
             lambda: bfs_repair(csr, prev_bfs, ch), prev_bfs),
            ("sssp", lambda: sssp(csr, 0,
                                  delta=auto_delta(csr, scaled=False)),
             lambda: sssp_repair(csr, prev_sssp, ch), prev_sssp),
        ):
            s_ms = _t(jax.jit(scratch_fn))
            r_ms = _t(jax.jit(repair_fn))
            speedups[name].append(s_ms / r_ms)
            ms[name] = (s_ms, r_ms)
        prev_bfs = bfs_repair(csr, prev_bfs, ch)
        prev_sssp = sssp_repair(csr, prev_sssp, ch)
        print(f"  epoch {e + 1}: bfs scratch {ms['bfs'][0]:7.2f} ms  repair "
              f"{ms['bfs'][1]:7.2f} ms ({speedups['bfs'][-1]:5.1f}x)   sssp "
              f"scratch {ms['sssp'][0]:7.2f} ms  repair {ms['sssp'][1]:7.2f} "
              f"ms ({speedups['sssp'][-1]:5.1f}x)")
    med = {k: float(np.median(v)) for k, v in speedups.items()}
    if med["sssp"] < 3.0:
        smoke_failures.append(
            f"REGRESSION: sssp repair speedup {med['sssp']:.1f}x < 3x for "
            f"{100 * batch / m:.2f}%-of-edges batches")
    if med["bfs"] < 1.0:
        smoke_failures.append(
            f"REGRESSION: bfs repair {med['bfs']:.1f}x — slower than scratch")

    # --- partition-scoped cache survival -----------------------------------
    svc2 = GraphService(g, batch_budget=8, cache_capacity=256)
    per = svc2.handle.per_partition
    for p in range(8):                  # 4 sample + 1 reach query / partition
        for off in (0, 7, 19, 31):
            svc2.query(NeighborSample((p * per + off) % n, fanout=2))
        svc2.query(Reachability((p * per + 3) % n, (p * per + 5) % n))
    before = len(svc2._cache)
    rep = svc2.apply_updates(inserts=(np.array([1]), np.array([2]),
                                      np.array([1e-4], np.float32)))
    survival = len(svc2._cache) / max(1, before)
    print(f"  ingest {ingest_eps:,.0f} edges/s through apply_updates; "
          f"repair speedup median bfs {med['bfs']:.1f}x sssp "
          f"{med['sssp']:.1f}x; cache survival {len(svc2._cache)}/{before} "
          f"= {survival:.2f} (update touched partitions "
          f"{rep.touched_partitions.tolist()})")
    if survival < 0.5:
        smoke_failures.append(
            f"REGRESSION: cache survival {survival:.2f} < 0.5 across a "
            "one-partition update")
    if not (np.isfinite(ingest_eps) and ingest_eps > 0):
        smoke_failures.append("REGRESSION: ingest throughput not positive")
    return {"scale": scale, "batch_edges": batch,
            "batch_frac": batch / m, "epochs": n_epochs,
            "repair_speedup_bfs": med["bfs"],
            "repair_speedup_sssp": med["sssp"],
            "ingest_edges_per_s": ingest_eps,
            "cache_survival": survival}


def obs_report(smoke_failures, scale=12, edge_factor=8, budget=32,
               trace_path=None):
    """Observability attribution + trace validity (PR 9, DESIGN.md §17).

    Serves one B=``budget`` reachability batch through a fully instrumented
    :class:`GraphService` (spans + per-level engine traces + an isolated
    metrics registry) on the same fixed RMAT-12 as `service_report`, then
    gates two acceptance bars: >= 90% of the flush's wall clock must land in
    the per-batch phase spans (svc.collect / dispatch / sync / extract /
    charge tile each svc.flush), and the exported Chrome ``trace_event``
    document must be structurally valid (every event has
    pid/tid/ts/dur/name; spans nest without partial overlap per tid).  With ``trace_path`` the trace JSON is
    written next to the bench document — the CI bench lane uploads it as an
    artifact alongside ``BENCH_*.json`` (it is named ``TRACE_*`` so the
    baseline glob never picks it up).
    """
    from repro.core import GraphService, Reachability
    from repro.obs import (MetricsRegistry, Observability, format_summary,
                           summarize, validate_chrome_trace)

    g = rmat(scale, edge_factor, seed=0)
    n = g.n_rows
    ob = Observability(metrics=MetricsRegistry())
    svc = GraphService(g, batch_budget=budget, obs=ob)
    svc.query(Reachability(0, 1))   # compile the runner outside the window
    svc.reset_stats()
    ob.clear()                      # attribution measures serving only
    rng = np.random.default_rng(0)
    stream = [Reachability(int(s), int(t))
              for s, t in zip(rng.integers(0, n, budget),
                              rng.integers(0, n, budget))]
    tickets = [svc.submit(q) for q in stream]
    svc.flush()
    for t in tickets:
        svc.result(t)

    spans = ob.spans.spans()
    flush_s = sum(sp.dur for sp in spans if sp.name == "svc.flush")
    phase_s = sum(sp.dur for sp in spans if sp.name in (
        "svc.collect", "svc.dispatch", "svc.sync", "svc.extract",
        "svc.charge"))
    frac = phase_s / max(flush_s, 1e-12)
    trace = ob.build_trace()
    errors = validate_chrome_trace(trace)
    summ = summarize(trace)
    print(f"\nobs (RMAT-{scale}, B={budget}): {len(spans)} spans, "
          f"{sum(summ['levels'].values())} traced levels, attribution "
          f"{frac:.3f} (target >= 0.90), {len(errors)} structural errors")
    print(format_summary(summ))
    if not frac >= 0.90:
        smoke_failures.append(
            f"REGRESSION: span attribution {frac:.3f} < 0.90 of flush wall")
    for e in errors:
        smoke_failures.append(f"REGRESSION: chrome trace invalid: {e}")
    if trace_path:
        with open(trace_path, "w") as f:
            json.dump(trace, f, indent=1)
            f.write("\n")
        print(f"wrote {trace_path}")
    return {"scale": scale, "budget": budget,
            "attribution_frac": frac,
            "trace_events": len(trace.get("traceEvents", ())),
            "trace_errors": len(errors),
            "wall_ms": summ["wall_ms"],
            "phases": summ["phases"],
            "metrics": ob.metrics.snapshot()}


def kernels_report(smoke_failures, scale: int):
    """Kernel lane (DESIGN.md §18): the tuned-vs-default BBCSR grid plus the
    folded jnp-oracle microbenches, with achieved-vs-roofline-peak fractions.

    The gate matches what the autotuner optimizes on this backend: measured
    time on a real device, the deterministic HBM byte model on CPU (where
    wall clock times the jnp oracle, not the interpreted kernel) — a tuned
    config must never score worse than the hand-picked default."""
    from repro import tune
    try:
        from benchmarks import roofline as _roofline
    except ImportError:
        import roofline as _roofline

    rows = tune.kernel_rows(scale)
    peak = tune.stream_peak_bytes_per_s()
    print(f"\nkernel lane (scale={scale}; peak={peak:.3e} B/s)")
    for r in _roofline.rows_to_report(rows, peak):
        print(f"  {r['name']:<40}{r['us_per_call']:>10.1f} us  {r['derived']}")

    by = {r["name"]: r for r in rows}
    for kern in ("bbcsr_add", "bbcsr_min"):
        d = by[f"kernels/{kern}/default"]
        t = by[f"kernels/{kern}/tuned"]
        metric = "us" if d["measured"] == "device" else "bytes_model"
        if t[metric] > d[metric] * 1.05:
            smoke_failures.append(
                f"REGRESSION: tuned {kern} {metric}={t[metric]:.1f} worse "
                f"than default {d[metric]:.1f}")
    if not all(np.isfinite(r["bytes_per_s"]) and r["bytes_per_s"] > 0
               for r in rows):
        smoke_failures.append("REGRESSION: non-finite kernel-lane throughput")

    out_rows = {}
    for r in rows:
        row = {k: r[k] for k in ("us", "bytes_model", "bytes_per_s",
                                 "measured")}
        if "config" in r:
            row["config"] = r["config"]
        out_rows[r["name"]] = row
    return {"peak_bytes_per_s": peak, "rows": out_rows}


def sweep_delta(scale: int = 10, edge_factor: int = 8):
    """Delta sweep (satellite): RMAT + uniform weights vs the histogram rule."""
    print("\ndelta-stepping sweep (iters = bucket expansions; ms best-of-3)")
    for name, g in [("rmat", rmat(scale, edge_factor, seed=0)),
                    ("uniform", uniform_random_graph(1 << scale, edge_factor,
                                                     seed=0))]:
        auto = auto_delta(g)
        deltas = [0.25 * auto, 0.5 * auto, auto, 2 * auto, 4 * auto, 1e9]
        tags = ["auto/4", "auto/2", "auto", "2*auto", "4*auto", "inf(BF)"]
        print(f"  {name}: n={g.n_rows} m={g.nnz} auto_delta={auto:.4f}")
        for tag, d in zip(tags, deltas):
            _, stats = sssp(g, 0, delta=d, return_stats=True)
            ms = _t(jax.jit(lambda d=d: sssp(g, 0, delta=d)))
            print(f"    delta={tag:<8} ({d:9.4f})  iters={int(stats['iters']):4d}"
                  f"  {ms:8.2f} ms")


def run(scale: int = 12, edge_factor: int = 8, smoke: bool = False,
        trace_path=None):
    failures = []
    g = rmat(scale, edge_factor, seed=0)
    n, m = g.n_rows, g.nnz
    kmax = int(np.asarray(g.degrees()).max())
    print(f"RMAT scale={scale}  n={n}  m={m}  max_deg={kmax}")

    rows = []
    stats_by_mode = {}
    levels_by_mode = {}
    for mode in ("push", "pull", "auto"):
        fn = jax.jit(lambda mode=mode: bfs(g, 0, mode=mode))
        ms = _t(fn)
        state0 = {"level": jnp.full((n,), -1, jnp.int32).at[0].set(0)}
        f0 = jnp.zeros((n,), jnp.int32).at[0].set(1)
        st, stats = engine.run(g, bfs_program(), state0, f0, max_iters=n,
                               mode=mode, return_stats=True)
        stats_by_mode[mode] = {k: int(v) for k, v in stats.items()}
        levels_by_mode[mode] = np.asarray(st["level"])
        rows.append((f"bfs/{mode}", ms, stats_by_mode[mode]))

    d_auto, s_stats = sssp(g, 0, return_stats=True)
    ms_sssp = _t(jax.jit(lambda: sssp(g, 0)))
    rows.append((f"sssp/auto(delta={auto_delta(g):.3f})", ms_sssp,
                 {k: int(v) for k, v in s_stats.items()}))
    from repro.core.algorithms import symmetrize
    gs = symmetrize(g)  # host-side prep, outside the jitted region
    ms_cc = _t(jax.jit(lambda: connected_components(gs, symmetrize_input=False)))
    rows.append(("cc/auto", ms_cc, {}))
    pr = pagerank(g, iters=10)
    ms_pr = _t(jax.jit(lambda: pagerank(g, iters=10)))
    rows.append(("pagerank/dense x10", ms_pr, {}))

    print(f"\n{'workload':<28}{'ms':>10}   iters/push/pull")
    for name, ms, st in rows:
        detail = (f"{st['iters']}/{st['pushes']}/{st['pulls']}" if st else "-")
        print(f"{name:<28}{ms:>10.2f}   {detail}")

    push_ms = dict((r[0], r[1]) for r in rows)["bfs/push"]
    auto_ms = dict((r[0], r[1]) for r in rows)["bfs/auto"]
    print(f"\nauto vs always-push: {push_ms / auto_ms:.2f}x "
          f"({stats_by_mode['auto']['pushes']} push + "
          f"{stats_by_mode['auto']['pulls']} pull levels)")

    bytes_doc = routed_bytes_report(n, m, stats_by_mode["auto"]["pushes"])
    reduction = bytes_doc["reduction"]
    louvain_doc = louvain_report(g, failures)
    fallback_doc = fallback_report(scale)
    dist_doc = distributed_report(min(scale, 8), failures)
    service_doc = service_report(failures)
    service_dist_doc = service_distributed_report(failures)
    async_doc = async_report(failures)
    streaming_doc = streaming_report(failures)
    obs_doc = obs_report(failures, trace_path=trace_path)
    kernels_doc = kernels_report(failures, scale)

    # --- smoke checks (ci.sh bench): NaN + regression markers ---------------
    for mode in ("push", "pull"):
        if not np.array_equal(levels_by_mode[mode], levels_by_mode["auto"]):
            failures.append(f"REGRESSION: bfs/{mode} disagrees with bfs/auto")
    d_np = np.asarray(d_auto)
    if np.isnan(d_np).any():
        failures.append("REGRESSION: NaN in sssp distances")
    if not np.isfinite(d_np[np.asarray(levels_by_mode['auto']) >= 0]).all():
        failures.append("REGRESSION: unreachable sssp distance on a reached vertex")
    pr_np = np.asarray(pr)
    if np.isnan(pr_np).any() or abs(float(pr_np.sum()) - 1.0) > 1e-2:
        failures.append("REGRESSION: pagerank is NaN or not a distribution")
    # the reduction is a model-level number; the meaningful guard is that the
    # capacity derivation still enables compaction at this scale (edge_cap
    # strictly below the full partition => run_distributed's compact path on)
    m_per_shard = -(-m // 8)
    if not (0 < engine.frontier_edge_capacity(m_per_shard, 1 / 32) < m_per_shard):
        failures.append("REGRESSION: derived push capacity no longer compacts")
    if reduction < 1.0:
        failures.append("REGRESSION: compacted routing moves MORE bytes than full")
    if not all(np.isfinite(r[1]) and r[1] > 0 for r in rows):
        failures.append("REGRESSION: non-finite timing")

    doc = {
        "meta": {"scale": scale, "edge_factor": edge_factor, "n": n, "m": m,
                 "n_shards": 8, "host": platform.node(),
                 # same-run STREAM peak: lets future baseline comparisons
                 # normalize wall clocks for host-speed drift between runs
                 "host_speed_bytes_per_s": kernels_doc["peak_bytes_per_s"]},
        "timings_ms": {name: ms for name, ms, _ in rows},
        "bytes": bytes_doc,
        "modularity": louvain_doc,
        "fallback": fallback_doc,
        "service": service_doc,
        "streaming": streaming_doc,
        "obs_report": obs_doc,
        "kernels": kernels_doc,
    }
    doc["timings_ms"]["louvain/multilevel"] = louvain_doc["ms"]
    # msbfs_b256_ms stays inside doc["service"] (not timings_ms): wall-clock
    # of a 100-300 ms batch swings well past the 25% gate run-to-run; the
    # gated form is the amortization ratio
    if dist_doc is not None:
        doc["distributed"] = dist_doc
    if service_dist_doc is not None:
        doc["service_distributed"] = service_dist_doc
    if async_doc is not None:
        doc["async"] = async_doc

    for f in failures:
        print(f)
    if smoke:
        print("SMOKE " + ("FAIL" if failures else "PASS"))
    return doc, failures


# ---------------------------------------------------------------------------
# Persisted bench trajectory (BENCH_*.json artifact + baseline comparison)
# ---------------------------------------------------------------------------

def _walk_numbers(node, path=""):
    if isinstance(node, dict):
        for k, v in node.items():
            yield from _walk_numbers(v, f"{path}/{k}")
    elif isinstance(node, list):
        for i, v in enumerate(node):
            yield from _walk_numbers(v, f"{path}[{i}]")
    elif isinstance(node, (int, float)) and not isinstance(node, bool):
        yield path, float(node)


def find_baseline():
    """Newest committed BENCH_*.json (by numeric suffix, then name).  The
    output file itself counts if it already exists — it is read *before* the
    new run overwrites it, so re-runs in one checkout still compare."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cands = sorted(
        glob.glob(os.path.join(root, "BENCH_*.json")),
        key=lambda p: (int((re.search(r"(\d+)", os.path.basename(p)) or
                            [0, 0])[1]), p))
    return cands[-1] if cands else None


def compare_to_baseline(doc, base, rel=0.25, ms_floor=2.0):
    """Regression gate for the bench lane: a timing more than ``rel`` slower
    (plus an absolute floor — tiny-scale timings are noisy), modularity more
    than ``rel`` lower, or the byte-model reduction more than ``rel`` smaller
    than the committed baseline.  Wall-clock timings are only compared when
    the baseline came from the *same host* (meta.host) — a baseline committed
    from the authoring machine must not fail heterogeneous CI runners; the
    machine-independent metrics (modularity, bytes) always gate.

    Same host is not same *speed*: on shared runners the achievable clock
    drifts between runs (measured here: the STREAM peak probe swinging
    2.0e10<->2.8e10 B/s minutes apart, louvain/multilevel 69<->96 ms with
    byte-identical code).  Since PR 10 every doc records that same-run probe
    (meta.host_speed_bytes_per_s), and the wall-clock allowance stretches by
    the baseline/current speed ratio, capped at DRIFT_CAP so a real 2x
    regression still fails even against a lucky-epoch baseline.  A faster
    current host never tightens the gate below ``rel``; a baseline predating
    the probe gets the full cap (its epoch speed is unknowable)."""
    failures = []
    for k in ("scale", "edge_factor", "n_shards"):
        if doc.get("meta", {}).get(k) != base.get("meta", {}).get(k):
            print(f"baseline meta mismatch ({k}: "
                  f"{base.get('meta', {}).get(k)} vs "
                  f"{doc.get('meta', {}).get(k)}): runs are not comparable, "
                  f"skipping baseline gate")
            return failures
    same_host = (doc.get("meta", {}).get("host")
                 and doc.get("meta", {}).get("host")
                 == base.get("meta", {}).get("host"))
    if not same_host:
        print("baseline from a different host: skipping wall-clock "
              "comparison (quality/byte metrics still gate)")
    DRIFT_CAP = 1.6  # measured worst epoch-to-epoch swing ~1.4x, plus margin
    speed_new = doc.get("meta", {}).get("host_speed_bytes_per_s")
    speed_old = base.get("meta", {}).get("host_speed_bytes_per_s")
    if speed_new and speed_old:
        drift = max(1.0, min(speed_old / speed_new, DRIFT_CAP))
    else:
        drift = DRIFT_CAP
    if same_host and drift > 1.0:
        print(f"host-speed drift allowance: wall-clock gates widened "
              f"x{drift:.2f}"
              + ("" if speed_new and speed_old else " (baseline has no probe)"))
    for k, new in (doc.get("timings_ms", {}) if same_host else {}).items():
        old = base.get("timings_ms", {}).get(k)
        if old is not None and new > old * (1 + rel) * drift + ms_floor:
            failures.append(f"REGRESSION: {k} {new:.2f} ms vs baseline "
                            f"{old:.2f} ms (> {100 * rel:.0f}% slower)")
    q_new = doc.get("modularity", {}).get("multilevel")
    q_old = base.get("modularity", {}).get("multilevel")
    if q_new is not None and q_old is not None:
        if q_new < q_old - rel * max(abs(q_old), 0.02):
            failures.append(f"REGRESSION: multilevel modularity {q_new:.5f} "
                            f"vs baseline {q_old:.5f}")
    r_new = doc.get("bytes", {}).get("reduction")
    r_old = base.get("bytes", {}).get("reduction")
    if r_new is not None and r_old is not None and r_new < r_old * (1 - rel):
        failures.append(f"REGRESSION: byte reduction {r_new:.1f}x vs "
                        f"baseline {r_old:.1f}x")
    # service: only the amortization *ratio* gates vs baseline — both of its
    # sides are measured within one run, so it is robust to host *load*,
    # unlike raw qps (observed ~1.7x swings between otherwise-identical
    # runs; qps stays a reported trajectory number, service_report's own
    # smoke checks gate positivity/occupancy/hit-rate and the absolute 0.15
    # bar).  It is still hardware-*shape* dependent (batched vs sequential
    # amortize differently per core count), so like the wall-clock timings
    # it only compares same-host.
    a_new = doc.get("service", {}).get("amortization_ratio")
    a_old = base.get("service", {}).get("amortization_ratio")
    if (same_host and a_new is not None and a_old is not None
            and a_new > a_old * (1 + rel) + 0.01):
        failures.append(f"REGRESSION: msbfs amortization ratio {a_new:.3f} "
                        f"vs baseline {a_old:.3f}")
    # streaming (PR 8): like the amortization ratio, both sides of the
    # repair speedup are measured within one run (robust to host load, still
    # hardware-shape dependent -> same-host); cache survival is a counted
    # fraction and always gates
    s_new = doc.get("streaming", {}).get("repair_speedup_sssp")
    s_old = base.get("streaming", {}).get("repair_speedup_sssp")
    if (same_host and s_new is not None and s_old is not None
            and s_new < s_old * (1 - rel)):
        failures.append(f"REGRESSION: sssp repair speedup {s_new:.1f}x vs "
                        f"baseline {s_old:.1f}x")
    c_new = doc.get("streaming", {}).get("cache_survival")
    c_old = base.get("streaming", {}).get("cache_survival")
    if (c_new is not None and c_old is not None
            and c_new < c_old * (1 - rel)):
        failures.append(f"REGRESSION: cache survival {c_new:.2f} vs "
                        f"baseline {c_old:.2f}")
    # async placement (PR 7): the reduction ratio is machine-independent
    # (counted collectives, not wall clock) so it always gates; latency p50
    # compares same-host like the other wall-clock numbers
    for bkey, brow in doc.get("async", {}).get("budgets", {}).items():
        orow = base.get("async", {}).get("budgets", {}).get(bkey, {})
        for name in ("bfs", "sssp"):
            r_new = brow.get(f"{name}_reduction_ratio")
            r_old = orow.get(f"{name}_reduction_ratio")
            if (r_new is not None and r_old is not None
                    and r_new < r_old * (1 - rel)):
                failures.append(
                    f"REGRESSION: async {name} reduction ratio {r_new:.1f}x "
                    f"vs baseline {r_old:.1f}x at B={bkey}")
            p_new = brow.get(f"{name}_async", {}).get("p50_ms")
            p_old = orow.get(f"{name}_async", {}).get("p50_ms")
            if (same_host and p_new is not None and p_old is not None
                    and p_new > p_old * (1 + rel) * drift + ms_floor):
                failures.append(
                    f"REGRESSION: async {name} p50 {p_new:.1f} ms vs "
                    f"baseline {p_old:.1f} ms at B={bkey}")
    # kernel lane (PR 10): the HBM byte model and tuned config are machine-
    # independent, so modeled bytes always gate; the oracle/device wall
    # clocks compare same-host like the other timings (µs floor instead of
    # ms_floor — single-kernel calls, not whole-algorithm runs)
    us_floor = 500.0
    for name, row in doc.get("kernels", {}).get("rows", {}).items():
        orow = base.get("kernels", {}).get("rows", {}).get(name)
        if orow is None:
            continue
        b_new, b_old = row.get("bytes_model"), orow.get("bytes_model")
        if b_new is not None and b_old is not None and b_new > b_old * (1 + rel):
            failures.append(f"REGRESSION: {name} modeled bytes {b_new} vs "
                            f"baseline {b_old} (> {100 * rel:.0f}% more)")
        u_new, u_old = row.get("us"), orow.get("us")
        if (same_host and u_new is not None and u_old is not None
                and u_new > u_old * (1 + rel) * drift + us_floor):
            failures.append(f"REGRESSION: {name} {u_new:.1f} us vs baseline "
                            f"{u_old:.1f} us (> {100 * rel:.0f}% slower)")
    # distributed-service latency (same-host): the PR-7 async serving path
    # must not drift back toward the per-level-barrier p50.  Since PR 9
    # ServiceStats percentiles are log-histogram bucket *upper edges*
    # (DESIGN.md §17): two runs can sit one bucket ratio apart with no real
    # movement (and the pr8 baseline recorded exact percentiles), so the
    # gate widens by one bucket growth factor on top of ``rel``
    hist_growth = 1.12
    for bkey, brow in doc.get("service_distributed", {}).get("budgets",
                                                             {}).items():
        p_new = brow.get("latency_p50_ms")
        p_old = base.get("service_distributed", {}).get("budgets", {}) \
                    .get(bkey, {}).get("latency_p50_ms")
        if (same_host and p_new is not None and p_old is not None
                and p_new > p_old * (1 + rel) * hist_growth * drift + ms_floor):
            failures.append(
                f"REGRESSION: distributed service p50 {p_new:.1f} ms vs "
                f"baseline {p_old:.1f} ms at B={bkey}")
    return failures


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=12)
    ap.add_argument("--edge-factor", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-scale CI lane: exit nonzero on NaN/regression")
    ap.add_argument("--sweep-delta", action="store_true")
    ap.add_argument("--json", metavar="PATH",
                    help="write the machine-readable result document")
    ap.add_argument("--baseline", default="none", metavar="PATH|auto|none",
                    help="compare against a previous BENCH_*.json and fail "
                         "on NaN or >25%% regression ('auto' = newest "
                         "committed file)")
    args = ap.parse_args()
    if args.sweep_delta:
        sweep_delta(min(args.scale, 10), args.edge_factor)
        sys.exit(0)
    base = None
    if args.baseline == "auto":
        path = find_baseline()
        if path is not None:
            with open(path) as f:
                base = (path, json.load(f))
    elif args.baseline != "none":
        with open(args.baseline) as f:
            base = (args.baseline, json.load(f))
    # Chrome trace rides next to the bench document; the TRACE_ prefix keeps
    # it out of find_baseline's BENCH_*.json glob (and load_cost_priors')
    trace_path = None
    if args.json:
        trace_path = os.path.join(
            os.path.dirname(args.json) or ".",
            re.sub(r"^BENCH", "TRACE", os.path.basename(args.json))
            if os.path.basename(args.json).startswith("BENCH")
            else "TRACE_" + os.path.basename(args.json))
    doc, failures = run(args.scale, args.edge_factor, smoke=args.smoke,
                        trace_path=trace_path)
    for path, v in _walk_numbers(doc):
        if math.isnan(v):
            failures.append(f"REGRESSION: NaN at {path}")
    if base is not None:
        base_path, base_doc = base
        cmp_failures = compare_to_baseline(doc, base_doc)
        print(f"\nbaseline {os.path.basename(base_path)}: "
              + ("OK" if not cmp_failures else f"{len(cmp_failures)} regressions"))
        for f in cmp_failures:
            print(f)
        failures += cmp_failures
    elif args.baseline == "auto":
        print("\nbaseline: none committed yet (first trajectory point)")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.json}")
    # --smoke and --baseline are both gates: any failure (smoke regression
    # marker, NaN, or baseline regression) exits nonzero under either flag
    if failures and (args.smoke or args.baseline != "none"):
        sys.exit(1)
