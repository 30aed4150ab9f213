"""The paper's Table II workloads end-to-end on one RMAT graph.

  PYTHONPATH=src python examples/graph_analytics.py [--scale 12]
                                                    [--placement sync|async]
                                                    [--stream N]

`--placement async` runs the distributed demo (any >= 2 devices) with
bounded-staleness shard pacing (DESIGN §14, PR 7) and prints a sync-vs-async
traversal latency comparison alongside the served stream.

`--stream N` runs the streaming-graph demo (DESIGN §16, PR 8): N edge-update
batches ingested through `GraphService.apply_updates` while the service keeps
answering queries, printing per-epoch repair-vs-scratch latency and the
partition-scoped cache survival.

`--trace out.json` (DESIGN §17, PR 9) attaches a telemetry bundle to the
served stream — the deadline mix when >= 2 devices, the local mixed stream
otherwise — writes the Chrome ``trace_event`` JSON (load it at
chrome://tracing or ui.perfetto.dev) and prints the per-phase summary table.
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rmat
from repro.launch.cache import use_compile_cache
from repro.core.algorithms import (spmv, spmspv, pagerank, bfs, random_walks,
                                   label_propagation, modularity, multilevel,
                                   ties_sample, sssp, connected_components,
                                   symmetrize)

ap = argparse.ArgumentParser()
ap.add_argument("--scale", type=int, default=12)
ap.add_argument("--placement", choices=("sync", "async"), default="sync",
                help="distributed demo placement; async = bounded-staleness "
                     "shard pacing (DESIGN §14)")
ap.add_argument("--sync-interval", type=int, default=8,
                help="micro-steps per global check when --placement async")
ap.add_argument("--stream", type=int, default=0, metavar="N",
                help="streaming demo: ingest N update batches and print "
                     "repair-vs-scratch latency per epoch (DESIGN §16)")
ap.add_argument("--trace", metavar="PATH",
                help="record the served stream (spans + per-level engine "
                     "traces) and write a Chrome trace_event JSON (DESIGN "
                     "§17)")
args = ap.parse_args()
use_compile_cache()

g = rmat(args.scale, 16, seed=7)
print(f"RMAT-{args.scale}: {g.n_rows} vertices, {g.nnz} edges")
x = jnp.asarray(np.random.default_rng(0).random(g.n_cols, np.float32))
key = jax.random.PRNGKey(0)


def timed(name, fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    print(f"  {name:24s} {1e3 * (time.perf_counter() - t0):8.1f} ms")
    return out


y = timed("SpMV", jax.jit(lambda: spmv(g, x)))
ys = timed("SpMSpV (32 active)", jax.jit(lambda: spmspv(
    g, jnp.arange(32, dtype=jnp.int32), jnp.ones(32), max_deg=256)))
pr = timed("PageRank (20 it)", jax.jit(lambda: pagerank(g, iters=20)))
lv = timed("BFS", jax.jit(lambda: bfs(g, 0, max_levels=48)))
wk = timed("Random walks (4096x16)", jax.jit(lambda: random_walks(
    g, jnp.arange(4096) % g.n_rows, 16, key)))
lab = timed("Louvain (LPA, 8 it)", jax.jit(lambda: label_propagation(g, iters=8)))
mlab, mscores = timed("Louvain (multi-level)", lambda: multilevel(g))
dist = timed("SSSP (delta-stepping)", jax.jit(lambda: sssp(g, 0)))
gsym = symmetrize(g)  # host-side prep for components
comp = timed("Connected components", jax.jit(lambda: connected_components(
    gsym, symmetrize_input=False)))
nodes, n_nodes, mask = timed("TIES sampler", jax.jit(lambda: ties_sample(
    g, 512, 1024, key)))

# --- graph query service: micro-batched multi-source serving (DESIGN §13) ---
from repro.core import (GraphService, Reachability, Distance, PPRTopK,
                        NeighborSample)

# --trace: one telemetry bundle for the served stream (DESIGN §17) — it
# rides the deadline-mix service when the distributed demo runs, else the
# local mixed stream, and is exported + summarized after serving
obs = None
if args.trace:
    from repro.obs import MetricsRegistry, Observability, format_summary
    obs = Observability(metrics=MetricsRegistry())
n_dev = len(jax.devices())
use_dist = n_dev >= 2

svc = GraphService(g, batch_budget=32, cache_capacity=1024,
                   obs=None if use_dist else obs)
for warm in (Reachability(0, 1), Distance(0, 1), PPRTopK(0, k=4),
             NeighborSample(0, fanout=2)):
    svc.query(warm)  # compile each kind's runner before timing the stream
svc.reset_stats()
if obs is not None and not use_dist:
    obs.clear()      # the trace shows serving, not the warmup compiles
rng = np.random.default_rng(3)
stream = []
for i in range(96):  # a mixed query stream, as a client would submit it
    s, t = int(rng.integers(0, g.n_rows)), int(rng.integers(0, g.n_rows))
    stream.append([Reachability(s, t), Distance(s, t), PPRTopK(s, k=4),
                   NeighborSample(s, fanout=2)][i % 4])
tickets = [svc.submit(q) for q in stream]
timed("Query service (96 q)", svc.flush)
for q in stream[:16]:  # resubmit a prefix: the LRU cache serves these
    svc.submit(q)
timed("Query service (16 cached)", svc.flush)
reach = svc.result(tickets[0])
print(f"\n  service stats          {svc.stats}")
print(f"  first query            {stream[0]} -> {reach}")

# --- distributed serving with deadlines (DESIGN §14): the same facade on the
# sharded engine — reach/dist ride run_batched_distributed, every query
# carries a latency SLO, and the stats report p50/p95 + deadline-miss rate.
if use_dist:
    from repro.launch.mesh import make_cores_mesh

    mesh = make_cores_mesh()

    if args.placement == "async":
        # head-to-head traversal latency: the same sharded graph, sync level
        # barrier vs bounded-staleness pacing (warm runs; first call compiles)
        from repro.core import dgas
        from repro.core.algorithms import sssp_batched_distributed
        from repro.core.algorithms.distgraph import shard_graph

        gsh, att = shard_graph(g, n_dev,
                               row_att=dgas.block_rule(g.n_rows, n_dev))
        srcs = jnp.asarray(rng.integers(0, g.n_rows, 8), jnp.int32)
        for pl in ("sync", "async"):
            fn = (lambda pl=pl: sssp_batched_distributed(
                gsh, att, srcs, mesh, placement=pl,
                sync_interval=args.sync_interval))
            fn()  # compile
            timed(f"SSSP x{n_dev} shards ({pl})", fn)

    dsvc = GraphService(g, batch_budget=32, mesh=mesh, cache_capacity=1024,
                        placement=args.placement,
                        sync_interval=args.sync_interval,
                        cost_seed="auto", obs=obs)
    for warm in (Reachability(0, 1), PPRTopK(0, k=4)):
        dsvc.query(warm)  # compile before the timed stream
    dsvc.reset_stats()
    if obs is not None:
        obs.clear()      # trace the deadline mix, not the warmup compiles
    dstream = []
    for i in range(64):  # a deadline mix: reachability + PPR top-k
        s = int(rng.integers(0, g.n_rows))
        q = (Reachability(s, int(rng.integers(0, g.n_rows)))
             if i % 2 == 0 else PPRTopK(s, k=4))
        dstream.append(dsvc.submit(q, deadline=30.0))
        dsvc.poll()      # the client-driven admission tick
    timed("Distributed service (64 q)", dsvc.flush)
    st = dsvc.stats
    print(f"  distributed stats      {st}")
    print(f"  latency p50/p95        {st.latency_p50_ms:.1f} / "
          f"{st.latency_p95_ms:.1f} ms")
    print(f"  deadline miss rate     {st.deadline_miss_rate:.3f} "
          f"({st.deadline_misses}/{st.deadline_queries})")
else:
    print(f"\n  distributed serving demo skipped ({n_dev} device < 2; run "
          "under XLA_FLAGS=--xla_force_host_platform_device_count=4)")

if obs is not None:
    tdoc = obs.export_chrome_trace(args.trace)
    print(f"\n  trace: wrote {args.trace} ({len(tdoc['traceEvents'])} events;"
          " load at chrome://tracing or ui.perfetto.dev)")
    for line in format_summary(obs.summary()).splitlines():
        print("  " + line)

# --- streaming graphs (DESIGN §16): epoch-versioned serving under updates ---
if args.stream > 0:
    from repro.core import GraphHandle
    from repro.core.algorithms import auto_delta, bfs_repair, sssp_repair

    print(f"\n  streaming: {args.stream} update batches "
          f"({max(1, g.nnz // 200)} edges each) while serving")
    ssvc = GraphService(g, batch_budget=32, cache_capacity=1024)
    per = ssvc.handle.per_partition
    probe = [NeighborSample((p * per + 3) % g.n_rows, fanout=2)
             for p in range(8)]
    for q in probe:
        ssvc.query(q)                 # one cached entry per partition
    handle = GraphHandle.wrap(g, n_partitions=8)
    prev_lv = bfs(handle.csr, 0)
    prev_d = sssp(handle.csr, 0, delta=auto_delta(handle.csr))
    srng = np.random.default_rng(11)
    k = max(1, g.nnz // 200)          # 0.5% of edges per batch

    def new_edges(csr):
        # genuinely new edges (rejection-sampled, weights at the graph's own
        # U[0,1) scale): pure growth, so every batch is monotone-safe
        have = np.repeat(np.arange(csr.n_rows, dtype=np.int64),
                         np.diff(np.asarray(csr.indptr))) * csr.n_cols \
            + np.asarray(csr.indices, np.int64)
        keys = np.empty(0, np.int64)
        while keys.size < k:
            cand = (srng.integers(0, csr.n_rows, 2 * k) * csr.n_cols
                    + srng.integers(0, csr.n_cols, 2 * k))
            keys = np.unique(np.concatenate([keys, cand[~np.isin(cand, have)]]))
        keys = srng.permutation(keys)[:k]
        return keys // csr.n_cols, keys % csr.n_cols, \
            srng.random(k).astype(np.float32)

    for epoch in range(1, args.stream + 1):
        ins = new_edges(handle.csr)
        cached_before = len(ssvc._cache)
        rep = ssvc.apply_updates(inserts=ins)
        handle, hrep = handle.apply(ins)
        csr, ch = handle.csr, hrep.changed_sources
        delta = auto_delta(csr)
        # each epoch changes nnz, so both paths recompile: jit + warm first,
        # then time, like every other demo in this file
        scratch_fn = jax.jit(lambda: sssp(csr, 0, delta=delta))
        repair_fn = jax.jit(lambda: sssp_repair(csr, prev_d, ch))
        jax.block_until_ready(scratch_fn())
        jax.block_until_ready(repair_fn())
        t0 = time.perf_counter()
        jax.block_until_ready(scratch_fn())
        scratch_ms = 1e3 * (time.perf_counter() - t0)
        t0 = time.perf_counter()
        prev_d = jax.block_until_ready(repair_fn())
        repair_ms = 1e3 * (time.perf_counter() - t0)
        prev_lv = bfs_repair(csr, prev_lv, ch)
        served = ssvc.query(probe[7])  # the stream keeps serving mid-ingest
        print(f"  epoch {epoch:3d}: sssp scratch {scratch_ms:8.1f} ms  repair "
              f"{repair_ms:8.1f} ms ({scratch_ms / repair_ms:5.1f}x)  "
              f"cache {len(ssvc._cache)}/{cached_before} live  "
              f"touched={rep.touched_partitions.tolist()}")
    print(f"  final epoch            {ssvc.epoch} (service) / "
          f"{handle.epoch} (handle)")
    print(f"  sssp reached (stream)  "
          f"{int(np.isfinite(np.asarray(prev_d)).sum())}/{g.n_rows}")

print(f"\n  pagerank mass          {float(pr.sum()):.4f}")
print(f"  bfs reached            {int((lv >= 0).sum())}/{g.n_rows}")
print(f"  sssp reached           {int(np.isfinite(np.asarray(dist)).sum())}"
      f"/{g.n_rows}")
print(f"  components             {len(np.unique(np.asarray(comp)))}")
print(f"  communities            {len(np.unique(np.asarray(lab)))}")
print(f"  modularity             {float(modularity(g, lab)):.4f}")
print(f"  multilevel communities {len(np.unique(np.asarray(mlab)))}")
print(f"  multilevel modularity  {(mscores[-1] if mscores else 0.0):.4f} "
      f"over {len(mscores)} levels")
print(f"  TIES nodes/edges       {int(n_nodes)}/{int(mask.sum())}")
