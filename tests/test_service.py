"""Graph query service: result correctness per query kind, micro-batch
grouping and ordering (round-robin across kinds), deadline-aware admission,
lane dedup/occupancy accounting, LRU cache behavior across graph epochs, and
the route-byte ledger."""
import numpy as np
import pytest

from repro.core import (Distance, GraphService, NeighborSample, PPRTopK,
                        Reachability, rmat, uniform_random_graph)
from repro.core.algorithms import bfs, ppr, sssp

G = rmat(7, 8, seed=11)


class FakeClock:
    """Deterministic time source for deadline-admission tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def make_service(**kw):
    kw.setdefault("batch_budget", 4)
    kw.setdefault("cache_capacity", 32)
    return GraphService(G, **kw)


# ---------------------------------------------------------------------------
# per-kind correctness against the direct algorithms
# ---------------------------------------------------------------------------

def test_reachability_matches_bfs():
    svc = make_service()
    lv = np.asarray(bfs(G, 3))
    assert svc.query(Reachability(3, 40)) == bool(lv[40] >= 0)
    unreachable = int(np.argmin(lv)) if (lv < 0).any() else None
    if unreachable is not None:
        assert svc.query(Reachability(3, unreachable)) is False


def test_distance_matches_sssp():
    svc = make_service()
    d = np.asarray(sssp(G, 5, delta=svc.delta))
    assert svc.query(Distance(5, 60)) == float(d[60])


def test_ppr_topk_matches_ppr():
    svc = make_service()
    ids, scores = svc.query(PPRTopK(2, k=5))
    full = np.asarray(ppr(G, 2, iters=svc.ppr_iters))
    np.testing.assert_allclose(np.sort(scores)[::-1],
                               np.sort(full)[::-1][:5], rtol=1e-6)
    assert ids.shape == (5,) and scores.shape == (5,)


def test_neighbor_sample_draws_real_neighbors():
    svc = make_service()
    indptr = np.asarray(G.indptr)
    v = int(np.argmax(np.diff(indptr)))  # a vertex with many neighbors
    nbrs = np.asarray(G.indices)[indptr[v]: indptr[v + 1]]
    out = svc.query(NeighborSample(v, fanout=4))
    assert out.shape == (4,)
    assert set(out.tolist()) <= set(nbrs.tolist())


def test_neighbor_sample_fanout_over_budget_rejected():
    svc = make_service(batch_budget=2)
    with pytest.raises(ValueError, match="fanout"):
        svc.submit(NeighborSample(0, fanout=3))


def test_unknown_query_type_rejected():
    svc = make_service()
    with pytest.raises(TypeError):
        svc.submit(("reach", 0, 1))


def test_out_of_range_vertex_rejected():
    svc = make_service()
    with pytest.raises(ValueError, match="outside"):
        svc.submit(Reachability(0, G.n_rows))
    with pytest.raises(ValueError, match="outside"):
        svc.submit(NeighborSample(-1))
    with pytest.raises(ValueError, match="PPRTopK.k"):
        svc.submit(PPRTopK(0, k=svc.ppr_k_max + 1))
    with pytest.raises(ValueError, match="PPRTopK.k"):
        svc.submit(PPRTopK(0, k=0))
    with pytest.raises(ValueError, match="fanout"):
        svc.submit(NeighborSample(0, fanout=0))


def test_update_graph_flushes_pending_against_old_graph():
    # admitted queries execute on the graph they were validated against
    small = uniform_random_graph(16, 3, seed=2)
    svc = make_service()
    old_delta = svc.delta
    t = svc.submit(Distance(100, 40))   # valid on G, out of range on `small`
    svc.update_graph(small)             # must flush t against G first
    ref = float(np.asarray(sssp(G, 100, delta=old_delta))[40])
    assert svc.result(t) == ref


# ---------------------------------------------------------------------------
# micro-batching: grouping, ordering, occupancy
# ---------------------------------------------------------------------------

def test_mixed_stream_results_in_submission_order():
    svc = make_service(batch_budget=3)
    queries = [Reachability(0, 5), Distance(1, 9), Reachability(2, 7),
               PPRTopK(3, k=2), Distance(4, 11), Reachability(6, 1),
               NeighborSample(0, fanout=2)]
    tickets = [svc.submit(q) for q in queries]
    done = svc.flush()
    assert done == sorted(tickets)
    # every ticket resolves, and each against its own query's reference
    for t, q in zip(tickets, queries):
        r = svc.result(t)
        if isinstance(q, Reachability):
            assert r == bool(np.asarray(bfs(G, q.source))[q.target] >= 0)
        elif isinstance(q, Distance):
            assert r == float(np.asarray(sssp(G, q.source,
                                              delta=svc.delta))[q.target])


def test_batches_group_by_kind_up_to_budget():
    svc = make_service(batch_budget=4)
    for s in range(6):
        svc.submit(Reachability(s, (s + 1) % G.n_rows))
    svc.flush()
    # 6 distinct sources under budget 4 -> 2 batches (4 + 2 lanes)
    assert svc.stats.batches == 2
    assert svc.stats.lanes_used == 6
    assert svc.stats.queries == 6
    assert 0 < svc.stats.occupancy <= 1


def test_duplicate_sources_share_a_lane():
    svc = make_service(batch_budget=4)
    for t in range(5):
        svc.submit(Reachability(7, t))
    svc.flush()
    assert svc.stats.batches == 1        # five queries, one lane
    assert svc.stats.lanes_used == 1
    assert svc.stats.queries == 5


def test_ppr_mixed_k_share_one_runner():
    svc = make_service(batch_budget=4)
    t1 = svc.submit(PPRTopK(0, k=2))
    t2 = svc.submit(PPRTopK(1, k=6))
    svc.flush()
    ids1, sc1 = svc.result(t1)
    ids2, sc2 = svc.result(t2)
    assert ids1.shape == (2,) and ids2.shape == (6,)
    svc.query(PPRTopK(2, k=3))  # a third k must not add a runner
    assert len([k for k in svc._runners if k[0] == "ppr"]) == 1


def test_unclaimed_results_are_bounded():
    svc = make_service(batch_budget=2, results_capacity=3)
    tickets = [svc.submit(Reachability(s, 0)) for s in range(5)]
    svc.flush()
    assert len(svc._results) == 3             # oldest two evicted
    assert svc.result(tickets[-1]) is not None
    with pytest.raises(KeyError):
        svc.result(tickets[0])


# ---------------------------------------------------------------------------
# round-robin kind selection (head-of-line fix) + deadline-aware admission
# ---------------------------------------------------------------------------

def test_round_robin_prevents_head_of_line_blocking():
    """A burst of one kind must not starve the others: each rotation serves
    every pending kind one batch before the burst continues."""
    svc = make_service(batch_budget=2)
    order = []
    orig = svc._execute
    svc._execute = lambda kind, batch, lanes: (order.append(kind),
                                               orig(kind, batch, lanes))[1]
    for s in range(6):                       # 3 budget-2 batches of reach
        svc.submit(Reachability(s, 0))
    svc.submit(Distance(0, 1))
    svc.submit(PPRTopK(1, k=2))
    done = svc.flush()
    assert len(done) == 8
    # dist and ppr are served inside the first rotation, not after the burst
    assert order[:3] == ["reach", "dist", "ppr"]
    assert order[3:] == ["reach", "reach"]


def test_no_autoflush_without_deadlines():
    """Deadline-free admission keeps the explicit-flush contract (the
    pre-PR-5 behavior): submissions alone never trigger execution."""
    svc = make_service(batch_budget=2)
    for s in range(5):
        svc.submit(Reachability(s, 0))
    assert svc.stats.batches == 0 and not svc._results


def test_poll_noop_while_slack_remains():
    clk = FakeClock()
    svc = make_service(clock=clk)
    t = svc.submit(Reachability(0, 1), deadline=50.0)
    assert svc.poll() == []
    assert svc.stats.batches == 0
    clk.t = 50.0                    # slack (est cost 0) exhausted exactly now
    assert svc.poll() == [t]
    assert svc.result(t) == bool(np.asarray(bfs(G, 0))[1] >= 0)
    assert svc.stats.deadline_queries == 1
    assert svc.stats.deadline_misses == 0   # served at, not after, the SLO


def test_deadline_armed_flushes_on_full_batch():
    clk = FakeClock()
    svc = make_service(batch_budget=2, clock=clk)
    svc.submit(Reachability(0, 1), deadline=100.0)
    t2 = svc.submit(Reachability(1, 2), deadline=100.0)  # fills the budget
    assert svc.stats.batches == 1          # flushed on admission
    assert t2 in svc._results


def test_negative_slack_flushes_on_admission():
    """A learned batch-cost estimate tightens the slack: a deadline shorter
    than the estimated execution cannot wait at all."""
    clk = FakeClock()
    svc = make_service(clock=clk)
    svc._cost_ewma["reach"] = 2.0
    t = svc.submit(Reachability(0, 1), deadline=1.5)
    assert t in svc._results               # served the moment it was admitted


def test_deadline_miss_and_latency_accounting():
    clk = FakeClock()
    svc = make_service(clock=clk)
    svc.submit(Distance(0, 1), deadline=5.0)
    clk.t = 20.0                            # client polled far too late
    svc.poll()
    st = svc.stats
    assert st.deadline_queries == 1 and st.deadline_misses == 1
    assert st.deadline_miss_rate == 1.0
    # percentiles come from the log-bucketed sketch (PR 9): the reported
    # value is the observation's bucket upper edge, within one 12% bucket
    assert 20e3 <= st.latency_p50_ms <= 20e3 * st.latency_hist.growth
    assert 20e3 <= st.latency_p95_ms <= 20e3 * st.latency_hist.growth
    d = st.as_dict()
    assert {"latency_p50_ms", "latency_p95_ms",
            "deadline_miss_rate"} <= set(d)


def test_cost_ewma_learns_from_measured_batches():
    svc = make_service()                    # real clock
    svc.query(Reachability(0, 1))
    first = svc._cost_ewma["reach"]
    assert first > 0
    svc.query(Reachability(1, 2))
    assert svc._cost_ewma["reach"] > 0      # EWMA keeps tracking


def test_cost_seed_warms_the_ewma():
    """An explicit cost_seed becomes the EWMA's starting estimate (PR 7):
    deadline slack is computed from it before any batch has run, and a
    measured batch blends into — not replaces — the prior."""
    svc = make_service(cost_seed={"reach": 2.0, "dist": 0.25})
    assert svc._est_cost("reach") == 2.0 and svc._est_cost("dist") == 0.25
    assert svc._est_cost("ppr") == 0.0      # unseeded kinds stay unknown
    # a seeded cost shorter than the deadline's slack defers the flush; one
    # longer forces it on admission (the test_negative_slack rule, but from
    # the seed rather than a measured batch)
    clk = FakeClock()
    svc2 = make_service(clock=clk, cost_seed={"reach": 2.0})
    t = svc2.submit(Reachability(0, 1), deadline=1.5)
    assert t in svc2._results               # served the moment it was admitted
    svc3 = make_service(clock=clk, cost_seed={"reach": 2.0})
    svc3.submit(Reachability(0, 1), deadline=10.0)
    assert svc3.stats.batches == 0          # slack remains: batch-fill wait
    # EWMA update blends the measurement with the seed rather than replacing
    # it: under the fake clock a batch measures 0 s, so exactly (1-a)*seed
    svc3.flush()
    a = GraphService.COST_EWMA_ALPHA
    assert svc3._cost_ewma["reach"] == pytest.approx((1 - a) * 2.0)


def test_cost_seed_auto_reads_newest_bench_doc(tmp_path, monkeypatch):
    """cost_seed='auto' resolves through load_cost_priors: the newest
    BENCH_pr<N>.json wins, the local section prices a batch as budget/qps,
    and a missing/unusable doc degrades to the unseeded behavior."""
    import json
    from repro.core.service import load_cost_priors
    (tmp_path / "BENCH_pr6.json").write_text(json.dumps(
        {"service": {"budgets": {"4": {"qps": 1.0}}}}))
    (tmp_path / "BENCH_pr7.json").write_text(json.dumps(
        {"service": {"budgets": {"4": {"qps": 100.0}}},
         "service_distributed": {"budgets": {
             "4": {"latency_p50_ms": 500.0}}}}))
    pri = load_cost_priors(budget=4, bench_dir=str(tmp_path))
    assert pri["reach"] == pytest.approx(4 / 100.0)   # pr7, not pr6
    pri_d = load_cost_priors(distributed=True, budget=4,
                             bench_dir=str(tmp_path))
    assert pri_d["dist"] == pytest.approx(0.5)
    assert load_cost_priors(budget=999, bench_dir=str(tmp_path)) == {}
    assert load_cost_priors(bench_dir=str(tmp_path / "nowhere")) == {}
    monkeypatch.chdir(tmp_path)
    svc = make_service(cost_seed="auto")
    assert svc._est_cost("reach") == pytest.approx(4 / 100.0)


def test_batch_wait_and_result_hold_on_fake_clock():
    """batch_wait_s sums submit -> dispatch of the serving batch,
    result_hold_s sums answers-stored -> flush return; the fake clock steps
    5 s after each batch, so a two-kind flush gives exact sums.  A cache hit
    runs no batch and adds nothing to either."""
    clk = FakeClock()
    svc = make_service(clock=clk)
    orig = svc._execute

    def execute(kind, batch, lanes):
        stored = orig(kind, batch, lanes)
        clk.t += 5.0
        return stored

    svc._execute = execute
    svc.submit(Reachability(3, 40))
    svc.submit(Reachability(5, 41))          # both due at t = 0
    clk.t = 1.0
    svc.submit(Distance(0, 9))
    clk.t = 10.0
    svc.flush()      # reach dispatched at 10, stored at 10; dist at 15, 15
    st = svc.stats.as_dict()
    assert st["batch_wait_s"] == pytest.approx(2 * 10.0 + (15.0 - 1.0))
    assert st["result_hold_s"] == pytest.approx(2 * (20.0 - 10.0)
                                                + (20.0 - 15.0))
    svc.submit(Reachability(3, 40))          # served from the cache
    svc.flush()
    assert svc.stats.cache_hits == 1
    assert svc.stats.batch_wait_s == st["batch_wait_s"]
    assert svc.stats.result_hold_s == st["result_hold_s"]


def test_vertex_slots_compile_nothing_per_batch_size_on_8_devices():
    """Under an 8-device CPU mesh, batches of different query counts read
    their targets' (owner, local) slots on the host: after the first batch,
    neither a 3-query nor a 5-query batch compiles anything."""
    import os
    import subprocess
    import sys
    code = """
import jax, numpy as np
from repro.core import Reachability, GraphService, rmat
from repro.launch.mesh import make_cores_mesh
compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, secs, **_: compiles.append(event)
    if event == "/jax/core/compile/backend_compile_duration" else None)
g = rmat(6, 8, seed=3)
svc = GraphService(g, batch_budget=8, mesh=make_cores_mesh(8))
lv = np.asarray(svc.query(Reachability(0, 5)))
n0 = len(compiles)
for k in (3, 5):
    ts = [svc.submit(Reachability(i, (7 * i + 1) % 64)) for i in range(k)]
    svc.flush()
    [svc.result(t) for t in ts]
print("NEW_COMPILES", len(compiles) - n0)
"""
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "NEW_COMPILES 0" in proc.stdout, proc.stdout[-2000:]


def test_deadline_validation():
    svc = make_service()
    with pytest.raises(ValueError, match="deadline"):
        svc.submit(Reachability(0, 1), deadline=-1.0)


def test_deadline_full_check_mirrors_sample_packing():
    """Fanout slots must replay _collect's greedy packing, not a plain sum:
    one fanout-3 query in a budget-4 batch leaves room, so no auto-flush;
    a second fanout-3 cannot join that batch, so the head batch is as full
    as it can get and the flush fires."""
    clk = FakeClock()
    svc = make_service(batch_budget=4, clock=clk)
    svc.submit(NeighborSample(0, fanout=3), deadline=100.0)
    assert svc.stats.batches == 0
    svc.submit(NeighborSample(1, fanout=3), deadline=100.0)
    assert svc.stats.batches >= 1


def test_deadline_full_check_ignores_cache_hits():
    """Queries that will be served from the cache occupy no lane, so they
    must not count toward the batch-full admission trigger."""
    clk = FakeClock()
    svc = make_service(batch_budget=2, clock=clk)
    svc.query(Reachability(0, 1))                    # now cached
    batches = svc.stats.batches
    svc.submit(Reachability(0, 1), deadline=100.0)   # pure cache hit
    svc.submit(Reachability(1, 2), deadline=100.0)   # one real lane of two
    assert svc.stats.batches == batches              # not full: no auto-flush


# ---------------------------------------------------------------------------
# cache + epochs
# ---------------------------------------------------------------------------

def test_cache_hit_skips_execution_and_counts():
    svc = make_service()
    q = Reachability(1, 8)
    first = svc.query(q)
    batches_before = svc.stats.batches
    again = svc.query(q)
    assert again == first
    assert svc.stats.cache_hits == 1
    assert svc.stats.batches == batches_before  # no new engine pass
    assert svc.stats.hit_rate > 0


def test_cache_invalidated_across_epochs():
    g2 = uniform_random_graph(G.n_rows, 3, seed=1)
    svc = make_service()
    q = Distance(0, 9)
    r1 = svc.query(q)
    epoch = svc.update_graph(g2)
    assert epoch == 1
    r2 = svc.query(q)
    ref2 = float(np.asarray(sssp(g2, 0, delta=svc.delta))[9])
    assert r2 == ref2
    assert svc.stats.cache_hits == 0  # epoch bump means a true recompute
    # the old graph's answer is not served, even if it differed
    if r1 != r2:
        assert svc.query(q) == r2  # and the *new* answer now caches
        assert svc.stats.cache_hits == 1


def test_cached_sample_is_stable_until_epoch_moves():
    svc = make_service()
    q = NeighborSample(2, fanout=3, seed=5)
    s1 = svc.query(q)
    s2 = svc.query(q)                  # LRU hit
    np.testing.assert_array_equal(s1, s2)
    svc._cache.clear()                 # simulate eviction
    s3 = svc.query(q)                  # recomputed draw is keyed identically
    np.testing.assert_array_equal(s1, s3)


def test_lru_evicts_oldest():
    svc = make_service(batch_budget=1, cache_capacity=2)
    svc.query(Reachability(0, 1))
    svc.query(Reachability(1, 2))
    svc.query(Reachability(2, 3))      # evicts (0, 1)
    hits_before = svc.stats.cache_hits
    svc.query(Reachability(1, 2))      # still cached
    assert svc.stats.cache_hits == hits_before + 1
    svc.query(Reachability(0, 1))      # was evicted -> recompute
    assert svc.stats.cache_hits == hits_before + 1


def test_zero_capacity_disables_cache():
    svc = make_service(cache_capacity=0)
    q = Reachability(0, 3)
    svc.query(q)
    svc.query(q)
    assert svc.stats.cache_hits == 0


# ---------------------------------------------------------------------------
# stats ledger
# ---------------------------------------------------------------------------

def test_stats_ledger_accumulates_and_resets():
    svc = make_service()
    svc.query(Reachability(0, 1))
    svc.query(Distance(0, 1))
    st = svc.stats
    assert st.queries == 2 and st.batches == 2
    assert st.route_bytes > 0 and st.route_bytes_per_query > 0
    assert st.busy_s > 0 and st.qps > 0
    d = st.as_dict()
    assert set(d) >= {"qps", "occupancy", "hit_rate", "route_bytes_per_query"}
    svc.reset_stats()
    assert svc.stats.queries == 0 and svc.stats.route_bytes == 0


# ---------------------------------------------------------------------------
# streaming updates: partition-scoped invalidation (DESIGN.md §16)
# ---------------------------------------------------------------------------

def _two_blob_graph(n=256, seed=21):
    """Two disconnected 128-vertex blobs: queries inside blob B (vertices
    128..255, partitions 4..7 under the default 8-partition block rule)
    can never touch blob A's partitions."""
    from repro.core import CSR
    half = n // 2
    a = uniform_random_graph(half, 3, seed=seed)
    b = uniform_random_graph(half, 3, seed=seed + 1)

    def coo(g, off):
        indptr = np.asarray(g.indptr)
        rows = np.repeat(np.arange(half), np.diff(indptr)) + off
        return rows, np.asarray(g.indices) + off, np.asarray(g.values)

    ra, ca, va = coo(a, 0)
    rb, cb, vb = coo(b, half)
    return CSR.from_coo(np.concatenate([ra, rb]), np.concatenate([ca, cb]),
                        np.concatenate([va, vb]), n, n)


def test_apply_updates_keeps_untouched_partition_entries():
    g = _two_blob_graph()
    svc = GraphService(g, batch_budget=4, cache_capacity=64)
    qa = [Reachability(1, 40), Distance(2, 50)]            # blob A
    qb = [Reachability(130, 170), Distance(140, 200),      # blob B
          Reachability(150, 255), Distance(160, 129)]
    for q in qa + qb:
        svc.query(q)
    n_cached = len(svc._cache)
    assert n_cached == len(qa) + len(qb)
    # insert an edge confined to blob A's first partition (vertices 0..31)
    rep = svc.apply_updates(inserts=(np.array([3]), np.array([4]),
                                     np.array([1e-4], np.float32)))
    assert svc.epoch == 1
    assert sorted(rep.touched_partitions.tolist()) == [0]
    # blob B entries survive (>= 50% of the cache), blob A entries are gone
    assert len(svc._cache) >= n_cached // 2
    hits_before = svc.stats.cache_hits
    batches_before = svc.stats.batches
    for q in qb:
        svc.query(q)
    assert svc.stats.cache_hits == hits_before + len(qb)
    assert svc.stats.batches == batches_before     # served from cache
    # blob A entries recompute against the updated graph
    svc.query(qa[0])
    assert svc.stats.batches == batches_before + 1
    lv = np.asarray(bfs(svc.csr, 1))
    assert svc.query(qa[0]) == bool(lv[40] >= 0)


def test_apply_updates_correctness_and_ledger():
    svc = make_service()
    d_before = svc.query(Distance(5, 60))
    rb_before = svc.stats.route_bytes
    # a tiny-weight shortcut 5 -> 60 must change the served distance
    rep = svc.apply_updates(inserts=(np.array([5]), np.array([60]),
                                     np.array([1e-4], np.float32)))
    assert rep.monotone_safe and svc.epoch == 1
    assert svc.stats.updates == 1 and svc.stats.update_edges >= 1
    assert svc.stats.route_bytes > rb_before       # ingest reshard is priced
    d_after = svc.query(Distance(5, 60))
    ref = float(np.asarray(sssp(svc.csr, 5, delta=svc.delta))[60])
    assert d_after == ref and d_after <= d_before


def test_update_graph_is_deprecated_shim():
    svc = make_service()
    svc.query(Reachability(0, 5))
    g2 = uniform_random_graph(G.n_rows, 3, seed=9)
    with pytest.warns(DeprecationWarning):
        epoch = svc.update_graph(g2)
    assert epoch == 1 and svc.epoch == 1
    assert len(svc._cache) == 0        # whole-graph swap stamps everything
