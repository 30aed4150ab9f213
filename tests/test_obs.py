"""Unified telemetry suite (DESIGN.md §17).

Four contracts, each pinned here:

* **bit transparency** — the per-level engine trace is a side buffer: every
  traced run replays the committed golden grid (``tests/golden/core_grid.npz``)
  bit-identically, and trace on/off results agree on the local placement and
  the async placement at every staleness bound;
* **span structure** — the service's ``svc.*`` spans reach a running
  ``jax.profiler`` trace with their batch args whether or not an
  ``Observability`` is attached; an instrumented ``GraphService`` exports a
  Chrome ``trace_event`` JSON that is structurally valid (pid/tid/ts/dur/
  name on every event, per-tid nesting without partial overlap) and whose
  per-batch phase spans cover ≥90% of the ``svc.flush`` span;
* **push counters and scopes** — ``push_edges`` is the NumPy sum of the
  frontier's out-degrees over push levels, the untraced loop carries what
  it always carried, and the compiled loop names its phases;
* **degradation counters** — the ROADMAP guardrail: push-capacity fallback,
  cache invalidation and compaction are observable as registry counters,
  with a test pinning each firing (the streaming deletion fallback fires in
  tests/test_streaming.py's mixed-stream replay);
* **sketch accuracy** — the log-bucketed latency histogram's p50/p95 land
  within one bucket width of the exact percentiles (the fixed example here;
  the hypothesis property lives in tests/test_property.py).
"""
import glob
import json
import math
import os

import jax
import numpy as np
import pytest

from repro.core import dgas, rmat
from repro.core.algorithms import (bfs, msbfs, msbfs_distributed, sssp,
                                   sssp_batched)
from repro.core.algorithms.distgraph import shard_graph
from repro.core.graph import CSR, GraphHandle
from repro.core.service import (Distance, GraphService, NeighborSample,
                                PPRTopK, Reachability)
from repro.launch.mesh import make_cores_mesh
from repro.obs import (Histogram, LevelTrace, MetricsRegistry, Observability,
                       SpanRecorder, build_chrome_trace, decode_level_trace,
                       format_summary, get_registry, span, summarize,
                       validate_chrome_trace)
from repro.obs.__main__ import main as obs_cli

GOLD = np.load(os.path.join(os.path.dirname(__file__), "golden",
                            "core_grid.npz"))
G = rmat(7, 8, seed=11)
DELTA = float(GOLD["meta_delta_g"])
SOURCES = np.array([0, 3, 17, 64, 0], dtype=np.int32)
MODES = ("push", "pull", "auto")
INTERVALS = (1, 2, 8)

_MESH1 = make_cores_mesh(1)
_GSH1, _ATT1 = shard_graph(G, 1, row_att=dgas.block_rule(G.n_rows, 1))


# ---------------------------------------------------------------------------
# metrics: histogram sketch accuracy (fixed example), counters, registry
# ---------------------------------------------------------------------------

def test_histogram_percentiles_within_one_bucket():
    """The deterministic twin of the hypothesis property: the sketch's
    nearest-rank percentile is the owning bucket's upper edge, so it may
    exceed the exact percentile by at most one growth factor."""
    h = Histogram("lat")
    rng = np.random.default_rng(7)
    xs = np.concatenate([rng.uniform(1e-4, 5e-3, 300),
                         rng.uniform(0.05, 2.0, 60), [40.0, 120.0]])
    for x in xs:
        h.observe(float(x))
    for pct in (50.0, 95.0, 99.0):
        exact_lo = float(np.percentile(xs, pct, method="lower"))
        exact_hi = float(np.percentile(xs, pct, method="higher"))
        got = h.percentile(pct)
        assert exact_lo <= got <= exact_hi * h.growth, (pct, exact_lo, got)
    assert h.snapshot()["count"] == len(xs)
    assert h.mean == pytest.approx(float(xs.mean()), rel=1e-6)


def test_histogram_edge_cases():
    h = Histogram("x")
    assert h.percentile(50) == 0.0          # empty -> 0.0, not NaN
    h.observe(float("nan"))                 # skipped, not a bucket
    assert h.snapshot()["count"] == 0
    h.observe(0.0)                          # clamps into the lowest bucket
    h.observe(1e9)                          # clamps into the highest bucket
    assert h.snapshot()["count"] == 2
    assert h.percentile(0) <= h.percentile(100)


def test_registry_counters_and_gauges():
    reg = MetricsRegistry()
    reg.counter("a").inc()
    reg.counter("a").inc(4)
    assert reg.counter("a").value == 5
    with pytest.raises(ValueError):
        reg.counter("a").inc(-1)            # counters are monotone
    reg.gauge("g").set(2.5)
    assert reg.gauge("g").value == 2.5
    snap = reg.snapshot()
    assert snap["a"] == 5 and snap["g"] == 2.5
    reg.reset()
    assert reg.counter("a").value == 0
    assert get_registry() is get_registry()  # the process-wide singleton


# ---------------------------------------------------------------------------
# spans: nesting, retroactive clip, export structure
# ---------------------------------------------------------------------------

def test_span_recorder_nests_and_clips():
    """Spans of the one span path nest in the recorder; a clock that steps
    back clips a span's duration to zero instead of going negative."""
    t = [0.0]
    clk = lambda: t[0]
    rec = SpanRecorder(clock=clk)
    with span("outer", rec, tid=1) as args:
        t[0] = 1.0
        with span("inner", rec, tid=1):
            t[0] = 2.0
        args["route_bytes"] = 64
        t[0] = 3.0
    with span("back", rec, tid=1):
        t[0] = 2.5
    assert rec.spans()[-1].dur == 0.0
    doc = build_chrome_trace(rec.spans())
    assert validate_chrome_trace(doc) == []
    names = [e["name"] for e in doc["traceEvents"]]
    assert names == ["outer", "inner", "back"]  # sorted by (tid, ts, -dur)
    outer = doc["traceEvents"][0]
    assert outer["args"]["route_bytes"] == 64   # args augmentable in-block
    with span("alone"):                         # no recorder: profiler only
        pass
    assert len(rec) == 3


def test_validator_rejects_partial_overlap_and_missing_fields():
    bad = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 0, "tid": 1, "ts": 0.0, "dur": 10.0},
        {"ph": "X", "name": "b", "pid": 0, "tid": 1, "ts": 5.0, "dur": 10.0},
        {"ph": "X", "name": "c", "pid": 0, "tid": 2, "ts": 0.0},  # no dur
    ]}
    errs = validate_chrome_trace(bad)
    assert any("partially overlaps" in e for e in errs)
    assert any("missing 'dur'" in e for e in errs)


# ---------------------------------------------------------------------------
# engine tracing: bit transparency against the golden grid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_msbfs_traced_replays_golden(mode):
    lv, st = msbfs(G, SOURCES, mode=mode, return_stats=True, trace=True)
    np.testing.assert_array_equal(np.asarray(lv), GOLD[f"bfs/packed/{mode}"])
    recs = decode_level_trace(st)
    assert len(recs) == int(st["pushes"] + st["pulls"])
    assert sum(r.direction == "push" for r in recs) == int(st["pushes"])
    assert sum(r.direction == "pull" for r in recs) == int(st["pulls"])
    assert all(r.frontier > 0 for r in recs)   # a level with no work is done


@pytest.mark.parametrize("mode", MODES)
def test_sssp_traced_replays_golden(mode):
    d, st = sssp_batched(G, SOURCES, delta=DELTA, mode=mode,
                         return_stats=True, trace=True)
    np.testing.assert_array_equal(np.asarray(d), GOLD[f"sssp/valued/{mode}"])
    assert len(decode_level_trace(st)) == int(st["pushes"] + st["pulls"])


def test_trace_on_off_identity_sync_and_async():
    ref = np.asarray(msbfs_distributed(_GSH1, _ATT1, SOURCES, _MESH1))
    for placement, ks in (("sync", (None,)), ("async", INTERVALS)):
        for k in ks:
            lv, st = msbfs_distributed(
                _GSH1, _ATT1, SOURCES, _MESH1, placement=placement,
                sync_interval=k, return_stats=True, trace=True)
            np.testing.assert_array_equal(np.asarray(lv), ref)
            recs = decode_level_trace(st)
            assert recs, (placement, k)
            if placement == "async":
                # each row is one global check; the outbox flush fired there
                assert all(r.flush and r.direction == "flush" for r in recs)
            else:
                assert not any(r.flush for r in recs)


def test_trace_len_truncates_by_dropping():
    full = decode_level_trace(
        msbfs(G, SOURCES, return_stats=True, trace=True)[1])
    assert len(full) >= 3
    short = decode_level_trace(
        msbfs(G, SOURCES, return_stats=True, trace=True, trace_len=2)[1])
    # rows past trace_len drop on device — never clamp-overwrite the last row
    assert [r.as_dict() for r in short] == [r.as_dict() for r in full[:2]]


def test_trace_argument_validation():
    with pytest.raises(ValueError, match="return_stats"):
        msbfs(G, SOURCES, trace=True)
    with pytest.raises(ValueError, match="trace"):
        msbfs(G, SOURCES, return_stats=True, trace_len=4)
    with pytest.raises(KeyError):
        decode_level_trace(msbfs(G, SOURCES, return_stats=True)[1])


# ---------------------------------------------------------------------------
# engine push counters and named scopes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ("auto", "push"))
def test_push_edges_match_numpy_frontier_degrees(mode):
    """push_edges is Σ out-degree over each push level's frontier (BFS's
    frontier entering level L is the set of vertices at level L) and
    push_slots the edge budget E each push level sized (nnz under 'push',
    the power of two at or above nnz/14 under 'auto'); the levels are
    bit-identical with the trace on and off."""
    lv, st = bfs(G, 3, mode=mode, return_stats=True, trace=True)
    lv = np.asarray(lv)
    np.testing.assert_array_equal(lv, np.asarray(bfs(G, 3, mode=mode)))
    deg = np.diff(np.asarray(G.indptr))
    push = [r.level for r in decode_level_trace(st) if r.direction == "push"]
    assert push
    assert float(st["push_edges"]) == sum(int(deg[lv == lvl].sum())
                                          for lvl in push)
    E = G.nnz if mode == "push" else min(G.nnz, 1 << (-(-G.nnz // 14) - 1)
                                         .bit_length())
    assert float(st["push_slots"]) == len(push) * E


def test_untraced_loop_carry_and_stats_keys_unchanged():
    """trace off: the stepping loop carries (level, frontier, it, alive,
    pushes, pulls, fallbacks) and the stats hold iters/pushes/pulls; the
    fallback count and push counters exist only with the trace on."""
    n = G.n_rows
    jaxpr = jax.make_jaxpr(lambda s: bfs(G, s, return_stats=True))(3).jaxpr
    loop, = [e for e in jaxpr.eqns if e.primitive.name == "while"]
    carry = [(str(v.aval.dtype), v.aval.shape) for v in loop.outvars]
    assert carry == [("int32", (n,))] * 2 + [("int32", ())] * 5
    assert set(bfs(G, 3, return_stats=True)[1]) == {"iters", "pushes",
                                                    "pulls"}
    assert set(bfs(G, 3, return_stats=True, trace=True)[1]) == {
        "iters", "pushes", "pulls", "fallbacks", "trace", "push_edges",
        "push_slots"}


@pytest.fixture(scope="module")
def sssp_hlo():
    """Compiled HLO text of a small SSSP.  The persistent compile cache keys
    programs without their metadata, so it is off here: a cached executable
    would carry another tree's scopes."""
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return jax.jit(lambda g, s: sssp(g, s, delta=DELTA)).lower(
            G.for_jit(), 3).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)


def test_engine_phases_named_in_compiled_hlo(sssp_hlo):
    """The loop's phases carry their named scopes into the compiled
    program's op metadata, where a device trace can read them."""
    for scope in ("engine.msg", "engine.push", "engine.pull",
                  "engine.update"):
        assert f"/{scope}/" in sssp_hlo, scope


def _scope_share_module():
    import importlib.util
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "scope_share.py")
    spec = importlib.util.spec_from_file_location("scope_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_scope_share_matches_trace_ops_to_named_scopes(sssp_hlo):
    """benchmarks/scope_share.py gives each traced op's time to the scope of
    the instruction it ran, matched by name and references: every
    instruction of a real program is matched; across two programs a shared
    name with other operands is told apart, a key with two scopes is
    ambiguous, and an op no program holds is unmatched."""
    ss = _scope_share_module()
    events = [line.strip().removeprefix("ROOT ").split(", metadata=")[0]
              for line in sssp_hlo.splitlines()
              if line.strip().startswith(("%", "ROOT %")) and " = " in line]
    got = ss.scope_share([(e, 1.0) for e in events], [sssp_hlo])
    assert got["unmatched_s"] == 0.0 and got["busy_s"] == len(events)
    assert got["matched_s"] + got["ambiguous_s"] == len(events)
    assert got["scope_s"]["engine.push"] >= 1.0
    assert got["scope_s"]["engine.pull"] >= 1.0

    a = ("HloModule a\n"
         '  %fusion.4 = f32[8]{0} fusion(%p.1), kind=kLoop, calls=%fc.1, '
         'metadata={op_name="jit(f)/while/body/engine.push/scatter-add"}\n'
         '  ROOT %fusion.5 = f32[8]{0} fusion(%fusion.4), kind=kLoop, '
         'calls=%fc.2, metadata={op_name="jit(f)/engine.pull/'
         'kernel.spmv_dma/pallas_call"}\n')
    b = ("HloModule b\n"
         '  %fusion.4 = f32[8]{0} fusion(%p.1), kind=kLoop, calls=%fc.1, '
         'metadata={op_name="jit(g)/engine.pull/gather"}\n'
         '  %fusion.6 = f32[8]{0} fusion(%p.2), kind=kLoop, calls=%fc.9, '
         'metadata={op_name="jit(g)/engine.push/scatter-add"}\n')
    ops = [("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p.1), kind=kLoop, "
            "calls=%fc.1", 2.0),
           ("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %fusion.4), kind=kLoop, "
            "calls=%fc.2", 3.0),
           ("%fusion.6 = f32[8]{0} fusion(f32[8]{0} %p.2), kind=kLoop, "
            "calls=%fc.9", 5.0),
           ("%copy.1 = f32[8]{0} copy(f32[8]{0} %p.3)", 7.0)]
    got = ss.scope_share(ops, [a, b], busy_s=20.0)
    assert got["scope_s"] == {"kernel.": 3.0, "engine.push": 5.0}
    assert got["scope_share_pct"] == {"kernel.": 15.0, "engine.push": 25.0}
    assert (got["ambiguous_s"], got["unmatched_s"]) == (2.0, 7.0)


# ---------------------------------------------------------------------------
# service spans: structural validity + wall-time attribution
# ---------------------------------------------------------------------------

def _served_service(budget=8, **kw):
    obs = Observability(metrics=MetricsRegistry())
    svc = GraphService(rmat(8, 8, seed=3), batch_budget=budget,
                       obs=obs, **kw)
    n = svc.csr.n_rows
    tickets = [svc.submit(Reachability(source=i, target=(i + 13) % n))
               for i in range(6)]
    tickets += [svc.submit(Distance(source=0, target=9)),
                svc.submit(PPRTopK(source=2, k=4)),
                svc.submit(NeighborSample(vertex=5, fanout=3))]
    svc.flush()
    for t in tickets:
        svc.result(t)
    return svc, obs


_BATCH_SPANS = ("svc.collect", "svc.dispatch", "svc.sync", "svc.extract",
                "svc.charge")


@pytest.fixture
def compiles():
    """The backend compiles that happen while the test runs."""
    seen = []

    def listen(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(event)
    jax.monitoring.register_event_duration_secs_listener(listen)
    yield seen
    jax.monitoring.unregister_event_duration_listener(listen)


def test_service_spans_reach_the_profiler_without_obs(tmp_path, compiles):
    """With no Observability attached, a profiled flush holds the service's
    svc.* host events with their batch args, and recompiles nothing."""
    from jax.profiler import ProfileData
    svc = GraphService(rmat(7, 8, seed=3), batch_budget=4)
    svc.query(Reachability(0, 5))
    svc.query(Distance(0, 5))               # both runners compiled
    n0 = len(compiles)
    jax.profiler.start_trace(os.fspath(tmp_path))
    try:
        svc.submit(Reachability(1, 6))
        svc.submit(Reachability(2, 7))
        svc.submit(Distance(3, 9))
        svc.flush()
    finally:
        jax.profiler.stop_trace()
    assert len(compiles) == n0
    path, = glob.glob(os.path.join(os.fspath(tmp_path), "plugins",
                                   "profile", "*", "*.xplane.pb"))
    events = [(e.name, dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith("svc.")]
    assert {"svc.flush", *_BATCH_SPANS} <= {name for name, _ in events}
    for phase in _BATCH_SPANS:
        args = {st["kind"]: (st["lanes"], st["queries"], st["budget"])
                for name, st in events if name == phase}
        assert args == {"reach": (2, 2, 4), "dist": (1, 1, 4)}, phase


def test_service_chrome_trace_structurally_valid(tmp_path):
    svc, obs = _served_service()
    path = os.fspath(tmp_path / "trace.json")
    doc = obs.export_chrome_trace(path)
    assert validate_chrome_trace(doc) == []
    with open(path) as f:
        assert json.load(f) == doc
    names = {e["name"] for e in doc["traceEvents"]
             if e.get("cat") == "service"}
    assert {"svc.flush", *_BATCH_SPANS} <= names
    # traversal kinds ran traced: their levels ride their svc.sync spans
    levels = [lv for e in doc["traceEvents"] if e["name"] == "svc.sync"
              for lv in e["args"].get("levels", ())]
    assert levels and {lv["direction"] for lv in levels} <= {"push", "pull"}
    assert sum(summarize(doc)["levels"].values()) == len(levels)
    # the CLI renders and exits 0 on a structurally valid trace
    assert obs_cli(["summarize", path]) == 0
    assert obs_cli(["summarize", path, "--json"]) == 0


def test_service_span_attribution_covers_wall():
    """≥90% of the flush's wall clock lands in the per-batch phase spans:
    collect / dispatch / sync / extract / charge tile each svc.flush."""
    svc, obs = _served_service()
    spans = obs.spans.spans()
    flush_s = sum(sp.dur for sp in spans if sp.name == "svc.flush")
    phase_s = sum(sp.dur for sp in spans if sp.name in _BATCH_SPANS)
    assert phase_s >= 0.9 * flush_s
    summ = summarize(obs.build_trace())
    frac = sum(row["wall_frac"] for name, row in summ["phases"].items()
               if name in _BATCH_SPANS)
    assert frac >= 0.9
    assert "wall time" in format_summary(summ)


def test_service_trace_off_records_nothing():
    svc = GraphService(rmat(7, 8, seed=3), batch_budget=4)
    svc.query(Reachability(source=0, target=5))
    assert svc.obs is None                  # no spans, no level runs


def test_service_engine_span_carries_batch_args():
    """Every batch span carries kind / lanes / queries / budget; the
    charge span the batch's priced route bytes; decoded levels ride the
    traversal kinds' svc.sync spans only."""
    svc, obs = _served_service()
    batch = [sp for sp in obs.spans.spans() if sp.name in _BATCH_SPANS]
    assert {sp.args["kind"] for sp in batch} == {"reach", "dist", "ppr",
                                                 "sample"}
    for sp in batch:
        assert sp.args["budget"] == 8
        assert 1 <= sp.args["queries"] and 1 <= sp.args["lanes"] <= 8
    assert all(sp.args["route_bytes"] > 0 for sp in batch
               if sp.name == "svc.charge")
    assert {sp.args["kind"] for sp in batch if "levels" in sp.args} == \
        {"reach", "dist", "ppr"}            # sampling has no level loop


# ---------------------------------------------------------------------------
# degradation counters: each firing pinned (the ROADMAP guardrail)
# ---------------------------------------------------------------------------

def test_push_capacity_fallback_counter_fires():
    """A star graph overflows the compacted push capacity at the hub level:
    capacity = m * switch_frac * slack = m/8 < m active edges."""
    n = 64
    rows = np.zeros(n - 1, np.int64)
    cols = np.arange(1, n, dtype=np.int64)
    star = CSR.from_coo(rows, cols, None, n, n)
    reg = MetricsRegistry()
    svc = GraphService(star, batch_budget=4, mesh=_MESH1,
                       obs=Observability(metrics=reg))
    assert svc.query(Reachability(source=0, target=n - 1)) is True
    assert reg.counter("service.push_capacity_fallback").value >= 1


def test_cache_invalidation_counter_fires():
    reg = MetricsRegistry()
    svc = GraphService(rmat(7, 8, seed=3), batch_budget=4,
                       obs=Observability(metrics=reg))
    svc.query(Reachability(source=0, target=5))
    assert reg.counter("service.cache_invalidations").value == 0
    svc.apply_updates(inserts=(np.array([0]), np.array([5])))
    evicted = svc.stats.cache_evicted
    assert evicted >= 1
    assert reg.counter("service.cache_invalidations").value == evicted


def test_graph_compaction_counter_fires():
    """Delta-log overflow compaction increments the process-wide counter
    (graph.py has no per-service context — library events are global)."""
    h = GraphHandle.wrap(rmat(6, 8, seed=2), compact_threshold=0.001)
    before = get_registry().counter("graph.compactions").value
    h2, rep = h.apply((np.array([1, 2, 3]), np.array([4, 5, 6])), None)
    assert rep.compacted
    assert get_registry().counter("graph.compactions").value == before + 1
