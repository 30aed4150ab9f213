"""CPU checks of the benchmark's own pieces: the generator, the references,
the work counts, the trace reduction, and that every cell's files exist."""
from __future__ import annotations

import hashlib
import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import (check, generate, kronecker, reference,  # noqa: E402
                       trace, work)

TINY = {"name": "tiny", "generator": "graph500_kronecker", "scale": 8,
        "edge_factor": 16, "A": 0.57, "B": 0.19, "C": 0.19, "graph_seed": 7,
        "weights": "uniform_0_1_f32"}


@pytest.mark.parametrize("directed,nnz,digest", [
    (True, 2601,
     "3529f3113c790903073a12eec13a07b355cebd26c1232883ed86bdc40a865100"),
    (False, 4324,
     "ebe6e068a347bde815ab791f7b008457a6c731c0451e2d3d0c0b560ff1d465d3"),
])
def test_kronecker_scale8_digest(directed, nnz, digest):
    rows, cols, w = kronecker.build_edges(dict(TINY, directed=directed))
    got = hashlib.sha256(rows.tobytes() + cols.tobytes()
                         + w.tobytes()).hexdigest()
    assert (rows.size, got) == (nnz, digest)
    key = rows.astype(np.int64) * 256 + cols
    assert (rows != cols).all() and (np.diff(key) > 0).all()
    if not directed:
        # every edge goes both ways with one weight
        back = dict(zip(zip(cols.tolist(), rows.tolist()), w.tolist()))
        assert all(back[(r, c)] == x for r, c, x in
                   zip(rows.tolist(), cols.tolist(), w.tolist()))


@pytest.fixture(scope="module")
def tiny_graph():
    sys.path.insert(0, os.path.join(ROOT, "src"))
    rows, cols, w = kronecker.build_edges(dict(TINY, directed=True))
    return 256, rows, cols, w


def test_references_match_engine(tiny_graph):
    import jax
    import jax.numpy as jnp
    from repro.core.algorithms import bfs, sssp
    from repro.core.algorithms.sssp import auto_delta
    from repro.core.graph import CSR
    n, rows, cols, w = tiny_graph
    g = reference.Graph(n, rows, cols, w)
    csr = CSR.from_coo(rows, cols, w, n, n)
    gj = csr.for_jit()
    f_bfs = jax.jit(lambda g, s: bfs(g, s))
    delta = auto_delta(csr)
    f_sssp = jax.jit(lambda g, s: sssp(g, s, delta=delta))
    for s in np.flatnonzero(g.deg > 0)[:4]:
        s = int(s)
        np.testing.assert_array_equal(
            np.asarray(f_bfs(gj, jnp.int32(s))), reference.bfs_levels(g, s))
        np.testing.assert_array_equal(
            np.asarray(f_sssp(gj, jnp.int32(s))), reference.sssp_f32(g, s))


def test_references_match_service(tiny_graph):
    from repro.core import GraphService
    from repro.core.graph import CSR
    from chipbench import system
    n, rows, cols, w = tiny_graph
    g = reference.Graph(n, rows, cols, w)
    svc = GraphService(CSR.from_coo(rows, cols, w, n, n), batch_budget=8)
    rng = np.random.default_rng(3)
    qs = generate.served_queries(
        {"kinds": {"reach": 1, "dist": 1, "ppr": 1}, "ppr_k": 5}, g.deg, 24,
        rng)
    tickets = [svc.submit(system.to_query(q)) for q in qs]
    svc.flush()
    got = check.served_numbers(g, [(q, svc.result(t))
                                   for q, t in zip(qs, tickets)],
                               {"damping": svc.damping,
                                "ppr_iters": svc.ppr_iters})
    assert got["reach_wrong"] == 0 and got["dist_wrong"] == 0
    assert got["ppr_gap"] < 1e-5


def test_ppr_columns_are_independent(tiny_graph):
    n, rows, cols, w = tiny_graph
    g = reference.Graph(n, rows, cols, w)
    srcs = [int(s) for s in np.flatnonzero(g.deg > 0)[:3]]
    both = reference.ppr(g, srcs, 0.85, 20)
    assert both.shape == (n, 3)
    np.testing.assert_allclose(both.sum(0), 1.0, rtol=1e-12)
    for i, s in enumerate(srcs):
        np.testing.assert_allclose(both[:, i],
                                   reference.ppr(g, [s], 0.85, 20)[:, 0],
                                   rtol=1e-12, atol=1e-15)


def test_work_counts_hand_checked():
    # undirected path 0-1-2 plus the edge 3-4: from 0 the search reaches
    # 0, 1, 2 (degrees 1, 2, 1) and not 3, 4
    deg = np.array([1, 2, 1, 1, 1])
    reached = np.array([True, True, True, False, False])
    assert work.traversed_edges(deg, reached) == 2
    assert work.least_bytes(deg, reached, weighted=False) == 4 * 4 + 12 * 3
    assert work.least_bytes(deg, reached, weighted=True) == 8 * 4 + 12 * 3


def test_kind_sequence_exact_shares():
    rng = np.random.default_rng(0)
    seq = generate.kind_sequence({"reach": .5, "dist": .3, "ppr": .2}, 101,
                                 rng)
    assert [seq.count(k) for k in ("reach", "dist", "ppr")] == [51, 30, 20]


_XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 500000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "all-reduce.3" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "while.7" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } } }
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 5000000 }
    events { metadata_id: 3 offset_ps: 2500000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.traced" } }
  event_metadata { key: 2 value { id: 2 name: "bench.flush" } }
  event_metadata { key: 3 value { id: 3 name: "bench.result" } }
  event_metadata { key: 4 value { id: 4 name: "other" } } }
"""


def test_trace_reduction_on_a_synthetic_trace():
    from jax.profiler import ProfileData
    device, spans = trace.events_of(ProfileData.from_text_proto(_XSPACE))
    assert sorted(device) == [0, 1] and len(spans) == 3
    r = trace.reduce_trace(device, spans)
    # window 10 us; chip 0 busy [0,2) and [5,6.5), chip 1 busy [1,5) in a
    # while.7 that holds fusion.2 for [1.5,2.5)
    assert r["window_s"] == pytest.approx(10e-6)
    assert r["busy_s"] == pytest.approx((3.5e-6 + 4e-6) / 2)
    assert r["collective_s"] == pytest.approx(1e-6 / 2)
    assert dict(r["top_ops"]) == {"fusion.1": pytest.approx(1.25e-6),
                                  "while.7": pytest.approx(1.5e-6),
                                  "all-reduce.3": pytest.approx(0.5e-6),
                                  "fusion.2": pytest.approx(0.5e-6)}
    # chip 0's gaps: [2,5) in bench.result at 3.5, [6.5,10) outside spans
    assert r["idle_gaps"] == [["outside harness spans", pytest.approx(3.5e-6)],
                              ["bench.result", pytest.approx(3e-6)]]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_names_files_that_exist():
    bench = _bench()
    here = os.path.join(ROOT, "chipbench")
    for cell in bench["workloads"]:
        name = cell["name"]
        cfg = os.path.join(here, "configs", f"{cell['config']}.json")
        with open(os.path.join(here, "traffic",
                               f"{cell['traffic']}.json")) as f:
            traffic = json.load(f)
        with open(cfg) as f:
            assert json.load(f)["chips"] == cell["chips"]
        assert hasattr(importlib.import_module(
            f"chipbench.drivers.{traffic['driver']}"), "setup")
        e2e = [m["name"] for m in bench["end_to_end"]
               if name in m.get("workloads", [name])]
        assert "setup_s" in e2e and len(e2e) >= 2, name
        layer = [m["name"] for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
        assert layer, name
        for m in layer:
            assert os.path.isfile(os.path.join(here, "metrics", f"{m}.py"))


def test_run_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", _bench()["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
