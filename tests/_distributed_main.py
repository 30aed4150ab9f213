"""Multi-device (8 fake CPU devices) validation of the DGAS offload engines
and distributed algorithms. Run via tests/test_distributed.py subprocess."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from functools import partial
from jax.sharding import AxisType, PartitionSpec as P
from repro.compat import shard_map
from repro.core import dgas, offload, rmat
from repro.core.algorithms import (spmv, pagerank, bfs, random_walks)
from repro.core.algorithms.spmv import spmv_distributed
from repro.core.algorithms.pagerank import pagerank_distributed
from repro.core.algorithms.bfs import bfs_distributed
from repro.core.algorithms.random_walks import random_walks_distributed
from repro.core.algorithms.distgraph import (shard_graph, shard_vertex_array,
                                             unshard_vertex_array)
from repro.launch.mesh import make_cores_mesh

S = 8
mesh = make_cores_mesh(S)
spec = P("cores")
rng = np.random.default_rng(0)
failures = []


def check(name, ok):
    print(("PASS" if ok else "FAIL"), name, flush=True)
    if not ok:
        failures.append(name)


# --- dgas_gather / remote_scatter_add vs local semantics --------------------
n = 128
for kind, att in [("interleave", dgas.interleave_rule(n, S)),
                  ("block", dgas.block_rule(n, S))]:
    table = rng.standard_normal(n).astype(np.float32)
    sharded = shard_vertex_array(table, att)
    gidx = rng.integers(0, n, (S, 16)).astype(np.int32)

    fn = shard_map(partial(lambda sh, gi, att=att: offload.dgas_gather(
        sh[0], gi[0], att, "cores", capacity=16)[None], ),
        mesh=mesh, in_specs=(spec, spec), out_specs=spec)
    out = np.asarray(fn(sharded, jnp.asarray(gidx)))
    check(f"dgas_gather/{kind}", np.allclose(out, table[gidx], atol=1e-6))

    dest0 = np.zeros(n, np.float32)
    idx = rng.integers(0, n, (S, 16)).astype(np.int32)
    vals = rng.standard_normal((S, 16)).astype(np.float32)
    fn = shard_map(partial(lambda sh, gi, vv, att=att: offload.remote_scatter_add(
        sh[0], gi[0], vv[0], att, "cores", capacity=16 * S)[None], ),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    out = np.asarray(fn(shard_vertex_array(dest0, att), jnp.asarray(idx),
                        jnp.asarray(vals)))
    expect = np.zeros(n, np.float32)
    np.add.at(expect, idx.reshape(-1), vals.reshape(-1))
    got = np.asarray(unshard_vertex_array(jnp.asarray(out), att))
    check(f"remote_scatter_add/{kind}", np.allclose(got, expect, atol=1e-4))

# --- all_gather_gather baseline equals dgas path ----------------------------
att = dgas.block_rule(n, S)
table = rng.standard_normal(n).astype(np.float32)
sharded = shard_vertex_array(table, att)
gidx = rng.integers(0, n, (S, 16)).astype(np.int32)
fn = shard_map(lambda sh, gi: offload.all_gather_gather(
    sh[0], gi[0], att, "cores")[None],
    mesh=mesh, in_specs=(spec, spec), out_specs=spec)
out = np.asarray(fn(sharded, jnp.asarray(gidx)))
check("all_gather_gather/block", np.allclose(out, table[gidx], atol=1e-6))

# --- queue engine balance ----------------------------------------------------
counts = np.array([13, 0, 7, 1, 0, 0, 25, 2], np.int32)
cap = 64
items = np.full((S, cap), -1, np.int32)
for s in range(S):
    items[s, :counts[s]] = rng.integers(0, 1000, counts[s])
fn = shard_map(lambda it, ct: (lambda q: (q.items[None], q.count[None, None]))(
    offload.queue_balance(offload.QueueState(it[0], ct[0, 0]), "cores")),
    mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec))
out_items, out_counts = fn(jnp.asarray(items), jnp.asarray(counts)[:, None])
out_counts = np.asarray(out_counts).reshape(-1)
total = counts.sum()
check("queue_balance/even", out_counts.max() - out_counts.min() <= 1
      and out_counts.sum() == total)
orig = sorted(items[items >= 0].tolist())
got = sorted(np.asarray(out_items)[np.asarray(out_items) >= 0].tolist())
check("queue_balance/preserves", orig == got)

# --- prefix scan -------------------------------------------------------------
x = rng.integers(0, 10, S).astype(np.int32)
fn = shard_map(lambda v: offload.prefix_scan(v[0, 0], "cores")[None, None],
               mesh=mesh, in_specs=(spec,), out_specs=spec)
out = np.asarray(fn(jnp.asarray(x)[:, None])).reshape(-1)
check("prefix_scan", np.array_equal(out, np.concatenate([[0], np.cumsum(x)[:-1]])))

# --- distributed algorithms vs local ----------------------------------------
g = rmat(8, 8, seed=1)
x = rng.random(g.n_cols).astype(np.float32)

gsh, row_att = shard_graph(g, S)
x_att = dgas.block_rule(g.n_cols, S)
x_sh = shard_vertex_array(x, x_att)
y_local = np.asarray(spmv(g, jnp.asarray(x)))
for mode in ("dgas", "allgather"):
    y = spmv_distributed(gsh, x_sh, x_att, row_att, mesh, axis="cores", mode=mode)
    got = np.asarray(unshard_vertex_array(y, row_att))
    check(f"spmv_distributed/{mode}", np.allclose(got, y_local, atol=1e-3))

pr_local = np.asarray(pagerank(g, iters=15))
gsh2, att2 = shard_graph(g, S, row_att=dgas.block_rule(g.n_rows, S))
pr = pagerank_distributed(gsh2, att2, mesh, axis="cores", iters=15)
got = np.asarray(unshard_vertex_array(pr, att2))
check("pagerank_distributed", np.allclose(got, pr_local, atol=1e-5))

lv_local = np.asarray(bfs(g, 0))
lv = bfs_distributed(gsh2, att2, 0, mesh, axis="cores")
got = np.asarray(unshard_vertex_array(lv, att2))
check("bfs_distributed", np.array_equal(got, lv_local))

# --- engine: direction-optimizing BFS + SSSP + CC on the same machinery -----
from repro.core import engine as eng
from repro.core.algorithms.sssp import sssp, sssp_distributed
from repro.core.algorithms.cc import (connected_components,
                                      connected_components_distributed,
                                      symmetrize)

g_rev = eng.reverse_graph(g, att2)
lv2 = bfs_distributed(gsh2, att2, 0, mesh, axis="cores", g_rev=g_rev,
                      mode="auto")
got = np.asarray(unshard_vertex_array(lv2, att2))
check("bfs_distributed/auto_direction", np.array_equal(got, lv_local))

d_local = np.asarray(sssp(g, 0, delta=0.5))
d = sssp_distributed(gsh2, att2, 0, mesh, axis="cores", delta=0.5)
got = np.asarray(unshard_vertex_array(d, att2))
check("sssp_distributed", np.allclose(got, d_local, atol=1e-5, equal_nan=True))

gsym = symmetrize(g)
gshs, atts = shard_graph(gsym, S, row_att=dgas.block_rule(gsym.n_rows, S))
lab_local = np.asarray(connected_components(gsym, symmetrize_input=False))
lab = connected_components_distributed(gshs, atts, mesh, axis="cores")
got = np.asarray(unshard_vertex_array(lab, atts))
check("cc_distributed", np.array_equal(got, lab_local))

# --- frontier-proportional compacted push routing ----------------------------
from repro.core.algorithms.bfs import bfs_program

owner0 = int(att2.owner(jnp.asarray(0)))
local0 = int(att2.local(jnp.asarray(0)))
st0 = {"level": jnp.full((S, att2.per_shard), -1, jnp.int32).at[owner0, local0].set(0)}
f0 = jnp.zeros((S, att2.per_shard), jnp.int32).at[owner0, local0].set(1)
for cap, name in [(16, "tiny_cap_fallback"),
                  (eng.frontier_edge_capacity(gsh2.edges_per_shard, 1 / 32),
                   "derived_cap"),
                  (0, "disabled")]:
    st = eng.run_distributed(gsh2, att2, mesh, bfs_program(), st0, f0,
                             axis="cores", max_iters=64, mode="push",
                             push_edge_capacity=cap)
    got = np.asarray(unshard_vertex_array(st["level"], att2))
    check(f"bfs_compact_push/{name}", np.array_equal(got, lv_local))

d2 = sssp_distributed(gsh2, att2, 0, mesh, axis="cores", delta=0.5)
got = np.asarray(unshard_vertex_array(d2, att2))
check("sssp_distributed/compact_default", np.allclose(got, d_local, atol=1e-5,
                                                      equal_nan=True))

# --- structured combine: distributed weighted label propagation --------------
from repro.core.algorithms.louvain import (label_propagation,
                                           label_propagation_distributed)

lpa_local = np.asarray(label_propagation(g, iters=5))
lpa_att = dgas.block_rule(g.n_rows, S)
lpa = label_propagation_distributed(g, mesh, axis="cores", iters=5)
got = np.asarray(unshard_vertex_array(lpa, lpa_att))
check("label_propagation_distributed", np.array_equal(got, lpa_local))

# --- engine runtime stats: compacted-push fallback counter -------------------
st_tiny, d_stats = eng.run_distributed(gsh2, att2, mesh, bfs_program(), st0, f0,
                                       axis="cores", max_iters=64, mode="push",
                                       push_edge_capacity=16, return_stats=True)
d_stats = {k: int(np.asarray(v)[0]) for k, v in d_stats.items()}
got = np.asarray(unshard_vertex_array(st_tiny["level"], att2))
check("run_distributed_stats/fallbacks",
      np.array_equal(got, lv_local) and d_stats["fallbacks"] > 0
      and d_stats["pushes"] == d_stats["iters"] and d_stats["pulls"] == 0)

# --- multi-level Louvain: modularity, contraction, full pipeline -------------
from repro.core import traffic
from repro.core.graph import contract
from repro.core.algorithms.louvain import (modularity, modularity_distributed,
                                           contract_distributed, multilevel,
                                           multilevel_distributed,
                                           partition_equal)

ml_att = dgas.block_rule(g.n_rows, S)
g_ml, _ = shard_graph(g, S, row_att=ml_att)
lab_rand = rng.integers(0, 40, g.n_rows).astype(np.int32)
q_loc = float(modularity(g, jnp.asarray(lab_rand)))
q_dist = float(np.asarray(modularity_distributed(
    g_ml, ml_att, mesh, shard_vertex_array(lab_rand, ml_att), axis="cores"))[0])
check("modularity_distributed", abs(q_loc - q_dist) < 1e-4)

ctr = traffic.RouteByteCounter(S, payload_bytes=traffic.CONTRACT_PAYLOAD_BYTES)
coarse_d, _, _, ren_d, routed = contract_distributed(
    g_ml, ml_att, jnp.asarray(lab_rand), counter=ctr)
coarse_l, ren_l = contract(g, lab_rand)
check("contract_distributed/renumber",
      np.array_equal(np.asarray(ren_d), np.asarray(ren_l)))
check("contract_distributed/weights",
      np.allclose(np.asarray(coarse_d.to_dense()),
                  np.asarray(coarse_l.to_dense()), atol=1e-3))
check("contract_distributed/route_bytes",
      routed > 0 and ctr.total_bytes == routed * traffic.CONTRACT_PAYLOAD_BYTES)


ml_local, ml_scores = multilevel(g)
ctr2 = traffic.RouteByteCounter(S, payload_bytes=traffic.CONTRACT_PAYLOAD_BYTES)
ml_dist, ml_scores_d = multilevel_distributed(g, mesh, axis="cores",
                                              counter=ctr2)
check("multilevel_distributed/partition", partition_equal(ml_local, ml_dist))
check("multilevel_distributed/scores",
      len(ml_scores) == len(ml_scores_d) and len(ml_scores_d) >= 1
      and all(abs(a - b) < 1e-3 for a, b in zip(ml_scores, ml_scores_d))
      and all(b > a for a, b in zip(ml_scores_d, ml_scores_d[1:])))
check("multilevel_distributed/contract_traffic",
      ctr2.levels == len(ml_scores_d) and ctr2.total_bytes > 0)

# queue-engine walks: walker count deliberately NOT divisible by S (the
# queue balancer owns the load spreading now, not a reshape)
walks = np.asarray(random_walks_distributed(g, jnp.arange(S * 4 + 3), 6,
                                            jax.random.PRNGKey(0), mesh,
                                            axis="cores"))
indptr, indices = np.asarray(g.indptr), np.asarray(g.indices)
ok = walks.shape == (S * 4 + 3, 7)
for w in walks:
    for a, b in zip(w[:-1], w[1:]):
        nbrs = indices[indptr[a]:indptr[a + 1]]
        if not ((b in nbrs) or (b == a and nbrs.size == 0)):
            ok = False
check("random_walks_distributed/queue_engine", ok)

# --- gradient compression ----------------------------------------------------
from repro.optim import compression
gr = {"a": rng.standard_normal((64,)).astype(np.float32) * 0.01}
gr_s = jnp.asarray(np.stack([gr["a"]] * S))  # same grad on each shard
fn = shard_map(lambda g_: compression.psum_bf16({"a": g_[0]}, "cores")["a"][None],
               mesh=mesh, in_specs=(spec,), out_specs=spec)
out = np.asarray(fn(gr_s))[0]
check("psum_bf16", np.allclose(out, gr["a"] * S, rtol=1e-2, atol=1e-3))

ef0 = jnp.zeros((S, 64), jnp.float32)
fn = shard_map(lambda g_, e_: (lambda o, ne: (o["a"][None], ne["a"][None]))(
    *compression.psum_int8_ef({"a": g_[0]}, {"a": e_[0]}, "cores")),
    mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec))
out, ef = fn(gr_s, ef0)
check("psum_int8_ef", np.allclose(np.asarray(out)[0], gr["a"] * S,
                                  rtol=0.05, atol=1e-3))

# --- hierarchical collectives on a 2-axis mesh -------------------------------
mesh2 = jax.make_mesh((2, 4), ("data", "model"),
                      axis_types=(AxisType.Auto,) * 2)
fn = shard_map(lambda v: offload.hierarchical_psum(v[0, 0], ["model", "data"])
               [None, None],
               mesh=mesh2, in_specs=(P("data", "model"),),
               out_specs=P("data", "model"))
vals = jnp.arange(8, dtype=jnp.float32).reshape(2, 4)
out = np.asarray(fn(vals))
check("hierarchical_psum", np.allclose(out, vals.sum()))

# --- GNN DGAS shard_map path == local path ----------------------------------
import dataclasses as _dc
from repro.models import gnn as _G
from repro.data.synthetic import gnn_batch as _gb
from repro.core.graph import uniform_random_graph as _urg
from repro.distributed.sharding import MeshRules as _MR

_mesh2 = jax.make_mesh((2, 4), ("data", "model"),
                       axis_types=(AxisType.Auto,) * 2)
_rules = _MR(mesh=_mesh2, batch=("data",), seq_sp=None, tp="model",
             fsdp=("data",), expert="model", flat=("data", "model"))
_g = _urg(64, 4, seed=9)   # 64 nodes % 8 == 0; edges padded below
for _arch, _kw in [("gin", dict(n_layers=2, d_hidden=16)),
                   ("gatedgcn", dict(n_layers=2, d_hidden=16)),
                   ("dimenet", dict(n_layers=2, d_hidden=16, triplet_chunk=64)),
                   ("equiformer_v2", dict(n_layers=1, d_hidden=8, l_max=2,
                                          m_max=2, n_heads=2, edge_chunk=32))]:
    _cfg = _G.GNNConfig(name=_arch, arch=_arch, d_feat=8, n_classes=3,
                        dgas_threshold=1,       # force the DGAS path
                        dgas_cap_factor=10**6,  # exact capacity (no drops)
                        **_kw)
    _cfg_local = _dc.replace(_cfg, dgas_threshold=10**12)
    _b = _gb(_arch, _g, 8, 3, l_max=2, seed=3)
    # pad edge arrays to a mesh multiple (input_specs does this in prod)
    _E = _b["src"].shape[0]
    _pad = -(-_E // 8) * 8 - _E
    for _k in ("src", "dst"):
        _b[_k] = np.concatenate([_b[_k], np.full(_pad, -1, np.int32)])
    if "wigner" in _b:
        _b["wigner"] = np.concatenate(
            [_b["wigner"], np.tile(np.eye(9, dtype=np.float32), (_pad, 1, 1))])
    if "triplet_kj" in _b:
        _T = _b["triplet_kj"].shape[0]
        _tp = -(-_T // 8) * 8 - _T
        _b["triplet_kj"] = np.concatenate([_b["triplet_kj"], np.full(_tp, -1, np.int32)])
        _b["triplet_ji"] = np.concatenate([_b["triplet_ji"], np.zeros(_tp, np.int32)])
        _b["angle"] = np.concatenate([_b["angle"], np.zeros(_tp, np.float32)])
    _bj = {k: jnp.asarray(v) for k, v in _b.items()}
    _p = _G.init_params(_cfg, jax.random.PRNGKey(0))
    with jax.sharding.use_mesh(_mesh2) if hasattr(jax.sharding, "use_mesh") else _mesh2:
        _l_dgas = float(jax.jit(lambda pp, bb: _G.loss_fn(_cfg, pp, bb, _rules)[0])(_p, _bj))
    _l_local = float(_G.loss_fn(_cfg_local, _p, _bj)[0])
    ok = abs(_l_dgas - _l_local) < 1e-3 * max(1.0, abs(_l_local))
    check(f"gnn_dgas_vs_local/{_arch}", ok)

# --- FM DGAS lookup == local lookup ------------------------------------------
from repro.models import recsys as _R
_cfgf = _R.FMConfig(name="fm-test", n_fields=4, embed_dim=4, rows_per_field=16,
                    use_dgas=True, dgas_cap_factor=10**6)
_pf = _R.init_params(_cfgf, jax.random.PRNGKey(0))
_ids = jnp.asarray(rng.integers(0, 64, (16, 4)).astype(np.int32))
_rules_f = _MR(mesh=_mesh2, batch=("data",), seq_sp=None, tp="model",
               fsdp=("data",), expert="model", flat=("data", "model"))
_s_dgas = np.asarray(jax.jit(lambda p, i: _R.fm_scores(_cfgf, p, i, _rules_f))(_pf, _ids))
_s_local = np.asarray(_R.fm_scores(_cfgf, _pf, _ids))
check("fm_dgas_vs_local", np.allclose(_s_dgas, _s_local, rtol=1e-4, atol=1e-4))
# gradient path (remote-atomic scatter-add transpose)
_lbl = jnp.asarray(rng.integers(0, 2, 16).astype(np.float32))
_g_d = jax.jit(jax.grad(lambda p: _R.loss_fn(_cfgf, p, {"ids": _ids, "labels": _lbl},
                                             _rules_f)[0]))(_pf)
_g_l = jax.grad(lambda p: _R.loss_fn(_cfgf, p, {"ids": _ids, "labels": _lbl})[0])(_pf)
check("fm_dgas_grad", all(np.allclose(np.asarray(a), np.asarray(b), atol=1e-4)
                          for a, b in zip(jax.tree.leaves(_g_d), jax.tree.leaves(_g_l))))

# --- batched multi-source traversal: partition identity ----------------------
from repro.core import engine as _eng, traffic as _traffic
from repro.core.algorithms import (msbfs_distributed, sssp_batched_distributed,
                                   sssp_distributed)
from repro.core.algorithms.sssp import sssp_batched as _sssp_batched
from repro.core.algorithms.bfs import msbfs as _msbfs
from repro.core.algorithms.distgraph import unshard_vertex_array as _unshard

_gq = rmat(7, 8, seed=3)
_att_q = dgas.block_rule(_gq.n_rows, S)
_gsh_q, _ = shard_graph(_gq, S, row_att=_att_q)
_srcs = np.array([0, 5, 33, 64, 100, 127], np.int32)

# msbfs lanes == per-source bfs_distributed, bit for bit (packed-word routing)
_lv_b = np.asarray(msbfs_distributed(_gsh_q, _att_q, _srcs, mesh))
_ok = all(np.array_equal(_lv_b[:, b, :],
                         np.asarray(bfs_distributed(_gsh_q, _att_q, int(s), mesh)))
          for b, s in enumerate(_srcs))
check("msbfs_distributed/partition_identity", _ok)

# distributed batched lanes == single-device batched lanes (unsharded)
_lv_l = np.asarray(_msbfs(_gq, _srcs))
_ok = all(np.array_equal(
    np.asarray(_unshard(jnp.asarray(_lv_b[:, b, :]), _att_q)), _lv_l[b])
    for b in range(len(_srcs)))
check("msbfs_distributed/matches_single_device", _ok)

# batched delta-stepping: remote atomic-min carries all lanes per exchange
_d_b = np.asarray(sssp_batched_distributed(_gsh_q, _att_q, _srcs, mesh,
                                           delta=1.0))
_ok = all(np.array_equal(_d_b[:, b, :],
                         np.asarray(sssp_distributed(_gsh_q, _att_q, int(s),
                                                     mesh, delta=1.0)))
          for b, s in enumerate(_srcs))
check("sssp_batched_distributed/partition_identity", _ok)

# batched remote_scatter_or == local segment_or semantics
_natt = 64
_att_or = dgas.interleave_rule(_natt, S)
_gidx = rng.integers(0, _natt, (S, 16)).astype(np.int32)
_words = rng.integers(0, 2**32, (S, 16, 2), dtype=np.uint64).astype(np.uint32)
_fn_or = shard_map(
    lambda gi, wo: offload.remote_scatter_or(
        _att_or.per_shard, gi[0], wo[0], _att_or, "cores", capacity=16 * S)[None],
    mesh=mesh, in_specs=(spec, spec), out_specs=spec)
_got = np.asarray(_fn_or(jnp.asarray(_gidx), jnp.asarray(_words)))
_expect = np.zeros((_natt, 2), np.uint32)
for _s in range(S):
    for _i in range(16):
        _expect[_gidx[_s, _i]] |= _words[_s, _i]
_got_global = np.zeros_like(_expect)
for _v in range(_natt):
    _got_global[_v] = _got[_att_or.owner(jnp.asarray(_v)),
                           _att_or.local(jnp.asarray(_v))]
check("remote_scatter_or/interleave", np.array_equal(_got_global, _expect))

# batched fallback counter still fires on toy graphs (stats plumbing)
_, _st_b = msbfs_distributed(_gsh_q, _att_q, _srcs, mesh, return_stats=True)
check("msbfs_distributed/stats_shape",
      all(int(np.asarray(_st_b[k])[0]) >= 0
          for k in ("iters", "pushes", "pulls", "fallbacks"))
      and int(np.asarray(_st_b["pulls"])[0]) == 0)

# --- the query service on the sharded engine (PR 5, DESIGN §14) --------------
# GraphService(mesh=...) must serve reach/dist through run_batched_distributed
# and agree with the local placement query-for-query.
from repro.core import GraphService, Reachability, Distance, PPRTopK

_svc_d = GraphService(_gq, batch_budget=8, mesh=mesh)
_svc_l = GraphService(_gq, batch_budget=8)
_qrng = np.random.default_rng(5)
_nq = _gq.n_rows
_stream = [Reachability(int(s), int(t)) for s, t in
           zip(_qrng.integers(0, _nq, 10), _qrng.integers(0, _nq, 10))]
_stream += [Distance(int(s), int(t)) for s, t in
            zip(_qrng.integers(0, _nq, 6), _qrng.integers(0, _nq, 6))]
_ok_r = all(_svc_d.query(q, deadline=120.0) == _svc_l.query(q)
            for q in _stream if isinstance(q, Reachability))
check("service_distributed/reach_matches_local", _ok_r)
_ok_d = all(abs(_svc_d.query(q, deadline=120.0) - _svc_l.query(q)) < 1e-4
            or _svc_d.query(q) == _svc_l.query(q)   # inf == inf
            for q in _stream if isinstance(q, Distance))
check("service_distributed/dist_matches_local", _ok_d)
# PPR stays on the local placement under a mesh — same answers either way
_ids_d, _sc_d = _svc_d.query(PPRTopK(3, k=4))
_ids_l, _sc_l = _svc_l.query(PPRTopK(3, k=4))
check("service_distributed/ppr_local_fallback",
      np.array_equal(_ids_d, _ids_l) and np.allclose(_sc_d, _sc_l))
check("service_distributed/deadline_miss_rate_zero",
      _svc_d.stats.deadline_miss_rate == 0.0
      and _svc_d.stats.deadline_queries >= 16)
check("service_distributed/route_bytes_measured",
      _svc_d.stats.route_bytes > 0 and _svc_d.stats.push_levels > 0
      and _svc_d.stats.n_model_shards == S)

# --- async placement: bounded-staleness pacing at S=8 (PR 7) -----------------
# Same partitions must fall out of the paced schedule: K collective-free
# micro-steps per global check, remote updates deferred in the dense outbox
# and delivered by one buffered_flush — the monotone combines make the stale
# reads invisible in the fixpoint.

for _k in (1, 2, 8):
    _lv_a = bfs_distributed(gsh2, att2, 0, mesh, axis="cores",
                            placement="async", sync_interval=_k)
    check(f"bfs_async/k{_k}",
          np.array_equal(np.asarray(unshard_vertex_array(_lv_a, att2)),
                         lv_local))
    _d_a = sssp_distributed(gsh2, att2, 0, mesh, axis="cores", delta=0.5,
                            max_iters=4 * g.n_rows, placement="async",
                            sync_interval=_k)
    check(f"sssp_async/k{_k}",
          np.allclose(np.asarray(unshard_vertex_array(_d_a, att2)), d_local,
                      atol=0, equal_nan=True))

_lab_a = connected_components_distributed(gshs, atts, mesh, axis="cores",
                                          placement="async", sync_interval=8)
check("cc_async/k8",
      np.array_equal(np.asarray(unshard_vertex_array(_lab_a, atts)),
                     lab_local))

_lv_ab, _st_a = msbfs_distributed(_gsh_q, _att_q, _srcs, mesh,
                                  placement="async", sync_interval=8,
                                  return_stats=True)
check("msbfs_async/partition_identity",
      np.array_equal(np.asarray(_lv_ab), _lv_b))

# the paced schedule's collective budget, on measured traces: sync pays
# level_collectives() per level (delta-stepping adds 2 bucket pmins), async
# pays 2 per flush.  SSSP must clear the 4x acceptance bar; BFS hops cross
# shards only at a flush, so its win is the per-check collective count
# (5 -> 2) — gate > 1x there.
_, _st_s = msbfs_distributed(_gsh_q, _att_q, _srcs, mesh, return_stats=True)
_sync_red = int(np.asarray(_st_s["iters"])[0]) \
    * _traffic.level_collectives(placement="sync")
_async_red = int(np.asarray(_st_a["pushes"])[0]) \
    * _traffic.level_collectives(placement="async")
check("async/bfs_fewer_reductions", _sync_red > _async_red > 0)
_, _st_ss = sssp_batched_distributed(_gsh_q, _att_q, _srcs, mesh, delta=1.0,
                                     return_stats=True)
_, _st_sa = sssp_batched_distributed(_gsh_q, _att_q, _srcs, mesh, delta=1.0,
                                     return_stats=True, placement="async",
                                     sync_interval=8)
_sync_red = int(np.asarray(_st_ss["iters"])[0]) \
    * _traffic.level_collectives(placement="sync", program_collectives=2)
_async_red = int(np.asarray(_st_sa["pushes"])[0]) \
    * _traffic.level_collectives(placement="async")
check("async/sssp_reduction_ratio_4x", _sync_red >= 4 * _async_red > 0)
check("async/stats_shape",
      int(np.asarray(_st_a["pulls"])[0]) == 0
      and int(np.asarray(_st_a["fallbacks"])[0]) == 0
      and int(np.asarray(_st_a["iters"])[0])
      >= int(np.asarray(_st_a["pushes"])[0]))

# the service serves identical answers under placement='async'
_svc_a = GraphService(_gq, batch_budget=8, mesh=mesh, placement="async",
                      sync_interval=8)
_ok_a = all(_svc_a.query(q) == _svc_l.query(q)
            for q in _stream if isinstance(q, Reachability))
_ok_ad = all(_svc_a.query(q) == _svc_l.query(q)  # exact: min-combine floats
             for q in _stream if isinstance(q, Distance))
check("service_async/matches_local", _ok_a and _ok_ad)
check("service_async/route_bytes_measured", _svc_a.stats.route_bytes > 0)

# --- streaming updates: incremental reshard + distributed repair (PR 8) ------
from repro.core import GraphHandle
from repro.core.algorithms import bfs_repair_distributed, cc_repair_distributed
from repro.core.algorithms.distgraph import update_shards

_h0 = GraphHandle.wrap(_gq, n_partitions=S)
_urng = np.random.default_rng(17)
_k = 24
_ins = (_urng.integers(0, _nq, _k), _urng.integers(0, _nq, _k),
        _urng.uniform(1e-4, 1e-3, _k).astype(np.float32))
_h1, _rep = _h0.apply(_ins)
check("streaming/monotone_safe_batch", _rep.monotone_safe)

# incremental touched-shard reshard == full reshard, bit for bit
_touched = np.unique(np.asarray(_att_q.owner(
    jnp.asarray(_rep.changed_sources, jnp.int32))))
_gsh_up = update_shards(_gsh_q, _h1.csr, _att_q, _touched)
_gsh_full, _ = shard_graph(_h1.csr, S, row_att=_att_q)
if _gsh_up is None:   # padding overflow: the documented full-reshard fallback
    _gsh_up = _gsh_full
check("streaming/update_shards_matches_full",
      np.array_equal(np.asarray(_gsh_up.src), np.asarray(_gsh_full.src))
      and np.array_equal(np.asarray(_gsh_up.dst), np.asarray(_gsh_full.dst))
      and np.array_equal(np.asarray(_gsh_up.val), np.asarray(_gsh_full.val)))

# distributed BFS repair: warm-start from the pre-update fixpoint, seeded by
# the changed endpoints — partition-identical to local scratch on the
# updated graph
_prev_lv = bfs_distributed(_gsh_q, _att_q, 0, mesh, axis="cores")
_lv_rep = bfs_repair_distributed(_gsh_up, _att_q, _prev_lv,
                                 _rep.changed_sources, mesh, axis="cores")
check("streaming/bfs_repair_distributed",
      np.array_equal(np.asarray(unshard_vertex_array(_lv_rep, _att_q)),
                     np.asarray(bfs(_h1.csr, 0))))

# distributed CC repair on the symmetrized updated edge set
_gsym0 = symmetrize(_gq)
_att_s = dgas.block_rule(_gsym0.n_rows, S)
_gshs0, _ = shard_graph(_gsym0, S, row_att=_att_s)
_prev_lab = connected_components_distributed(_gshs0, _att_s, mesh,
                                             axis="cores")
_gshs1, _ = shard_graph(symmetrize(_h1.csr), S, row_att=_att_s)
_lab_rep = cc_repair_distributed(_gshs1, _att_s, _prev_lab,
                                 _rep.changed_vertices, mesh, axis="cores")
check("streaming/cc_repair_distributed",
      np.array_equal(np.asarray(unshard_vertex_array(_lab_rep, _att_s)),
                     np.asarray(connected_components(_h1.csr))))

# the mesh service ingests the same batch and stays partition-identical to a
# fresh local service on the updated graph
_svc_d.apply_updates(inserts=_ins)
_svc_fresh = GraphService(_h1.csr, batch_budget=8)
check("streaming/service_epoch_bumped", _svc_d.epoch == 1)
_ok_r = all(_svc_d.query(q) == _svc_fresh.query(q)
            for q in _stream if isinstance(q, Reachability))
_ok_d = all(_svc_d.query(q) == _svc_fresh.query(q)   # min-combine floats
            for q in _stream if isinstance(q, Distance))
check("streaming/service_apply_updates_matches_local", _ok_r and _ok_d)

print("FAILURES(final):", failures, flush=True)
sys.exit(1 if failures else 0)
