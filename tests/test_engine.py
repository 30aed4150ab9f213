"""Frontier engine: output equivalence vs reference implementations,
direction switching, the SpMSpV kernel path, the structured combines
(argmax / sample), and the new algorithms."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, offload, rmat, uniform_random_graph, to_padded_ell
from repro.core.graph import CSR
from repro.core.algorithms import (bfs, bfs_program, pagerank, sssp, auto_delta,
                                   connected_components, symmetrize, spmv,
                                   label_propagation, lpa_program, random_walks,
                                   msbfs, sssp_batched)
from repro.core.reference import (bfs_levels as _np_bfs,
                                  pagerank as _np_pagerank,
                                  dijkstra as _np_dijkstra)
from repro.kernels import ops, ref

RNG = np.random.default_rng(11)


# ---------------------------------------------------------------------------
# references
# ---------------------------------------------------------------------------

def _np_components(n, rows, cols):
    parent = list(range(n))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for r, c in zip(rows, cols):
        a, b = find(int(r)), find(int(c))
        if a != b:
            parent[max(a, b)] = min(a, b)
    return np.array([find(i) for i in range(n)])


def _same_partition(a, b):
    m1, m2 = {}, {}
    for x, y in zip(a.tolist(), b.tolist()):
        if m1.setdefault(x, y) != y or m2.setdefault(y, x) != x:
            return False
    return True


# ---------------------------------------------------------------------------
# output equivalence: engine-backed ports vs references
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["push", "pull", "auto"])
def test_engine_bfs_matches_numpy_all_modes(mode):
    g = uniform_random_graph(250, 4, seed=1)
    lv = np.asarray(bfs(g, 0, mode=mode))
    ref_lv = _np_bfs(np.asarray(g.indptr), np.asarray(g.indices), 0)
    np.testing.assert_array_equal(lv, ref_lv)


def test_engine_bfs_matches_on_rmat():
    g = rmat(8, 8, seed=4)
    lv = np.asarray(bfs(g, 0))
    ref_lv = _np_bfs(np.asarray(g.indptr), np.asarray(g.indices), 0)
    np.testing.assert_array_equal(lv, ref_lv)


def test_engine_pagerank_matches_numpy():
    g = rmat(7, 8, seed=2)
    pr = np.asarray(pagerank(g, iters=25))
    ref_pr = _np_pagerank(g, iters=25)
    np.testing.assert_allclose(pr, ref_pr, rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# direction switching
# ---------------------------------------------------------------------------

def test_push_pull_steps_agree():
    """The two directions compute the same acc for the same frontier."""
    g = uniform_random_graph(120, 5, seed=7)
    prog = bfs_program()
    n = g.n_rows
    frontier = jnp.zeros((n,), jnp.int32).at[jnp.arange(0, n, 7)].set(1)
    msg = prog.msg_fn({}, frontier)
    dense = engine._dense_step(g.row_ids(), g.indices, None, msg, n, prog)
    sparse = engine._sparse_step(g.indptr, g.indices, None, msg, frontier,
                                 n, n, g.nnz, prog)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(sparse))


def _push_budget_graph(hub: bool) -> CSR:
    """n = 256, about 3 weighted out-edges per vertex (nnz 764, or 879 with
    the hub: 'auto' budgets E = 64 edge slots).  With ``hub``, vertex 0's one
    out-edge leads to vertex 1, whose 120 out-edges pass that budget."""
    n = 256
    rng = np.random.default_rng(5)
    src, dst = rng.integers(0, n, 3 * n), rng.integers(0, n, 3 * n)
    if hub:
        keep = src != 0
        src = np.r_[src[keep], 0, np.ones(120, np.int64)]
        dst = np.r_[dst[keep], 1, np.arange(2, 122)]
    vals = rng.random(src.shape[0]).astype(np.float32)
    return CSR.from_coo(src, dst, vals, n, n, sum_duplicates=True)


_LANES = np.array([0, 0, 9], np.int32)
_LANE_KINDS = {
    "scalar-bfs": lambda g, mode, **kw: bfs(g, 0, mode=mode, **kw),
    "scalar-sssp": lambda g, mode, **kw: sssp(g, 0, delta=0.25, mode=mode,
                                               **kw),
    "packed-msbfs": lambda g, mode, **kw: msbfs(g, _LANES, mode=mode, **kw),
    "valued-sssp_batched": lambda g, mode, **kw: sssp_batched(
        g, _LANES, delta=0.25, mode=mode, **kw),
}


@pytest.mark.parametrize("hub", (True, False), ids=("hub", "flat"))
@pytest.mark.parametrize("kind", tuple(_LANE_KINDS))
def test_edge_budgeted_push_matches_pull(kind, hub):
    """The flat, edge-budgeted push step gives the pull step's results bit
    for bit at every lane kind, trace on and off, in 'auto' and in 'push'
    (budget nnz).  A hub over the budget makes 'auto' fall back to pull at
    its level; without one no level falls back."""
    g = _push_budget_graph(hub)
    run = _LANE_KINDS[kind]
    want = np.asarray(run(g, "pull"))
    got, st = run(g, "auto", return_stats=True, trace=True)
    assert int(st["pushes"]) > 0
    assert (int(st["fallbacks"]) > 0) == hub
    for out in (got, run(g, "auto"), run(g, "push")):
        np.testing.assert_array_equal(np.asarray(out), want)


def test_auto_mode_actually_switches():
    """A long path pushes (frontier of 1); a star pulls after one hop (its
    hub's out-edges are over the push step's edge budget)."""
    n = 200
    path = CSR.from_coo(np.arange(n - 1), np.arange(1, n), None, n, n)
    state0 = {"level": jnp.full((n,), -1, jnp.int32).at[0].set(0)}
    f0 = jnp.zeros((n,), jnp.int32).at[0].set(1)
    _, stats = engine.run(path, bfs_program(), state0, f0, max_iters=n,
                          mode="auto", return_stats=True)
    assert int(stats["pulls"]) == 0 and int(stats["pushes"]) >= n - 1

    # 0 -> hub 1 -> all others: the source's one edge pushes, the hub's
    # n-2 edges pull, then the frontier jumps over any n/32 threshold
    star = CSR.from_coo(np.r_[0, np.ones(n - 2, np.int64)],
                        np.arange(1, n), None, n, n)
    _, stats = engine.run(star, bfs_program(), state0, f0, max_iters=n,
                          mode="auto", return_stats=True)
    assert int(stats["pushes"]) >= 1 and int(stats["pulls"]) >= 1


def test_engine_rejects_bad_programs():
    with pytest.raises(ValueError):
        engine.VertexProgram(edge_op="div", combine="add",
                             msg_fn=None, update_fn=None)
    with pytest.raises(ValueError):
        engine.VertexProgram(edge_op="mul", combine="median",
                             msg_fn=None, update_fn=None)
    g = uniform_random_graph(50, 3, seed=2)
    with pytest.raises(ValueError):
        bfs(g, 0, mode="psuh")
    # a weighted kernel operand under an edge_op='copy' program would
    # silently multiply by edge weights — must be rejected
    bb_weighted = engine.build_pull_operand(g, block_rows=32, block_cols=32,
                                            tile_nnz=64)
    with pytest.raises(ValueError):
        bfs(g, 0, kernel_bb=bb_weighted)


def test_push_capacity_overflow_falls_back_to_dense():
    """mode='push' with a small capacity must not truncate the frontier."""
    g = uniform_random_graph(200, 4, seed=1)
    ref_lv = np.asarray(bfs(g, 0))
    n = g.n_rows
    state0 = {"level": jnp.full((n,), -1, jnp.int32).at[0].set(0)}
    f0 = jnp.zeros((n,), jnp.int32).at[0].set(1)
    st, stats = engine.run(g, bfs_program(), state0, f0, max_iters=n,
                           mode="push", push_capacity=4, return_stats=True)
    np.testing.assert_array_equal(np.asarray(st["level"]), ref_lv)
    assert int(stats["pulls"]) > 0  # oversized levels took the dense path


# ---------------------------------------------------------------------------
# SpMSpV kernel path
# ---------------------------------------------------------------------------

def test_spmspv_kernel_matches_masked_spmv():
    g = rmat(7, 6, seed=9)
    bb = engine.build_pull_operand(g, block_rows=32, block_cols=32,
                                   tile_nnz=64)
    n = g.n_rows
    frontier = jnp.zeros((n,), jnp.int32).at[jnp.asarray([3, 50, 77])].set(1)
    x = jnp.where(frontier > 0, jnp.asarray(RNG.random(n, np.float32)), 0.0)
    got = np.asarray(ops.spmspv_dma(bb, x, engine.tile_active(bb, frontier)))
    want = np.asarray(ref.spmv_bbcsr_ref(bb, x))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_bfs_kernel_path_matches():
    g = uniform_random_graph(200, 4, seed=12)
    bb = engine.build_pull_operand(g, unit_values=True, block_rows=32,
                                   block_cols=32, tile_nnz=64)
    lv_k = np.asarray(bfs(g, 0, kernel_bb=bb))
    lv = np.asarray(bfs(g, 0))
    np.testing.assert_array_equal(lv_k, lv)


# ---------------------------------------------------------------------------
# new engine-backed algorithms
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["push", "pull", "auto"])
def test_sssp_matches_dijkstra(mode):
    g = uniform_random_graph(220, 4, seed=5)
    got = np.asarray(sssp(g, 0, mode=mode))
    want = _np_dijkstra(g, 0)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_sssp_unweighted_equals_bfs_levels():
    g = uniform_random_graph(150, 3, seed=6, weighted=False)
    d = np.asarray(sssp(g, 0))
    lv = np.asarray(bfs(g, 0)).astype(np.float64)
    lv[lv < 0] = np.inf
    np.testing.assert_allclose(d, lv)


def test_sssp_delta_insensitive():
    g = uniform_random_graph(150, 4, seed=8)
    a = np.asarray(sssp(g, 0, delta=0.05))
    b = np.asarray(sssp(g, 0, delta=10.0))  # ~Bellman-Ford
    np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("mode", ["push", "pull", "auto"])
def test_connected_components_match_union_find(mode):
    g = uniform_random_graph(300, 1, seed=10)
    lab = np.asarray(connected_components(g, mode=mode))
    rows, cols = np.asarray(g.row_ids()), np.asarray(g.indices)
    want = _np_components(300, rows, cols)
    assert _same_partition(lab, want)


def test_connected_components_two_cliques():
    rows, cols = [], []
    for c in range(2):
        for i in range(6):
            for j in range(6):
                if i != j:
                    rows.append(c * 6 + i)
                    cols.append(c * 6 + j)
    g = CSR.from_coo(rows, cols, None, 12, 12)
    lab = np.asarray(connected_components(g))
    assert len(set(lab[:6])) == 1 and len(set(lab[6:])) == 1
    assert lab[0] != lab[6]


def test_symmetrize_is_symmetric():
    g = rmat(6, 4, seed=3)
    s = symmetrize(g)
    d = np.asarray(s.to_dense()) > 0
    assert (d == d.T).all()


# ---------------------------------------------------------------------------
# structured combines: argmax_weighted (LPA) and sample
# ---------------------------------------------------------------------------

_PAD = jnp.int32(2 ** 30)


def _weighted_mode_ell(labels, weights, fallback):
    """The pre-refactor per-row weighted mode (reference for equivalence)."""
    n, k = labels.shape
    order = jnp.argsort(labels, axis=1)
    sl = jnp.take_along_axis(labels, order, 1)
    sw = jnp.take_along_axis(weights, order, 1)
    is_start = jnp.concatenate(
        [jnp.ones((n, 1), bool), sl[:, 1:] != sl[:, :-1]], axis=1)
    run_id = jnp.cumsum(is_start, axis=1) - 1
    seg = (jnp.arange(n)[:, None] * k + run_id).reshape(-1)
    run_w = jax.ops.segment_sum(sw.reshape(-1), seg, num_segments=n * k).reshape(n, k)
    run_l = jnp.full((n * k,), _PAD, jnp.int32).at[seg].min(sl.reshape(-1)).reshape(n, k)
    run_w = jnp.where(run_l == _PAD, -1.0, run_w)
    best = jnp.argmax(run_w, axis=1)
    lab = jnp.take_along_axis(run_l, best[:, None], 1)[:, 0]
    has_any = jnp.max(run_w, axis=1) > 0
    return jnp.where(has_any, lab, fallback)


def _lpa_reference(csr, iters):
    """The pre-refactor label_propagation (ELL gather + per-row mode)."""
    cols, vals, mask = to_padded_ell(csr)
    n = csr.n_rows

    def body(_, labels):
        nl = offload.dma_gather(labels, jnp.where(mask, cols, -1), fill=0)
        nl = jnp.where(mask, nl, _PAD).astype(jnp.int32)
        w = jnp.where(mask, vals, 0.0)
        return _weighted_mode_ell(nl, w, labels)

    return jax.lax.fori_loop(0, iters, body, jnp.arange(n, dtype=jnp.int32))


@pytest.mark.parametrize("seed", [4, 5])
def test_lpa_engine_matches_prerefactor_exactly(seed):
    """Engine-backed LPA == the bespoke implementation, label for label
    (fixed smaller-label tie-breaking)."""
    g = rmat(8, 8, seed=seed)
    got = np.asarray(label_propagation(g, iters=6))
    want = np.asarray(_lpa_reference(g, iters=6))
    np.testing.assert_array_equal(got, want)


def test_argmax_push_pull_steps_agree():
    """Both directions compute the same (weight, label) acc for a partial
    frontier — the structured-combine analogue of test_push_pull_steps_agree."""
    g = uniform_random_graph(150, 5, seed=3)
    n = g.n_rows
    prog = lpa_program()
    labels = jnp.asarray(RNG.integers(0, 12, n).astype(np.int32))
    frontier = jnp.zeros((n,), jnp.int32).at[jnp.arange(0, n, 3)].set(1)
    msg = prog.msg_fn({"label": labels}, frontier)
    dw, dl = engine._dense_step(g.row_ids(), g.indices, g.values, msg, n, prog)
    k = int(np.asarray(g.degrees()).max())
    sw, sl = engine._structured_sparse_step(g.indptr, g.indices, g.values,
                                            msg, frontier, n, n, k, prog)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(sw), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(dl), np.asarray(sl))


def test_sample_combine_is_uniform_pick():
    """combine='sample' through engine.run: each destination picks uniformly
    among its active in-neighbors."""
    n, hub = 9, 0
    srcs = np.arange(1, n)
    g = CSR.from_coo(srcs, np.full(n - 1, hub), None, n, n)

    def msg_fn(state, frontier):
        return jnp.where(frontier > 0, jnp.arange(n, dtype=jnp.int32), -1)

    def update_fn(state, acc, frontier, it):
        _, pick = acc
        return {"pick": pick}, jnp.zeros_like(frontier)  # one step

    prog = engine.VertexProgram(edge_op="copy", combine="sample",
                                msg_fn=msg_fn, update_fn=update_fn)
    frontier0 = jnp.ones((n,), jnp.int32)
    run1 = jax.jit(lambda key: engine.run(
        g, prog, {"pick": jnp.full((n,), -1, jnp.int32)}, frontier0,
        max_iters=1, mode="pull", key=key)["pick"][hub])
    counts = np.zeros(n, np.int64)
    for s in range(400):
        counts[int(run1(jax.random.PRNGKey(s)))] += 1
    assert counts[hub] == 0 and counts[1:].min() > 0
    expected = 400 / (n - 1)
    assert counts[1:].max() < 3 * expected  # loose uniformity bound


def test_sample_requires_key_and_structured_rejects_add():
    g = uniform_random_graph(30, 2, seed=1)
    prog = engine.VertexProgram(edge_op="copy", combine="sample",
                                msg_fn=lambda s, f: f, update_fn=None)
    with pytest.raises(ValueError):
        engine.run(g, prog, {}, jnp.ones((30,), jnp.int32), max_iters=1)
    with pytest.raises(ValueError):
        engine.VertexProgram(edge_op="add", combine="argmax_weighted",
                             msg_fn=None, update_fn=None)


def test_sample_neighbors_distribution_and_sinks():
    n = 7
    g = CSR.from_coo(np.zeros(n - 1, np.int64), np.arange(1, n), None, n, n)
    qs = jnp.zeros((3000,), jnp.int32)
    picks = np.asarray(engine.sample_neighbors(g, qs, jax.random.PRNGKey(0)))
    cnt = np.bincount(picks, minlength=n)
    assert cnt[0] == 0
    assert cnt[1:].min() > 0.7 * 3000 / (n - 1)
    assert cnt[1:].max() < 1.3 * 3000 / (n - 1)
    # sinks (vertices 1..n-1 have no out-edges) self-sample
    sinks = np.asarray(engine.sample_neighbors(
        g, jnp.arange(1, n, dtype=jnp.int32), jax.random.PRNGKey(1)))
    np.testing.assert_array_equal(sinks, np.arange(1, n))


def test_random_walks_next_step_marginal_uniform():
    """Distribution-level equivalence: one-step marginals from a hub match
    the uniform neighbor pick of the pre-refactor sampler."""
    n = 6
    g = CSR.from_coo(np.zeros(n - 1, np.int64), np.arange(1, n), None, n, n)
    walks = np.asarray(random_walks(g, jnp.zeros((4000,), jnp.int32), 1,
                                    jax.random.PRNGKey(3)))
    cnt = np.bincount(walks[:, 1], minlength=n)
    assert cnt[0] == 0
    assert cnt[1:].min() > 0.7 * 4000 / (n - 1)
    assert cnt[1:].max() < 1.3 * 4000 / (n - 1)


def test_auto_delta_tracks_weight_scale():
    g = uniform_random_graph(300, 4, seed=2)
    d1 = auto_delta(g)
    g10 = CSR(g.indptr, g.indices, g.values * 10.0, g.n_rows, g.n_cols)
    d10 = auto_delta(g10)
    assert 5.0 < d10 / d1 < 20.0  # quantile rule scales with the weights
    assert auto_delta(CSR(g.indptr, g.indices, None, g.n_rows, g.n_cols)) == 1.0


# ---------------------------------------------------------------------------
# engine as SpMV (one dense step of the (add, mul) program)
# ---------------------------------------------------------------------------

def test_engine_dense_step_is_spmv():
    # messages flow src->dst, so a dense step over A^T's edge list == A @ x
    g = rmat(6, 6, seed=13)
    t = g.transpose()
    x = jnp.asarray(RNG.random(g.n_cols, np.float32))
    prog = engine.VertexProgram(edge_op="mul", combine="add",
                                msg_fn=lambda s, f: s, update_fn=None)
    acc = engine._dense_step(t.row_ids(), t.indices, t.values, x,
                             t.n_cols, prog)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(spmv(g, x)),
                               rtol=1e-4, atol=1e-5)
