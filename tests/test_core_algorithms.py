"""Graph algorithms vs independent numpy references + invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import rmat, uniform_random_graph, to_padded_ell
from repro.core.graph import CSR
from repro.core.algorithms import (spmv, spmv_ell, spmspv, pagerank, bfs,
                                   random_walks, label_propagation, modularity,
                                   ties_sample, neighbor_sample)
from repro.core.reference import bfs_levels as _np_bfs

RNG = np.random.default_rng(7)


@pytest.mark.parametrize("scale", [6, 8])
def test_spmv_matches_dense(scale):
    g = rmat(scale, 8, seed=scale)
    x = jnp.asarray(RNG.random(g.n_cols, np.float32))
    np.testing.assert_allclose(np.asarray(spmv(g, x)),
                               np.asarray(g.to_dense() @ x), rtol=1e-4, atol=1e-4)


def test_spmv_ell_matches():
    g = rmat(7, 8, seed=1)
    cols, vals, mask = to_padded_ell(g)
    x = jnp.asarray(RNG.random(g.n_cols, np.float32))
    np.testing.assert_allclose(np.asarray(spmv_ell(cols, vals, mask, x)),
                               np.asarray(spmv(g, x)), rtol=1e-4, atol=1e-4)


def test_spmspv_matches_dense_rows():
    g = rmat(7, 8, seed=2)
    ids = jnp.asarray(np.array([3, 17, 42, -1], np.int32))
    vals = jnp.asarray(np.array([1.0, -2.0, 0.5, 9.9], np.float32))
    y = spmspv(g, ids, vals)
    dense = np.asarray(g.to_dense())
    refv = 1.0 * dense[3] - 2.0 * dense[17] + 0.5 * dense[42]
    np.testing.assert_allclose(np.asarray(y), refv, rtol=1e-4, atol=1e-4)


def test_pagerank_is_distribution_and_converges():
    g = rmat(8, 8, seed=3)
    pr = pagerank(g, iters=50)
    assert abs(float(pr.sum()) - 1.0) < 1e-3
    assert float(pr.min()) >= 0
    pr2 = pagerank(g, iters=51)
    assert float(jnp.max(jnp.abs(pr - pr2))) < 1e-5  # converged


def test_pagerank_ring_uniform():
    n = 64
    g = CSR.from_coo(np.arange(n), (np.arange(n) + 1) % n,
                     np.ones(n, np.float32), n, n)
    pr = pagerank(g, iters=100)
    np.testing.assert_allclose(np.asarray(pr), np.full(n, 1.0 / n), atol=1e-6)


@pytest.mark.parametrize("seed", [0, 1])
def test_bfs_matches_numpy(seed):
    g = uniform_random_graph(200, 4, seed=seed)
    lv = np.asarray(bfs(g, 0))
    ref = _np_bfs(np.asarray(g.indptr), np.asarray(g.indices), 0)
    np.testing.assert_array_equal(lv, ref)


def test_random_walks_follow_edges():
    g = uniform_random_graph(100, 4, seed=4)
    walks = np.asarray(random_walks(g, jnp.arange(20), 10, jax.random.PRNGKey(0)))
    indptr, indices = np.asarray(g.indptr), np.asarray(g.indices)
    for w in walks:
        for a, b in zip(w[:-1], w[1:]):
            nbrs = indices[indptr[a]:indptr[a + 1]]
            assert (b in nbrs) or (b == a and nbrs.size == 0)


def test_label_propagation_two_cliques():
    rows, cols = [], []
    for c in range(2):
        for i in range(8):
            for j in range(8):
                if i != j:
                    rows.append(c * 8 + i); cols.append(c * 8 + j)
    rows += [0, 8]; cols += [8, 0]
    g = CSR.from_coo(rows, cols, np.ones(len(rows), np.float32), 16, 16)
    lab = np.asarray(label_propagation(g, iters=10))
    assert len(set(lab[:8])) == 1 and len(set(lab[8:])) == 1
    assert lab[0] != lab[8]
    assert float(modularity(g, jnp.asarray(lab))) > 0.4


def test_ties_sampler_induced():
    g = rmat(7, 8, seed=5)
    nodes, n_nodes, mask = ties_sample(g, 32, 64, jax.random.PRNGKey(1))
    nodes = np.asarray(nodes)
    valid = set(nodes[nodes >= 0].tolist())
    rows = np.asarray(g.row_ids()); cols = np.asarray(g.indices)
    m = np.asarray(mask)
    # every induced edge has both endpoints in the node set
    assert all(r in valid and c in valid for r, c in zip(rows[m], cols[m]))


def test_neighbor_sample_shapes_and_validity():
    g = uniform_random_graph(100, 4, seed=6)
    layers = neighbor_sample(g, jnp.arange(8), [3, 2], jax.random.PRNGKey(2))
    assert [tuple(l.shape) for l in layers] == [(8,), (8, 3), (8, 3, 2)]
    indptr, indices = np.asarray(g.indptr), np.asarray(g.indices)
    l0, l1 = np.asarray(layers[0]), np.asarray(layers[1])
    for i, s in enumerate(l0):
        nbrs = indices[indptr[s]:indptr[s + 1]]
        for v in l1[i]:
            assert v in nbrs or (v == s and nbrs.size == 0)
