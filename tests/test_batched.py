"""Batched multi-source engine: lane exactness vs per-source runs (the
acceptance bar is *bit identity*, not tolerance), bit-packing edge cases,
the segment_or reduction, the Pallas min/max tile combine, and the
byte-model accounting for batched payloads."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, offload, rmat, traffic, uniform_random_graph
from repro.core.algorithms import (auto_delta, bfs, msbfs, ppr, ppr_batched,
                                   ppr_topk, sssp, sssp_batched)
from repro.kernels import ops

RNG = np.random.default_rng(7)


# ---------------------------------------------------------------------------
# lane packing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B", [1, 31, 32, 33, 64, 70])
def test_pack_unpack_roundtrip(B):
    bits = (RNG.random((B, 57)) < 0.3).astype(np.int32)
    words = engine.pack_lanes(jnp.asarray(bits))
    assert words.shape == (57, engine.lane_words(B))
    assert words.dtype == jnp.uint32
    back = np.asarray(engine.unpack_lanes(words, B))
    np.testing.assert_array_equal(back, bits)


def test_segment_or_matches_numpy():
    n, m, W = 40, 300, 3
    idx = RNG.integers(-2, n + 2, m).astype(np.int32)  # includes OOB
    words = RNG.integers(0, 2 ** 32, (m, W), dtype=np.uint64).astype(np.uint32)
    got = np.asarray(offload.segment_or(jnp.asarray(idx), jnp.asarray(words), n))
    expect = np.zeros((n, W), np.uint32)
    for i in range(m):
        if 0 <= idx[i] < n:
            expect[idx[i]] |= words[i]
    np.testing.assert_array_equal(got, expect)


def test_segment_or_presorted_matches_unsorted():
    n, m = 16, 120
    idx = np.sort(RNG.integers(0, n, m)).astype(np.int32)
    words = RNG.integers(0, 2 ** 20, (m, 2), dtype=np.uint64).astype(np.uint32)
    a = np.asarray(offload.segment_or(jnp.asarray(idx), jnp.asarray(words), n,
                                      presorted=True))
    b = np.asarray(offload.segment_or(jnp.asarray(idx), jnp.asarray(words), n))
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# msbfs == per-source bfs (bit identity)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["push", "pull", "auto"])
def test_msbfs_matches_per_source_all_modes(mode):
    g = uniform_random_graph(200, 4, seed=3)
    srcs = np.array([0, 7, 50, 199, 0])  # duplicate lane on purpose
    lv = np.asarray(msbfs(g, srcs, mode=mode))
    for b, s in enumerate(srcs):
        np.testing.assert_array_equal(lv[b], np.asarray(bfs(g, int(s),
                                                            mode=mode)))


def test_msbfs_word_boundary_lanes():
    g = rmat(7, 8, seed=2)
    srcs = np.arange(40) % g.n_rows  # spans the 32-lane word boundary
    lv = np.asarray(msbfs(g, srcs))
    for b in (0, 31, 32, 39):
        np.testing.assert_array_equal(lv[b], np.asarray(bfs(g, int(srcs[b]))))


def test_msbfs_single_lane():
    g = rmat(7, 8, seed=2)
    lv = np.asarray(msbfs(g, np.array([5])))
    np.testing.assert_array_equal(lv[0], np.asarray(bfs(g, 5)))


def test_msbfs_under_jit_and_stats():
    g = rmat(7, 8, seed=1)
    srcs = np.array([0, 3, 9])
    lv, stats = jax.jit(lambda: msbfs(g, srcs, return_stats=True))()
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(msbfs(g, srcs)))
    assert int(stats["iters"]) == int(stats["pushes"]) + int(stats["pulls"])


@pytest.mark.parametrize("mode", ["push", "pull", "auto"])
def test_msbfs_graph_as_jit_argument(mode):
    # the service passes CSR.for_jit() to its runners as an argument: the
    # traced graph must give the closed-over graph's levels, bit for bit
    g = rmat(8, 8, seed=5)
    gj = g.for_jit()
    src, dst = (np.asarray(a) for a in gj.dst_stream)
    assert np.all(np.diff(dst) >= 0)
    np.testing.assert_array_equal(
        np.sort(src.astype(np.int64) * g.n_cols + dst),
        np.sort(np.asarray(g.row_ids(), np.int64) * g.n_cols
                + np.asarray(g.indices)))
    srcs = jnp.asarray([0, 3, 9, 200], jnp.int32)
    lv = jax.jit(lambda gg, s: msbfs(gg, s, mode=mode))(gj, srcs)
    np.testing.assert_array_equal(np.asarray(lv),
                                  np.asarray(msbfs(g, srcs, mode=mode)))


# ---------------------------------------------------------------------------
# sssp_batched == per-source sssp (bit identity)
# ---------------------------------------------------------------------------

def test_sssp_batched_matches_per_source():
    g = rmat(8, 8, seed=4)
    d = auto_delta(g)
    srcs = np.array([0, 3, 17, 99, 255])
    db = np.asarray(sssp_batched(g, srcs, delta=d))
    for b, s in enumerate(srcs):
        np.testing.assert_array_equal(db[b], np.asarray(sssp(g, int(s),
                                                             delta=d)))


def test_sssp_batched_unweighted_equals_bfs_levels():
    g = uniform_random_graph(150, 4, seed=5, weighted=False)
    srcs = np.array([0, 10])
    db = np.asarray(sssp_batched(g, srcs, delta=1.5))
    lv = np.asarray(msbfs(g, srcs))
    finite = np.isfinite(db)
    np.testing.assert_array_equal(finite, lv >= 0)
    np.testing.assert_array_equal(db[finite].astype(np.int64),
                                  lv[lv >= 0].astype(np.int64))


# ---------------------------------------------------------------------------
# ppr_batched == per-source ppr (bit identity), and top-k
# ---------------------------------------------------------------------------

def test_ppr_batched_matches_per_source():
    g = rmat(7, 8, seed=6)
    srcs = np.array([0, 5, 100])
    pb = np.asarray(ppr_batched(g, srcs))
    for b, s in enumerate(srcs):
        np.testing.assert_array_equal(pb[b], np.asarray(ppr(g, int(s))))


def test_ppr_mass_and_personalization():
    g = rmat(7, 8, seed=6)
    x = np.asarray(ppr(g, 3))
    assert abs(float(x.sum()) - 1.0) < 1e-3   # a distribution
    # restart mass concentrates at/near the source
    assert x[3] == x.max()


def test_ppr_topk_shapes_and_order():
    g = rmat(7, 8, seed=6)
    srcs = np.array([1, 2])
    vals, idx = ppr_topk(g, srcs, 5)
    assert vals.shape == (2, 5) and idx.shape == (2, 5)
    v = np.asarray(vals)
    assert (np.diff(v, axis=1) <= 1e-9).all()  # descending
    full = np.asarray(ppr_batched(g, srcs))
    for b in range(2):
        np.testing.assert_allclose(v[b], np.sort(full[b])[::-1][:5], rtol=1e-6)


# ---------------------------------------------------------------------------
# program validation
# ---------------------------------------------------------------------------

def test_or_combine_rejected_outside_batched():
    g = uniform_random_graph(32, 3, seed=0)
    prog = engine.VertexProgram(edge_op="copy", combine="or",
                                msg_fn=lambda s, f: f,
                                update_fn=lambda s, a, f, i: (s, f))
    with pytest.raises(ValueError, match="run_batched"):
        engine.run(g, prog, {}, jnp.zeros((32,), jnp.int32), max_iters=2)


def test_or_combine_requires_copy_edge_op():
    with pytest.raises(ValueError, match="copy"):
        engine.VertexProgram(edge_op="mul", combine="or",
                             msg_fn=lambda s, f: f,
                             update_fn=lambda s, a, f, i: (s, f))


def test_run_batched_rejects_structured():
    g = uniform_random_graph(32, 3, seed=0)
    prog = engine.VertexProgram(edge_op="copy", combine="sample",
                                msg_fn=lambda s, f: f,
                                update_fn=lambda s, a, f, i: (s, f))
    with pytest.raises(NotImplementedError):
        engine.run_batched(g, prog, {}, jnp.zeros((2, 32), jnp.int32),
                           max_iters=1)


# ---------------------------------------------------------------------------
# Pallas min/max tile combine (the extended SpMSpV kernel)
# ---------------------------------------------------------------------------

def _minplus_reference(g, x):
    """y[v] = min over in-edges (u, v) of x[u] + w(u, v)."""
    indptr = np.asarray(g.indptr)
    rows = np.repeat(np.arange(g.n_rows), np.diff(indptr))
    cols = np.asarray(g.indices)
    w = (np.asarray(g.values) if g.values is not None
         else np.ones_like(cols, np.float32))
    y = np.full(g.n_cols, np.inf, np.float32)
    np.minimum.at(y, cols, x[rows] + w)
    return y


@pytest.mark.parametrize("combine", ["min", "max"])
def test_spmspv_kernel_select_combine(combine):
    g = rmat(7, 8, seed=9)
    bb = engine.build_pull_operand(g, block_rows=32, block_cols=32,
                                  tile_nnz=32)
    n = g.n_rows
    ident = np.inf if combine == "min" else -np.inf
    x = np.full(n, ident, np.float32)
    act = RNG.choice(n, 9, replace=False)
    x[act] = RNG.random(9).astype(np.float32)
    frontier = jnp.asarray(np.isfinite(x).astype(np.int32))
    y = np.asarray(ops.spmspv_dma(bb, jnp.asarray(x),
                                  engine.tile_active(bb, frontier),
                                  combine=combine))
    if combine == "min":
        expect = _minplus_reference(g, x)
    else:
        indptr = np.asarray(g.indptr)
        rows = np.repeat(np.arange(n), np.diff(indptr))
        cols = np.asarray(g.indices)
        w = np.asarray(g.values)
        expect = np.full(n, -np.inf, np.float32)
        np.maximum.at(expect, cols, x[rows] + w)
    np.testing.assert_array_equal(y, expect)


def test_spmspv_min_requires_mask():
    g = rmat(6, 4, seed=9)
    bb = engine.build_pull_operand(g, block_rows=32, block_cols=32,
                                  tile_nnz=32)
    import dataclasses as dc
    bare = dc.replace(bb, tile_cnt=None)
    with pytest.raises(ValueError, match="mask"):
        ops.spmspv_dma(bare, jnp.full((g.n_rows,), jnp.inf),
                       jnp.ones((bb.n_tiles,), jnp.int32), combine="min")


def test_sssp_kernel_path_matches_plain():
    g = rmat(7, 8, seed=10)
    d = auto_delta(g)
    bb = engine.build_pull_operand(g, block_rows=32, block_cols=32,
                                  tile_nnz=32)
    ref = np.asarray(sssp(g, 0, delta=d))
    srcs = np.array([0, 12, 60])
    got = np.asarray(sssp_batched(g, srcs, delta=d, kernel_bb=bb))
    np.testing.assert_allclose(got[0], ref, rtol=0, atol=0)
    for b, s in enumerate(srcs):
        np.testing.assert_allclose(got[b], np.asarray(sssp(g, int(s), delta=d)),
                                   rtol=0, atol=0)


# ---------------------------------------------------------------------------
# byte model
# ---------------------------------------------------------------------------

def test_batched_payload_bytes_amortizes():
    # 256 packed lanes ride in 8 words: vs 256 single-source items the packed
    # item is ~64x smaller than B * ROUTE_PAYLOAD_BYTES
    b256 = traffic.batched_payload_bytes(256, packed=True)
    assert b256 == 4 + 1 + 4 * 8
    singles = 256 * traffic.ROUTE_PAYLOAD_BYTES
    assert singles / b256 > 60
    assert traffic.batched_payload_bytes(1, packed=False) == 9
    with pytest.raises(ValueError):
        traffic.batched_payload_bytes(0)


def test_route_byte_counter_payload_override():
    ctr = traffic.RouteByteCounter(8)
    base = ctr.push_level(100)
    batched = ctr.push_level(100, payload_bytes=traffic.batched_payload_bytes(
        64, packed=True))
    assert base == 8 * 100 * traffic.ROUTE_PAYLOAD_BYTES
    assert batched == 8 * 100 * (4 + 1 + 4 * 2)
    assert ctr.levels == 2


# ---------------------------------------------------------------------------
# randomized seed sweep (deterministic; the hypothesis-driven property
# variants live in test_property.py, which is skipped without hypothesis)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_seed_sweep_batched_equals_per_source(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 80))
    g = uniform_random_graph(n, int(rng.integers(1, 5)), seed=seed)
    srcs = rng.integers(0, n, int(rng.integers(1, 6)))
    lv = np.asarray(msbfs(g, srcs))
    d = auto_delta(g)
    db = np.asarray(sssp_batched(g, srcs, delta=d))
    pb = np.asarray(ppr_batched(g, srcs, iters=8))
    for b, s in enumerate(srcs):
        np.testing.assert_array_equal(lv[b], np.asarray(bfs(g, int(s))))
        np.testing.assert_array_equal(db[b], np.asarray(sssp(g, int(s),
                                                             delta=d)))
        np.testing.assert_array_equal(pb[b], np.asarray(ppr(g, int(s),
                                                            iters=8)))
