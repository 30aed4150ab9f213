"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import rmat, uniform_random_graph, to_bbcsr
from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("scale,ef", [(6, 4), (7, 8), (8, 16)])
@pytest.mark.parametrize("block", [(32, 64, 64), (64, 32, 128), (128, 128, 256)])
def test_spmv_dma_sweep(scale, ef, block):
    br, bc, tn = block
    g = rmat(scale, ef, seed=scale * 10 + ef)
    bb = to_bbcsr(g, block_rows=br, block_cols=bc, tile_nnz=tn)
    x = jnp.asarray(RNG.random(g.n_cols, np.float32))
    y_k = ops.spmv_dma(bb, x)
    y_r = ref.spmv_bbcsr_ref(bb, x)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=1e-5, atol=1e-5)
    y_d = np.asarray(g.to_dense() @ x)
    np.testing.assert_allclose(np.asarray(y_k), y_d, rtol=1e-4, atol=1e-4)


def test_spmspv_collapsed_index_sequence():
    """SpMSpV must not DMA the x block of inactive tiles: the collapsed cb
    schedule re-uses the previous active tile's block (no index transition
    => the Pallas pipeline issues no new copy), and the kernel output with
    the collapsed schedule still matches the dense reference."""
    from repro.core import engine
    from repro.kernels.spmv_dma import collapse_inactive_blocks

    # hand-checked pattern: leading inactive tiles pin block 0
    cb = jnp.asarray(np.array([3, 1, 4, 4, 2, 5], np.int32))
    act = jnp.asarray(np.array([0, 1, 0, 1, 0, 1], np.int32))
    got = np.asarray(collapse_inactive_blocks(cb, act))
    np.testing.assert_array_equal(got, [0, 1, 1, 4, 4, 5])

    g = rmat(7, 6, seed=21)
    bb = to_bbcsr(g.transpose(), block_rows=32, block_cols=32, tile_nnz=64)
    n = g.n_rows
    frontier = jnp.zeros((n,), jnp.int32).at[jnp.asarray([5, 40])].set(1)
    x = jnp.where(frontier > 0, jnp.asarray(RNG.random(n, np.float32)), 0.0)
    tact = engine.tile_active(bb, frontier)
    sched = np.asarray(collapse_inactive_blocks(bb.tile_cb, tact))
    a = np.asarray(tact)
    # every index transition (= a new x DMA) happens at an active tile, and
    # active tiles keep their true block
    trans = np.nonzero(sched[1:] != sched[:-1])[0] + 1
    assert (a[trans] == 1).all()
    np.testing.assert_array_equal(sched[a == 1], np.asarray(bb.tile_cb)[a == 1])
    assert len(trans) <= int(a.sum())  # never more DMAs than active tiles
    y = np.asarray(ops.spmspv_dma(bb, x, tact))
    np.testing.assert_allclose(y, np.asarray(ref.spmv_bbcsr_ref(bb, x)),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("kind", ["spmv", "spmspv_add", "spmspv_min"])
def test_bbcsr_kernels_default_tiles_match_ref(kind):
    """The three BBCSR kernels at the default tile shape (what a TPU
    resolves), interpret mode, against the kernels/ref.py oracles; the
    sparse kernels on a mid-traversal frontier, so tiles are both computed
    and skipped."""
    from repro.core import engine
    g = rmat(11, 16, seed=7)
    bb = to_bbcsr(g.transpose())
    n = g.n_rows
    frontier = jnp.zeros((n,), jnp.int32).at[jnp.asarray([3, 40, 1500])].set(1)
    xr = jnp.asarray(RNG.random(n, np.float32))
    act = engine.tile_active(bb, frontier)
    assert 0 < int(act.sum()) < bb.n_tiles
    if kind == "spmv":
        y, want = ops.spmv_dma(bb, xr), ref.spmv_bbcsr_ref(bb, xr)
    elif kind == "spmspv_add":
        x = jnp.where(frontier > 0, xr, 0.0)
        y, want = ops.spmspv_dma(bb, x, act), ref.spmspv_bbcsr_ref(bb, x, act)
    else:
        x = jnp.where(frontier > 0, xr, jnp.inf)
        y = ops.spmspv_dma(bb, x, act, combine="min")
        want = ref.spmspv_bbcsr_ref(bb, x, act, combine="min")
        np.testing.assert_array_equal(np.asarray(y), np.asarray(want))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5,
                               atol=1e-6)


def test_spmv_dma_empty_rows():
    # matrix with fully empty row blocks must still zero its output
    from repro.core.graph import CSR
    g = CSR.from_coo([0, 511], [1, 2], np.ones(2, np.float32), 512, 512)
    bb = to_bbcsr(g, block_rows=64, block_cols=64, tile_nnz=64)
    x = jnp.asarray(RNG.random(512).astype(np.float32))
    y = np.asarray(ops.spmv_dma(bb, x))
    assert y.shape == (512,)
    assert np.count_nonzero(y) <= 2
    np.testing.assert_allclose(y, np.asarray(g.to_dense() @ x), rtol=1e-5)


@pytest.mark.parametrize("n,d,m,bn", [(100, 8, 13, 32), (500, 32, 64, 128),
                                      (1000, 1, 7, 256)])
def test_segment_sum_sweep(n, d, m, bn):
    seg = np.sort(RNG.integers(0, m, n)).astype(np.int32)
    data = RNG.standard_normal((n, d)).astype(np.float32)
    out_k = ops.segment_sum_sorted(jnp.asarray(data), jnp.asarray(seg), m, block_n=bn)
    out_r = ref.segment_sum_ref(jnp.asarray(data), jnp.asarray(seg), m)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=1e-5,
                               atol=1e-5)


def test_segment_sum_padding_dropped():
    seg = jnp.asarray(np.array([0, 0, 1, -1, -1], np.int32))
    data = jnp.asarray(np.ones((5, 4), np.float32))
    out = ops.segment_sum_sorted(data, seg, 2, block_n=4)
    np.testing.assert_allclose(np.asarray(out), [[2] * 4, [1] * 4])


@pytest.mark.parametrize("v,d,n,b,weighted,mode", [
    (50, 8, 40, 10, False, "sum"), (100, 16, 64, 7, True, "sum"),
    (30, 4, 25, 5, False, "mean"), (200, 32, 128, 16, True, "mean")])
def test_embedding_bag_sweep(v, d, n, b, weighted, mode):
    table = jnp.asarray(RNG.standard_normal((v, d)).astype(np.float32))
    idx = RNG.integers(0, v, n).astype(np.int32)
    idx[RNG.random(n) < 0.1] = -1  # padding
    bag = np.sort(RNG.integers(0, b, n)).astype(np.int32)
    w = jnp.asarray(RNG.random(n).astype(np.float32)) if weighted else None
    out_k = ops.embedding_bag(table, jnp.asarray(idx), jnp.asarray(bag), b, w, mode)
    out_r = ref.embedding_bag_ref(table, jnp.asarray(idx), jnp.asarray(bag), b, w, mode)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D", [
    (1, 2, 2, 64, 64, 16), (2, 4, 2, 128, 128, 32), (1, 8, 1, 64, 64, 64),
    (2, 4, 4, 1, 128, 32),   # decode shape
])
@pytest.mark.parametrize("window", [None, 33])
def test_flash_attention_sweep(B, Hq, Hkv, Sq, Skv, D, window):
    q = jnp.asarray(RNG.standard_normal((B, Hq, Sq, D)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((B, Hkv, Skv, D)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((B, Hkv, Skv, D)).astype(np.float32))
    bq = 1 if Sq == 1 else 32
    out_k = ops.flash_attention(q, k, v, causal=True, window=window,
                                block_q=bq, block_k=32)
    out_r = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=2e-4,
                               atol=2e-4)


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.standard_normal((1, 2, 64, 32)), jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((1, 2, 64, 32)), jnp.bfloat16)
    v = jnp.asarray(RNG.standard_normal((1, 2, 64, 32)), jnp.bfloat16)
    out_k = ops.flash_attention(q, k, v, block_q=32, block_k=32)
    out_r = ref.flash_attention_ref(q, k, v)
    assert out_k.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), rtol=5e-2, atol=5e-2)
