"""Offload-engine edge cases: queue balance with empty/overfull queues,
owner routing at degenerate capacities, remote combines with all-inactive
input, the structured segment combines, and the routed-byte model.

Multi-shard behavior is covered by tests/_distributed_main.py; everything
here runs on a single-device mesh (the collectives degenerate but the slot
bookkeeping, masking and compaction logic are all exercised).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, PartitionSpec as P
from repro.compat import shard_map
from repro.core import dgas, engine, offload, traffic

MESH = jax.make_mesh((1,), ("x",), axis_types=(AxisType.Auto,))
SPEC = P("x")


def _mapped(fn, n_in, n_out=1):
    return shard_map(fn, mesh=MESH, in_specs=(SPEC,) * n_in,
                     out_specs=(SPEC,) * n_out if n_out > 1 else SPEC)


# ---------------------------------------------------------------------------
# _route degenerate capacities
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("capacity", [0, 1])
def test_route_tiny_capacity_drops_overflow(capacity):
    vals = jnp.asarray(np.array([[10, 20, 30]], np.int32))
    dest = jnp.zeros((1, 3), jnp.int32)  # all to shard 0

    def fn(v, d):
        recv, recvv, _, valid = offload._route(v[0], d[0], "x", capacity)
        return (recv[None], recvv[None], valid[None])

    recv, recvv, valid = _mapped(fn, 2, 3)(vals, dest)
    recv, recvv, valid = (np.asarray(x)[0] for x in (recv, recvv, valid))
    assert recv.shape == (capacity,) and recvv.shape == (capacity,)
    # fixed per-peer capacity: first `capacity` items land, the rest drop
    assert int(valid.sum()) == capacity
    if capacity == 1:
        assert recvv[0] and recv[0] == 10  # deterministic: stable slot order
    else:
        assert not valid.any()


def test_route_negative_dest_dropped():
    vals = jnp.asarray(np.array([[7, 8]], np.int32))
    dest = jnp.asarray(np.array([[-1, 0]], np.int32))

    def fn(v, d):
        recv, recvv, _, valid = offload._route(v[0], d[0], "x", 4)
        return (recv[None], recvv[None], valid[None])

    recv, recvv, valid = _mapped(fn, 2, 3)(vals, dest)
    assert list(np.asarray(valid)[0]) == [False, True]
    got = np.asarray(recv)[0][np.asarray(recvv)[0]]
    assert list(got) == [8]


# ---------------------------------------------------------------------------
# queue_balance: empty and (over)full queues, payload companion
# ---------------------------------------------------------------------------

def test_queue_balance_empty_queue():
    cap = 8
    items = jnp.full((1, cap), -1, jnp.int32)

    def fn(it):
        q = offload.queue_balance(
            offload.QueueState(it[0], jnp.int32(0)), "x")
        return q.items[None], q.count[None, None]

    out_items, out_count = _mapped(fn, 1, 2)(items)
    assert int(np.asarray(out_count).reshape(())) == 0
    assert (np.asarray(out_items) == -1).all()


def test_queue_balance_full_queue_keeps_capacity_and_items():
    cap = 6
    vals = np.arange(100, 100 + cap, dtype=np.int32)

    def fn(it):
        q = offload.queue_balance(
            offload.QueueState(it[0], jnp.int32(cap)), "x")
        return q.items[None], q.count[None, None]

    out_items, out_count = _mapped(fn, 1, 2)(jnp.asarray(vals[None]))
    out_items = np.asarray(out_items)[0]
    # the balanced queue keeps the input buffer size (fixed point under
    # iterated balancing) and loses nothing when the global count fits
    assert out_items.shape == (cap,)
    assert int(np.asarray(out_count).reshape(())) == cap
    assert sorted(out_items.tolist()) == vals.tolist()


def test_queue_balance_routes_payload_with_items():
    cap = 5
    items = np.full((1, cap), -1, np.int32)
    items[0, :3] = [11, 12, 13]
    payload = np.full((1, cap), -7, np.int32)
    payload[0, :3] = [110, 120, 130]

    def fn(it, pl):
        q, p = offload.queue_balance(
            offload.QueueState(it[0], jnp.int32(3)), "x", pl[0])
        return q.items[None], p[None]

    out_items, out_pl = _mapped(fn, 2, 2)(jnp.asarray(items), jnp.asarray(payload))
    out_items, out_pl = np.asarray(out_items)[0], np.asarray(out_pl)[0]
    got = {int(i): int(p) for i, p in zip(out_items, out_pl) if i >= 0}
    assert got == {11: 110, 12: 120, 13: 130}
    # empty slots are scrubbed, not leaking stale payload
    assert (out_pl[out_items < 0] == 0).all()


# ---------------------------------------------------------------------------
# remote combines with all-inactive input
# ---------------------------------------------------------------------------

def test_remote_scatter_combine_all_inactive_is_noop():
    att = dgas.block_rule(8, 1)
    local = jnp.asarray(np.arange(8, dtype=np.float32))
    gidx = jnp.full((1, 4), -1, jnp.int32)
    vals = jnp.full((1, 4), 123.0, jnp.float32)

    def fn(l, g, v):
        return offload.remote_scatter_combine(
            l[0], g[0], v[0], att, "x", combine="min", identity=np.inf,
            capacity=4)[None]

    out = _mapped(fn, 3)(local[None], gidx, vals)
    np.testing.assert_array_equal(np.asarray(out)[0], np.arange(8, dtype=np.float32))


def test_remote_weighted_mode_all_inactive_votes():
    att = dgas.block_rule(4, 1)
    gidx = jnp.full((1, 6), -1, jnp.int32)
    labs = jnp.full((1, 6), 3, jnp.int32)
    w = jnp.ones((1, 6), jnp.float32)

    def fn(g, l, v):
        bw, bl = offload.remote_scatter_weighted_mode(
            4, g[0], l[0], v[0], att, "x", capacity=6)
        return bw[None], bl[None]

    bw, bl = _mapped(fn, 3, 2)(gidx, labs, w)
    assert np.isneginf(np.asarray(bw)[0]).all()
    assert (np.asarray(bl)[0] == -1).all()


# ---------------------------------------------------------------------------
# structured segment combines (pure, no mesh)
# ---------------------------------------------------------------------------

def test_segment_argmax_matches_bruteforce():
    rng = np.random.default_rng(3)
    m, n = 300, 12
    idx = rng.integers(-1, n, m)
    score = rng.random(m).astype(np.float32)
    payload = rng.integers(0, 50, m)
    bw, bp = offload.segment_argmax(jnp.asarray(idx), jnp.asarray(score),
                                    jnp.asarray(payload), n)
    for v in range(n):
        sel = idx == v
        if not sel.any():
            assert np.isneginf(float(bw[v])) and int(bp[v]) == -1
        else:
            best = score[sel].max()
            winners = payload[sel][score[sel] == best]
            assert abs(float(bw[v]) - best) < 1e-6
            assert int(bp[v]) == winners.min()  # ties -> smaller payload


def test_segment_weighted_mode_matches_bruteforce():
    rng = np.random.default_rng(4)
    m, n, L = 400, 15, 7
    idx = rng.integers(-1, n, m)
    lab = rng.integers(-1, L, m)
    w = rng.random(m).astype(np.float32)
    bw, bl = offload.segment_weighted_mode(jnp.asarray(idx), jnp.asarray(lab),
                                           jnp.asarray(w), n)
    for v in range(n):
        sums = {}
        for i in np.nonzero((idx == v) & (lab >= 0))[0]:
            sums[int(lab[i])] = sums.get(int(lab[i]), 0.0) + float(w[i])
        if not sums:
            assert np.isneginf(float(bw[v])) and int(bl[v]) == -1
        else:
            best = max(sums.values())
            want = min(l for l, s in sums.items() if abs(s - best) < 1e-4)
            assert abs(float(bw[v]) - best) < 1e-3
            assert int(bl[v]) == want


def test_segment_weighted_mode_empty_stream():
    bw, bl = offload.segment_weighted_mode(
        jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32),
        jnp.zeros((0,), jnp.float32), 3)
    assert np.isneginf(np.asarray(bw)).all() and (np.asarray(bl) == -1).all()


# ---------------------------------------------------------------------------
# routed-byte model + capacity rule
# ---------------------------------------------------------------------------

def test_frontier_edge_capacity_shrinks_with_frontier_bound():
    m = 1 << 16
    caps = [engine.frontier_edge_capacity(m, f)
            for f in (1 / 2, 1 / 8, 1 / 32, 1 / 128)]
    assert caps == sorted(caps, reverse=True)
    assert caps[-1] < caps[0] <= m
    assert engine.frontier_edge_capacity(m, 1e-9) >= 1  # floor


def test_routed_bytes_shrink_with_capacity():
    S, m = 8, 1 << 14
    full = traffic.push_level_route_bytes(S, m)
    by_frac = [traffic.push_level_route_bytes(
        S, engine.frontier_edge_capacity(m, f)) for f in (1 / 8, 1 / 32, 1 / 128)]
    assert all(b < full for b in by_frac)
    assert by_frac == sorted(by_frac, reverse=True)
    c = traffic.RouteByteCounter(S)
    c.push_level(m)
    c.push_level(engine.frontier_edge_capacity(m, 1 / 32))
    assert c.levels == 2
    assert c.total_bytes == full + by_frac[1]
