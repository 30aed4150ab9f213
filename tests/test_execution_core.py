"""ExecutionCore golden-equivalence grid (DESIGN.md §14).

The PR-5 refactor collapsed the engine's five runners into one stepping loop
parameterized by (lane representation x placement).  This suite replays the
pre-refactor outputs — captured by ``scripts/make_golden_core.py`` against
the PR-4 engine and committed as ``tests/golden/core_grid.npz`` — and
asserts **bit identity** across the whole (program family x lane
representation x mode) grid on the local placement, plus the direction-trace
stats.  The distributed placement's equivalence gates in
``tests/_distributed_main.py`` (partition identity under 8 forced devices,
goldens there would bake in the device count).

Also guards the structural invariant itself: ``engine.py`` holds exactly one
stepping loop (the same check `scripts/check_single_core.py` runs in CI).
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, rmat, uniform_random_graph
from repro.core.algorithms import (bfs, connected_components,
                                   label_propagation, msbfs, ppr, ppr_batched,
                                   sssp, sssp_batched)

GOLD = np.load(os.path.join(os.path.dirname(__file__), "golden",
                            "core_grid.npz"))
G = rmat(7, 8, seed=11)
U = uniform_random_graph(150, 4, seed=5)
DELTA = float(GOLD["meta_delta_g"])
SOURCES = np.array([0, 3, 17, 64, 0], dtype=np.int32)  # dup lane on purpose
MODES = ("push", "pull", "auto")


def _gold(key):
    assert key in GOLD.files, f"golden entry {key} missing — regenerate only "\
        "with scripts/make_golden_core.py against a pre-refactor tree"
    return GOLD[key]


# ---------------------------------------------------------------------------
# scalar lanes, local placement
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_bfs_scalar_golden(mode):
    np.testing.assert_array_equal(np.asarray(bfs(G, 0, mode=mode)),
                                  _gold(f"bfs/scalar/{mode}"))


@pytest.mark.parametrize("mode", MODES)
def test_sssp_scalar_golden(mode):
    np.testing.assert_array_equal(
        np.asarray(sssp(G, 0, delta=DELTA, mode=mode)),
        _gold(f"sssp/scalar/{mode}"))


@pytest.mark.parametrize("mode", MODES)
def test_cc_scalar_golden(mode):
    np.testing.assert_array_equal(
        np.asarray(connected_components(U, mode=mode)),
        _gold(f"cc/scalar/{mode}"))


def test_ppr_scalar_golden():
    np.testing.assert_array_equal(np.asarray(ppr(G, 3, iters=12)),
                                  _gold("ppr/scalar/pull"))


def test_lpa_structured_golden():
    np.testing.assert_array_equal(np.asarray(label_propagation(G, iters=4)),
                                  _gold("lpa/scalar/auto"))


def test_sample_structured_golden():
    key = jax.random.PRNGKey(7)
    q = jnp.arange(64, dtype=jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(engine.sample_neighbors(G, q, key)),
        _gold("sample/scalar/push"))
    np.testing.assert_array_equal(
        np.asarray(engine.sample_neighbors(G, q, key, weighted=True)),
        _gold("sample/scalar/weighted"))


# ---------------------------------------------------------------------------
# packed / valued lanes, local placement
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_msbfs_packed_golden(mode):
    np.testing.assert_array_equal(np.asarray(msbfs(G, SOURCES, mode=mode)),
                                  _gold(f"bfs/packed/{mode}"))


@pytest.mark.parametrize("mode", MODES)
def test_sssp_valued_golden(mode):
    np.testing.assert_array_equal(
        np.asarray(sssp_batched(G, SOURCES, delta=DELTA, mode=mode)),
        _gold(f"sssp/valued/{mode}"))


def test_ppr_valued_golden():
    np.testing.assert_array_equal(
        np.asarray(ppr_batched(G, SOURCES, iters=12)),
        _gold("ppr/valued/pull"))


# ---------------------------------------------------------------------------
# async placement (PR 7): bounded-staleness pacing replays the same goldens
# ---------------------------------------------------------------------------
# A 1-device mesh makes the distributed runners (and so the async placement)
# executable inside the ordinary suite — S=1 still traces the full pacing
# machinery (micro-step scan, buffered flush, termination psum), and the
# monotone programs must land on the *identical* fixpoint the pre-refactor
# engine produced, at every staleness bound.  The S=8 partition checks live
# in tests/_distributed_main.py.

from repro.core import dgas
from repro.core.algorithms import (bfs_distributed, msbfs_distributed,
                                   sssp_distributed, sssp_batched_distributed,
                                   connected_components_distributed,
                                   symmetrize)
from repro.core.algorithms.distgraph import shard_graph
from repro.launch.mesh import make_cores_mesh

INTERVALS = (1, 2, 8)
_MESH1 = make_cores_mesh(1)
_GSH1, _ATT1 = shard_graph(G, 1, row_att=dgas.block_rule(G.n_rows, 1))
_US = symmetrize(U)
_GSH1_U, _ATT1_U = shard_graph(_US, 1, row_att=dgas.block_rule(_US.n_rows, 1))


def _unshard1(x, n):
    return np.asarray(x).reshape(-1)[:n]


@pytest.mark.parametrize("k", INTERVALS)
def test_bfs_async_golden(k):
    lv = bfs_distributed(_GSH1, _ATT1, 0, _MESH1, placement="async",
                         sync_interval=k)
    np.testing.assert_array_equal(_unshard1(lv, G.n_rows),
                                  _gold("bfs/scalar/push"))


@pytest.mark.parametrize("k", INTERVALS)
def test_msbfs_async_golden(k):
    lv = msbfs_distributed(_GSH1, _ATT1, SOURCES, _MESH1, placement="async",
                           sync_interval=k)
    lv = np.asarray(lv).transpose(1, 0, 2).reshape(len(SOURCES), -1)
    np.testing.assert_array_equal(lv[:, : G.n_rows], _gold("bfs/packed/push"))


@pytest.mark.parametrize("k", INTERVALS)
def test_sssp_async_golden(k):
    d = sssp_distributed(_GSH1, _ATT1, 0, _MESH1, delta=DELTA,
                         max_iters=4 * G.n_rows, placement="async",
                         sync_interval=k)
    np.testing.assert_array_equal(_unshard1(d, G.n_rows),
                                  _gold("sssp/scalar/push"))


@pytest.mark.parametrize("k", INTERVALS)
def test_sssp_batched_async_golden(k):
    d = sssp_batched_distributed(_GSH1, _ATT1, SOURCES, _MESH1, delta=DELTA,
                                 max_iters=4 * G.n_rows, placement="async",
                                 sync_interval=k)
    d = np.asarray(d).transpose(1, 0, 2).reshape(len(SOURCES), -1)
    np.testing.assert_array_equal(d[:, : G.n_rows], _gold("sssp/valued/push"))


@pytest.mark.parametrize("k", INTERVALS)
def test_cc_async_golden(k):
    lab = connected_components_distributed(_GSH1_U, _ATT1_U, _MESH1,
                                           placement="async", sync_interval=k)
    np.testing.assert_array_equal(_unshard1(lab, U.n_rows),
                                  _gold("cc/scalar/push"))


def test_async_rejects_structured_and_pull():
    with pytest.raises(ValueError):
        bfs_distributed(_GSH1, _ATT1, 0, _MESH1, mode="pull",
                        placement="async")
    with pytest.raises(ValueError):
        sssp_distributed(_GSH1, _ATT1, 0, _MESH1, placement="async",
                         sync_interval=0)


# ---------------------------------------------------------------------------
# direction-decision traces (the refactor must not re-route any level)
# ---------------------------------------------------------------------------

def _vertex_decisions(st):
    """[iters, pushes, pulls] as the frontier-population test alone decides
    them: the goldens predate the push step's edge budget, whose fallback
    levels (within the population bound, over the budget) pull instead."""
    fb = int(st["fallbacks"])
    return [int(st["iters"]), int(st["pushes"]) + fb, int(st["pulls"]) - fb]


def test_sssp_stats_trace_golden():
    _, st = sssp(G, 0, delta=DELTA, return_stats=True, trace=True)
    np.testing.assert_array_equal(_vertex_decisions(st),
                                  _gold("sssp/stats/auto"))


def test_msbfs_stats_trace_golden():
    _, st = msbfs(G, SOURCES, return_stats=True, trace=True)
    np.testing.assert_array_equal(_vertex_decisions(st),
                                  _gold("msbfs/stats/auto"))


# ---------------------------------------------------------------------------
# structural invariant: exactly one stepping loop
# ---------------------------------------------------------------------------

def test_engine_has_single_stepping_loop():
    """The in-suite twin of scripts/check_single_core.py: every frontier
    runner must lower to the one `_core_loop` while_loop."""
    src = open(os.path.join(os.path.dirname(__file__), os.pardir, "src",
                            "repro", "core", "engine.py")).read()
    assert len(re.findall(r"lax\.while_loop\(", src)) == 1
    assert len(re.findall(r"lax\.scan\(", src)) <= 1  # run_queue's body
    for runner in ("def run(", "def run_batched(", "def run_distributed(",
                   "def run_batched_distributed(", "def run_queue("):
        assert runner in src


def test_mapped_cache_is_shared_with_algorithms():
    """One `_MAPPED_CACHE` keying scheme across placements (DESIGN §14)."""
    from repro.core.algorithms import louvain
    assert louvain._MAPPED_CACHE is engine._MAPPED_CACHE
