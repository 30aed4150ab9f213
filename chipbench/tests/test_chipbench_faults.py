"""A run at a tiny scale on the CPU, with the look for a chip skipped: sound,
it comes out correct; with the control in the program's place, or with the
timed path broken underneath, ``correct`` comes out false."""
from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import run  # noqa: E402

CELLS = ("svc-mix-open", "graph500-bfs", "graph500-sssp")
SECONDS = 1.0


def _tiny(cell: str) -> dict:
    spec = run.cell_spec(cell)
    spec["config"] = dict(spec["config"], scale=7,
                          name=f"{spec['config']['name']}-test")
    if "rate_qps" in spec["traffic"]:
        # arrivals fast enough that batches hold several lanes
        spec["traffic"] = dict(spec["traffic"], rate_qps=400.0)
    return spec


def _run(cell: str, control: bool = False) -> dict:
    return run.run(cell, 2**31 + 7, SECONDS, False, spec=_tiny(cell),
                   require_tpu=False, control=control)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_control_is_not(cell):
    out = _run(cell)
    assert out["correct"], out["checked"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checked"
    assert not _run(cell, control=True)["correct"]


def _init_like(x, srcs, unreached):
    """The state a traversal starts from: its source at 0, else unreached."""
    import jax.numpy as jnp
    cols = jnp.arange(x.shape[-1])
    return jnp.where(cols[None, :] == srcs[:, None], 0, unreached).astype(
        x.dtype)


def _broken_output(kind: str, x, srcs, fault: str):
    import jax.numpy as jnp
    unreached = -1 if kind == "reach" else jnp.inf
    init = _init_like(x, srcs, unreached)
    if fault == "unchanged":
        return init
    if fault == "half_batch":
        # every other lane left out: padding lanes replay lane 0, so this is
        # half of the lanes in use however full the batch is
        odd = (jnp.arange(x.shape[0]) % 2 == 1)[:, None]
        return jnp.where(odd, init, x)
    if fault == "one_lane":
        # one lane of every batch altered, the rest sound
        bad = _broken_output(kind, x, srcs, "altered")
        return jnp.where((jnp.arange(x.shape[0]) == 1)[:, None], bad, x)
    # an answer altered where it is produced
    if kind == "reach":
        return jnp.where(x > 0, -1, x)
    return x * jnp.float32(1.0 + 2.0 ** -12)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered",
                                   "one_lane"])
def test_served_faults_are_caught(monkeypatch, fault):
    import jax
    from repro.core import service

    program = service.GraphService.program

    def broken(self, kind, trace=False):
        fn, shapes = program(self, kind, trace)
        if kind not in ("reach", "dist"):
            return fn, shapes

        def f(g, s):
            out, stats = fn(g, s)
            return _broken_output(kind, out, s, fault), stats
        return jax.jit(f), shapes

    monkeypatch.setattr(service.GraphService, "program", broken)
    assert not _run("svc-mix-open")["correct"]


@pytest.mark.parametrize("cell,fault", [
    (c, f) for c in ("graph500-bfs", "graph500-sssp")
    for f in ("unchanged", "altered")])
def test_search_faults_are_caught(monkeypatch, cell, fault):
    import jax.numpy as jnp
    from repro.core import algorithms

    search = "bfs" if cell.endswith("bfs") else "sssp"
    kind = "reach" if search == "bfs" else "dist"
    fn = getattr(algorithms, search)

    def broken(g, s, **kw):
        x = fn(g, s, **kw)[None]
        return _broken_output(kind, x, jnp.reshape(s, (1,)), fault)[0]

    monkeypatch.setattr(algorithms, search, broken)
    assert not _run(cell)["correct"]
