#!/usr/bin/env python3
"""Smoke run of the graph query service and its Pallas kernels on a TPU.

    python3 chip_smoke.py                # one chip: phases (a)-(e) below
    python3 chip_smoke.py --four-chips   # four chips: the sharded service only
    python3 chip_smoke.py --rehearse     # no chip needed: compile the served
                                         # programs for a described v5e and
                                         # find the largest scale that fits

Every phase runs on a Graph500-parameter RMAT graph, ``rmat(scale, 16,
seed=7)``, and checks its answers against the plain NumPy references in
``repro.core.reference``:

(a) platform: print the platform, device kind and device count.
(b) service: ``GraphService(g)`` at its default lane budget serves a mixed
    stream of Reachability, Distance, PPRTopK and NeighborSample queries.
    Reachability and Distance must match exactly; PPR top-k within
    ``PPR_RTOL``/``PPR_ATOL``; every sample must be a real neighbour.
(c) kernel path: single-source ``bfs``, ``sssp`` and ``pagerank`` with
    ``kernel_bb`` (the Pallas ``spmv_dma``/``spmspv_dma`` kernels, compiled:
    a ``tpu_custom_call`` must be in each kernel's lowered program) and
    without; they must agree with each other and with the reference.
(d) scale: ``CHIP_SCALE``, the largest scale whose served programs fit one
    v5e chip by the compile-time rehearsal (``--rehearse``).
(e) the last line: ``{"ok": true, "device": {...}}``.

``--four-chips`` runs only ``GraphService(g, mesh=make_cores_mesh(4))``
serving Reachability and Distance under ``placement="sync"`` and
``"async"``, checks that the sharded graph lands on all four devices, and
compares both placements with each other and with the reference.

Off a TPU the phases run at ``OFF_CHIP_SCALE`` and the script exits 1
without printing a result.  Any failed check exits 1; an exception ends the
run with its traceback.  One process drives the chip; nothing is spawned.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import (Distance, GraphService, NeighborSample,  # noqa: E402
                        PPRTopK, Reachability, engine, reference, rmat,
                        to_bbcsr)
from repro.core.algorithms import bfs, pagerank, sssp  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import HW, make_cores_mesh  # noqa: E402

# By the rehearsal (`--rehearse`, TPU v5e, 16 GiB) the served Distance
# program, the largest, needs about 0.6 GB of temporaries at scale 16 and
# about 1.4 GB at 17 since its push step is edge-budgeted; 16 keeps the
# smoke run short.
CHIP_SCALE = 16
OFF_CHIP_SCALE = 8
REHEARSAL_SCALES = (15, 16, 17)
GRAPH_SEED = 7
HBM_USABLE = 0.85               # headroom for the runtime and fragmentation
# PPR and PageRank sum in f32 with 'add' combines, whose order differs from
# the float64 reference (and between the XLA and kernel paths): compared
# with a tolerance, not bit for bit (ROADMAP C.1)
PPR_RTOL, PPR_ATOL = 1e-4, 1e-6
PR_RTOL = 1e-4

# XLA backend compiles (tracing and lowering nest, so they are left in the
# serve time of the phase that first calls a program)
_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)
_compile_s = [0.0]
_failures: list = []


def _on_duration(event: str, secs: float, **_):
    if event in _COMPILE_EVENTS:
        _compile_s[0] += secs


def check(ok: bool, what: str) -> None:
    print(f"  {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        _failures.append(what)


class Phase:
    """Times a phase and splits its wall time into compile and serve."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        print(f"phase {self.name}", flush=True)
        self.t0, self.c0 = time.perf_counter(), _compile_s[0]
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self.t0
        comp = _compile_s[0] - self.c0
        print(f"  time {self.name}: compile_s={comp:.2f} "
              f"serve_s={wall - comp:.2f} wall_s={wall:.2f}", flush=True)
        return False


def _neighbours(g, v: int) -> np.ndarray:
    ip = np.asarray(g.indptr)
    return np.asarray(g.indices)[ip[v]:ip[v + 1]]


def _pick_sources(g, rng, n: int) -> np.ndarray:
    deg = np.diff(np.asarray(g.indptr))
    return rng.choice(np.flatnonzero(deg > 0), n, replace=False)


def _traversal_stream(g, rng, n_src: int, n_tgt: int, kind):
    return [kind(int(s), int(t)) for s in _pick_sources(g, rng, n_src)
            for t in rng.integers(0, g.n_rows, n_tgt)]


def _check_traversals(g, queries, answers, label: str) -> None:
    """Reachability / Distance answers against the exact references."""
    ip, ix = np.asarray(g.indptr), np.asarray(g.indices)
    levels, dists = {}, {}
    bad, n_true = 0, 0
    for q, a in zip(queries, answers):
        if isinstance(q, Reachability):
            if q.source not in levels:
                levels[q.source] = reference.bfs_levels(ip, ix, q.source)
            want = bool(levels[q.source][q.target] >= 0)
            n_true += want
            bad += (a != want)
        else:
            if q.source not in dists:
                dists[q.source] = reference.dijkstra(g, q.source)
            want = dists[q.source][q.target]
            n_true += bool(np.isfinite(want))
            bad += not (np.float32(a) == want or (np.isinf(a) and np.isinf(want)))
    check(bad == 0, f"{label}: {len(queries)} answers equal the reference "
          f"({n_true} reachable, {bad} wrong)")


def phase_service(g, rng) -> None:
    with Phase("b service (warm-up: one query per kind compiles its runner)"):
        svc = GraphService(g)
        check(svc.budget == 32, f"default batch budget {svc.budget} == 32")
        s0 = int(_pick_sources(g, rng, 1)[0])
        for q in (Reachability(s0, s0), Distance(s0, s0), PPRTopK(s0, k=8),
                  NeighborSample(s0, fanout=4)):
            svc.query(q)
    reach = _traversal_stream(g, rng, 4, 4, Reachability)
    dist = _traversal_stream(g, rng, 4, 3, Distance)
    ppr = [PPRTopK(int(s), k=k) for s, k in
           zip(_pick_sources(g, rng, 8), (4, 8, 16, 8, 4, 8, 16, 8))]
    samp = [NeighborSample(int(v), fanout=4, seed=i)
            for i, v in enumerate(_pick_sources(g, rng, 8))]
    stream = [q for group in zip(reach[:8], dist[:8], ppr, samp)
              for q in group] + reach[8:] + dist[8:]
    with Phase(f"b service (mixed stream of {len(stream)} queries)"):
        tickets = [svc.submit(q) for q in stream]
        svc.flush()
        answers = {q: svc.result(t) for q, t in zip(stream, tickets)}
    print(f"  {svc.stats}", flush=True)
    trav = reach + dist
    _check_traversals(g, trav, [answers[q] for q in trav],
                      "Reachability + Distance")
    bad = 0
    for q in ppr:
        ids, scores = answers[q]
        ref = reference.ppr(g, q.source, damping=svc.damping,
                            iters=svc.ppr_iters)
        top = np.sort(ref)[::-1][:q.k]
        bad += not (len(ids) == q.k
                    and np.allclose(scores, ref[ids], PPR_RTOL, PPR_ATOL)
                    and np.allclose(scores, top, PPR_RTOL, PPR_ATOL))
    check(bad == 0, f"PPRTopK: {len(ppr)} top-k lists match the reference "
          f"(rtol {PPR_RTOL}, atol {PPR_ATOL}; {bad} wrong)")
    bad = sum(not (len(answers[q]) == q.fanout
                   and np.isin(answers[q], _neighbours(g, q.vertex)).all())
              for q in samp)
    check(bad == 0, f"NeighborSample: {len(samp)} draws are real neighbours "
          f"({bad} wrong)")


def phase_kernels(g, rng, on_tpu: bool) -> None:
    with Phase("c kernel path (build operands, lower the three kernels)"):
        bb_unit = engine.build_pull_operand(g, unit_values=True)
        bb_min = engine.build_pull_operand(g, combine="min")
        print(f"  BBCSR: {bb_min.n_tiles} tiles of {bb_min.tile_nnz} "
              f"({bb_min.n_tiles * bb_min.tile_nnz / g.nnz:.1f}x nnz), "
              f"blocks {bb_min.block_rows}x{bb_min.block_cols}", flush=True)
        x = jnp.ones((g.n_rows,), jnp.float32)
        act = jnp.ones((bb_min.n_tiles,), jnp.int32)
        for name, fn, bb in (
                ("spmv_dma", lambda b, v, a: ops.spmv_dma(b, v), bb_unit),
                ("spmspv_dma add", lambda b, v, a: ops.spmspv_dma(
                    b, v, a, combine="add"), bb_unit),
                ("spmspv_dma min", lambda b, v, a: ops.spmspv_dma(
                    b, v, a, combine="min"), bb_min)):
            text = jax.jit(fn).lower(bb, x, act).as_text()
            check("tpu_custom_call" in text or not on_tpu,
                  f"{name} lowers to a compiled kernel (tpu_custom_call)"
                  + ("" if on_tpu else ": skipped, interpret mode off a TPU"))
    s = int(_pick_sources(g, rng, 1)[0])
    ip, ix = np.asarray(g.indptr), np.asarray(g.indices)
    with Phase(f"c kernel path (bfs from {s})"):
        lv_k = np.asarray(bfs(g, s, kernel_bb=bb_unit))
        lv = np.asarray(bfs(g, s))
    ref = reference.bfs_levels(ip, ix, s)
    check(np.array_equal(lv_k, lv) and np.array_equal(lv, ref),
          f"bfs: kernel == XLA == reference ({int((ref >= 0).sum())} reached)")
    with Phase(f"c kernel path (sssp from {s})"):
        d_k = np.asarray(sssp(g, s, kernel_bb=bb_min))
        d = np.asarray(sssp(g, s))
    ref = reference.dijkstra(g, s)
    check(np.array_equal(d_k, d) and np.array_equal(d, ref),
          f"sssp: kernel == XLA == reference ({int(np.isfinite(ref).sum())} "
          "reached)")
    with Phase("c kernel path (pagerank, 20 iterations)"):
        pr_k = np.asarray(pagerank(g, kernel_bb=bb_unit))
        pr = np.asarray(pagerank(g))
    ref = reference.pagerank(g)
    atol = PR_RTOL / g.n_rows
    check(np.allclose(pr_k, pr, PR_RTOL, atol)
          and np.allclose(pr, ref, PR_RTOL, atol),
          f"pagerank: kernel ~ XLA ~ reference (rtol {PR_RTOL}, atol "
          f"{atol:.2e}; max |kernel - ref| {np.abs(pr_k - ref).max():.2e})")


def _bytes_per_device(arrays) -> list:
    """Bytes of `arrays` resident on each device, shard by shard."""
    per = {d: 0 for d in jax.devices()}
    for a in arrays:
        for sh in a.addressable_shards:
            per[sh.device] += sh.data.nbytes
    return list(per.values())


def phase_four_chips(g, rng) -> None:
    reach = _traversal_stream(g, rng, 8, 4, Reachability)
    dist = _traversal_stream(g, rng, 8, 3, Distance)
    stream = [q for pair in zip(reach, dist) for q in pair] + reach[len(dist):]
    mesh = make_cores_mesh(4)
    got = {}
    for placement in ("sync", "async"):
        with Phase(f"four-chip service, placement={placement} (build + "
                   "warm-up)"):
            before = {id(a) for a in jax.live_arrays()}
            svc = GraphService(g, mesh=mesh, placement=placement)
            grew = _bytes_per_device(a for a in jax.live_arrays()
                                     if id(a) not in before)
            # every shard holds >= nnz/4 edges (src, dst, val: 12 B each)
            want = 12 * g.nnz // 4
            check(all(x >= want for x in grew),
                  f"sharded graph on all 4 devices: new bytes per device "
                  f"{grew} >= {want}")
            svc.query(reach[0])
            svc.query(dist[0])
        with Phase(f"four-chip service, placement={placement} "
                   f"({len(stream)} queries)"):
            tickets = [svc.submit(q) for q in stream]
            svc.flush()
            got[placement] = [svc.result(t) for t in tickets]
        print(f"  {svc.stats}", flush=True)
        _check_traversals(g, stream, got[placement],
                          f"placement={placement}")
        del svc
    check(got["sync"] == got["async"],
          f"sync and async answers identical ({len(stream)} queries)")


def rehearse() -> None:
    """Compile the served programs for a described v5e (no chip needed) at
    each of ``REHEARSAL_SCALES`` and print what each needs; the largest
    scale that fits is the one-chip scale.  The programs are the ones
    ``GraphService.program`` gives its runners, plus the kernel operands."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    def compile_fit(fn, *args):
        """The compiled program, or None where the compiler refuses it for
        want of memory (HBM, or SMEM for a kernel's scalar operands)."""
        try:
            return fn.lower(*args).compile()
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            print(f"    does not fit: {str(e).splitlines()[0][:300]}",
                  flush=True)
            return None

    usable = HBM_USABLE * HW["hbm_per_chip"]
    fits = []
    for scale in REHEARSAL_SCALES:
        g = rmat(scale, 16, seed=GRAPH_SEED)
        svc = GraphService(g)
        # the default tile shape: what repro.tune resolves on a TPU
        bb = to_bbcsr(g.transpose())
        bb_bytes = 2 * sum(a.nbytes for a in jax.tree.leaves(bb))
        print(f"scale {scale}: n {g.n_rows}, nnz {g.nnz}, max degree "
              f"{int(np.diff(np.asarray(g.indptr)).max())}, push capacity "
              f"{max(1, g.n_rows // 32)}; BBCSR {bb.n_tiles} tiles "
              f"({bb.n_tiles * bb.tile_nnz / g.nnz:.2f}x nnz), two operands "
              f"{bb_bytes / 1e9:.3f} GB", flush=True)
        peak = 0
        for kind in ("reach", "dist", "ppr", "sample"):
            fn, shapes = svc.program(kind)
            t0 = time.perf_counter()
            c = compile_fit(fn, *on_chip(shapes))
            if c is None:
                peak = float("inf")
                continue
            m = c.memory_analysis()
            peak = max(peak, m.argument_size_in_bytes + m.output_size_in_bytes
                       + m.temp_size_in_bytes + m.generated_code_size_in_bytes)
            print(f"  {kind}: args {m.argument_size_in_bytes / 1e9:.3f} GB, "
                  f"out {m.output_size_in_bytes / 1e9:.3f} GB, temp "
                  f"{m.temp_size_in_bytes / 1e9:.3f} GB, code "
                  f"{m.generated_code_size_in_bytes / 1e9:.3f} GB; compiled "
                  f"in {time.perf_counter() - t0:.1f} s", flush=True)
        x = jax.ShapeDtypeStruct((g.n_rows,), jnp.float32, sharding=one)
        act = jax.ShapeDtypeStruct((bb.n_tiles,), jnp.int32, sharding=one)
        kernels_ok = True
        for name, fn in (
                ("spmv_dma", lambda b, v, a: ops.spmv_dma(
                    b, v, interpret=False)),
                ("spmspv_dma add", lambda b, v, a: ops.spmspv_dma(
                    b, v, a, combine="add", interpret=False)),
                ("spmspv_dma min", lambda b, v, a: ops.spmspv_dma(
                    b, v, a, combine="min", interpret=False))):
            c = compile_fit(jax.jit(fn), on_chip(bb), x, act)
            ok_k = c is not None and "tpu_custom_call" in c.as_text()
            print(f"  {name} at the default tile shape: "
                  + ("compiles to a tpu_custom_call" if ok_k
                     else "does not compile"), flush=True)
            kernels_ok = kernels_ok and ok_k
        peak += bb_bytes
        ok = kernels_ok and peak <= usable
        fits.append((scale, ok))
        print(f"  scale {scale}: peak {peak / 1e9:.2f} GB vs "
              f"{usable / 1e9:.2f} GB usable: "
              f"{'fits' if ok else 'does not fit'}", flush=True)
    best = max([s for s, ok in fits if ok], default=None)
    print(f"largest scale that fits one v5e chip: {best}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded service on four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="compile for a described v5e and report memory")
    args = ap.parse_args()
    if args.rehearse:
        rehearse()
        return 0

    use_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    on_tpu = dev["platform"] == "tpu"
    print(f"phase a platform: {dev['platform']}, device_kind "
          f"{dev['kind']}, {dev['count']} device(s)", flush=True)
    scale = CHIP_SCALE if on_tpu else OFF_CHIP_SCALE
    t0 = time.perf_counter()
    g = rmat(scale, 16, seed=GRAPH_SEED)
    deg = np.diff(np.asarray(g.indptr))
    print(f"phase d scale: rmat({scale}, 16, seed={GRAPH_SEED}): n "
          f"{g.n_rows}, nnz {g.nnz}, max degree {int(deg.max())} (generated "
          f"in {time.perf_counter() - t0:.1f} s)", flush=True)
    rng = np.random.default_rng(0)
    if args.four_chips:
        check(dev["count"] == 4, f"{dev['count']} devices == 4")
        if dev["count"] == 4:
            phase_four_chips(g, rng)
    else:
        phase_service(g, rng)
        phase_kernels(g, rng, on_tpu)
    if on_tpu:
        peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
                 for d in jax.devices()]
        print(f"peak device memory (GB): "
              f"{[round(p / 1e9, 3) for p in peaks]}", flush=True)
    if not on_tpu:
        _failures.append(f"platform is {dev['platform']}, not tpu")
    if _failures:
        print(f"FAILED: {'; '.join(_failures)}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
