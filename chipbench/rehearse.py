#!/usr/bin/env python3
"""Compile a configuration's programs for a described TPU v5e; no chip needed.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --config graph500-1chip \
        --scales 16 17 18 19
    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --config svc-g500k-s16

For each scale (the configuration's own by default) it builds the graph the
benchmark serves, compiles the programs a cell of that configuration runs —
the served ``GraphService.program`` kinds, or the jitted single-source
``bfs`` and ``sssp`` — for one chip of a described ``v5e:2x2`` and prints
``compiled.memory_analysis()``, or the compiler's refusal.  Nothing runs, so
it gives no time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _gb(x: int) -> str:
    return f"{x / 1e9:.3f} GB"


def compile_report(label: str, fn, *args) -> None:
    import jax
    t = time.perf_counter()
    try:
        c = fn.lower(*args).compile()
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        print(f"  {label}: refused: {str(e).splitlines()[0][:300]}",
              flush=True)
        return
    m = c.memory_analysis()
    print(f"  {label}: args {_gb(m.argument_size_in_bytes)}, out "
          f"{_gb(m.output_size_in_bytes)}, temp {_gb(m.temp_size_in_bytes)}, "
          f"code {_gb(m.generated_code_size_in_bytes)}; compiled in "
          f"{time.perf_counter() - t:.1f} s", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--scales", type=int, nargs="*")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from chipbench import kronecker, system

    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(HERE, "configs", f"{args.config}.json")) as f:
        config = json.load(f)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on(tree, sharding):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    for scale in args.scales or [config["scale"]]:
        cfg = dict(config, scale=scale)
        rows, cols, w = kronecker.build_edges(cfg)
        n = 1 << scale
        csr = system.csr_of(n, rows, cols, w)
        deg = np.bincount(rows, minlength=n)
        print(f"{args.config} at scale {scale}: n {n}, nnz {csr.nnz}, max "
              f"degree {int(deg.max())}", flush=True)
        if "service" in cfg:
            svc = system.service_of(cfg, csr)
            for kind in ("reach", "dist", "ppr"):
                fn, shapes = svc.program(kind)
                compile_report(kind, fn, *on(shapes, one))
        else:
            from repro.core.algorithms import bfs, sssp
            from repro.core.algorithms.sssp import auto_delta
            g = on(jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype), csr.for_jit()), one)
            key = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
            delta = auto_delta(csr)
            compile_report("bfs", jax.jit(lambda g, s: bfs(g, s)), g, key)
            compile_report("sssp", jax.jit(
                lambda g, s: sssp(g, s, delta=delta)), g, key)
    return 0


if __name__ == "__main__":
    sys.exit(main())
