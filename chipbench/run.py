#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; it names a
configuration (``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/traffic/<traffic>.json``), whose ``driver`` is the module
``chipbench/drivers/<driver>.py``.  The per-layer metrics listed for the cell
are read by ``chipbench/metrics/<metric>.py``.  Adding a cell, a mix, a
configuration or a metric is adding files and entries; nothing here names
one.

The run fails, printing no result, unless JAX finds TPU devices, exactly as
many as the cell's ``chips``.  Set-up (graph, program build, warm-up of the
cell's own programs) ends where the window starts; nothing compiles in the
window.  With ``--trace 1`` the window is traced and the
per-layer metrics are reported instead of the end-to-end ones.  After the
window the program's state is freed and every answer the window produced
is compared with the benchmark's own reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checked``, each compared number with its limit;
the same numbers are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_spec(name: str, root: str = ROOT) -> dict:
    """The cell's entry, configuration, mix and metrics, found by name."""
    bench = _load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(there are {sorted(cells)})")
    cell = cells[name]
    return {
        "cell": cell,
        "config": _load_json(HERE, "configs", f"{cell['config']}.json"),
        "traffic": _load_json(HERE, "traffic", f"{cell['traffic']}.json"),
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def read_metric(name: str, record: dict):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name}", os.path.join(HERE, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(record)


def _use_compile_cache(jax) -> None:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices_for(jax, chips: int):
    """The run's devices; raises unless they are exactly ``chips`` TPUs."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != chips:
        raise RuntimeError(
            f"the cell needs {chips} TPU chip(s); JAX finds {len(devs)} "
            f"{devs[0].platform} device(s) of kind {devs[0].device_kind!r}")
    return devs


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        spec: dict = None, require_tpu: bool = True, control: bool = False):
    """One run of the cell; returns the result line's object.  ``spec``
    replaces the files' cell (tests), ``require_tpu=False`` skips the look
    for a chip (tests), ``control`` puts the control's answers in place of
    the program's before the comparison."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    import jax
    from chipbench.harness import Harness

    spec = spec or cell_spec(workload)
    cell, config = spec["cell"], spec["config"]
    if require_tpu:
        _use_compile_cache(jax)
    devs = devices_for(jax, cell["chips"]) if require_tpu else jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    peaks = _load_json(HERE, "peaks.json")
    if require_tpu and dev["kind"] not in peaks:
        raise RuntimeError(f"no peaks for device kind {dev['kind']!r} in "
                           "chipbench/peaks.json")
    h = Harness(ROOT, trace)
    h.log(f"device: {dev['platform']}, {dev['kind']}, {dev['count']} "
          f"device(s); workload {workload}, seed {seed}, {seconds} s, "
          f"trace {int(trace)}")
    driver = importlib.import_module(
        f"chipbench.drivers.{spec['traffic']['driver']}")
    state = driver.setup(h, config, spec["traffic"], seed)
    setup_s = time.perf_counter() - T_START
    compiles0 = h.compiles
    h.start_trace()
    result = state.window(seconds)
    h.finish_trace()
    h.log(f"compiles in the window: {h.compiles - compiles0}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    dev["memory_peak_bytes"] = int(peak)
    h.log(f"memory_stats peak_bytes_in_use: {int(peak)} B")
    metrics = {}
    if trace:
        record = dict(result["record"], trace=h.traced,
                      peak=peaks.get(dev["kind"]))
        for m in spec["per_layer"]:
            v = read_metric(m["name"], record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if h.traced:
            dev["busy_s"] = h.traced["busy_s"]
            dev["window_s"] = h.traced["window_s"]
    else:
        values = dict(result["e2e"], setup_s=setup_s)
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    state.release()
    t = time.perf_counter()
    numbers, limits = state.check(result, control=control)
    h.log(f"check took {time.perf_counter() - t:.1f} s")
    from chipbench.check import verdict
    correct, shown = verdict(numbers, limits)
    out = {"correct": correct, "attempted": result["attempted"],
           "failed": result["failed"], "metrics": metrics, "device": dev}
    if trace and h.traced:
        out["breakdown"] = {"device_ops": h.traced["top_ops"],
                            "idle_gaps": h.traced["idle_gaps"]}
    out["checked"] = shown
    for k, v in shown.items():
        h.log(f"checked {k}: {v['value']} (limit {v['limit']})")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
