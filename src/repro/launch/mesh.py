"""Production meshes.

Single pod: 16x16 = 256 chips, axes ("data", "model").
Multi-pod:  2x16x16 = 512 chips, axes ("pod", "data", "model") — the pod axis
is the HyperX top level (optical links in PIUMA; ICI-over-DCN on TPU pods).

Defined as functions so importing this module never touches jax device state
(device count is locked at first jax init — dryrun.py sets
xla_force_host_platform_device_count BEFORE importing anything).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_cores_mesh", "HW"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_cores_mesh(n: int | None = None, name: str = "cores"):
    """1-D mesh over all available devices (graph-algorithm tests/benchmarks).

    The axis is ``Auto``: the graph code places data with ``shard_map`` specs
    and sharding constraints, which the ``Explicit`` default refuses."""
    n = n or len(jax.devices())
    return jax.make_mesh((n,), (name,), axis_types=(AxisType.Auto,))


# TPU v5e hardware constants for the roofline (per chip / per link)
HW = {
    "peak_bf16_flops": 197e12,   # FLOP/s
    "hbm_bw": 819e9,             # B/s
    "ici_bw": 50e9,              # B/s per link
    "hbm_per_chip": 16 * 2**30,  # bytes
}
