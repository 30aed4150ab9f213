"""The BBCSR Pallas kernels compile for a TPU v5e chip.

Interpret mode on CPU runs the kernel bodies as plain jnp, so it cannot see
what the chip's compiler (Mosaic) refuses: block shapes whose last two dims
are not tile-aligned, relayouts it cannot lower.  These tests ask the TPU
compiler installed with jax to compile each kernel for a described (not
attached) v5e chip, at the default tile shape on an operand of real width,
and check that the kernel is in the program as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers import every
test file.  All such compiles live in this one file for the same reason.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import rmat, to_bbcsr
from repro.kernels import spmv_dma as K


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def operand():
    """A^T of an RMAT-14 graph in BBCSR at the default tile shape (what
    `repro.tune` resolves on a TPU, which has no tuned entry yet)."""
    return to_bbcsr(rmat(14, 16, seed=7).transpose())


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("kind", ["spmv", "spmspv_add", "spmspv_min"])
def test_bbcsr_kernel_compiles_for_v5e(kind, one_chip, operand):
    bb = operand
    assert (bb.block_rows, bb.block_cols, bb.tile_nnz) == (256, 512, 512)
    assert bb.rows_local.shape == (bb.n_tiles, 1, bb.tile_nnz)
    x = jax.ShapeDtypeStruct((bb.n_cols,), jnp.float32, sharding=one_chip)
    act = jax.ShapeDtypeStruct((bb.n_tiles,), jnp.int32, sharding=one_chip)
    bb_abs = _abstract(bb, one_chip)
    if kind == "spmv":
        fn = lambda b, v, a: K.spmv_bbcsr_kernel_call(b, v, interpret=False)
    else:
        combine = kind.split("_")[1]
        fn = lambda b, v, a: K.spmspv_bbcsr_kernel_call(
            b, v, a, combine=combine, interpret=False)
    compiled = jax.jit(fn).lower(bb_abs, x, act).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.out_info
    assert out.shape == (bb.n_rows,) and out.dtype == np.float32
