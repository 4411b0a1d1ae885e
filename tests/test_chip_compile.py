"""Compile the main path for a TPU v5e that is described, not attached.

The Pallas gram/xtv kernels and the block-masked spmm wrappers are
compiled at the paper's width (100K x 1K, padded to the 512 x 256 tiles:
100,352 x 1,024) from float64 operands with x64 on, as the DSL calls
them; the float64 solve of the normal equations is compiled on one chip
and on a four-chip mesh. Nothing runs, so this proves only that the
chip's compiler accepts these programs.

The topology is described inside a module fixture, never at import: only
the worker that runs this file loads the TPU library. The persistent
compile cache is off around these compiles, since a TPU executable
written to it cannot be read back without the chip.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core  # noqa: F401  (x64 on, as at runtime)
from repro.core import backend
from repro.kernels.gram import ops as gram_ops
from repro.kernels.spmm import ops as spmm_ops

M, N = 100_352, 1_024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _f64(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float64, sharding=sharding)


KERNELS = {
    "gram": (lambda x: gram_ops.gram(x, use_pallas=True), [(M, N)]),
    "xtv": (lambda x, v: gram_ops.xtv(x, v, use_pallas=True),
            [(M, N), (M, 1)]),
    "gram_dense_masked": (spmm_ops.gram_dense_masked, [(M, N)]),
    "xtv_dense_masked": (spmm_ops.xtv_dense_masked, [(M, N), (M, 1)]),
    "spmm_dense_masked": (spmm_ops.spmm_dense_masked, [(M, N), (N, 1)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    compiled = jax.jit(fn).lower(
        *(_f64(s, one_chip) for s in shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_f64_solve_compiles_for_v5e(one_chip):
    solve = backend.get_kernel("solve", {})
    jax.jit(solve).lower(_f64((N, N), one_chip),
                         _f64((N, 1), one_chip)).compile()


def test_f64_solve_compiles_on_v5e_mesh(topo):
    # a replicated float64 solve on four chips: what the sharded lmDS
    # and the config-sharded grid run after their collectives
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(4, 1),
                ("data", "config"))
    rep = NamedSharding(mesh, PartitionSpec())
    solve = backend.get_kernel("solve", {})
    jax.jit(solve).lower(_f64((128, 128), rep), _f64((128, 1), rep)).compile()
