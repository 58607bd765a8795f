"""Compile the device path for a described TPU v5e, with no chip attached.

The TPU compiler ships with jaxlib, so it refuses here what the chip
would refuse: Mosaic block shapes off the (8, 128) tile, primitives with
no Pallas TPU lowering, kernels over the scoped VMEM limit, and a
``pallas_call`` under a ``shard_map`` that checks replication.  Nothing
runs; these tests say nothing about results or speed.

The topology is described inside a module fixture (only the worker that
runs this file loads the TPU library), and the persistent compilation
cache is off around these compiles: an entry written for a described
chip cannot be read back without one.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.dist.shard_refine import make_refine_fn
from repro.engine.backend import JnpBackend, PallasBackend, _pallas_grouped_solver
from repro.engine.layout import JNP_LAYOUT, PALLAS_LAYOUT
from repro.engine.yen_engine import grouped_solver
from repro.kernels.bf_relax import bf_relax

# one real bucket of the FLA-s smoke (36x36 grid, z=48): the jnp layout
# packs z to its 8-lane tile, the Pallas layout to the 128-lane tile
_S, _J = 8, 16
_Z_JNP = JNP_LAYOUT.align_z(48)
_Z_PALLAS = PALLAS_LAYOUT.align_z(48)


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"))


def _solve_args(S, J, z, sharding):
    """Shapes of one grouped solve: (adj, init, bv, so, bn, cap)."""
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32,  # noqa: E731
                                          sharding=sharding)
    mask = jax.ShapeDtypeStruct((S, J, z), jnp.bool_, sharding=sharding)
    return (f32(S, z, z), f32(S, J, z), mask, mask, mask, f32(S, J))


@pytest.mark.parametrize("S,J,z", [(8, 8, 128), (4, PALLAS_LAYOUT.j_max, 1024)],
                         ids=["small", "largest-J-at-z1024"])
def test_bf_relax_compiles(one_chip, S, J, z):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32,  # noqa: E731
                                          sharding=one_chip)
    compiled = jax.jit(
        lambda d, a, so, bn, cap: bf_relax(d, a, so, bn, cap)
    ).lower(f32(S, J, z), f32(S, z, z), f32(S, J, z), f32(S, J, z),
            f32(S, J)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_grouped_solver_compiles(one_chip):
    solver = _pallas_grouped_solver(_S, _J, _Z_PALLAS, False, donate=True)
    compiled = solver.lower(
        *_solve_args(_S, _J, _Z_PALLAS, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_jnp_grouped_solver_compiles(one_chip):
    solver = grouped_solver(_S, _J, _Z_JNP, donate=True)
    compiled = solver.lower(*_solve_args(_S, _J, _Z_JNP, one_chip)).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize(
    "backend", [JnpBackend(), PallasBackend(interpret=False)],
    ids=["jnp", "pallas"])
def test_mesh_refine_compiles(mesh4, backend):
    z = backend.layout.align_z(48)
    spec = NamedSharding(mesh4, P(("data", "model")))
    refine = make_refine_fn(mesh4, backend=backend)
    text = refine.lower(*_solve_args(_S, _J, z, spec)).compile().as_text()
    # the psum-any convergence flag is the one collective
    assert "all-reduce" in text
    if backend.name == "pallas":
        assert "tpu_custom_call" in text
