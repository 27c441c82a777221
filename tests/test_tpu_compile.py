"""The main path's kernels compiled at real widths for a described TPU
v5e, without a chip: what Mosaic or XLA:TPU refuses (tile alignment,
VMEM, memory) fails here at no chip time. Nothing runs; results and
times come only from ``chip_smoke.py`` on the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this
file."""

import jax
import jax.numpy as jnp
import pytest

from mpi_model_tpu import Coupled, Diffusion
from mpi_model_tpu.core.cell import MOORE_OFFSETS


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # analysis: ignore[broad-except] — no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compile(fn, *args, kernels=()):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the Pallas kernel is in the program
    # each kernel's op carries its stable name in the device's trace
    for name in kernels:
        assert f"%{name}." in text, name


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_dense_kernel(one_chip, dtype):
    from mpi_model_tpu.ops.pallas_stencil import pallas_dense_step

    _compile(lambda v: pallas_dense_step(v, 0.1, interpret=False),
             _sds((16384, 16384), dtype, one_chip), kernels=["mm_stencil"])


def test_field_kernel(one_chip):
    from mpi_model_tpu.ops.pallas_stencil import PallasFieldStep

    grid = _sds((8192, 8192), "float32", one_chip)
    step = PallasFieldStep(
        grid.shape, [Diffusion(0.1, attr="a"),
                     Coupled(flow_rate=0.05, attr="a", modulator="b"),
                     Diffusion(0.2, attr="b")], interpret=False)
    _compile(lambda a, b: step({"a": a, "b": b}), grid, grid,
             kernels=["mm_field"])


def test_composed_vpu_kernel(one_chip):
    from mpi_model_tpu.ops.composed_stencil import composed_dense_step

    _compile(lambda v: composed_dense_step(v, 0.1, 2, variant="vpu",
                                           interpret=False),
             _sds((16384, 16384), "bfloat16", one_chip))


def test_halo_shard_kernel(one_chip):
    """One shard of the 2x2-mesh 24576² cell: a depth-4 ring, 4 fused
    steps per exchange."""
    from mpi_model_tpu.ops.pallas_stencil import pallas_halo_step

    h = w = 12288
    d = 4
    f32 = "float32"
    ring = {"n": _sds((d, w), f32, one_chip), "s": _sds((d, w), f32, one_chip),
            "w": _sds((h, d), f32, one_chip), "e": _sds((h, d), f32, one_chip),
            **{c: _sds((d, d), f32, one_chip)
               for c in ("nw", "ne", "sw", "se")}}
    _compile(lambda v, r, o: pallas_halo_step(v, r, o, (2 * h, 2 * w), 0.1,
                                              interpret=False, nsteps=d),
             _sds((h, w), f32, one_chip), ring,
             _sds((2,), "int32", one_chip), kernels=["mm_stencil_halo"])


@pytest.mark.parametrize("dtype, k, ring", [
    ("float32", 1, 1), ("float32", 2, 2), ("float32", 1, 2),
    ("bfloat16", 1, 1), ("bfloat16", 2, 2)])
def test_fused_active_kernel(one_chip, dtype, k, ring):
    """The compute + scatter pair at the 16384² plan: k=2 takes the
    composed tap path, ring > k is the runner's remainder pass."""
    from mpi_model_tpu.ops.active import plan_for
    from mpi_model_tpu.ops.pallas_active import fused_active_pass

    grid = _sds((16384, 16384), dtype, one_chip)
    plan = plan_for(grid.shape)
    lanes = _sds((plan.capacity,), "int32", one_chip)
    _compile(lambda p, ids, c, s, o: fused_active_pass(
                 p, ids, c, s, 0.1, plan, o, grid.shape, MOORE_OFFSETS,
                 jnp.dtype(dtype), k=k, ring=ring, interpret=False),
             _sds((16384 + 2 * ring, 16384 + 2 * ring), dtype, one_chip),
             lanes, _sds((), "int32", one_chip), lanes,
             _sds((2,), "int32", one_chip),
             kernels=["mm_active_compute", "mm_active_scatter"])
