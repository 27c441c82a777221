"""The main path's kernels compiled at real widths for a described TPU
v5e, without a chip: what Mosaic or XLA:TPU refuses (tile alignment,
VMEM, memory) fails here at no chip time. Nothing runs; results and
times come only from ``chip_smoke.py`` on the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this
file."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_model_tpu import CellularSpace, Coupled, Diffusion, Model
from mpi_model_tpu.core.cell import MOORE_OFFSETS


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e.devices[0])


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


def _loop_bodies(text):
    """The instructions of each while loop's body computation."""
    comps, name = {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = line.split()[0]
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    return [comps[b] for b in set(re.findall(r"body=(%[\w.\-]+)", text))]


def _assert_paired_body(text, kernel, state):
    """The kernel passes run two to a loop iteration, and no loop body
    that does so copies the whole state (``state``, e.g.
    ``f32[24576,24576]``). One body may hold one pass and the copy: the
    odd pass, whose loop runs at most once per call."""
    state = re.escape(state)
    copies, passes = [], []
    for body in _loop_bodies(text):
        copies.append(sum(bool(re.search(rf"= {state}\S* copy\(", ln))
                          for ln in body))
        passes.append(sum(f"%{kernel}." in ln.split("=")[0] for ln in body))
    # (kernel calls, whole-state copies) of each loop body
    assert sorted(zip(passes, copies)) == [(1, 1), (2, 0)]


def test_dense_horizon_loop_copies_no_state(v5e, one_chip):
    """The one-chip horizon cell's runner (16384² bf16, 4 fused steps per
    Pallas pass): a loop of one pass per iteration copies the whole
    state before every pass, because the kernel cannot write into the
    carry it reads."""
    from mpi_model_tpu.models.model import _loop_runner

    n = 16384
    grid = _sds((n, n), "bfloat16", one_chip)
    space = CellularSpace({"value": grid}, *grid.shape)  # shapes only
    # make_step takes interpret mode from the default device
    with jax.default_device(v5e.devices[0]):
        step = Model(Diffusion(0.1)).make_step(space, impl="pallas",
                                               substeps=4)
    assert step.impl == "pallas"
    count = _sds((), "int32", one_chip)
    text = _loop_runner(step, None).lower(
        {"value": grid}, count, count).compile().as_text()
    _assert_paired_body(text, "mm_stencil", f"bf16[{n},{n}]")


def test_sharded_horizon_loop_copies_no_shard(v5e):
    """The four-chip horizon cell's runner (49152² f32 on a 2x2 mesh,
    halo depth 4): besides the carry copy, the ghost columns cut from
    the carry made XLA lay a transposed copy of the whole shard; the
    rings are now cut from each kernel's output and carried."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mpi_model_tpu.parallel import ShardMapExecutor
    from mpi_model_tpu.parallel.mesh import grid_spec

    n = 49152
    mesh = Mesh(np.array(v5e.devices).reshape(2, 2), ("x", "y"))
    grid = jax.ShapeDtypeStruct((n, n), jnp.float32,
                                sharding=NamedSharding(mesh, grid_spec(mesh)))
    space = CellularSpace({"value": grid}, *grid.shape)  # shapes only
    model = Model(Diffusion(0.1))
    ex = ShardMapExecutor(mesh, step_impl="pallas", halo_depth=4)
    runner = ex._build_pallas_runner(model, space,
                                     ex._pallas_plan(model, space))
    count = jax.ShapeDtypeStruct((), jnp.int32,
                                 sharding=NamedSharding(mesh, P()))
    text = runner.lower({"value": grid}, count).compile().as_text()
    _assert_paired_body(text, "mm_stencil_halo", f"f32[{n // 2},{n // 2}]")
