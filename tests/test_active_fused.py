"""Fused Pallas active-tile kernel (ISSUE 8): the PR 3 exactness
discipline over the Pallas engine.

The contracts under test, in interpret mode (tier-1's exactness mode —
the kernels trace to the same XLA ops the oracle runs):

- k=1 is BITWISE against both the dense XLA step and the XLA active
  path, at f64 and f32, across an activity sweep (0.5%–20%), including
  sharded ghost-flag activation and ensemble lanes;
- composed-k passes keep the exact iterated path on near-edge/frontier
  tiles (bitwise vs k dense steps there), interior tap tiles match
  algebraically, skipped tiles stay EXACTLY zero, and
  ``k · passes == substeps`` (degrading cleanly to k=1);
- the in-kernel flag computation is observable (``flags_fused``) and
  auditor-asserted (``jaxpr-fused-flags``), and the written-tile export
  keeps delta checkpoints (PR 6) working identically;
- the scheduler degradation ladder walks active_fused → active → xla.
"""

from __future__ import annotations

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mpi_model_tpu as mm
from mpi_model_tpu.core.cell import MOORE_OFFSETS
from mpi_model_tpu.models.model import SerialExecutor
from mpi_model_tpu.ops import active as act
from mpi_model_tpu.ops import pallas_active as pact


def point_space(g, dtype, sources=((64, 64, 1.7),)):
    v = np.zeros((g, g), np.float64)
    for x, y, a in sources:
        v[x, y] = a
    return mm.CellularSpace.create(g, g, 0.0, dtype=dtype).with_values(
        {"value": jnp.asarray(v, dtype)})


def blob_space(g, frac, dtype, seed=0):
    """A centered square blob covering ~``frac`` of the grid."""
    rng = np.random.default_rng(seed)
    side = max(1, int(g * np.sqrt(frac)))
    v = np.zeros((g, g), np.float64)
    lo = (g - side) // 2
    v[lo:lo + side, lo:lo + side] = rng.uniform(0.5, 1.5, (side, side))
    return mm.CellularSpace.create(g, g, 0.0, dtype=dtype).with_values(
        {"value": jnp.asarray(v, dtype)})


def dense_steps(space, model, n):
    out, _ = model.execute(space, SerialExecutor(step_impl="xla"),
                           steps=n, check_conservation=False)
    return np.asarray(out.values["value"])


# -- kernel-level parity ------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
def test_fused_pass_bitwise_vs_active_pass(dtype):
    h = w = 64
    plan = act.plan_for((h, w), tile=(16, 16), capacity=12)
    rng = np.random.default_rng(0)
    v = np.zeros((h, w))
    v[20:25, 20:25] = rng.uniform(0.5, 1.5, (5, 5))
    v = jnp.asarray(v, dtype)
    rate = 0.1
    tmap = act.tile_nonzero_map(v, plan)
    flags = act.dilate_tile_map(tmap)
    ids, count = act.compact_tile_ids(flags, plan)
    padded = jnp.pad(v, 1)
    upd = jnp.zeros((plan.capacity,) + plan.tile, dtype)

    ref_p, _, ref_anyf = jax.jit(
        lambda p, u, i, c: act.active_pass(
            p, u, i, c, rate, plan, (0, 0), (h, w), MOORE_OFFSETS,
            jnp.dtype(dtype)))(padded, upd, ids, count)
    selfnz = tmap.reshape(-1)[ids].astype(jnp.int32)
    got_p, got_anyf = jax.jit(
        lambda p, i, c, s: pact.fused_active_pass(
            p, i, c, s, rate, plan, jnp.zeros((2,), jnp.int32), (h, w),
            MOORE_OFFSETS, jnp.dtype(dtype)))(padded, ids, count, selfnz)
    assert np.array_equal(np.asarray(ref_p), np.asarray(got_p))
    assert np.array_equal(np.asarray(ref_anyf), np.asarray(got_anyf))


def test_fused_pass_empty_grid_is_identity():
    # count == 0: lane 0 still computes (tile 0 of a zero grid is zero),
    # so the aliased scatter never flushes an unwritten block
    plan = act.plan_for((32, 32), tile=(16, 16))
    padded = jnp.zeros((34, 34), jnp.float64)
    ids = jnp.zeros((plan.capacity,), jnp.int32)
    out, anyf = jax.jit(
        lambda p, i: pact.fused_active_pass(
            p, i, jnp.int32(0), jnp.zeros((plan.capacity,), jnp.int32),
            0.1, plan, jnp.zeros((2,), jnp.int32), (32, 32),
            MOORE_OFFSETS, jnp.float64))(padded, ids)
    assert not np.asarray(out).any() and not np.asarray(anyf).any()


def test_fused_pass_validation():
    plan = act.plan_for((32, 32), tile=(8, 8))
    padded = jnp.zeros((34, 34), jnp.float64)
    ids = jnp.zeros((plan.capacity,), jnp.int32)
    z = jnp.zeros((plan.capacity,), jnp.int32)
    with pytest.raises(ValueError, match="dilation exactness"):
        pact.fused_active_pass(padded, ids, jnp.int32(0), z, 0.1, plan,
                               jnp.zeros((2,), jnp.int32), (32, 32),
                               MOORE_OFFSETS, jnp.float64, k=9)
    with pytest.raises(ValueError, match="shallower"):
        pact.fused_active_pass(padded, ids, jnp.int32(0), z, 0.1, plan,
                               jnp.zeros((2,), jnp.int32), (32, 32),
                               MOORE_OFFSETS, jnp.float64, k=2, ring=1)


def test_choose_fused_k():
    plan = act.plan_for((64, 64), tile=(8, 8))
    assert pact.choose_fused_k(1, plan) == 1
    assert pact.choose_fused_k(8, plan) == 8
    assert pact.choose_fused_k(12, plan) == 6   # largest divisor <= 8
    assert pact.choose_fused_k(11, plan) == 1   # prime beyond the cap
    with pytest.raises(ValueError, match="substeps"):
        pact.choose_fused_k(0, plan)


# -- serial runner: the three-way bitwise sweep -------------------------------

@pytest.mark.parametrize("frac", [0.005, 0.02, 0.08, 0.2])
def test_runner_bitwise_activity_sweep(frac):
    # the acceptance sweep (0.5%–20%): fused == XLA active == dense,
    # bitwise at f64, with the engine genuinely active (no fallback)
    space = blob_space(120, frac, jnp.float64)
    model = mm.Model(mm.Diffusion(0.1), 1.0, 1.0)
    opts = {"tile": (24, 24), "max_active_frac": 1.0}
    ex_f = SerialExecutor(step_impl="active_fused", active_opts=opts)
    ex_a = SerialExecutor(step_impl="active", active_opts=opts)
    of, rf = model.execute(space, ex_f, steps=8, check_conservation=False)
    oa, _ = model.execute(space, ex_a, steps=8, check_conservation=False)
    od = dense_steps(space, model, 8)
    got = np.asarray(of.values["value"])
    assert np.array_equal(got, np.asarray(oa.values["value"]))
    assert np.array_equal(got, od)
    br = rf.backend_report
    assert br["impl"] == "active_fused" and br["fallback_steps"] == 0
    assert br["flags_fused"] == 8          # every pass flagged in-kernel
    assert 0.0 < br["mean_active_fraction"] <= 1.0


@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
def test_runner_bitwise_point_sources(dtype):
    space = point_space(96, dtype, sources=((48, 48, 1.7), (10, 13, 2.2)))
    model = mm.Model(mm.Diffusion(0.1), 1.0, 1.0)
    ex = SerialExecutor(step_impl="active_fused",
                        active_opts={"tile": (8, 8),
                                     "max_active_frac": 0.9})
    out, rep = model.execute(space, ex, steps=20, check_conservation=False)
    assert np.array_equal(np.asarray(out.values["value"]),
                          dense_steps(space, model, 20))
    assert ex.last_impl == "active_fused"
    assert rep.backend_report["fallback_steps"] == 0


def test_runner_quiet_ocean_stays_exactly_zero():
    space = point_space(96, jnp.float64, sources=((48, 48, 1.0),))
    model = mm.Model(mm.Diffusion(0.2), 1.0, 1.0)
    ex = SerialExecutor(step_impl="active_fused",
                        active_opts={"tile": (16, 16)})
    out, _ = model.execute(space, ex, steps=3, check_conservation=False)
    v = np.asarray(out.values["value"])
    assert (v[:40, :40] == 0.0).all() and (v[60:, :30] == 0.0).all()
    assert v[48, 48] != 0.0


def test_fallback_engages_matches_and_is_counted():
    # a fully-lit grid trips the activity threshold every pass: dense
    # fallback each time, flags_fused stays 0, and fb + ff == passes
    space = mm.CellularSpace.create(64, 64, 1.0, dtype=jnp.float64)
    model = mm.Model(mm.Diffusion(0.1), 1.0, 1.0)
    ex = SerialExecutor(step_impl="active_fused",
                        active_opts={"tile": (8, 8),
                                     "max_active_frac": 0.25})
    out, rep = model.execute(space, ex, steps=5, check_conservation=False)
    br = rep.backend_report
    assert br["fallback_steps"] == 5 and br["flags_fused"] == 0
    assert br["fallback_steps"] + br["flags_fused"] == br["passes"]
    assert np.array_equal(np.asarray(out.values["value"]),
                          dense_steps(space, model, 5))


def test_counter_identity_multi_channel():
    # the counters accumulate (attr, pass) pairs: with two live
    # channels, flags_fused + fallback_steps == passes × attrs
    rng = np.random.default_rng(5)
    va = np.zeros((64, 64)); va[10:14, 10:14] = rng.uniform(0.5, 1.5,
                                                            (4, 4))
    vb = np.zeros((64, 64)); vb[40:44, 40:44] = rng.uniform(0.5, 1.5,
                                                            (4, 4))
    space = mm.CellularSpace.create(
        64, 64, {"a": 0.0, "b": 0.0}, dtype=jnp.float64).with_values(
        {"a": jnp.asarray(va), "b": jnp.asarray(vb)})
    model = mm.Model([mm.Diffusion(0.1, attr="a"),
                      mm.Diffusion(0.3, attr="b")], 1.0, 1.0)
    ex = SerialExecutor(step_impl="active_fused",
                        active_opts={"tile": (8, 8),
                                     "max_active_frac": 0.9})
    out, rep = model.execute(space, ex, steps=6, check_conservation=False)
    br = rep.backend_report
    assert br["flags_fused"] + br["fallback_steps"] == br["passes"] * 2
    for k in ("a", "b"):
        ox, _ = model.execute(space, SerialExecutor(step_impl="xla"),
                              steps=6, check_conservation=False)
        assert np.array_equal(np.asarray(out.values[k]),
                              np.asarray(ox.values[k])), k


def test_capacity_overflow_falls_back_and_matches():
    space = point_space(96, jnp.float64)
    model = mm.Model(mm.Diffusion(0.1), 1.0, 1.0)
    ex = SerialExecutor(step_impl="active_fused",
                        active_opts={"tile": (8, 8), "capacity": 2})
    out, rep = model.execute(space, ex, steps=6, check_conservation=False)
    assert rep.backend_report["fallback_steps"] == 6
    assert np.array_equal(np.asarray(out.values["value"]),
                          dense_steps(space, model, 6))


# -- composed-k passes --------------------------------------------------------

def test_composed_k_exact_band_and_interior_tolerance():
    # k=4 via substeps: frontier and near-edge tiles keep the exact
    # iterated path (bitwise vs 8 dense steps); interior self-lit tiles
    # run the tap table (algebraic, ~k-ulp); mass is conserved exactly
    g, t = 96, 16
    space = blob_space(g, 0.02, jnp.float64, seed=3)
    corner = np.asarray(space.values["value"]).copy()
    rng = np.random.default_rng(4)
    corner[0:4, 0:4] = rng.uniform(0.5, 1.5, (4, 4))  # near-edge mass
    space = space.with_values({"value": jnp.asarray(corner)})
    model = mm.Model(mm.Diffusion(0.1), 1.0, 1.0)
    ex = SerialExecutor(step_impl="active_fused", substeps=4,
                        active_opts={"tile": (t, t),
                                     "max_active_frac": 1.0})
    out, rep = model.execute(space, ex, steps=8, check_conservation=False)
    br = rep.backend_report
    assert br["composed_k"] == 4 and br["passes"] == 2
    got = np.asarray(out.values["value"])
    want = dense_steps(space, model, 8)
    # the near-edge corner tile took the iterated path: bitwise
    assert np.array_equal(got[:t, :t], want[:t, :t])
    # everything matches to ~k ulps; mass conserved exactly enough
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-13)
    assert abs(got.sum() - want.sum()) < 1e-9
    # skipped tiles are EXACTLY zero under composed passes too
    assert (got[:t, 40:] == 0.0).all()


def test_composed_k_remainder_steps_stay_bitwise():
    # n % k remainder steps run depth-1 passes on the same buffer —
    # and depth-1 passes are bitwise, so a 10-step run at k=4 matches
    # dense everywhere EXCEPT interior tap tiles of the two full passes
    space = point_space(64, jnp.float64, sources=((32, 32, 1.7),))
    model = mm.Model(mm.Diffusion(0.1), 1.0, 1.0)
    ex = SerialExecutor(step_impl="active_fused", substeps=4,
                        active_opts={"tile": (16, 16),
                                     "max_active_frac": 1.0})
    out, rep = model.execute(space, ex, steps=10,
                             check_conservation=False)
    assert rep.backend_report["passes"] == 4  # 2 full + 2 remainder
    want = dense_steps(space, model, 10)
    np.testing.assert_allclose(np.asarray(out.values["value"]), want,
                               rtol=0, atol=1e-13)


def test_composed_k_degrades_to_one_with_warning():
    space = point_space(64, jnp.float64, sources=((32, 32, 1.0),))
    model = mm.Model(mm.Diffusion(0.1), 1.0, 1.0)
    with pytest.warns(RuntimeWarning, match="auto-k degenerated"):
        step = model.make_step(space, impl="active_fused", substeps=17)
    assert step.composed_k == 1 and step.composed_passes == 17


def test_make_step_composed_k_contract():
    space = point_space(64, jnp.float64, sources=((32, 32, 1.0),))
    model = mm.Model(mm.Diffusion(0.1), 1.0, 1.0)
    for substeps in (1, 4, 6):
        step = model.make_step(space, impl="active_fused",
                               substeps=substeps)
        assert step.impl == "active_fused"
        assert step.composed_k * step.composed_passes == substeps


# -- stateless make_step form -------------------------------------------------

def test_make_step_fused_bitwise_under_jit():
    space = point_space(96, jnp.float64)
    model = mm.Model(mm.Diffusion(0.1), 1.0, 1.0)
    step_f = jax.jit(model.make_step(space, impl="active_fused"))
    step_x = jax.jit(model.make_step(space, impl="xla"))
    vf, vx = dict(space.values), dict(space.values)
    for _ in range(10):
        vf, vx = step_f(vf), step_x(vx)
    assert np.array_equal(np.asarray(vf["value"]), np.asarray(vx["value"]))


def test_make_step_fused_composes_with_point_flows():
    space = point_space(96, jnp.float64)
    model = mm.Model([mm.Diffusion(0.1),
                      mm.Exponencial(mm.Cell(19, 3, mm.Attribute(99, 2.2)),
                                     0.1)], 1.0, 1.0)
    ex = SerialExecutor(step_impl="active_fused")
    out, rep = model.execute(space, ex, steps=8, check_conservation=False)
    ox, _ = model.execute(space, SerialExecutor(step_impl="xla"),
                          steps=8, check_conservation=False)
    assert ex.last_impl == "active_fused"
    assert np.array_equal(np.asarray(out.values["value"]),
                          np.asarray(ox.values["value"]))
    assert np.asarray(out.values["value"])[18, 3] != 0.0
    # the generic-loop path still reports k visibility honestly
    assert rep.backend_report["impl"] == "active_fused"
    with pytest.raises(ValueError, match="fire between sub-steps"):
        model.make_step(space, impl="active_fused", substeps=2)


def test_make_step_fused_partition_space():
    space = point_space(96, jnp.float64)
    part = space.slice_partition(mm.Partition(32, 0, 64, 96, rank=1))
    model = mm.Model(mm.Diffusion(0.1), 1.0, 1.0)
    pf = jax.jit(model.make_step(part, impl="active_fused"))
    px = jax.jit(model.make_step(part, impl="xla"))
    uf, ux = dict(part.values), dict(part.values)
    for _ in range(6):
        uf, ux = pf(uf), px(ux)
    assert np.array_equal(np.asarray(uf["value"]), np.asarray(ux["value"]))


def test_make_step_fused_rejects_ineligible_models():
    space = mm.CellularSpace.create(
        64, 64, {"a": 1.0, "b": 1.0}, dtype=jnp.float32)
    coupled = mm.Model([mm.Diffusion(0.1, attr="a"),
                        mm.Coupled(flow_rate=0.05, attr="a",
                                   modulator="b")], 1.0, 1.0)
    with pytest.raises(ValueError, match="plain\\s+Diffusion"):
        coupled.make_step(space, impl="active_fused")
    zero = mm.Model(mm.Diffusion(0.0), 1.0, 1.0)
    sp = mm.CellularSpace.create(64, 64, 1.0, dtype=jnp.float32)
    with pytest.raises(ValueError, match="nothing to step"):
        zero.make_step(sp, impl="active_fused")
    mixed = mm.CellularSpace.create(
        64, 64, {"aux": (1.0, "float32"), "value": (1.0, "float64")})
    with pytest.raises(ValueError, match="space dtype"):
        mm.Model(mm.Diffusion(0.1), 1.0, 1.0).make_step(
            mixed, impl="active_fused")


def test_all_point_models_route_to_point_subsystem():
    space = mm.CellularSpace.create(64, 64, 1.0, dtype=jnp.float64)
    model = mm.Model(
        mm.Exponencial(mm.Cell(19, 3, mm.Attribute(99, 2.2)), 0.1),
        10.0, 0.2)
    ex = SerialExecutor(step_impl="active_fused")
    model.execute(space, ex, steps=5)
    assert ex.last_impl == "point"


# -- sharded: ghost-flag activation preserved ---------------------------------

@pytest.mark.parametrize("mesh_shape", [(4, 1), (2, 2)])
def test_shardmap_fused_bitwise(eight_devices, mesh_shape):
    from mpi_model_tpu.parallel import ShardMapExecutor, make_mesh, \
        make_mesh_2d

    lines, cols = mesh_shape
    mesh = (make_mesh(lines, devices=eight_devices[:lines]) if cols == 1
            else make_mesh_2d(lines, cols,
                              devices=eight_devices[:lines * cols]))
    # sources near shard seams: cross-shard frontier arrival rides the
    # ghost ring and must activate the receiving shard's edge tiles
    space = point_space(64, jnp.float64,
                        sources=((31, 5, 1.7), (32, 32, 2.0), (0, 63, 1.1)))
    model = mm.Model(mm.Diffusion(0.1), 1.0, 1.0)
    ex = ShardMapExecutor(mesh, step_impl="active_fused")
    out = ex.run_model(model, space, 16)
    assert ex.last_impl == "active_fused"
    assert np.array_equal(np.asarray(out["value"]),
                          dense_steps(space, model, 16))
    br = ex.last_backend_report
    assert br["impl"] == "active_fused"
    assert br["shards"] == lines * cols
    # kernel-flagged + fallback (shard, attr, step) triples partition
    # the triple total — the psum'd observability contract
    assert (br["flags_fused"] + br["fallback_steps"]
            == 16 * br["shards"])
    assert 0.0 < br["mean_active_fraction"] <= 1.0


def test_shardmap_fused_dense_fallback_counted(eight_devices):
    from mpi_model_tpu.parallel import ShardMapExecutor, make_mesh

    mesh = make_mesh(4, devices=eight_devices[:4])
    rng = np.random.default_rng(7)
    v = rng.uniform(0.5, 1.5, (256, 256))
    space = mm.CellularSpace.create(256, 256, 0.0,
                                    dtype=jnp.float64).with_values(
        {"value": jnp.asarray(v, jnp.float64)})
    model = mm.Model(mm.Diffusion(0.1), 1.0, 1.0)
    ex = ShardMapExecutor(mesh, step_impl="active_fused")
    out = ex.run_model(model, space, 3)
    br = ex.last_backend_report
    assert br["fallback_steps"] == 3 * br["shards"]
    assert br["flags_fused"] == 0
    ex_x = ShardMapExecutor(mesh, step_impl="xla")
    want = ex_x.run_model(model, space, 3)
    assert np.array_equal(np.asarray(out["value"]),
                          np.asarray(want["value"]))


def test_shardmap_fused_validation(eight_devices):
    from mpi_model_tpu.parallel import ShardMapExecutor, make_mesh

    mesh = make_mesh(4, devices=eight_devices[:4])
    with pytest.raises(ValueError, match="halo_depth"):
        ShardMapExecutor(mesh, step_impl="active_fused", halo_depth=2)
    space = mm.CellularSpace.create(
        64, 64, {"a": 1.0, "b": 1.0}, dtype=jnp.float32)
    model = mm.Model([mm.Diffusion(0.1, attr="a"),
                      mm.Coupled(flow_rate=0.05, attr="a",
                                 modulator="b")], 1.0, 1.0)
    with pytest.raises(ValueError, match="plain Diffusion"):
        ShardMapExecutor(mesh, step_impl="active_fused").run_model(
            model, space, 2)


# -- ensemble lanes -----------------------------------------------------------

def test_ensemble_fused_matches_serial_per_lane():
    from mpi_model_tpu.ensemble import EnsembleExecutor

    spaces, models = [], []
    for i in range(3):
        spaces.append(point_space(48, jnp.float64,
                                  sources=((10 + 5 * i, 20, 1.0 + i),)))
        models.append(mm.Model(mm.Diffusion(0.05 + 0.02 * i), 1.0, 1.0))
    ex = EnsembleExecutor(impl="active_fused")
    outs = models[0].execute_many(spaces, models=models, executor=ex,
                                  steps=10)
    for i in range(3):
        want = dense_steps(spaces[i], models[i], 10)
        assert np.array_equal(
            np.asarray(outs[i][0].values["value"]), want), i
    assert ex.last_impl == "active_fused"
    br = ex.last_backend_report
    assert br["impl"] == "active_fused"
    assert br["flags_fused"] + br["fallback_steps"] == 3 * br["passes"]
    for sp, rep in outs:
        assert "flags_fused" in rep.backend_report


def test_ensemble_fused_composed_k_bitwise():
    # traced per-lane rates force the iterated path at every depth, so
    # composed-k ensemble lanes stay BITWISE vs the serial dense run
    from mpi_model_tpu.ensemble import EnsembleExecutor

    spaces = [point_space(48, jnp.float64, sources=((12, 12, 1.5),)),
              point_space(48, jnp.float64, sources=((30, 30, 2.5),))]
    model = mm.Model(mm.Diffusion(0.08), 1.0, 1.0)
    ex = EnsembleExecutor(impl="active_fused", substeps=3)
    outs = model.execute_many(spaces, executor=ex, steps=9)
    assert ex.last_backend_report["composed_k"] == 3
    for i, (sp, rep) in enumerate(outs):
        want = dense_steps(spaces[i], model, 9)
        assert np.array_equal(np.asarray(sp.values["value"]), want), i


def test_ensemble_fused_rejects_non_diffusion():
    from mpi_model_tpu.ensemble import EnsembleExecutor

    space = mm.CellularSpace.create(48, 48, 1.0, dtype=jnp.float64)
    model = mm.Model(
        mm.Exponencial(mm.Cell(19, 3, mm.Attribute(99, 2.2)), 0.1),
        1.0, 1.0)
    with pytest.raises(ValueError, match="all-Diffusion"):
        model.execute_many(
            [space], executor=EnsembleExecutor(impl="active_fused"),
            steps=2)


# -- degradation ladder (chaos) -----------------------------------------------

def test_scheduler_ladder_fused_to_active_to_xla():
    from mpi_model_tpu.ensemble.scheduler import EnsembleScheduler
    from mpi_model_tpu.resilience import inject
    from mpi_model_tpu.resilience.inject import Fault, FaultPlan

    def scen(i):
        return point_space(32, jnp.float64, sources=((8 + i, 8, 4.0),))

    model = mm.Model(mm.Diffusion(0.1), 1.0, 1.0)
    sch = EnsembleScheduler(impl="active_fused", retry="solo",
                            max_batch=2, degrade_after=1)
    # dispatch 0: faulted batch; 1-2: solo recoveries; 3: second fault
    plan = FaultPlan((Fault("batch_exc", at=0), Fault("batch_exc", at=3)))
    with inject.armed(plan):
        with pytest.warns(RuntimeWarning, match="degraded to 'active'"):
            a = sch.submit(scen(0), model, steps=4)
            b = sch.submit(scen(1), model, steps=4)
            ra, rb = sch.poll(a), sch.poll(b)
        assert sch.stats()["impl"] == "active"
        with pytest.warns(RuntimeWarning, match="degraded to 'xla'"):
            c = sch.submit(scen(2), model, steps=4)
            d = sch.submit(scen(3), model, steps=4)
            rc, rd = sch.poll(c), sch.poll(d)
    st = sch.stats()
    assert st["impl"] == "xla"
    assert st["degraded_from"] == "active_fused"
    assert all(r is not None for r in (ra, rb, rc, rd))
    for res in (ra, rc):
        assert res[1].backend_report["degraded_from"] == "active_fused"


# -- dirty-tile checkpoint parity (PR 6) --------------------------------------

def test_fused_dirty_export_matches_active(tmp_path):
    space = point_space(96, jnp.float64, sources=((48, 48, 1.7),))
    model = mm.Model(mm.Diffusion(0.1), 1.0, 1.0)
    opts = {"tile": (8, 8), "max_active_frac": 0.9}
    ex_f = SerialExecutor(step_impl="active_fused", active_opts=opts)
    ex_a = SerialExecutor(step_impl="active", active_opts=opts)
    model.execute(space, ex_f, steps=10, check_conservation=False)
    model.execute(space, ex_a, steps=10, check_conservation=False)
    df, da = ex_f.last_dirty_tiles, ex_a.last_dirty_tiles
    assert df is not None and df["tile"] == da["tile"]
    assert np.array_equal(df["map"], da["map"])


def test_fused_delta_checkpoint_roundtrip(tmp_path):
    # the fused executor's written-tile export feeds delta checkpoints
    # identically: save via supervised_run, restore, bitwise compare
    import json

    from mpi_model_tpu.io import CheckpointManager
    from mpi_model_tpu.resilience import supervised_run

    space = point_space(48, jnp.float64, sources=((24, 24, 1.7),))
    model = mm.Model(mm.Diffusion(0.1), 1.0, 1.0)
    ex = SerialExecutor(step_impl="active_fused",
                        active_opts={"tile": (8, 8),
                                     "max_active_frac": 0.9})
    mgr = CheckpointManager(str(tmp_path), keep=100, layout="delta",
                            keyframe_every=8, delta_tile=(8, 8))
    res = supervised_run(model, space, mgr, steps=8, every=2,
                         executor=ex)
    ck = mgr.latest()
    assert ck.step == 8
    assert np.array_equal(np.asarray(ck.space.values["value"]),
                          np.asarray(res.space.values["value"]))
    # deltas actually happened (not all keyframes degraded)
    with open(mgr._chain.manifest_path) as f:
        kinds = [r["kind"] for r in json.load(f)["records"]]
    assert "delta" in kinds


# -- auditor contracts --------------------------------------------------------

def test_jaxpr_goldens_for_fused_impls():
    from mpi_model_tpu.analysis.jaxpr_audit import (CONTRACTS,
                                                    audit_built)

    built = CONTRACTS["active_fused"]()
    assert built.composed_k * built.composed_passes == built.substeps
    assert built.expect_prefetch_arg
    assert audit_built(built) == []
    runner = CONTRACTS["active_fused_runner"]()
    assert runner.fused_flags_tile_elems is not None
    assert audit_built(runner) == []


def test_jaxpr_fused_flags_rule_distinguishes_xla_runner():
    # the XLA active runner reduces over whole tiles in its per-step
    # loop (the per-lane any-nonzero); the fused runner must not — the
    # rule's reduction scan is what enforces the difference
    from mpi_model_tpu.analysis import jaxpr_audit as ja
    from mpi_model_tpu.ops.active import build_active_runner

    plan = act.plan_for((64, 64), tile=(16, 16))
    run = build_active_runner((64, 64), {"value": 0.1}, MOORE_OFFSETS,
                              jnp.float64, plan=plan)
    closed = jax.make_jaxpr(run)(
        {"value": jax.ShapeDtypeStruct((64, 64), np.dtype("float64"))},
        jax.ShapeDtypeStruct((), np.dtype("int32")))
    hits = []
    for eqn in ja._iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "while":
            continue
        body = eqn.params["body_jaxpr"].jaxpr
        if ja._has_eqn(body, lambda e: e.primitive.name == "while"):
            continue
        hits.extend(ja._grid_reductions(body, 16 * 16))
    assert hits  # the XLA runner's tile-size reduction is visible

    # ... and the fused runner's innermost loops are clean
    frun = pact.build_fused_runner((64, 64), {"value": 0.1},
                                   MOORE_OFFSETS, jnp.float64, plan=plan)
    fclosed = jax.make_jaxpr(frun)(
        {"value": jax.ShapeDtypeStruct((64, 64), np.dtype("float64"))},
        jax.ShapeDtypeStruct((), np.dtype("int32")))
    for eqn in ja._iter_eqns(fclosed.jaxpr):
        if eqn.primitive.name != "while":
            continue
        body = eqn.params["body_jaxpr"].jaxpr
        if not ja._has_eqn(body,
                           lambda e: "pallas" in e.primitive.name):
            continue
        if ja._has_eqn(body, lambda e: e.primitive.name == "while"):
            continue
        assert list(ja._grid_reductions(body, 16 * 16)) == []


# -- persistent compile cache -------------------------------------------------

def test_configure_compile_cache(tmp_path, monkeypatch):
    from mpi_model_tpu.utils.compile_cache import (configure_compile_cache,
                                                   configured_dir)

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert configure_compile_cache(None) is None
    d = tmp_path / "cc"
    got = configure_compile_cache(str(d))
    assert got == str(d) and d.is_dir()
    assert configured_dir() == str(d)
    # idempotent re-point
    assert configure_compile_cache(str(d)) == str(d)
    # a jitted call actually lands entries in the armed directory
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
    assert any(d.iterdir())


@pytest.mark.slow
def test_compile_cache_populates_across_processes(tmp_path):
    # ISSUE 8 satellite: a SECOND process must be served from the cache
    # the first one populated — same program, no new cache entries
    import subprocess
    import sys as _sys

    cache = tmp_path / "cc"
    prog = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import jax, jax.numpy as jnp\n"
        "from mpi_model_tpu.utils.compile_cache import "
        "configure_compile_cache\n"
        f"configure_compile_cache({str(cache)!r})\n"
        "import mpi_model_tpu as mm\n"
        "from mpi_model_tpu.models.model import SerialExecutor\n"
        "s = mm.CellularSpace.create(32, 32, 1.0, dtype=jnp.float32)\n"
        "m = mm.Model(mm.Diffusion(0.1), 1.0, 1.0)\n"
        "m.execute(s, SerialExecutor(step_impl='active_fused'), steps=2,"
        " check_conservation=False)\n"
        "print('OK')\n"
    )
    env = dict(__import__("os").environ)
    env.pop("JAX_ENABLE_X64", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r1 = subprocess.run([_sys.executable, "-c", prog], env=env,
                        capture_output=True, text=True, timeout=300)
    assert r1.returncode == 0, r1.stderr[-2000:]
    files1 = sorted(p.name for p in cache.iterdir()
                    if p.name.endswith("-cache"))
    assert files1, "first process populated no cache entries"
    r2 = subprocess.run([_sys.executable, "-c", prog], env=env,
                        capture_output=True, text=True, timeout=300)
    assert r2.returncode == 0, r2.stderr[-2000:]
    files2 = sorted(p.name for p in cache.iterdir()
                    if p.name.endswith("-cache"))
    # the second process compiled nothing new: every executable came
    # out of the shared cache
    assert files2 == files1


# -- CLI ----------------------------------------------------------------------

def test_cli_impl_active_fused(capsys):
    import json

    from mpi_model_tpu.cli import main

    rc = main(["run", "--flow=diffusion", "--impl=active_fused",
               "--dimx=48", "--dimy=48", "--steps=3", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["conserved"] and out["impl"] == "active_fused"


def test_cli_ensemble_impl_active_fused(capsys):
    import json

    from mpi_model_tpu.cli import main

    rc = main(["run", "--flow=diffusion", "--ensemble=2",
               "--ensemble-impl=active_fused", "--dimx=48", "--dimy=48",
               "--steps=3", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["conserved"] and out["impl"] == "active_fused"


def test_cli_compile_cache_flag(tmp_path, capsys, monkeypatch):
    from mpi_model_tpu.cli import main

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    d = tmp_path / "cc"
    rc = main(["run", "--flow=diffusion", "--impl=active_fused",
               "--dimx=48", "--dimy=48", "--steps=1",
               f"--compile-cache={d}", "--json"])
    capsys.readouterr()
    assert rc == 0 and d.is_dir() and any(d.iterdir())
