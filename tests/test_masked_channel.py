"""The int/bool half of the L0 seam, end to end (ISSUE 2 satellite): a
bool land-water mask channel stored beside float channels, halo-exchanged
under sharded execution, checkpointed and resumed — while ``make_step``
keeps rejecting non-float FLOWS (transport on an int/bool channel stays a
TypeError)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mpi_model_tpu import (
    CellularSpace,
    Coupled,
    Diffusion,
    Model,
)
from mpi_model_tpu import oracle
from mpi_model_tpu.ops.flow import Flow


def make_masked_scenario(g=32, dtype=jnp.float64, rate=0.2, seed=3):
    rng = np.random.default_rng(seed)
    space = CellularSpace.create(
        g, g, {"value": 1.0, "mask": (False, "bool")}, dtype=dtype)
    mask = np.zeros((g, g), dtype=bool)
    mask[g // 4: 3 * g // 4, g // 8: 7 * g // 8] = True
    v = rng.uniform(0.5, 2.0, (g, g))
    space = space.with_values({"value": jnp.asarray(v, dtype),
                               "mask": jnp.asarray(mask)})
    model = Model(Coupled(flow_rate=rate, attr="value", modulator="mask"),
                  1.0, 1.0)
    return space, model, v, mask


# -- storage: per-channel dtypes ---------------------------------------------

def test_create_per_channel_dtype():
    s = CellularSpace.create(
        8, 8, {"value": 1.5, "mask": (True, "bool"), "age": (0, "int32")})
    assert s.values["value"].dtype == jnp.float32
    assert s.values["mask"].dtype == jnp.bool_
    assert s.values["age"].dtype == jnp.int32
    assert bool(s.values["mask"][0, 0]) is True
    # the space's arithmetic dtype is the FLOAT channel's, regardless of
    # dict order
    s2 = CellularSpace.create(
        8, 8, {"mask": (False, "bool"), "value": (1.0, "float64")})
    assert s2.dtype == jnp.float64
    # totals: bool sums count Trues
    assert float(s.total("mask")) == 64.0


def test_make_step_keeps_rejecting_nonfloat_flows():
    s = CellularSpace.create(8, 8, {"value": 1.0, "mask": (True, "bool")})
    m = Model(Diffusion(0.1, attr="mask"), 1.0, 1.0)
    with pytest.raises(TypeError, match="floating dtype.*'mask'"):
        m.make_step(s)
    # an int space with a flow on the int channel is still refused
    si = CellularSpace.create(8, 8, 1, dtype=jnp.int32)
    with pytest.raises(TypeError, match="floating"):
        Model(Diffusion(0.1), 1.0, 1.0).make_step(si)
    # a flow on a channel the space lacks: the clear error, not a
    # KeyError deep inside jit tracing (same contract as the ensemble
    # path's make_scenario_step)
    with pytest.raises(ValueError, match="does not carry"):
        Model(Diffusion(0.1, attr="heat"), 1.0, 1.0).make_step(s)


# -- masked diffusion: serial ------------------------------------------------

def test_masked_diffusion_serial_matches_oracle_and_conserves():
    space, model, v, mask = make_masked_scenario()
    out, rep = model.execute(space, steps=3)
    # oracle: outflow = rate * value * mask, exact transport, 3 steps
    want = v.copy()
    for _ in range(3):
        want = oracle.transport_np(want, 0.2 * want * mask)
    np.testing.assert_allclose(np.asarray(out.values["value"]), want,
                               atol=1e-12, rtol=0)
    # the mask channel is storage: bit-identical, dtype preserved
    assert out.values["mask"].dtype == jnp.bool_
    np.testing.assert_array_equal(np.asarray(out.values["mask"]), mask)
    assert rep.conservation_error() < 1e-9
    # land cells shed nothing: a land cell with no water neighbor is
    # exactly unchanged
    far_land = np.asarray(out.values["value"])[0, 0]
    assert far_land == v[0, 0]


# -- halo exchange: sharded paths --------------------------------------------

def test_masked_diffusion_sharded_matches_serial(eight_devices,
                                                assert_gspmd_close):
    from mpi_model_tpu.parallel import (AutoShardedExecutor,
                                        ShardMapExecutor, make_mesh)

    space, model, v, mask = make_masked_scenario()
    want, _ = model.execute(space, steps=4)
    mesh = make_mesh(4, devices=eight_devices[:4])
    with jax.default_device(eight_devices[0]):
        got, rep = model.execute(space, ShardMapExecutor(mesh), steps=4)
        got_g, _ = model.execute(space, AutoShardedExecutor(mesh), steps=4)
    np.testing.assert_array_equal(np.asarray(got.values["value"]),
                                  np.asarray(want.values["value"]))
    assert_gspmd_close(got_g.values["value"], want.values["value"], 4)
    for out in (got, got_g):
        assert out.values["mask"].dtype == jnp.bool_
        np.testing.assert_array_equal(np.asarray(out.values["mask"]), mask)


class NeighborMaskedDiffusion(Flow):
    """ring1 masked flow: a water cell sheds only when it has at least
    one WATER neighbor — reads the bool mask channel's 3x3 neighborhood,
    so the mask itself must ride the halo exchange."""

    footprint = "ring1"

    def __init__(self, flow_rate=0.2, attr="value", mask_attr="mask"):
        self.flow_rate = flow_rate
        self.attr = attr
        self.mask_attr = mask_attr

    def outflow_padded(self, padded, origin=(0, 0)):
        v = padded[self.attr]
        m = padded[self.mask_attr].astype(v.dtype)
        h, w = v.shape[0] - 2, v.shape[1] - 2
        nbr_water = sum(
            m[1 + dx:1 + dx + h, 1 + dy:1 + dy + w]
            for dx in (-1, 0, 1) for dy in (-1, 0, 1)
            if (dx, dy) != (0, 0))
        inner_v = v[1:-1, 1:-1]
        inner_m = m[1:-1, 1:-1]
        return (self.flow_rate * inner_v * inner_m
                * (nbr_water > 0).astype(v.dtype))


def test_bool_mask_rides_the_halo_exchange(eight_devices):
    """The ring1 flow reads mask NEIGHBORS, so sharded execution must
    halo-exchange the bool channel itself; matching the serial full-grid
    run proves the exchanged ghost masks carried real neighbor values."""
    from mpi_model_tpu.parallel import ShardMapExecutor, make_mesh

    space, _, v, mask = make_masked_scenario()
    model = Model(NeighborMaskedDiffusion(0.2), 1.0, 1.0)
    want, _ = model.execute(space, steps=3)
    with jax.default_device(eight_devices[0]):
        got, _ = model.execute(
            space, ShardMapExecutor(make_mesh(4,
                                              devices=eight_devices[:4])),
            steps=3)
    np.testing.assert_allclose(np.asarray(got.values["value"]),
                               np.asarray(want.values["value"]),
                               atol=1e-12, rtol=0)
    assert got.values["mask"].dtype == jnp.bool_


def test_deep_halo_refuses_nonfloat_channels_clearly(eight_devices):
    """halo_depth > 1 with general pointwise flows masks every channel
    in the flow dtype — a bool channel would be silently float-ified, so
    the executor refuses with a clear error instead."""
    from mpi_model_tpu.parallel import ShardMapExecutor, make_mesh

    space, model, _, _ = make_masked_scenario()
    ex = ShardMapExecutor(make_mesh(4, devices=eight_devices[:4]),
                          halo_depth=2)
    with pytest.raises(ValueError, match="non-float channels.*mask"):
        ex.run_model(model, space, 4)


# -- checkpoint / resume -----------------------------------------------------

def test_bool_channel_checkpoint_roundtrip(tmp_path):
    from mpi_model_tpu.io import load_checkpoint, save_checkpoint

    space, _, v, mask = make_masked_scenario()
    p = str(tmp_path / "ck.npz")
    save_checkpoint(p, space, step=5, extra={"note": "lake"})
    ck = load_checkpoint(p)
    assert ck.step == 5
    assert ck.space.values["mask"].dtype == jnp.bool_
    np.testing.assert_array_equal(np.asarray(ck.space.values["mask"]),
                                  mask)
    np.testing.assert_array_equal(np.asarray(ck.space.values["value"]),
                                  np.asarray(space.values["value"]))


def test_masked_run_resumes_bit_identical(tmp_path):
    from mpi_model_tpu.io import CheckpointManager, run_checkpointed

    space, model, _, mask = make_masked_scenario()
    want, _, _ = run_checkpointed(
        model, space, CheckpointManager(str(tmp_path / "a")),
        steps=6, every=2)
    # interrupted at 4, resumed to 6 from the on-disk checkpoint
    d = str(tmp_path / "b")
    run_checkpointed(model, space, CheckpointManager(d), steps=4, every=2)
    got, step, _ = run_checkpointed(
        model, space, CheckpointManager(d), steps=6, every=2)
    assert step == 6
    np.testing.assert_array_equal(np.asarray(got.values["value"]),
                                  np.asarray(want.values["value"]))
    assert got.values["mask"].dtype == jnp.bool_
    np.testing.assert_array_equal(np.asarray(got.values["mask"]), mask)
