"""Mesh-sharded ensemble tests (ISSUE 16 tentpole): the (batch × space)
device mesh under the ensemble engine — bitwise-at-f64 parity of the
mesh-sharded dispatch against the single-device ensemble AND the
per-scenario serial path (diffusion and Gray-Scott both), the
scheduler's pad-to-(bucket × mesh) round-up (honest padding waste,
inert pads, flush ordering unchanged), the mesh-parameterized runner
cache (a mesh change REBUILDS; an equal-shape mesh hits), and the
wire-safe (batch, space) spec resolution."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mpi_model_tpu import (
    CellularSpace,
    Diffusion,
    EnsembleExecutor,
    EnsembleScheduler,
    Model,
)
from mpi_model_tpu.ensemble import (
    EnsembleSpace,
    make_ensemble_mesh,
    resolve_ensemble_mesh,
    run_ensemble,
)
from mpi_model_tpu.ir.library import build_model
from mpi_model_tpu.models.model import SerialExecutor


def make_scenarios(B=3, g=16, dtype=jnp.float64, seed=0, base_rate=0.05):
    rng = np.random.default_rng(seed)
    spaces, models = [], []
    for i in range(B):
        v = rng.uniform(0.5, 2.0, (g, g))
        spaces.append(CellularSpace.create(g, g, 1.0, dtype=dtype)
                      .with_values({"value": jnp.asarray(v, dtype)}))
        models.append(Model(Diffusion(base_rate + 0.03 * i), 1.0, 1.0))
    return spaces, models


def bitwise(a, b) -> bool:
    return np.array_equal(np.asarray(a), np.asarray(b))


# -- EnsembleMesh unit surface ------------------------------------------------

def test_mesh_round_up_and_validate(eight_devices):
    m = make_ensemble_mesh(batch=3, devices=eight_devices[:3])
    assert m.batch == 3 and m.space == 1
    assert [m.round_up(k) for k in (1, 2, 3, 4, 6, 7)] == [3, 3, 3, 6, 6, 9]
    m.validate(6, (16, 16))  # divisible: fine
    with pytest.raises(ValueError, match="multiple of the mesh batch"):
        m.validate(4, (16, 16))
    m2 = make_ensemble_mesh(batch=2, space=2, devices=eight_devices[:4])
    assert m2.batch == 2 and m2.space == 2
    with pytest.raises(ValueError, match="space"):
        m2.validate(2, (15, 16))  # rows not divisible by space=2


def test_mesh_spec_resolution(eight_devices):
    assert resolve_ensemble_mesh(None) is None
    m = resolve_ensemble_mesh(2)  # the wire form: a batch extent
    assert (m.batch, m.space) == (2, 1)
    m = resolve_ensemble_mesh((2, 2))  # the wire form: (batch, space)
    assert (m.batch, m.space) == (2, 2)
    assert resolve_ensemble_mesh(m) is m  # already-built passes through
    with pytest.raises(ValueError):
        make_ensemble_mesh(batch=len(jax.devices("cpu")) + 1)


# -- bitwise-at-f64 parity: mesh == single-device == serial ------------------

def test_mesh_diffusion_bitwise_vs_single_device_and_serial(eight_devices):
    spaces, models = make_scenarios(B=8)
    ref = run_ensemble(models[0], spaces, models=models,
                       executor=EnsembleExecutor(), steps=5)
    emesh = make_ensemble_mesh(batch=4, devices=eight_devices[:4])
    got = run_ensemble(models[0], spaces, models=models,
                       executor=EnsembleExecutor(mesh=emesh), steps=5)
    ser = SerialExecutor(step_impl="xla")
    for i in range(8):
        want, wrep = models[i].execute(spaces[i], ser, steps=5)
        assert bitwise(got[i][0].values["value"], ref[i][0].values["value"])
        assert bitwise(got[i][0].values["value"], want.values["value"])
        # the stat/conservation lanes reduce over the SPACE axes on a
        # sharded [B,H,W] batch — the totals must still be bitwise
        assert float(got[i][1].final_total["value"]) == \
            float(ref[i][1].final_total["value"])
        assert float(got[i][1].final_total["value"]) == \
            float(wrep.final_total["value"])


def test_mesh_2d_batch_space_bitwise(eight_devices, assert_gspmd_close):
    """The full 2-D layout: batch AND space both sharded. Space sharding
    partitions the step, so values are held to the GSPMD bound."""
    spaces, models = make_scenarios(B=4)
    ref = run_ensemble(models[0], spaces, models=models,
                       executor=EnsembleExecutor(), steps=4)
    emesh = make_ensemble_mesh(batch=2, space=2,
                               devices=eight_devices[:4])
    got = run_ensemble(models[0], spaces, models=models,
                       executor=EnsembleExecutor(mesh=emesh), steps=4)
    for i in range(4):
        assert_gspmd_close(got[i][0].values["value"],
                           ref[i][0].values["value"], 4)
        assert_gspmd_close(got[i][1].final_total["value"],
                           ref[i][1].final_total["value"], 4)


def test_mesh_gray_scott_bitwise(eight_devices):
    """The nonlinear two-channel workload: mesh == single-device ==
    serial, bitwise at f64, values AND totals, per lane."""
    model, space = build_model("gray_scott", 16, dtype=jnp.float64)
    models = [model.with_rates([r * (1.0 + 0.05 * i)
                                for r in model.term_rates()])
              for i in range(4)]
    spaces = []
    for i in range(4):
        vals = {k: jnp.asarray(np.roll(np.asarray(v), i, axis=0),
                               jnp.float64)
                for k, v in space.values.items()}
        spaces.append(space.with_values(vals))
    ref = run_ensemble(models[0], spaces, models=models,
                       executor=EnsembleExecutor(), steps=6)
    emesh = make_ensemble_mesh(batch=2, devices=eight_devices[:2])
    got = run_ensemble(models[0], spaces, models=models,
                       executor=EnsembleExecutor(mesh=emesh), steps=6)
    for i in range(4):
        want, wrep = models[i].execute(spaces[i], steps=6)
        for k in ("u", "v"):
            assert bitwise(got[i][0].values[k], ref[i][0].values[k])
            assert bitwise(got[i][0].values[k], want.values[k])
            assert float(got[i][1].final_total[k]) == \
                float(wrep.final_total[k])


def test_mesh_indivisible_batch_names_the_padding_protocol(eight_devices):
    spaces, models = make_scenarios(B=3)
    emesh = make_ensemble_mesh(batch=2, devices=eight_devices[:2])
    with pytest.raises(ValueError, match="pad the scenario"):
        run_ensemble(models[0], spaces, models=models,
                     executor=EnsembleExecutor(mesh=emesh), steps=2)


def test_mesh_rejects_non_xla_impls(eight_devices):
    emesh = make_ensemble_mesh(batch=2, devices=eight_devices[:2])
    with pytest.raises(ValueError, match="impl='xla' only"):
        EnsembleExecutor(impl="pipeline", mesh=emesh)


# -- the mesh-parameterized runner cache (satellite 2 regression) ------------

def test_runner_cache_rebuilds_on_mesh_change(eight_devices):
    """Review regression: the runner cache key carries the mesh token —
    changing the mesh MUST rebuild (a stale runner would pin the old
    sharding), while an equal-shape mesh over the same devices hits."""
    spaces, models = make_scenarios(B=4)
    es = EnsembleSpace.stack(spaces)
    ex = EnsembleExecutor(mesh=make_ensemble_mesh(
        batch=2, devices=eight_devices[:2]))
    ex.runner_for(models[0], es)
    assert (ex.builds, ex.cache_hits) == (1, 0)
    ex.mesh = make_ensemble_mesh(batch=4, devices=eight_devices[:4])
    ex.runner_for(models[0], es)
    assert (ex.builds, ex.cache_hits) == (2, 0)  # mesh change → rebuild
    ex.mesh = make_ensemble_mesh(batch=4, devices=eight_devices[:4])
    ex.runner_for(models[0], es)
    assert (ex.builds, ex.cache_hits) == (2, 1)  # same shape+devices → hit
    ex.mesh = None
    ex.runner_for(models[0], es)
    assert (ex.builds, ex.cache_hits) == (3, 1)  # unsharded is distinct


def test_runner_cache_keys_on_device_set(eight_devices):
    """Same (batch, space) extents over DIFFERENT devices is a
    different mesh: a resized rig must not serve the old placement."""
    spaces, models = make_scenarios(B=4)
    es = EnsembleSpace.stack(spaces)
    ex = EnsembleExecutor(mesh=make_ensemble_mesh(
        batch=2, devices=eight_devices[:2]))
    ex.runner_for(models[0], es)
    ex.mesh = make_ensemble_mesh(batch=2, devices=eight_devices[2:4])
    ex.runner_for(models[0], es)
    assert ex.builds == 2 and ex.cache_hits == 0


# -- the scheduler's pad-to-(bucket × mesh) protocol -------------------------

def test_scheduler_pads_to_bucket_times_mesh(eight_devices):
    """A 3-scenario flush on a batch-2 mesh with buckets (3, 5): the
    ladder picks 3, the mesh rounds to 4 — and the row's occupancy is
    computed against the ROUNDED bucket (honest padding waste)."""
    spaces, models = make_scenarios(B=3)
    sch = EnsembleScheduler(buckets=(3, 5), mesh=2)
    tickets = [sch.submit(spaces[i], models[i], steps=3)
               for i in range(3)]
    sch.pump(force=True)
    st = sch.stats()
    assert st["dispatches"] == 1
    assert sch.dispatch_log[0]["bucket"] == 4   # 3 rounded up to 2×2
    assert sch.dispatch_log[0]["count"] == 3
    assert st["batch_occupancy"] == pytest.approx(0.75)
    assert st["mesh"] == {"batch": 2, "space": 1, "devices": 2}
    # inert pads: every real lane still matches its serial run bitwise
    ser = SerialExecutor(step_impl="xla")
    for i, t in enumerate(tickets):
        sp, rep = sch.poll(t)
        want, _ = models[i].execute(spaces[i], ser, steps=3)
        assert bitwise(sp.values["value"], want.values["value"])


def test_scheduler_nonpower_mesh_extent_rounds_honestly(eight_devices):
    """A batch-3 mesh under power-of-two buckets: 4 scenarios round to
    6 lanes — occupancy 2/3, not the unrounded bucket's 1.0."""
    spaces, models = make_scenarios(B=4)
    sch = EnsembleScheduler(buckets=(1, 2, 4, 8), mesh=3)
    for i in range(4):
        sch.submit(spaces[i], models[i], steps=2)
    sch.pump(force=True)
    st = sch.stats()
    assert sch.dispatch_log[0]["bucket"] == 6
    assert st["batch_occupancy"] == pytest.approx(4 / 6)


def test_scheduler_solo_retry_rounds_to_mesh(eight_devices):
    """The solo-retry quarantine path dispatches mesh-shaped batches
    too: a poisoned lane's solo re-run pads 1 → mesh batch."""
    spaces, models = make_scenarios(B=2)
    bad = spaces[1].with_values(
        {"value": spaces[1].values["value"].at[0, 0].set(jnp.nan)})
    sch = EnsembleScheduler(buckets=(1, 2, 4), mesh=2, retry="solo")
    t0 = sch.submit(spaces[0], models[0], steps=2)
    t1 = sch.submit(bad, models[1], steps=2)
    sch.pump(force=True)
    assert sch.poll(t0) is not None
    with pytest.raises(Exception):
        sch.poll(t1)
    solo = [d for d in sch.dispatch_log if d.get("solo_retry")]
    assert solo and all(d["bucket"] % 2 == 0 for d in solo)


def test_scheduler_flush_ordering_unchanged_with_mesh(eight_devices):
    """The mesh round-up changes lane counts, never flush ORDER: the
    max-wait ladder still flushes oldest-first."""
    clock = {"t": 0.0}
    sch = EnsembleScheduler(max_wait_s=1.0, clock=lambda: clock["t"],
                            mesh=2)
    spaces, models = make_scenarios(B=4)
    ta = sch.submit(spaces[0], models[0], steps=2)   # group A @ t=0
    clock["t"] = 0.5
    tb = sch.submit(spaces[1], models[1], steps=3)   # group B @ t=0.5
    assert sch.pump() == 0
    clock["t"] = 1.2                                  # A due, B not
    assert sch.pump() == 1
    assert [d["steps"] for d in sch.dispatch_log] == [2]
    assert sch.poll(ta) is not None
    assert sch.poll(tb) is None
    clock["t"] = 1.6                                  # B due now
    assert sch.pump() == 1
    assert [d["steps"] for d in sch.dispatch_log] == [2, 3]
    # every dispatched lane count is a mesh multiple
    assert all(d["bucket"] % 2 == 0 for d in sch.dispatch_log)



# -- the CLI surface ----------------------------------------------------------

def test_cli_ensemble_mesh_run_json(eight_devices, capsys):
    import json

    from mpi_model_tpu import cli

    rc = cli.main(["run", "--dimx=16", "--dimy=16", "--flow=diffusion",
                   "--steps=3", "--ensemble=4", "--ensemble-mesh=2",
                   "--json"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert out["backend"] == "ensemble"
    assert out["conserved"] is True
    assert out["mesh"] == {"batch": 2, "space": 1, "devices": 2}


def test_cli_mesh_flag_guards():
    """Inapplicable flag combinations are ERRORS (the CLI discipline),
    never silent ignores."""
    from mpi_model_tpu import cli

    for argv in (
            # --ensemble-mesh without an ensemble/serve run
            ["run", "--ensemble-mesh=2"],
            # malformed spec
            ["run", "--ensemble=2", "--ensemble-mesh=bogus"],
            # mesh dispatch is xla-only
            ["run", "--ensemble=2", "--ensemble-mesh=2",
             "--ensemble-impl=pipeline"],
            # member-env without a serve run
            ["run", "--serve-member-env=A=1"],
            # member-env needs real processes to pin
            ["run", "--serve", "--serve-member-env=A=1"]):
        with pytest.raises(SystemExit):
            cli.main(argv)


def test_cli_mesh_and_member_env_parsers():
    from mpi_model_tpu.cli import _parse_ensemble_mesh, _parse_member_env

    assert _parse_ensemble_mesh(None) is None
    assert _parse_ensemble_mesh("4") == 4
    assert _parse_ensemble_mesh("2x2") == (2, 2)
    assert _parse_ensemble_mesh("2×2") == (2, 2)
    with pytest.raises(SystemExit, match="batch extent"):
        _parse_ensemble_mesh("2x2x2")
    assert _parse_member_env(None) is None
    assert _parse_member_env(["A=1", "B=x=y"]) == {"A": "1", "B": "x=y"}
    with pytest.raises(SystemExit, match="KEY=VAL"):
        _parse_member_env(["bogus"])


def test_service_mesh_stats_and_windowed_donation(eight_devices,
                                                  assert_gspmd_close):
    """The service facade with a mesh: results vs the meshless service
    within the GSPMD bound (the mesh shards space), stats surface the
    mesh, and the windowed donated dispatch stays copy-free under the
    sharding constraints."""
    from mpi_model_tpu.ensemble import EnsembleService

    spaces, models = make_scenarios(B=4)
    plain = EnsembleService(models[0], steps=4, buckets=(1, 2, 4))
    tp = [plain.submit(spaces[i], model=models[i]) for i in range(4)]
    plain.flush()
    want = [plain.result(t)[0] for t in tp]

    svc = EnsembleService(models[0], steps=4, buckets=(1, 2, 4),
                          mesh=(2, 2), windows=2, donate=True)
    ts = [svc.submit(spaces[i], model=models[i]) for i in range(4)]
    svc.flush()
    for i, t in enumerate(ts):
        sp, _ = svc.result(t)
        assert_gspmd_close(sp.values["value"], want[i].values["value"], 4)
    st = svc.stats()
    assert st["mesh"] == {"batch": 2, "space": 2, "devices": 4}
    logged = [d for d in svc.scheduler.dispatch_log if "windows" in d]
    assert logged and all(d["donated_windows"] == d["windows"]
                          for d in logged)
