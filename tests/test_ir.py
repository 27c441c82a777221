"""Flow IR (ISSUE 11): the term grammar, the ONE registered lowering,
and its cross-engine contracts.

The acceptance matrix this file pins:

- the linear diffusion model RE-EXPRESSED as an IR Transport term is
  bitwise-at-f64 equal to the pre-IR hand-written step on every impl
  (dense/composed/active/active_fused) and under serial/sharded/
  ensemble execution — and the hand-written dense step now IS the IR
  lowering (jaxpr-identical), the single source of truth;
- Gray-Scott, SIR and predator-prey run end-to-end through
  ``Model.execute_many``, the async service and the fleet with zero
  per-model step code, bitwise-at-f64 across serial/sharded/ensemble
  and every eligible impl;
- conservation generalizes to per-term budget reconciliation: declared
  source/sink budgets integrate and reconcile, violations raise NAMING
  the term (serial and per-lane ensemble paths alike);
- the chaos matrix (exc/nan/halo/lane_nan) passes with an IR model
  armed.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_model_tpu import (
    Chan,
    Clock,
    ConservationError,
    Diffusion,
    EnsembleConservationError,
    FlowIRModel,
    Model,
    Sink,
    Source,
    Transfer,
    Transport,
    build_model,
)
from mpi_model_tpu.ensemble import EnsembleExecutor, run_ensemble
from mpi_model_tpu.ir import expr as ir_expr
from mpi_model_tpu.ir import library, lower
from mpi_model_tpu.models.model import SerialExecutor
from mpi_model_tpu.parallel import (AutoShardedExecutor, ShardMapExecutor,
                                    make_mesh, make_mesh_2d)

ALL_MODELS = ("gray_scott", "sir", "predator_prey")


def bitwise_equal(a, b) -> bool:
    return all(np.array_equal(np.asarray(a.values[k]),
                              np.asarray(b.values[k])) for k in b.values)


# -- expression grammar -------------------------------------------------------

def test_expr_whitelist_and_operators():
    u, v = Chan("u"), Chan("v")
    e = (1.0 - u) * v ** 2 + ir_expr.exp(-v) / 2.0
    env = {"u": jnp.asarray([[0.5]]), "v": jnp.asarray([[2.0]])}
    got = np.asarray(ir_expr.evaluate(e, env, jnp.float64))[0, 0]
    want = (1.0 - 0.5) * 4.0 + np.exp(-2.0) / 2.0
    assert np.isclose(got, want)
    assert ir_expr.channels(e) == {"u", "v"}


def test_expr_rejects_non_whitelisted_shapes():
    u = Chan("u")
    with pytest.raises(TypeError, match="integer exponent"):
        u ** 0.5
    with pytest.raises(TypeError, match="cannot use"):
        ir_expr.as_expr("not a number")
    # a hand-built node with an op outside the whitelist refuses to
    # evaluate, naming the op
    bad = ir_expr.Unary("tanh", u)
    with pytest.raises(ValueError, match="tanh"):
        ir_expr.evaluate(bad, {"u": jnp.ones((2, 2))}, jnp.float32)
    # unknown channel names the channel and the space's inventory
    with pytest.raises(KeyError, match="'w'"):
        ir_expr.evaluate(Chan("w"), {"u": jnp.ones((2, 2))}, jnp.float32)


def test_zero_point_derivations():
    u, v = Chan("u"), Chan("v")
    assert ir_expr.zero_point(v) == ("v", 0.0)
    assert ir_expr.zero_point(v ** 2 * u) == ("v", 0.0)
    assert ir_expr.zero_point(1.0 - u) == ("u", 1.0)
    assert ir_expr.zero_point(-(v * 3.0)) == ("v", 0.0)
    # no proof -> None (conservative: the term stays always-active)
    assert ir_expr.zero_point(u + v) is None
    assert ir_expr.zero_point(ir_expr.exp(u)) is None


# -- term validation ----------------------------------------------------------

def test_term_validation_errors():
    with pytest.raises(ValueError, match="at least one term"):
        FlowIRModel([])
    with pytest.raises(ValueError, match="duplicate term name"):
        FlowIRModel([Transport("u", name="t"), Transport("v", name="t")])
    with pytest.raises(ValueError, match="self-transfer"):
        Transfer("u", "u", Chan("u"))
    with pytest.raises(ValueError, match="_b_"):
        Transport("u", name="_b_evil")
    with pytest.raises(TypeError, match="not an IR Term"):
        FlowIRModel([Diffusion(0.1)])
    with pytest.raises(ValueError, match="non-negative"):
        Transport("u", weights=(-1.0,) * 8)


def test_missing_channels_and_budgets_raise_clearly():
    m = FlowIRModel([Transport("u", rate=0.1),
                     Source("u", 1.0 - Chan("u"), rate=0.01, name="feed")])
    from mpi_model_tpu import CellularSpace
    bare = CellularSpace.create(8, 8, {"u": 1.0}, dtype=jnp.float64)
    with pytest.raises(ValueError, match="_b_feed"):
        m.make_step(bare)
    fixed = m.with_budget_channels(bare)
    m.make_step(fixed)  # builds
    # created spaces carry the budgets from the start
    sp = m.create_space(8, 8, {"u": 1.0}, dtype=jnp.float64)
    assert "_b_feed" in sp.values


def test_written_channels_must_be_floating():
    m = FlowIRModel([Transport("mask", rate=0.1)])
    from mpi_model_tpu import CellularSpace
    sp = CellularSpace.create(8, 8, {"v": 1.0, "mask": (True, "bool")},
                              dtype=jnp.float64)
    with pytest.raises(TypeError, match="floating"):
        m.make_step(sp)


# -- the registry (jaxpr-term-registry rule) ---------------------------------

def test_every_term_kind_has_exactly_one_lowering():
    from mpi_model_tpu.analysis.jaxpr_audit import check_term_registry

    assert check_term_registry() == []
    for kind in (Transport, Transfer, Source, Sink):
        assert kind in lower.LOWERINGS
        assert lower.LOWERINGS[kind].__module__ == lower.__name__


def test_unregistered_term_kind_is_flagged():
    from mpi_model_tpu.analysis.jaxpr_audit import check_term_registry

    class Rogue(lower.Term):  # no lowering registered anywhere in MRO
        name = "rogue"
        rate = 1.0

    try:
        findings = check_term_registry()
        assert any("Rogue" in f.message for f in findings)
    finally:
        # drop the class so later registry checks stay clean
        import gc
        del Rogue
        gc.collect()


def test_double_registration_refused():
    with pytest.raises(ValueError, match="exactly one"):
        lower.register_lowering(Transport)(object())


# -- diffusion re-expressed: the bitwise single-source-of-truth gate ----------

def test_diffusion_ir_bitwise_serial_f64():
    m_ir, space = build_model("diffusion", 32, dtype=jnp.float64)
    m_flow = Model(Diffusion(0.1), 10.0, 1.0)
    for impl in ("xla", "active"):
        a, _ = m_ir.execute(space, SerialExecutor(step_impl=impl),
                            steps=8)
        b, _ = m_flow.execute(space, SerialExecutor(step_impl=impl),
                              steps=8)
        assert bitwise_equal(a, b), impl


def test_diffusion_ir_bitwise_composed_and_fused_f32():
    # composed/active_fused are f32/bf16 engines (the Pallas dtype rule)
    m_ir, space = build_model("diffusion", 64, dtype=jnp.float32)
    m_flow = Model(Diffusion(0.1), 10.0, 1.0)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # CPU-rig dense-fallback probes
        for impl, kw in (("composed", dict(substeps=4)),
                         ("active_fused", {})):
            a, _ = m_ir.execute(space, SerialExecutor(step_impl=impl,
                                                      **kw), steps=8)
            b, _ = m_flow.execute(space, SerialExecutor(step_impl=impl,
                                                        **kw), steps=8)
            assert bitwise_equal(a, b), impl


def test_diffusion_ir_bitwise_sharded_and_ensemble(eight_devices):
    m_ir, space = build_model("diffusion", 32, dtype=jnp.float64)
    m_flow = Model(Diffusion(0.1), 10.0, 1.0)
    mesh = make_mesh(4, devices=eight_devices[:4])
    a, _ = m_ir.execute(space, ShardMapExecutor(mesh), steps=6)
    b, _ = m_flow.execute(space, ShardMapExecutor(mesh), steps=6)
    assert bitwise_equal(a, b)
    # ensemble: a linear IR model even BATCHES with a flow-built model
    # (identical structure key), and lanes match the serial run bitwise
    from mpi_model_tpu.ensemble.batch import structure_key
    assert structure_key(m_ir, space) == structure_key(m_flow, space)
    res = run_ensemble(m_flow, [space, space], models=[m_flow, m_ir],
                       steps=6)
    want, _ = m_flow.execute(space, SerialExecutor(), steps=6)
    for sp, _rep in res:
        assert bitwise_equal(sp, want)


def test_model_dense_step_is_the_ir_lowering():
    """The single-source-of-truth clause: the flow-built Model's dense
    XLA step and the IR model's dense step trace to the IDENTICAL
    jaxpr — the hand-written transport branch is the IR lowering."""
    m_ir, space = build_model("diffusion", 16, dtype=jnp.float64)
    m_flow = Model(Diffusion(0.1), 10.0, 1.0)
    args = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
            for k, v in space.values.items()}
    ja = jax.make_jaxpr(m_ir.make_step(space, impl="xla"))(args)
    jb = jax.make_jaxpr(m_flow.make_step(space, impl="xla"))(args)
    assert str(ja) == str(jb)


# -- the nonlinear parity matrix ---------------------------------------------

@pytest.mark.parametrize("name", ALL_MODELS)
def test_ir_model_bitwise_across_serial_impls(name):
    model, space = build_model(name, 32, dtype=jnp.float64)
    want, _ = model.execute(space, steps=8)
    for impl in ("active", "composed"):
        out, _ = model.execute(space, SerialExecutor(step_impl=impl),
                               steps=8)
        assert bitwise_equal(out, want), (name, impl)


@pytest.mark.parametrize("name", ALL_MODELS)
def test_ir_model_bitwise_sharded(name, eight_devices, assert_gspmd_close):
    model, space = build_model(name, 32, dtype=jnp.float64)
    want, _ = model.execute(space, steps=8)
    for ex in (ShardMapExecutor(make_mesh(4, devices=eight_devices[:4])),
               ShardMapExecutor(make_mesh_2d(2, 2,
                                             devices=eight_devices[:4])),
               AutoShardedExecutor(make_mesh(4,
                                             devices=eight_devices[:4]))):
        out, rep = model.execute(space, ex, steps=8)
        if isinstance(ex, AutoShardedExecutor):
            for k in want.values:
                assert_gspmd_close(out.values[k], want.values[k], 8)
        else:
            assert bitwise_equal(out, want), (name, type(ex).__name__)
        assert rep.comm_size == 4


@pytest.mark.parametrize("name", ALL_MODELS)
def test_ir_model_bitwise_ensemble_lanes(name):
    """execute_many: per-scenario term rates as traced [B, F] lanes;
    every lane reproduces its own serial run bitwise at f64 (the
    zero-per-model-step-code acceptance leg)."""
    model, space = build_model(name, 24, dtype=jnp.float64)
    models = [model,
              model.with_rates([r * 1.1 for r in model.term_rates()]),
              model.with_rates([r * 0.9 for r in model.term_rates()])]
    res = model.execute_many([space] * 3, models=models, steps=8)
    for m, (sp, rep) in zip(models, res):
        want, _ = m.execute(space, steps=8)
        assert bitwise_equal(sp, want)
        assert rep.steps == 8


def test_ir_active_window_path_bitwise_and_skipping():
    """SIR at a multi-tile plan: the term-derived predicate keeps the
    outbreak's neighborhood active and provably-quiescent tiles
    skipped, bitwise vs the dense lowering."""
    base, space = library.sir(128, dtype=jnp.float64)
    model = FlowIRModel(base.ir_terms, base.time, base.time_step,
                        active_opts={"tile": (32, 32),
                                     "max_active_frac": 0.9})
    want, _ = model.execute(space, steps=8)
    out, _ = model.execute(space, SerialExecutor(step_impl="active"),
                           steps=8)
    assert bitwise_equal(out, want)
    # the predicate really is sparse: far-corner tiles are quiescent
    spec = lower.activity_spec(model.ir_terms)
    assert not spec.always
    assert {p[0] for p in spec.probes} == {"I"}  # all probes key on I


def test_activity_spec_conservative_fallback():
    # a term whose expression offers no zero-point proof keeps every
    # tile active (spec.always) — conservative, never wrong
    m = FlowIRModel([Transport("u", rate=0.1),
                     Source("u", Chan("u") + 1.0, rate=0.01,
                            name="affine")])
    spec = lower.activity_spec(m.ir_terms)
    assert spec.always


# -- budget reconciliation ----------------------------------------------------

def test_budgets_reconcile_and_sign_contracts_hold():
    for name in ("gray_scott", "predator_prey"):
        model, space = build_model(name, 24, dtype=jnp.float64)
        out, rep = model.execute(space, steps=10)  # raises on violation
        buds = model.budget_totals(out)
        for t in model.ir_terms:
            if t.conservation == "source":
                assert buds[t.name] >= -1e-9, (name, t.name)
            elif t.conservation == "sink":
                assert buds[t.name] <= 1e-9, (name, t.name)
        assert model.report_conservation_error(rep) <= \
            model.conservation_threshold(space)


def test_sir_is_fully_conserving():
    model, space = build_model("sir", 24, dtype=jnp.float64)
    out, rep = model.execute(space, steps=10)
    assert model.budget_totals(out) == {}  # no declared sources/sinks
    # population is constant even though per-channel totals migrate
    assert rep.conservation_error() > 1e-6  # raw S drift IS large
    assert model.report_conservation_error(rep) < 1e-9


def test_lying_sink_raises_naming_the_term():
    # a DECLARED sink whose expression is negative ADDS mass: the
    # integrated budget runs positive and the gate names the term
    m = FlowIRModel([Transport("u", rate=0.1),
                     Sink("u", -Chan("u"), rate=0.1, name="liar")])
    space = m.create_space(16, 16, {"u": 1.0}, dtype=jnp.float64)
    with pytest.raises(ConservationError, match="liar"):
        m.execute(space, steps=4)


def test_lying_source_raises_naming_the_term():
    m = FlowIRModel([Transport("u", rate=0.1),
                     Source("u", -Chan("u"), rate=0.1, name="drain")])
    space = m.create_space(16, 16, {"u": 1.0}, dtype=jnp.float64)
    with pytest.raises(ConservationError, match="drain"):
        m.execute(space, steps=4)


def test_unreconciled_residual_names_conserving_terms():
    m = FlowIRModel([Transport("u", rate=0.1, name="mix")])
    space = m.create_space(8, 8, {"u": 1.0}, dtype=jnp.float64)
    # doctored totals: mass vanished with no budget to explain it
    with pytest.raises(ConservationError, match="mix"):
        m._raise_if_violated(space, {"u": 64.0}, {"u": 32.0}, 1e-3, None)


def test_ensemble_violation_names_the_term_per_lane():
    m = FlowIRModel([Transport("u", rate=0.1),
                     Sink("u", -Chan("u"), rate=0.1, name="liar")])
    space = m.create_space(16, 16, {"u": 1.0}, dtype=jnp.float64)
    with pytest.raises(EnsembleConservationError, match="liar") as ei:
        run_ensemble(m, [space, space], steps=4)
    assert ei.value.scenario == 0
    # "mark" mode: the error object lands in the lane's result slot
    res = run_ensemble(m, [space, space], steps=4, on_violation="mark")
    assert all(isinstance(r, EnsembleConservationError) for r in res)
    assert "liar" in str(res[1])


def test_time_varying_masked_source_integrates_exactly():
    """Time-varying + masked source: amount = rate * t * mask read from
    a Clock term's channel; the integrated budget equals the analytic
    sum (steps are 0-indexed at read time: sum_{s<n} s * |mask|)."""
    mask = np.zeros((8, 8))
    mask[2:4, 2:4] = 1.0  # 4 masked cells
    m = FlowIRModel([
        Clock("t"),
        Source("u", Chan("t") * Chan("mask"), rate=0.5, name="pulse"),
    ], 1.0, 1.0)
    space = m.create_space(8, 8, {"u": 0.0, "t": 0.0, "mask": 0.0},
                           dtype=jnp.float64)
    space = space.with_values({**space.values,
                               "mask": jnp.asarray(mask, jnp.float64)})
    n = 6
    out, _rep = m.execute(space, steps=n)  # budget gate passes
    want = 0.5 * sum(range(n)) * mask.sum()
    assert np.isclose(m.budget_totals(out)["pulse"], want)
    assert np.isclose(float(out.total("t")), n * 64)  # clock reconciled


def test_weighted_transport_conserves_and_redistributes():
    # anisotropic taps: all weight on the N/S neighbors
    w = tuple(1.0 if (dx, dy) in ((-1, 0), (1, 0)) else 0.0
              for dx, dy in Model.offsets)
    m = FlowIRModel([Transport("u", rate=0.2, weights=w)])
    space = m.create_space(9, 9, {"u": 0.0}, dtype=jnp.float64)
    vals = np.zeros((9, 9))
    vals[4, 4] = 1.0
    space = space.with_values({"u": jnp.asarray(vals, jnp.float64)})
    out, rep = m.execute(space, steps=1)
    got = np.asarray(out.values["u"])
    assert got[3, 4] > 0 and got[5, 4] > 0  # N/S received
    assert got[4, 3] == 0 and got[4, 5] == 0  # E/W got nothing
    assert rep.conservation_error() < 1e-12
    # sharded run of the same weighted model matches serially
    mesh = make_mesh(3)
    out_sh, _ = m.execute(space, ShardMapExecutor(mesh), steps=1)
    np.testing.assert_allclose(np.asarray(out_sh.values["u"]), got,
                               rtol=0, atol=1e-15)


def test_sharded_ir_runner_cache_keys_on_terms(eight_devices):
    """Review regression: two nonlinear IR models sharing a geometry
    must NOT share one compiled sharded runner (the term fingerprints
    are part of the cache identity — rates are baked concretely)."""
    model, space = build_model("gray_scott", 32, dtype=jnp.float64)
    doubled = model.with_rates([r * 2 for r in model.term_rates()])
    ex = ShardMapExecutor(make_mesh(4, devices=eight_devices[:4]))
    a, _ = model.execute(space, ex, steps=4)
    b, _ = doubled.execute(space, ex, steps=4)  # SAME executor instance
    want_b, _ = doubled.execute(space, steps=4)
    assert not bitwise_equal(b, a)
    assert bitwise_equal(b, want_b)


def test_weighted_transport_stranded_cells_shed_nothing():
    """Review regression: a weight set that strands boundary cells
    (all in-bounds taps zero-weighted) must stay finite AND conserving
    — the stranded cell sheds nothing — in every context."""
    # all weight on the NORTH tap: row 0 has no in-bounds north
    w = tuple(1.0 if (dx, dy) == (-1, 0) else 0.0
              for dx, dy in Model.offsets)
    m = FlowIRModel([Transport("u", rate=0.2, weights=w)])
    space = m.create_space(6, 6, {"u": 1.0}, dtype=jnp.float64)
    out, rep = m.execute(space, steps=3)
    got = np.asarray(out.values["u"])
    assert np.isfinite(got).all()
    assert rep.conservation_error() < 1e-12
    # sharded agrees (the ctxs share the stranded-cell rule)
    out_sh, _ = m.execute(space, ShardMapExecutor(make_mesh(3)), steps=3)
    np.testing.assert_allclose(np.asarray(out_sh.values["u"]), got,
                               rtol=0, atol=1e-15)


def test_with_rates_preserves_active_opts():
    base, _ = build_model("sir", 16, dtype=jnp.float64)
    m = FlowIRModel(base.ir_terms, active_opts={"tile": (8, 8)})
    assert m.with_rates(m.term_rates()).active_opts == {"tile": (8, 8)}


def test_check_health_view_survives_pre_ir_baseline():
    """Review regression: a supervised baseline captured before a
    budget channel existed (resume from a pre-IR checkpoint) must skip
    the drift check, not KeyError into the failure counter."""
    from mpi_model_tpu.resilience.supervisor import check_health

    model, space = build_model("gray_scott", 16, dtype=jnp.float64)
    stale = {"u": float(space.total("u")), "v": float(space.total("v"))}
    assert check_health(space, stale, threshold=1e-6,
                        view=model.conservation_view) == []


# -- eligibility / incompatibility errors ------------------------------------

def test_nonlinear_incompatible_impls_raise_clearly():
    model, space = build_model("gray_scott", 16, dtype=jnp.float64)
    for impl in ("pallas", "active_fused"):
        with pytest.raises(ValueError, match="linear-stencil"):
            model.make_step(space, impl=impl)
    with pytest.raises(ValueError, match="linear-stencil"):
        model.execute(space, ShardMapExecutor(make_mesh(4),
                                              step_impl="composed"),
                      steps=2)
    with pytest.raises(ValueError, match="halo depth"):
        model.execute(space, ShardMapExecutor(make_mesh(4),
                                              halo_depth=2), steps=2)
    # ensemble engines that batch all-Diffusion lanes refuse too
    with pytest.raises(ValueError):
        model.execute_many([space], executor=EnsembleExecutor(
            impl="pipeline"), steps=2)
    with pytest.raises(ValueError):
        model.execute_many([space], executor=EnsembleExecutor(
            impl="active"), steps=2)


def test_nonlinear_composed_forces_k1_with_warning():
    model, space = build_model("sir", 16, dtype=jnp.float64)
    with pytest.warns(RuntimeWarning, match="k=1"):
        step = model.make_step(space, impl="composed", substeps=4)
    assert step.composed_k == 1 and step.composed_passes == 4
    # and the degenerate form still equals iterated dense
    want, _ = model.execute(space, steps=4)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        out, _ = model.execute(
            space, SerialExecutor(step_impl="composed", substeps=4),
            steps=4)
    assert bitwise_equal(out, want)


# -- serving stack end-to-end -------------------------------------------------

@pytest.mark.parametrize("name", ALL_MODELS)
def test_ir_through_async_service(name):
    from mpi_model_tpu.ensemble import AsyncEnsembleService

    model, space = build_model(name, 16, dtype=jnp.float64)
    want, _ = model.execute(space, steps=4)
    svc = AsyncEnsembleService(model, steps=4, buckets=(2,), start=False)
    try:
        t1 = svc.submit(space)
        t2 = svc.submit(space)
        got = {}
        for _ in range(10):
            svc.pump_once(force=True)
            for t in (t1, t2):
                if t not in got:
                    r = svc.poll(t)
                    if r is not None:
                        got[t] = r
            if len(got) == 2:
                break
        assert len(got) == 2
        for sp, _rep in got.values():
            assert bitwise_equal(sp, want)
    finally:
        svc.stop()


def test_ir_through_fleet():
    from mpi_model_tpu.ensemble import FleetSupervisor, run_soak

    model, space = build_model("gray_scott", 16, dtype=jnp.float64)
    want, _ = model.execute(space, steps=4)
    with FleetSupervisor(model, services=2, steps=4,
                         buckets=(2,)) as fleet:
        rep = run_soak(fleet, [(space, None, None)] * 6,
                       arrival_rate_hz=1e9)
    assert rep["served"] == 6 and rep["ledger_complete"]


def test_ir_scheduler_lane_nan_chaos_recovers():
    """The lane_nan chaos row with an IR model armed: a poisoned lane
    is caught by the budget-reconciled per-lane conservation view,
    solo-retried clean, and the batchmate is untouched."""
    from mpi_model_tpu.ensemble import EnsembleScheduler
    from mpi_model_tpu.resilience import inject
    from mpi_model_tpu.resilience.inject import Fault, FaultPlan

    model, space = build_model("gray_scott", 16, dtype=jnp.float64)
    want, _ = model.execute(space, steps=4)
    sched = EnsembleScheduler(max_batch=2, retry="solo")
    plan = FaultPlan((Fault("lane_nan", ticket=0, once=True),))
    with inject.armed(plan) as st:
        t1 = sched.submit(space, model, steps=4)
        t2 = sched.submit(space, model, steps=4)
        r1 = sched.poll(t1)
        r2 = sched.poll(t2)
    assert [f["kind"] for f in st.fired] == ["lane_nan"]
    assert sched.stats()["recovered_failures"] == 1
    for sp, _rep in (r1, r2):
        assert bitwise_equal(sp, want)


def test_ir_supervised_chaos_exc_nan_recover_bitwise():
    from mpi_model_tpu import supervised_run
    from mpi_model_tpu.resilience import inject
    from mpi_model_tpu.resilience.inject import Fault, FaultPlan

    model, space = build_model("predator_prey", 16, dtype=jnp.float64)
    want, _ = model.execute(space, steps=8)
    for kind, kw in (("exc", {}), ("nan", {"cell": (3, 4)})):
        with inject.armed(FaultPlan((Fault(kind, at=1, **kw),))) as st:
            res = supervised_run(model, space, steps=8, every=2,
                                 executor=SerialExecutor())
        assert [f["kind"] for f in st.fired] == [kind]
        assert len(res.events) == 1
        assert bitwise_equal(res.space, want), kind


def test_ir_supervised_halo_chaos_recovers_bitwise(eight_devices):
    from mpi_model_tpu import supervised_run
    from mpi_model_tpu.resilience import inject
    from mpi_model_tpu.resilience.inject import Fault, FaultPlan

    model, space = build_model("sir", 32, dtype=jnp.float64)
    mesh = make_mesh(4, devices=eight_devices[:4])
    want, _ = model.execute(space, ShardMapExecutor(mesh), steps=8)
    ex = ShardMapExecutor(make_mesh(4, devices=eight_devices[:4]))
    with inject.armed(FaultPlan((Fault("halo", at=1),), seed=7)) as st:
        res = supervised_run(model, space, steps=8, every=2, executor=ex)
    assert [f["kind"] for f in st.fired] == ["halo"]
    assert len(res.events) == 1
    assert bitwise_equal(res.space, want)


# -- CLI ----------------------------------------------------------------------

def run_cli(capsys, *argv):
    from mpi_model_tpu.cli import main
    rc = main(list(argv))
    out = capsys.readouterr()
    return rc, out.out, out.err


def test_cli_model_run_conserved(capsys):
    import json
    rc, out, _ = run_cli(capsys, "run", "--model=gray_scott",
                         "--dimx=24", "--dimy=24", "--dtype=float64",
                         "--steps=4", "--json")
    assert rc == 0
    row = json.loads(out.strip().splitlines()[-1])
    assert row["conserved"] is True
    assert "_b_feed" in row["final"]  # the budget ledger is in the row


def test_cli_model_ensemble_and_impl(capsys):
    import json
    rc, out, _ = run_cli(capsys, "run", "--model=sir", "--dimx=16",
                         "--dimy=16", "--dtype=float64", "--steps=3",
                         "--ensemble=3", "--json")
    assert rc == 0
    row = json.loads(out.strip().splitlines()[-1])
    assert row["conserved"] is True and row["ensemble"] == 3
    rc, out, _ = run_cli(capsys, "run", "--model=predator_prey",
                         "--dimx=16", "--dimy=16", "--dtype=float64",
                         "--steps=3", "--impl=active", "--json")
    assert rc == 0


def test_cli_model_incompatible_combos():
    from mpi_model_tpu.cli import main
    with pytest.raises(SystemExit, match="pick one"):
        main(["run", "--model=gray_scott", "--flow=diffusion"])
    with pytest.raises(SystemExit, match="linear-stencil"):
        main(["run", "--model=gray_scott", "--impl=active_fused"])
    with pytest.raises(SystemExit, match="ensemble-impl"):
        main(["run", "--model=sir", "--ensemble=2",
              "--ensemble-impl=pipeline"])
    with pytest.raises(SystemExit, match="registry"):
        main(["run", "--model=gray_scott", "--rate=0.5"])
    with pytest.raises(SystemExit, match="ModelRectangular"):
        main(["run", "--model=gray_scott", "--rectangular=2x2"])


def test_unknown_registry_model_lists_options():
    with pytest.raises(ValueError, match="diffusion.*gray_scott"):
        build_model("unknown_physics")


# -- analysis rules -----------------------------------------------------------

def test_hardcoded_physics_rule():
    from mpi_model_tpu.analysis import lint_source

    def rules_of(findings):
        return [f.rule for f in findings if not f.suppressed]

    PKG = "mpi_model_tpu/fake.py"
    src = ("from mpi_model_tpu.ops.stencil import transport\n"
           "def my_step(v, o, c):\n"
           "    return transport(v, o, c)\n")
    assert rules_of(lint_source(src, PKG)) == ["hardcoded-physics"]
    # allowed in ops/ and ir/ (the kernel layer + the lowering)
    assert rules_of(lint_source(src, "mpi_model_tpu/ops/fake.py")) == []
    assert rules_of(lint_source(src, "mpi_model_tpu/ir/fake.py")) == []
    # pragma-able with a reason
    src2 = src.replace(
        "    return transport(v, o, c)\n",
        "    # analysis: ignore[hardcoded-physics] — legacy path\n"
        "    return transport(v, o, c)\n")
    assert rules_of(lint_source(src2, PKG)) == []
    # unrelated names never fire
    src3 = "def f(x):\n    return x.transport_report()\n"
    assert rules_of(lint_source(src3, PKG)) == []


def test_ir_jaxpr_contracts_clean():
    from mpi_model_tpu.analysis.jaxpr_audit import (CONTRACTS,
                                                    run_jaxpr_audit)

    names = [n for n in CONTRACTS if n.startswith("ir_")]
    # three models x three eligible impls + the diffusion re-expression
    assert len(names) == 10
    findings = run_jaxpr_audit(impls=["ir_gray_scott_xla",
                                      "ir_sir_active",
                                      "ir_predator_prey_composed"])
    assert [f for f in findings if not f.suppressed] == []


# -- bench / ladder -----------------------------------------------------------

def test_bench_ir_quick_row():
    import bench as bench_mod

    row = bench_mod.bench_ir(grid=32, steps=3, trials=1)
    assert row["budget_gate"] == "passed"
    assert set(row["impls"]) == {"xla", "composed", "active"}
    for impl_row in row["impls"].values():
        assert impl_row["cups"] and impl_row["cups"] > 0
    assert row["budgets"]["feed"] > 0 > row["budgets"]["kill"]


def test_ladder_config11_quick():
    from benchmarks.ladder import config11

    row = config11(quick=True)
    assert row["config"] == 11 and row["budget_gate"] == "passed"
