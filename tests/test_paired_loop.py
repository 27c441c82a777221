"""The horizon loops that drive the Pallas kernels run two kernel passes
per loop iteration (``ops.pallas_stencil.paired_loop``), and the sharded
loop carries the next pass's ghost rings in its state. The output must be
bitwise that of one pass per iteration, for every pass count (odd and
even, with and without a remainder of steps). Interpret mode on the
virtual CPU devices; the compiled program's want of copies is checked in
``tests/test_tpu_compile.py``."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_model_tpu import (CellularSpace, Coupled, Diffusion, Model,
                           build_model)
from mpi_model_tpu.models.model import SerialExecutor, _loop_runner
from mpi_model_tpu.parallel import ShardMapExecutor, make_mesh_2d
from mpi_model_tpu.utils import Tracer, set_tracer

PASSES = (1, 2, 3, 4, 5)


def _space(channels, h=32, w=256, seed=5):
    rng = np.random.default_rng(seed)
    space = CellularSpace.create(h, w, {c: 1.0 for c in channels},
                                 dtype=jnp.float32)
    return space.with_values({c: jnp.asarray(
        rng.uniform(0.5, 2.0, (h, w)), jnp.float32) for c in channels})


def _flows(kind):
    if kind == "field":
        return [Coupled(flow_rate=0.05, attr="a", modulator="b"),
                Diffusion(0.08, attr="a"), Diffusion(0.1, attr="b")]
    # a zero-rate channel rides along unexchanged
    return [Diffusion(0.13, attr="a"), Diffusion(0.0, attr="b")]


def _np(values):
    return {k: np.asarray(v) for k, v in values.items()}


def _assert_bitwise(got, want):
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


@pytest.mark.parametrize("impl, kind", [
    ("pallas", "diffusion"), ("composed", "diffusion"), ("pallas", "field")])
def test_serial_paired_loop_is_bitwise_the_one_pass_loop(impl, kind):
    """SerialExecutor against a loop of one step call per iteration,
    built from the same steps: q = 1..5 kernel passes, each with and
    without a remainder step."""
    substeps = 2
    space = _space(("a", "b"))
    model = Model(_flows(kind))
    ex = SerialExecutor(step_impl=impl, substeps=substeps)
    stepk = model.make_step(space, impl=impl, substeps=substeps)
    step1 = model.make_step(space, impl=impl)
    assert stepk.impl == step1.impl == impl
    assert stepk.kernel_pass and step1.kernel_pass

    @jax.jit
    def one_pass(v, nq, nr):
        v = jax.lax.fori_loop(0, nq, lambda i, c: stepk(c), v)
        return jax.lax.fori_loop(0, nr, lambda i, c: step1(c), v)

    for q in PASSES:
        for r in (0, 1):
            got = _np(ex.run_model(model, space, q * substeps + r))
            assert ex.last_kernel_passes == {
                "passes": q + r, "paired_passes": q - q % 2}
            want = _np(one_pass(dict(space.values), q, r))
            _assert_bitwise(got, want)


@pytest.mark.parametrize("model_kind, impl", [
    ("diffusion", "xla"), ("gray_scott", "composed")])
def test_serial_xla_step_keeps_the_one_pass_body(model_kind, impl):
    """A step that is no Pallas kernel keeps one call per iteration: the
    runner's program is the plain loop's, and no passes are recorded.
    Nonlinear IR terms under "composed" are XLA fusions too."""
    if model_kind == "diffusion":
        space = _space(("a", "b"), h=16, w=16)
        model = Model(_flows("diffusion"))
    else:
        model, space = build_model(model_kind, 16, dtype=jnp.float32)
    with pytest.warns(RuntimeWarning) if impl == "composed" else \
            contextlib.nullcontext():
        stepk = model.make_step(space, impl=impl, substeps=2)
    step1 = model.make_step(space, impl=impl)
    assert stepk.impl == impl and not stepk.kernel_pass

    def plain(v, nq, nr):
        v = jax.lax.fori_loop(0, nq, lambda i, c: stepk(c), v)
        return jax.lax.fori_loop(0, nr, lambda i, c: step1(c), v)

    args = (dict(space.values), jnp.int32(3), jnp.int32(1))
    (run,) = jax.make_jaxpr(_loop_runner(stepk, step1))(*args).eqns
    assert str(run.params["jaxpr"]) == str(jax.make_jaxpr(plain)(*args))
    ex = SerialExecutor(step_impl=impl, substeps=2)
    ex.run_model(model, space, 7)
    assert ex.last_impl == impl
    assert ex.last_kernel_passes is None


@pytest.fixture(scope="module")
def mesh22(eight_devices):
    return make_mesh_2d(2, 2, devices=eight_devices[:4])


@pytest.mark.parametrize("impl, kind, halo_mode", [
    ("pallas", "diffusion", "exchange"), ("composed", "diffusion", "exchange"),
    ("pallas", "field", "exchange"), ("pallas", "diffusion", "zero")])
def test_sharded_paired_loop_is_bitwise_one_pass_per_call(
        mesh22, impl, kind, halo_mode):
    """The 2x2 sharded loop against the same executor driven one kernel
    pass per call (a call of ``depth`` steps runs the single-pass
    branch, a shorter call the remainder switch): q = 1..5 passes, each
    with and without a remainder of steps."""
    depth = 2
    space = _space(("a", "b"))
    model = Model(_flows(kind))
    ex = ShardMapExecutor(mesh22, step_impl=impl, halo_mode=halo_mode,
                          halo_depth=depth)
    chain = [space.values]
    for _ in range(max(PASSES)):
        chain.append(ex.run_model(model, space.with_values(chain[-1]),
                                  depth))
        assert ex.last_kernel_passes == {"passes": 1, "paired_passes": 0}
    for q in PASSES:
        for r in (0, 1):
            want = chain[q]
            if r:
                want = ex.run_model(model, space.with_values(want), r)
            got = ex.run_model(model, space, q * depth + r)
            assert ex.last_impl == impl
            assert ex.last_kernel_passes == {
                "passes": q + r, "paired_passes": q - q % 2}
            _assert_bitwise(_np(got), _np(want))


@pytest.mark.parametrize("steps, passes, paired", [(32, 8, 8), (20, 5, 4)])
def test_kernel_passes_reach_the_enqueue_span(steps, passes, paired):
    """``Model.execute`` puts the executor's pass counts on its
    ``executor.enqueue`` span: 8 of 8 paired at 32 steps of 4, 4 of 5 at
    20."""
    space = _space(("value",), h=16, w=128)
    model = Model(Diffusion(0.1))
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        model.execute(space, SerialExecutor(step_impl="pallas", substeps=4),
                      steps=steps, check_conservation=False)
    finally:
        set_tracer(prev)
    (span,) = [s for s in tr.spans if s.name == "executor.enqueue"]
    assert span.meta == {"passes": passes, "paired_passes": paired}


def test_sharded_kernel_passes_reach_the_enqueue_span(mesh22):
    space = _space(("value",), h=32, w=256)
    model = Model(Diffusion(0.1))
    ex = ShardMapExecutor(mesh22, step_impl="pallas", halo_depth=4)
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        for steps in (32, 20):
            model.execute(space, ex, steps=steps, check_conservation=False)
    finally:
        set_tracer(prev)
    metas = [s.meta for s in tr.spans if s.name == "executor.enqueue"]
    assert metas == [{"passes": 8, "paired_passes": 8},
                     {"passes": 5, "paired_passes": 4}]
