"""Model: the simulation orchestrator (time loop + conservation contract).

Rebuild of ``Model<T>`` (``/root/reference/src/Model.hpp:14-263``). The
reference's ``execute<R>(comm, cs)`` inlines decomposition, a string control
protocol, the flow step, a halo exchange, a hand-rolled reduction and file
merge. Here those concerns are factored:

- the **step** is a pure function (``ops``), compiled once;
- the **time loop** is ``lax.scan`` inside one ``jit`` — the reference's
  loop is written but disabled (``Model.hpp:180-183``), so it always runs
  exactly one step; we implement the intended ``time / time_step`` schedule
  (pass ``steps=1`` for reference-exact behavior);
- **decomposition/halo** live in the pluggable ``Executor`` (serial here,
  sharded in ``parallel.executors``);
- the **conservation contract** (``Model.hpp:88-95``: global attribute sum
  preserved to 1e-3) is checked with a proper ``abs`` — the reference's
  assert lacks ``fabs`` (SURVEY §2 defects) — against the *measured* initial
  total instead of a hardcoded 10000;
- the per-rank reduction becomes ``jnp.sum`` on the (possibly sharded)
  array — XLA lowers it to an ICI all-reduce, replacing the hand-rolled
  send/recv loops (``Model.hpp:88-92,238-243``).
"""

from __future__ import annotations

import dataclasses
import math
import time as _time
import warnings
from typing import Callable, Optional, Protocol, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core.cell import MOORE_OFFSETS
from ..core.cellular_space import CellularSpace
from ..ops.flow import Flow, PointFlow, build_outflow
from ..ops.stencil import neighbor_counts_traced, point_flow_step, transport
from ..resilience import inject

Values = dict[str, jax.Array]


class ConservationError(AssertionError):
    """Mass-conservation contract violated (``Model.hpp:95``, with fabs)."""


def default_conservation_rtol(shape: tuple[int, int], dtype) -> float:
    """Default relative conservation tolerance ≈ 4·eps·log2(N): the
    pairwise-summation error bound for XLA reductions. THE one copy of
    the bound — ``Model.conservation_threshold`` (serial) and
    ``ensemble.batch.conservation_thresholds`` (per-lane) both derive
    from it, so the two paths cannot drift apart."""
    n = max(shape[0] * shape[1], 2)
    return 4.0 * float(jnp.finfo(dtype).eps) * math.log2(n)


@dataclasses.dataclass
class Report:
    """Run report — the live realization of the reference's vestigial
    ``MPI_Report{comm_size, rank_id}`` (``MPI_Report.hpp:5-20``, never used
    there), extended with what a run actually needs to report."""

    comm_size: int
    rank_id: int
    steps: int
    initial_total: dict[str, float]
    final_total: dict[str, float]
    #: per-flow amounts evaluated on the FINAL state (what the next step
    #: would move), aligned with Model.flows. For frozen-snapshot flows —
    #: the reference's live case — this equals the amount of the last
    #: executed step, i.e. the ``Flow::last_execute`` memo (``Flow.hpp:14``);
    #: for dynamic flows it trails it by one step.
    last_execute: list[float]
    wall_time_s: float
    #: the executing backend's OWN report, when it produces one (the
    #: native C++ engine's totals/conservation numbers — kept instead of
    #: discarded so cross-backend drift is visible); None for pure-JAX
    #: executors, whose report IS this Report.
    backend_report: Optional[dict] = None

    def conservation_error(self) -> float:
        return max(
            abs(self.final_total[k] - self.initial_total[k])
            for k in self.initial_total
        )


class Executor(Protocol):
    """Execution strategy: how the compiled step runs over devices."""

    def run_model(self, model: "Model", space: CellularSpace,
                  num_steps: int) -> Values: ...

    @property
    def comm_size(self) -> int: ...


class SerialExecutor:
    """Single-device execution: a jitted step loop (the reference's serial
    ``execute()`` stub, ``Model.hpp:47-51``, 'missing implement' — here
    implemented). The jitted runner is cached per step pair; trip counts
    are TRACED scalars, so repeated ``execute`` calls never retrace —
    whatever the step count.

    ``step_impl`` selects the per-step kernel: ``"xla"`` (fused stencil
    ops), ``"pallas"`` (the fused TPU kernel — Diffusion-only field flows),
    ``"active"`` (the active-tile engine, ``ops.active`` — all-Diffusion
    models run the amortized whole-run active stepper: pad once, carry
    the tile map across steps, compute only active tiles; per-step dense
    fallbacks and the measured activity land in
    ``Report.backend_report``), ``"active_fused"`` (the fused Pallas
    active kernel, ``ops.pallas_active`` — the same skip rule with
    scalar-prefetched window streaming and in-kernel flag computation;
    ``substeps`` requests composed-k passes and the report adds
    ``flags_fused``/``composed_k``), or ``"auto"`` (pallas when
    eligible).
    ``substeps`` batches that many model steps into each compiled step
    call (``Model.make_step``'s multi-step fusion — the HBM-amortizing
    fast path on TPU); any remainder of ``num_steps`` runs as single
    steps, so semantics are independent of the setting.

    ``active_opts`` tunes the active engine (keys ``tile``,
    ``capacity``, ``max_active_frac`` — see ``ops.active.plan_for``).
    """

    comm_size = 1

    def __init__(self, step_impl: str = "xla", substeps: int = 1,
                 compute_dtype=None, active_opts: Optional[dict] = None):
        self.step_impl = step_impl
        self.substeps = max(1, int(substeps))
        #: active-tile engine knobs (ops.active.plan_for); ignored by
        #: the other impls
        self.active_opts = active_opts
        #: interior-tile window math dtype for the Pallas kernels
        #: (None → f32; ``Model.make_step(compute_dtype=...)``); the XLA
        #: path ignores it
        self.compute_dtype = compute_dtype
        #: kernel the last run actually used ("pallas"/"xla"), what
        #: "auto" resolved to — the CLI/bench report it so a user never
        #: believes they measured a configuration that never ran
        self.last_impl: Optional[str] = None
        #: per-run report detail (Report.backend_report); None until a
        #: run records one
        self.last_backend_report: Optional[dict] = None
        #: dirty-tile export of the last ACTIVE run (ISSUE 7): a dict
        #: {"tile", "grid", "map"} whose bool [gi, gj] "map" is the
        #: union of every tile the run wrote — the activity-sourced
        #: dirtiness the delta checkpoint layer (io.delta) consumes
        #: instead of diffing the full grid. None after any run that
        #: cannot vouch for it (dense/composed/point paths, a poisoned
        #: chunk), which makes the consumer fall back to the diff.
        self.last_dirty_tiles: Optional[dict] = None
        self._cache: dict = {}

    def run_model(self, model: "Model", space: CellularSpace,
                  num_steps: int) -> Values:
        # chaos seam (resilience.inject): one module-global read when no
        # plan is armed — the jitted runners below are untouched, so the
        # step jaxprs are identical to an uninstrumented build
        st = inject.active()
        if st is None:
            return self._run_inner(model, space, num_steps)
        idx = st.bump("executor")
        fault = st.take("executor", idx, kinds=("exc", "nan"))
        if fault is not None and fault.kind == "exc":
            # the call index rides the message so two injected faults
            # never share a failure signature (that would read as ONE
            # deterministic fault to the supervisor's classifier)
            raise inject.InjectedFault(
                f"injected executor fault on call {idx} "
                f"({num_steps}-step chunk)")
        out = self._run_inner(model, space, num_steps)
        if fault is not None:  # kind == "nan": poison the chunk OUTPUT
            out = inject.poison_values(out, fault, st.plan)
            # the poison wrote outside the engine's tracked set: the
            # dirty export no longer covers this output
            self.last_dirty_tiles = None
        return out

    def _run_inner(self, model: "Model", space: CellularSpace,
                   num_steps: int) -> Values:
        #: per-run report detail (Report.backend_report) — reset so a
        #: previous run's composed/active record never leaks forward
        self.last_backend_report = None
        # likewise the dirty-tile export: a stale map from a previous
        # active run must never describe THIS run's output
        self.last_dirty_tiles = None
        # all-point-flow models step only the ≤9k involved cells in the
        # compiled loop (one O(grid) gather/scatter per RUN, bitwise
        # equal to the full-grid path) — the reference's live workload
        # (Main.cpp:32-33) at µs-step grids beat a NumPy loop this way
        # ("active" included: the point subsystem IS the ultimate
        # active-set optimization for all-point models)
        if (self.step_impl in ("xla", "auto", "active", "active_fused")
                and num_steps > 0
                and model.flows
                and all(isinstance(f, PointFlow) for f in model.flows)):
            from ..ops.point_kernel import build_point_plans, \
                serial_point_runner

            key = ("pointmini", space.shape, space.global_shape,
                   (space.x_init, space.y_init), str(space.dtype),
                   model.offsets,
                   tuple(f.fingerprint() for f in model.flows))
            runner = self._cache.get(key)
            if runner is None:
                plans = build_point_plans(model.flows, space, model.offsets)
                # cache False for "ineligible" so the plan build isn't
                # re-paid on every chunk of a supervised run
                runner = (jax.jit(serial_point_runner(
                    plans, jnp.dtype(space.dtype)))
                    if plans is not None else False)
                self._cache[key] = runner
            if runner:
                # distinct label: "point" is the subsystem fast path (an
                # XLA program, but a consumer — or a regression test —
                # must be able to tell it from the full-grid XLA step)
                self.last_impl = "point"
                return runner(dict(space.values), jnp.int32(num_steps))

        # the amortized active-tile runner (ops.active): pads once and
        # carries the tile map + update buffer across the WHOLE run, so
        # per-step work is O(active tiles), never O(grid) — the engine
        # ISSUE 3 builds. All-Diffusion models only (the skip rule's
        # exactness argument); models with point flows or other field
        # flows drop to the generic loop below, whose stateless
        # make_step(impl="active") form recomputes activity per step.
        if self.step_impl == "active" and num_steps > 0:
            rates = model.pallas_rates()
            live = {a: r for a, r in (rates or {}).items() if r != 0.0}
            # the amortized runner computes every live channel in
            # space.dtype: a non-float or off-space-dtype flow channel
            # drops to the generic loop, whose make_step raises the
            # clean "requires a floating dtype" TypeError / "computes
            # every flow channel in the space dtype" ValueError instead
            # of a mid-trace lax dtype mismatch
            if (rates is not None and live
                    and not any(isinstance(f, PointFlow)
                                for f in model.flows)
                    and all(jnp.issubdtype(space.values[a].dtype,
                                           jnp.floating)
                            and space.values[a].dtype == jnp.dtype(
                                space.dtype)
                            for a in live)):
                key = ("activerun", space.shape, space.global_shape,
                       (space.x_init, space.y_init), str(space.dtype),
                       model.offsets, tuple(sorted(live.items())),
                       tuple(sorted((self.active_opts or {}).items())))
                entry = self._cache.get(key)
                if entry is None:
                    from ..ops.active import build_active_runner, plan_for

                    opts = dict(self.active_opts or {})
                    plan = plan_for(
                        space.shape, tile=opts.get("tile"),
                        capacity=opts.get("capacity"),
                        max_active_frac=opts.get("max_active_frac", 0.25))
                    # fallback steps run the fused dense kernel where it
                    # actually compiles+runs here, else the bitwise XLA
                    # transport (ops.active.dense_from_padded)
                    dense_fns = {}
                    for a, r in live.items():
                        fn = model._pallas_dense_stepper(
                            space, r, self.compute_dtype)
                        if fn is not None:
                            dense_fns[a] = fn
                    run = jax.jit(build_active_runner(
                        space.shape, live, model.offsets, space.dtype,
                        origin=(space.x_init, space.y_init),
                        global_shape=space.global_shape, plan=plan,
                        dense_fns=dense_fns, track_dirty=True))
                    entry = (run, plan)
                    self._cache[key] = entry
                run, plan = entry
                out, (fb, at, dirty) = run(dict(space.values),
                                           jnp.int32(num_steps))
                self.last_impl = "active"
                # dirty-tile export (ISSUE 7): the union of tiles this
                # run wrote, for the delta checkpoint layer — [gi, gj]
                # of bools, a few KB even at the bench geometry
                self.last_dirty_tiles = {
                    "tile": plan.tile, "grid": plan.grid,
                    "map": np.asarray(dirty),
                }
                nattr = len(live)
                self.last_backend_report = {
                    "impl": "active",
                    "steps": int(num_steps),
                    #: (attr, step) pairs that ran the dense fallback —
                    #: the honest record that the engine measured is the
                    #: one that ran (executors.py point-routing pattern)
                    "fallback_steps": int(fb),
                    "tile": list(plan.tile),
                    "tiles": plan.ntiles,
                    "capacity": plan.capacity,
                    "fallback_tiles": plan.fallback_tiles,
                    "mean_active_fraction": (
                        float(at) / (num_steps * nattr * plan.ntiles)
                        if num_steps and nattr else None),
                }
                return out

        # the amortized FUSED active runner (ops.pallas_active, ISSUE 8):
        # the active engine's loop shape with the gather/compute/flags
        # replaced by the scalar-prefetched Pallas pass — flags are
        # computed in-kernel, and ``substeps`` requests composed-k
        # passes (k auto-chosen dividing it). Same eligibility rule as
        # the XLA active runner; ineligible models drop to the generic
        # loop whose make_step raises the clean errors.
        if self.step_impl == "active_fused" and num_steps > 0:
            rates = model.pallas_rates()
            live = {a: r for a, r in (rates or {}).items() if r != 0.0}
            if (rates is not None and live
                    and not any(isinstance(f, PointFlow)
                                for f in model.flows)
                    and all(jnp.issubdtype(space.values[a].dtype,
                                           jnp.floating)
                            and space.values[a].dtype == jnp.dtype(
                                space.dtype)
                            for a in live)):
                key = ("fusedrun", space.shape, space.global_shape,
                       (space.x_init, space.y_init), str(space.dtype),
                       model.offsets, tuple(sorted(live.items())),
                       self.substeps,
                       tuple(sorted((self.active_opts or {}).items())))
                entry = self._cache.get(key)
                if entry is None:
                    from ..ops.pallas_active import (build_fused_runner,
                                                     choose_fused_k)
                    from ..ops.active import plan_for
                    from ..ops.pallas_stencil import resolve_interpret

                    opts = dict(self.active_opts or {})
                    plan = plan_for(
                        space.shape, tile=opts.get("tile"),
                        capacity=opts.get("capacity"),
                        max_active_frac=opts.get("max_active_frac", 0.25))
                    k = choose_fused_k(self.substeps, plan)
                    dense_fns = {}
                    for a, r in live.items():
                        fn = model._pallas_dense_stepper(
                            space, r, self.compute_dtype)
                        if fn is not None:
                            dense_fns[a] = fn
                    interp = resolve_interpret(
                        next(iter(space.values.values())))
                    run = jax.jit(build_fused_runner(
                        space.shape, live, model.offsets, space.dtype,
                        origin=(space.x_init, space.y_init),
                        global_shape=space.global_shape, plan=plan, k=k,
                        dense_fns=dense_fns, track_dirty=True,
                        interpret=interp))
                    entry = (run, plan, k)
                    self._cache[key] = entry
                run, plan, k = entry
                out, (fb, at, ff, dirty) = run(dict(space.values),
                                               jnp.int32(num_steps))
                self.last_impl = "active_fused"
                self.last_dirty_tiles = {
                    "tile": plan.tile, "grid": plan.grid,
                    "map": np.asarray(dirty),
                }
                from ..ops.pallas_active import pass_count

                nattr = len(live)
                passes = pass_count(num_steps, k)
                self.last_backend_report = {
                    "impl": "active_fused",
                    "steps": int(num_steps),
                    "composed_k": k,
                    "passes": passes,
                    #: (attr, pass) pairs that ran the dense fallback
                    "fallback_steps": int(fb),
                    #: (attr, pass) pairs whose next-step flags came out
                    #: of the kernel — the in-kernel flag counter the
                    #: observability satellite tracks (fallback passes
                    #: recompute flags in XLA, so flags_fused +
                    #: fallback_steps == passes × live attrs)
                    "flags_fused": int(ff),
                    "tile": list(plan.tile),
                    "tiles": plan.ntiles,
                    "capacity": plan.capacity,
                    "fallback_tiles": plan.fallback_tiles,
                    "mean_active_fraction": (
                        float(at) / (passes * nattr * plan.ntiles)
                        if passes and nattr else None),
                }
                return out

        # q multi-step calls + r single-step calls == num_steps steps
        q, r = divmod(num_steps, self.substeps)
        stepk = model.make_step(space, impl=self.step_impl,
                                substeps=self.substeps,
                                compute_dtype=self.compute_dtype
                                ) if q else None
        step1 = model.make_step(space, impl=self.step_impl,
                                compute_dtype=self.compute_dtype
                                ) if r else None
        step_any = stepk or step1
        # num_steps=0 builds no step at all — nothing ran, report None
        self.last_impl = step_any.impl if step_any is not None else None
        if step_any is not None and step_any.impl == "active_fused":
            # the stateless fused form (point-flow compositions land
            # here): k visibility mirrors the composed record — the
            # amortized runner above reports the full counter set
            self.last_backend_report = {
                "impl": "active_fused",
                "substeps": self.substeps,
                "composed_k": getattr(stepk or step1, "composed_k", None),
                "composed_passes_per_call": getattr(
                    stepk or step1, "composed_passes", None),
                "remainder_steps": r,
            }
        if step_any is not None and step_any.impl == "composed":
            # auto-k visibility (ISSUE 3 satellite): the chosen k and
            # the remainder chunk's depth land in Report.backend_report,
            # so impl="composed" silently equaling the iterated path
            # (k=1) is observable, not inferred
            self.last_backend_report = {
                "impl": "composed",
                "substeps": self.substeps,
                "composed_k": getattr(stepk or step1, "composed_k", None),
                "composed_passes_per_call": getattr(
                    stepk or step1, "composed_passes", None),
                "remainder_steps": r,
                "remainder_k": (getattr(step1, "composed_k", None)
                                if step1 is not None else None),
            }
        # the trip counts are TRACED scalars, so the cache key is only
        # which steps exist: chunked/supervised runs of any size reuse
        # one compile (at most 3 variants: k-only, 1-only, k+1)
        key = (stepk, step1)
        runner = self._cache.get(key)
        if runner is None:
            def _run(v, nq, nr):
                def loop(fn, c, count):
                    return jax.lax.fori_loop(
                        0, count, lambda i, carry: fn(carry), c)
                if stepk is not None:
                    v = loop(stepk, v, nq)
                if step1 is not None:
                    v = loop(step1, v, nr)
                return v
            runner = jax.jit(_run)
            self._cache[key] = runner
        return runner(dict(space.values), jnp.int32(q), jnp.int32(r))


class Model:
    """Orchestrates flows over a CellularSpace for ``time/time_step`` steps.

    Signature parity: the reference constructs
    ``Model<Exponencial<double>>(flow, final_time, time_step)``
    (``Main.cpp:32-33``, ``Model.hpp:23-27``).
    """

    #: neighborhood used by transport (ModelRectangular overrides docs-wise)
    offsets: tuple[tuple[int, int], ...] = MOORE_OFFSETS

    def __init__(self, flow: Union[Flow, Sequence[Flow]], time: float = 1.0,
                 time_step: float = 1.0, *,
                 offsets: Optional[Sequence[tuple[int, int]]] = None):
        self.flows: list[Flow] = list(flow) if isinstance(flow, (list, tuple)) else [flow]
        self.time = float(time)
        self.time_step = float(time_step)
        if offsets is not None:
            self.offsets = tuple(offsets)
        self._step_cache: dict = {}
        self._default_executor: Optional[SerialExecutor] = None
        self._default_ensemble = None

    @property
    def flow(self) -> Flow:
        """The reference's single-flow accessor."""
        return self.flows[0]

    @property
    def num_steps(self) -> int:
        return max(1, int(round(self.time / self.time_step)))

    # -- step construction -------------------------------------------------

    def pallas_rates(self) -> Optional[dict[str, float]]:
        """attr → summed uniform rate when every field flow is a plain
        ``Diffusion`` (the shape the fused Pallas kernel computes); None
        when any field flow needs the general outflow path."""
        from ..ops.flow import Diffusion
        rates: dict[str, float] = {}
        for f in self.flows:
            if isinstance(f, PointFlow):
                continue
            if type(f) is not Diffusion:
                return None
            rates[f.attr] = rates.get(f.attr, 0.0) + f.flow_rate
        return rates

    def _pallas_dense_stepper(self, space: CellularSpace, rate: float,
                              compute_dtype=None):
        """The fused dense kernel as an ACTIVE-path fallback stepper —
        returned when this process runs it compiled (interpret mode makes
        it a perf trap on CPU rigs, and the bitwise-at-f64 contract needs
        the XLA transport there anyway), chosen from eligibility alone: a
        kernel that cannot compile raises in the caller's jit. None → use
        the bitwise XLA transport."""
        from ..ops.pallas_stencil import PallasDiffusionStep, \
            resolve_interpret

        if (space.is_partition or not self.pallas_dtype_ok(space)
                or resolve_interpret(next(iter(space.values.values())))):
            return None
        return PallasDiffusionStep(
            space.shape, rate, dtype=space.dtype, offsets=self.offsets,
            interpret=False, compute_dtype=compute_dtype)

    def _active_live_rates(self, space: CellularSpace,
                           impl: str) -> dict[str, float]:
        """Shared eligibility gate of the active-tile impls (XLA
        ``"active"`` and fused ``"active_fused"``): all-Diffusion field
        flows (the tile-skip rule is only bitwise-exact for uniform-rate
        linear flows), at least one nonzero rate, every live channel in
        the space dtype. Returns the live attr → rate map; raises the
        clean errors the tests and executors match on."""
        rates = self.pallas_rates()
        if rates is None:
            raise ValueError(
                f"impl='{impl}' requires all field flows to be plain "
                "Diffusion (the tile-skip rule is only bitwise-exact "
                "for uniform-rate linear flows); got "
                f"flows={[type(f).__name__ for f in self.flows]}. "
                "Use impl='xla'/'auto'.")
        live = {a: r for a, r in rates.items() if r != 0.0}
        if rates and not live:
            raise ValueError(
                f"impl='{impl}' has nothing to step: every Diffusion "
                "rate is 0.0 (no field transport). Use "
                "impl='xla'/'auto' for a no-op field step.")
        if not rates:
            raise ValueError(
                f"impl='{impl}' needs a Diffusion field flow; "
                "all-point models already take the point-subsystem "
                "fast path (the executors route them automatically).")
        for a in live:
            adt = space.values[a].dtype
            if adt != jnp.dtype(space.dtype):
                raise ValueError(
                    f"impl='{impl}' computes every flow channel in "
                    f"the space dtype ({jnp.dtype(space.dtype).name});"
                    f" channel {a!r} is {adt}. Use impl='xla'.")
        return live

    @staticmethod
    def pallas_dtype_ok(space: CellularSpace) -> bool:
        """Pallas kernels compute in f32 internally; f64 grids stay on
        the XLA path so "auto" never silently downgrades the oracle-tier
        precision (f32/bf16/f16 are eligible)."""
        return jnp.dtype(space.dtype).itemsize <= 4

    def make_step(self, space: CellularSpace, impl: str = "xla",
                  substeps: int = 1,
                  compute_dtype=None) -> Callable[[Values], Values]:
        """Build the pure per-step function for this space's geometry.

        Point-source flows take the sparse scatter path
        (``ops.stencil.point_flow_step`` — O(1) work instead of a dense
        one-hot field over the grid); field flows take the dense transport.
        All amounts are computed from the pre-step values, so the result is
        identical to summing every flow's outflow field. Cached per
        geometry so repeat executions reuse the same compiled step.

        ``impl`` selects the field-flow kernel: ``"xla"`` (stencil ops,
        works for every flow), ``"pallas"`` (the fused one-HBM-pass TPU
        kernel, ``ops.pallas_stencil`` — requires all field flows to be
        plain ``Diffusion`` on a full non-partition grid; raises
        ``ValueError`` otherwise), ``"composed"`` (the composed k-step
        filter, ``ops.composed_stencil`` — same eligibility as pallas
        Diffusion; k is auto-chosen as the largest window-composable
        divisor of ``substeps`` and each compiled call runs
        ``substeps/k`` single-pass composed filters), or ``"auto"``
        (pallas when eligible, else xla; a Pallas failure propagates —
        nothing falls back to hide it). The returned step carries
        ``.impl`` naming the kernel actually in use.

        ``substeps > 1`` returns a step that advances the model that many
        steps per call. On the Pallas path the steps are fused INSIDE the
        kernel (one HBM round-trip for all of them — the bandwidth
        amortization that pushes the TPU kernel toward its roofline;
        requires Diffusion-only models, since a point flow must fire
        between sub-steps); elsewhere the single step is composed
        ``substeps`` times inside one jitted call, which is semantically
        identical to calling the step repeatedly.

        ``compute_dtype`` (Pallas paths only; None → f32) sets the
        INTERIOR-tile window math dtype of the fused kernels —
        ``bfloat16`` trades interior precision for VPU throughput; the
        near-ring exact path always computes in f32. The XLA path
        ignores it (its math runs in the storage dtype)."""
        for f in self.flows:
            ch = space.values.get(f.attr)
            if ch is None:
                raise ValueError(
                    f"flow {type(f).__name__} targets channel {f.attr!r} "
                    f"which the space does not carry "
                    f"(has {tuple(space.values)})")
            if not jnp.issubdtype(ch.dtype, jnp.floating):
                raise TypeError(
                    f"flow transport requires a floating dtype, got "
                    f"{ch.dtype} for channel {f.attr!r} (integer/bool "
                    "channels are supported for storage/comm/masks, "
                    "not flows)")
        if impl not in ("xla", "pallas", "auto", "composed", "active",
                        "active_fused"):
            raise ValueError(f"unknown step impl {impl!r}")
        substeps = int(substeps)
        if substeps < 1:
            raise ValueError(f"substeps must be >= 1, got {substeps}")
        key = (space.shape, space.global_shape, (space.x_init, space.y_init),
               str(space.dtype), self.offsets, impl, substeps,
               str(compute_dtype) if compute_dtype is not None else None,
               tuple(f.fingerprint() for f in self.flows))
        cached = self._step_cache.get(key)
        if cached is not None:
            return cached

        offsets = self.offsets
        origin = (space.x_init, space.y_init)
        point_flows = [f for f in self.flows if isinstance(f, PointFlow)]
        field_flows = [f for f in self.flows if not isinstance(f, PointFlow)]
        pt_by_attr: dict[str, list[PointFlow]] = {}
        for f in point_flows:
            # Sources outside this partition contribute nothing here (the
            # reference's owner-rank test, Model.hpp:176).
            if f.local_source({f.attr: next(iter(space.values.values()))},
                              origin)[2]:
                pt_by_attr.setdefault(f.attr, []).append(f)

        pallas_steppers = None
        pallas_field_stepper = None
        composed_steppers = None
        composed_passes = 1
        if impl == "composed":
            # composed k-step filter (ops.composed_stencil): one
            # (2k+1)² tap pass per k steps of a uniform-rate
            # (all-Diffusion) model — the radius-1-ceiling breaker
            # named by the round-5 roofline investigation. k is
            # auto-chosen as the largest window-composable divisor of
            # ``substeps``, so one compiled call runs ``substeps/k``
            # composed passes with no remainder step.
            rates = self.pallas_rates()
            if rates is not None and not any(r != 0.0
                                             for r in rates.values()):
                raise ValueError(
                    "impl='composed' has nothing to compose: every "
                    "Diffusion rate is 0.0 (no field transport). Use "
                    "impl='xla'/'auto' for a no-op field step.")
            eligible = (bool(rates) and not space.is_partition
                        and self.pallas_dtype_ok(space)
                        and (substeps == 1 or not pt_by_attr))
            if not eligible:
                raise ValueError(
                    "impl='composed' requires all field flows to be plain "
                    "Diffusion (a uniform rate is what composes into an "
                    "explicit tap table) on a full (non-partition) "
                    "f32/bf16 grid, with no point flows when "
                    "substeps > 1; got "
                    f"flows={[type(f).__name__ for f in self.flows]}, "
                    f"is_partition={space.is_partition}, "
                    f"dtype={space.dtype}, substeps={substeps}. Use "
                    "impl='xla'/'auto', or ShardMapExecutor("
                    "step_impl='composed', halo_depth=k) for sharded "
                    "runs.")
            from ..ops.composed_stencil import (ComposedDiffusionStep,
                                               choose_k, max_k)
            from ..ops.pallas_stencil import resolve_interpret
            interp = resolve_interpret(next(iter(space.values.values())))
            ck = choose_k(substeps, space.shape, space.dtype)
            composed_passes = substeps // ck
            if ck == 1 and substeps > 1:
                # auto-k degenerated (prime substeps beyond the window's
                # composable depth): every "composed" call is substeps
                # iterated radius-1 passes — observable, not silent
                warnings.warn(
                    f"impl='composed' auto-k degenerated to k=1 for "
                    f"substeps={substeps} (no divisor <= the window's "
                    f"composable depth "
                    f"{max_k(space.shape, space.dtype)}): each call "
                    "runs iterated radius-1 passes, equaling the "
                    "iterated path. Pick substeps with a small divisor "
                    "to actually compose.", RuntimeWarning)
            composed_steppers = {
                attr: ComposedDiffusionStep(
                    space.shape, rate, ck, dtype=space.dtype,
                    offsets=offsets, interpret=interp,
                    compute_dtype=compute_dtype)
                for attr, rate in rates.items() if rate != 0.0}
        active_steppers = None
        if impl == "active":
            # the active-tile engine (ops.active): compute only tiles
            # whose ring-1 neighborhood holds mass — bitwise-exact
            # skipping for uniform-rate linear flows (zero stays zero),
            # dense fallback the same step above the capacity/activity
            # threshold. Point flows compose (they fire after the field
            # step; activity is recomputed from the values each call).
            live = self._active_live_rates(space, "active")
            from ..ops.active import ActiveDiffusionStep
            active_steppers = {
                attr: ActiveDiffusionStep(
                    space.shape, rate, dtype=space.dtype, offsets=offsets,
                    origin=origin, global_shape=space.global_shape,
                    dense_fn=self._pallas_dense_stepper(space, rate,
                                                        compute_dtype))
                for attr, rate in live.items()}
        fused_steppers = None
        fused_k = None
        fused_passes = None
        if impl == "active_fused":
            # the fused Pallas active-tile kernel (ops.pallas_active,
            # ISSUE 8): scalar-prefetched sparse streaming with in-kernel
            # flag computation; substeps > 1 composes k flow steps per
            # tile-resident pass (k auto-chosen dividing substeps, the
            # impl="composed" contract — a point flow must fire between
            # sub-steps, so substeps > 1 disqualifies point-flow models).
            live = self._active_live_rates(space, "active_fused")
            if substeps > 1 and pt_by_attr:
                raise ValueError(
                    "impl='active_fused' with substeps > 1 composes the "
                    "sub-steps inside the kernel pass; a point flow must "
                    "fire between sub-steps. Use substeps=1 or drop the "
                    "point flows.")
            from ..ops.pallas_active import (FusedActiveStep,
                                            choose_fused_k, plan_for)
            from ..ops.pallas_stencil import resolve_interpret
            interp = resolve_interpret(next(iter(space.values.values())))
            fused_k = choose_fused_k(substeps, plan_for(space.shape))
            fused_passes = substeps // fused_k
            if fused_k == 1 and substeps > 1:
                warnings.warn(
                    f"impl='active_fused' auto-k degenerated to k=1 for "
                    f"substeps={substeps} (no divisor fits the tile "
                    "geometry): each pass advances one step, equaling "
                    "the k=1 fused path. Pick substeps with a small "
                    "divisor to actually compose.", RuntimeWarning)
            fused_steppers = {
                attr: FusedActiveStep(
                    space.shape, rate, dtype=space.dtype, offsets=offsets,
                    origin=origin, global_shape=space.global_shape,
                    k=fused_k, passes=fused_passes, interpret=interp,
                    dense_fn=self._pallas_dense_stepper(space, rate,
                                                        compute_dtype))
                for attr, rate in live.items()}
        if impl in ("pallas", "auto"):
            rates = self.pallas_rates()
            all_pointwise = all(
                getattr(f, "footprint", "unknown") == "pointwise"
                for f in field_flows) and bool(field_flows)
            # substeps > 1 fuses steps inside the kernel, so a (local)
            # point flow — which must fire between sub-steps — disqualifies
            # f64 grids stay on the XLA path: the Pallas kernels compute
            # in f32 internally, and "auto" must never silently downgrade
            # the oracle-tier precision a user asked for
            base_ok = (not space.is_partition
                       and self.pallas_dtype_ok(space)
                       and (substeps == 1 or not pt_by_attr))
            if impl == "auto" and base_ok:
                # the fused steps must fit the kernel window's ghost
                # depth; deeper chunks compose the XLA step instead
                from ..ops.composed_stencil import max_k
                base_ok = substeps <= max_k(space.shape, space.dtype)
            # an EMPTY/all-zero rates map means no field transport at all
            # (pure point-flow model): nothing for a kernel to do, and
            # the step must not be labeled "pallas" (the scatter runs in
            # plain XLA — a user reading the CLI/bench impl field would
            # believe a kernel ran that never did)
            eligible = (bool(rates) and base_ok
                        and any(r != 0.0 for r in rates.values()))
            # the general field kernel is for models that NEED it (some
            # non-Diffusion pointwise flow → rates is None); an
            # all-Diffusion model with zero rates has no transport and
            # must not run (or be labeled) a no-op kernel
            field_eligible = all_pointwise and base_ok and rates is None
            if impl == "pallas" and not (eligible or field_eligible):
                raise ValueError(
                    "impl='pallas' requires all field flows to be "
                    "POINTWISE (Diffusion/Coupled/...) on a full "
                    "(non-partition) f32/bf16 grid — the kernel computes "
                    "in f32, so f64 stays on the XLA path — (and no "
                    "point flows when substeps > 1); got "
                    f"flows={[type(f).__name__ for f in self.flows]}, "
                    f"is_partition={space.is_partition}, "
                    f"dtype={space.dtype}, "
                    f"substeps={substeps}. Use impl='xla' "
                    "or 'auto'; for sharded DIFFUSION models use "
                    "ShardMapExecutor(mesh, step_impl='pallas') — the "
                    "per-shard halo kernel — or halo_depth>1; other "
                    "sharded flows run the XLA shard step.")
            # resolve interpret HERE, from the space's concrete arrays —
            # inside the executor's jit the values are tracers and
            # sample-based resolution would fall through to ambient
            # config, which can disagree with the data's real placement
            # (round-3 VERDICT weak #1)
            from ..ops.pallas_stencil import resolve_interpret
            interp = resolve_interpret(next(iter(space.values.values())))
            if eligible:
                # every field flow a plain Diffusion: the specialized
                # kernel with the closed-form interior fast path
                from ..ops.pallas_stencil import PallasDiffusionStep
                pallas_steppers = {
                    attr: PallasDiffusionStep(space.shape, rate,
                                              dtype=space.dtype,
                                              offsets=offsets,
                                              interpret=interp,
                                              nsteps=substeps,
                                              compute_dtype=compute_dtype)
                    for attr, rate in rates.items() if rate != 0.0}
            elif field_eligible:
                # general pointwise flows (Coupled, user flows): the
                # multi-channel fused field kernel — every outflow is
                # evaluated elementwise on the VMEM windows
                from ..ops.pallas_stencil import PallasFieldStep
                pallas_field_stepper = PallasFieldStep(
                    space.shape, field_flows, dtype=space.dtype,
                    offsets=offsets, interpret=interp, nsteps=substeps,
                    compute_dtype=compute_dtype)

        gshape = space.global_shape
        shape = (space.dim_x, space.dim_y)

        # the dense XLA path's transport is owned by the Flow IR's ONE
        # registered lowering (ISSUE 11): plain-Diffusion field flows
        # convert to IR Transport terms and the step body delegates to
        # ir.lower.dense_apply — the lowering the diffusion-as-IR gate
        # proves bitwise, now the single source of truth. Flows the IR
        # cannot represent exactly (user flows, several Diffusions on
        # one attr, off-space-dtype channels) keep the summed-outflow
        # legacy path.
        dense_ir = None
        dense_ir_meta = None
        if field_flows and impl in ("xla", "auto"):
            from ..ir.lower import StepMeta, diffusion_terms

            terms = diffusion_terms(field_flows)
            if terms is not None and all(
                    space.values[t.channel].dtype == jnp.dtype(space.dtype)
                    for t in terms):
                dense_ir = terms
                dense_ir_meta = StepMeta(
                    shape=shape, origin=origin, global_shape=gshape,
                    dtype=space.dtype, offsets=offsets)

        def single(values: Values) -> Values:
            new = dict(values)
            # counts as traced iota arithmetic INSIDE the step: closing
            # over the materialized numpy grid bakes an O(grid) constant
            # into the compiled program (256MB at 8192² f32)
            counts = neighbor_counts_traced(shape, offsets, origin, gshape,
                                            space.dtype)
            if composed_steppers is not None:
                # substeps/k composed passes per call (each pass = k
                # flow steps in one kernel invocation); eligibility
                # guaranteed no point flows interleave when substeps > 1
                for attr, stepper in composed_steppers.items():
                    cur = values[attr]
                    for _ in range(composed_passes):
                        cur = stepper(cur)
                    new[attr] = cur
            elif pallas_steppers is not None:
                # with substeps > 1, each stepper advances ALL the
                # sub-steps inside the kernel (and eligibility guaranteed
                # there are no point flows to interleave)
                for attr, stepper in pallas_steppers.items():
                    new[attr] = stepper(values[attr])
            elif pallas_field_stepper is not None:
                new.update(pallas_field_stepper(values))
            elif active_steppers is not None:
                # one active-set pass per flow channel; zero-rate
                # Diffusions move nothing and are skipped (the pallas/
                # composed discipline)
                for attr, stepper in active_steppers.items():
                    new[attr] = stepper(values[attr])
            elif fused_steppers is not None:
                # the fused Pallas active pass — each call advances
                # passes * k = substeps flow steps per channel
                for attr, stepper in fused_steppers.items():
                    new[attr] = stepper(values[attr])
            elif dense_ir is not None:
                from ..ir.lower import dense_apply

                new.update(dense_apply(
                    dense_ir, values, [t.rate for t in dense_ir],
                    dense_ir_meta, counts))
            else:
                outflow = build_outflow(field_flows, values, origin)
                for attr, o in outflow.items():
                    # analysis: ignore[hardcoded-physics] — legacy FLOW
                    # fallback for what the IR cannot represent exactly
                    # (user flows, summed same-attr outflows); the
                    # convertible dense path above runs the IR lowering
                    new[attr] = transport(values[attr], o, counts, offsets)
            # Point amounts read the PRE-step values (matches summed-outflow
            # semantics: transport is linear in outflow).
            for attr, pflows in pt_by_attr.items():
                locs = [f.local_source(values, origin) for f in pflows]
                xs = jnp.asarray([lx for lx, _, _ in locs])
                ys = jnp.asarray([ly for _, ly, _ in locs])
                amts = jnp.stack([f.amount(values, origin) for f in pflows])
                # analysis: ignore[hardcoded-physics] — the point-source
                # scatter (the reference's live workload) is outside the
                # IR field-term grammar by design
                new[attr] = point_flow_step(new[attr], xs, ys, amts, counts,
                                            offsets)
            return new

        if (substeps == 1 or pallas_steppers is not None
                or pallas_field_stepper is not None
                or composed_steppers is not None
                or fused_steppers is not None):
            step = single
        else:
            def step(values: Values) -> Values:
                for _ in range(substeps):
                    values = single(values)
                return values

        # which field-flow kernel the step actually uses (what "auto"
        # resolved to) — callers like bench report it
        step.impl = ("active_fused" if fused_steppers is not None
                     else "active" if active_steppers is not None
                     else "composed" if composed_steppers is not None
                     else "pallas" if (pallas_steppers is not None
                                       or pallas_field_stepper is not None)
                     else "xla")
        step.substeps = substeps
        # auto-k visibility (ISSUE 3 satellite): the chosen composed k
        # rides the step so executors/Reports can record it — the fused
        # active impl composes the same way (k·passes == substeps, the
        # jaxpr-halo audit contract)
        step.composed_k = (next(iter(composed_steppers.values())).k
                           if composed_steppers is not None
                           else fused_k)
        step.composed_passes = (composed_passes
                                if composed_steppers is not None
                                else fused_passes)
        self._step_cache[key] = step
        return step

    # -- execution ---------------------------------------------------------

    def conservation_threshold(self, space: CellularSpace,
                               tolerance: float = 1e-3,
                               rtol: Optional[float] = None,
                               initial_totals: Optional[dict] = None) -> float:
        """Allowed |Δtotal|: ``tolerance + rtol * |initial_total|``.

        ``tolerance`` is the reference's absolute 1e-3 contract
        (``Model.hpp:95``); the relative term absorbs the reduction's own
        floating-point noise, which grows with grid size — without it a
        *perfectly conserving* f32 run on a large grid trips the absolute
        bound. Default rtol ≈ 4·eps·log2(N), the pairwise-summation error
        bound for XLA reductions."""
        if rtol is None:
            rtol = default_conservation_rtol(space.shape, space.dtype)
        if initial_totals is None:
            initial_totals = {k: float(space.total(k)) for k in space.values}
        scale = max(abs(t) for t in initial_totals.values())
        return tolerance + rtol * scale

    def execute(
        self,
        space: CellularSpace,
        executor: Optional[Executor] = None,
        *,
        steps: Optional[int] = None,
        check_conservation: bool = True,
        tolerance: float = 1e-3,
        rtol: Optional[float] = None,
    ) -> tuple[CellularSpace, Report]:
        """Run the model; returns the final space and a Report.

        ``check_conservation`` enforces the reference's correctness contract
        (global sum within tolerance of its initial value, ``Model.hpp:95``)
        and raises ``ConservationError`` on violation; see
        ``conservation_threshold`` for how the bound scales.

        Executing a standalone *partition* space runs it like a reference
        worker before any halo receive: shares crossing the partition's
        interior edges are dropped (they belong to neighbor partitions), so
        conservation is a global—not per-partition—property and the check is
        skipped automatically. Use a sharded executor on the full space for
        distributed runs with halo delivery.
        """
        if executor is None:
            if self._default_executor is None:
                self._default_executor = SerialExecutor()
            executor = self._default_executor
        num_steps = self.num_steps if steps is None else steps

        from ..utils.tracing import trace_span

        with trace_span("model.execute", steps=num_steps,
                        executor=type(executor).__name__):
            initial = {k: float(space.total(k)) for k in space.values}
            t0 = _time.perf_counter()
            with trace_span("executor.run"):
                out_values = executor.run_model(self, space, num_steps)
                out_values = jax.tree.map(jax.block_until_ready, out_values)
            wall = _time.perf_counter() - t0

            with trace_span("model.report"):
                out_space = space.with_values(out_values)
                final = {k: float(out_space.total(k))
                         for k in out_space.values}
                last_exec = [float(f.execute(out_space))
                             for f in self.flows]

        report = Report(
            comm_size=getattr(executor, "comm_size", 1),
            # this process's rank in the cluster — the reference's
            # comm_rank (Main.cpp:23); 0 single-process, the true
            # process index under jax.distributed (multihost)
            rank_id=jax.process_index(),
            steps=num_steps,
            initial_total=initial,
            final_total=final,
            last_execute=last_exec,
            wall_time_s=wall,
            backend_report=getattr(executor, "last_backend_report", None),
        )
        if check_conservation and not space.is_partition:
            self._raise_if_violated(space, initial, final, tolerance, rtol)
        return out_space, report

    def _raise_if_violated(self, space: CellularSpace, initial: dict,
                           final: dict, tolerance: float,
                           rtol: Optional[float]) -> None:
        """The conservation gate, as an overridable seam: the classic
        per-channel |Δtotal| contract here; ``ir.FlowIRModel`` replaces
        it with per-term budget reconciliation (declared sources/sinks
        integrated and reconciled, violations naming the term)."""
        thresh = self.conservation_threshold(space, tolerance, rtol,
                                             initial_totals=initial)
        err = max(abs(final[k] - initial[k]) for k in initial)
        if err > thresh:
            raise ConservationError(
                f"mass conservation violated: |Δ| = "
                f"{err:.3e} > {thresh:.3e} "
                f"(initial={initial}, final={final})")

    def execute_many(
        self,
        spaces,
        *,
        models=None,
        executor=None,
        steps: Optional[int] = None,
        check_conservation: bool = True,
        tolerance: float = 1e-3,
        rtol: Optional[float] = None,
    ) -> list:
        """Run B independent scenarios as ONE batched device program
        (the ensemble engine, ``ensemble.batch``); returns a list of
        ``(space, Report)`` — one per scenario, matching B independent
        ``SerialExecutor`` runs of the same scenarios.

        ``models`` (default: this model for every lane) may vary NUMERIC
        flow parameters per scenario — rates, frozen snapshots — but
        must share this model's structure (flow types/attrs/sources,
        offsets) and the spaces' geometry/channel dtypes; anything else
        is a different compiled program and raises ``ValueError``.
        ``executor`` is an ``ensemble.EnsembleExecutor``
        (``impl="xla"`` — vmapped parametric step — or ``"pipeline"``,
        the pipelined-window Pallas kernel per lane under ``lax.map``).

        The conservation contract is enforced PER SCENARIO (a vmapped
        reduction yields per-lane totals); a violation raises
        ``ensemble.EnsembleConservationError`` carrying the failing
        scenario's index instead of poisoning the batch aggregate."""
        from ..ensemble.batch import EnsembleExecutor, run_ensemble

        if executor is None:
            if self._default_ensemble is None:
                self._default_ensemble = EnsembleExecutor()
            executor = self._default_ensemble
        return run_ensemble(
            self, spaces, models=models, executor=executor, steps=steps,
            check_conservation=check_conservation, tolerance=tolerance,
            rtol=rtol)
