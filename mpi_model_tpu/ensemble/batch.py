"""Ensemble batching: B independent scenarios stepped as ONE device program.

Every entry point before this subsystem steps exactly one scenario per
dispatch — the single-master design of the reference's ``Main.cpp``
carried over unchanged. The serving workload the ROADMAP names ("heavy
traffic from millions of users") has the opposite shape: MANY independent
small/medium simulations, each individually cheap, where per-dispatch
overhead (dispatch latency, Python, cache lookups) dominates a
one-at-a-time loop. Round-5 VERDICT (weak #5) named the same shape as the
pipelined-window kernel's real niche: "independent-dispatch workloads,
e.g. stepping an ensemble of grids". This module opens that workload:

- ``EnsembleSpace`` — B same-geometry scenarios stacked per channel into
  ``[B, H, W]`` arrays: the struct-of-arrays pytree with a LEADING BATCH
  AXIS. The batch axis is orthogonal to mesh axes — vmap sits OUTSIDE
  any sharding an interior step may use, so one scenario is always one
  whole lane, never split across devices (see docs/DESIGN.md).
- shared STRUCTURE, per-scenario PARAMETERS — two scenarios batch
  together when their models agree on everything except numeric flow
  parameters (rates, frozen snapshots): the ``structure_key``. The
  batched step is the serial XLA step's expression with flow parameters
  replaced by lanes of a traced ``[B, F]`` array, vmapped over the batch
  axis, so each lane reproduces a ``SerialExecutor`` run of the same
  scenario (bitwise at f64 — proven in ``tests/test_ensemble.py``).
- per-scenario CONSERVATION via a vmapped reduction: ``[B]`` totals per
  channel, the contract enforced PER LANE. A violation raises (or, for
  the scheduler's serving path, marks) ``EnsembleConservationError``
  carrying the failing scenario's INDEX — one bad scenario neither
  poisons nor hides inside a batch aggregate.
- ``impl="pipeline"`` — the opt-in interior engine: the pipelined-window
  Pallas kernel (``ops.pallas_stencil._pipeline_call``) applied
  per-scenario under ``lax.map``, so successive kernel dispatches read
  INDEPENDENT buffers — exactly the repeated-independent-dispatch
  pattern it measured 1.4x fast on (and the chained single-run scan it
  measured slow on never occurs back-to-back). Resolves VERDICT weak #5
  by giving the kernel the workload it wins.
"""

from __future__ import annotations

import copy
import dataclasses
import time as _time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.cellular_space import CellularSpace, first_float_dtype
from ..models.model import (ConservationError, Model, Report,
                            default_conservation_rtol)
from ..resilience import inject, lockdep
from ..ops.flow import Diffusion, PointFlow, build_outflow
from ..ops.stencil import neighbor_counts_traced, point_flow_step, transport

Values = dict[str, jax.Array]


class EnsembleConservationError(ConservationError):
    """Per-scenario mass-conservation contract violated; ``scenario`` is
    the index of the failing lane within its batch (the scheduler also
    attaches ``ticket`` when the lane came from a submission)."""

    def __init__(self, message: str, scenario: int):
        super().__init__(message)
        self.scenario = int(scenario)
        self.ticket: Optional[int] = None


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EnsembleSpace:
    """B stacked scenarios: one ``[B, H, W]`` array per attribute channel.

    A pytree (like ``CellularSpace``); the batch extent and grid dims are
    static. Only FULL grids stack — partitioning belongs INSIDE a
    scenario (a mesh executor), never across lanes.
    """

    values: dict[str, jax.Array]
    batch: int = dataclasses.field(metadata=dict(static=True))
    dim_x: int = dataclasses.field(metadata=dict(static=True))
    dim_y: int = dataclasses.field(metadata=dict(static=True))

    @staticmethod
    def stack(spaces: Sequence[CellularSpace]) -> "EnsembleSpace":
        """Stack same-geometry scenarios along a new leading batch axis.
        Every space must be a full grid with identical shape, channel
        names and per-channel dtypes."""
        spaces = list(spaces)
        if not spaces:
            raise ValueError("EnsembleSpace.stack needs at least one scenario")
        first = spaces[0]
        names = tuple(first.values.keys())
        for i, s in enumerate(spaces):
            if s.is_partition:
                raise ValueError(
                    f"scenario {i} is a partition; the ensemble engine "
                    "batches FULL grids — shard inside a scenario with a "
                    "mesh executor instead")
            if s.shape != first.shape:
                raise ValueError(
                    f"scenario {i} geometry {s.shape} != {first.shape}")
            if tuple(s.values.keys()) != names:
                raise ValueError(
                    f"scenario {i} channels {tuple(s.values)} != {names}")
            for k in names:
                if s.values[k].dtype != first.values[k].dtype:
                    raise ValueError(
                        f"scenario {i} channel {k!r} dtype "
                        f"{s.values[k].dtype} != {first.values[k].dtype}")
        vals = {k: jnp.stack([s.values[k] for s in spaces]) for k in names}
        return EnsembleSpace(vals, len(spaces), first.dim_x, first.dim_y)

    @property
    def shape(self) -> tuple[int, int]:
        return (self.dim_x, self.dim_y)

    @property
    def attribute_names(self) -> tuple[str, ...]:
        return tuple(self.values.keys())

    @property
    def dtype(self):
        """First FLOATING channel's dtype (the flow/transport dtype) —
        the same rule as ``CellularSpace.dtype``."""
        return first_float_dtype(self.values)

    def scenario(self, i: int) -> CellularSpace:
        """Lane ``i`` as its own full-grid ``CellularSpace``."""
        if not 0 <= i < self.batch:
            raise IndexError(f"scenario {i} out of range [0, {self.batch})")
        return CellularSpace({k: v[i] for k, v in self.values.items()},
                             self.dim_x, self.dim_y)

    def unstack(self) -> list[CellularSpace]:
        return [self.scenario(i) for i in range(self.batch)]


# -- structure vs parameters -------------------------------------------------

def _ir_nonlinear(model) -> bool:
    """True for a FlowIRModel whose terms need the general IR lowering
    (nonlinear/coupled/source-sink physics). LINEAR IR models present an
    exact Diffusion flows view and ride every flow-based code path
    below unchanged — a linear IR scenario even batches with an
    equivalent flow-built scenario."""
    return (getattr(model, "ir_terms", None) is not None
            and not model.ir_linear)


def structure_key(model, space) -> tuple:
    """Hashable batch-compatibility key: everything two (model, space)
    pairs must SHARE to ride one compiled ensemble program — flow
    structure (types, attrs, sources, modulators, frozen-ness), offsets,
    grid geometry and per-channel dtypes. Numeric per-scenario
    parameters (``flow_rate``, the frozen snapshot VALUE) are excluded:
    they travel as traced ``[B, F]`` lanes instead. ``space`` may be a
    ``CellularSpace`` or an ``EnsembleSpace``.

    Nonlinear IR models key on their TERM structure (``term_structure``
    — term kinds/names/channels/expressions, rates excluded: each
    term's rate is its parameter lane)."""
    if _ir_nonlinear(model):
        chans = tuple(sorted((k, str(v.dtype))
                             for k, v in space.values.items()))
        return (("__ir__",) + model.term_structure(),
                (space.dim_x, space.dim_y), chans)
    flows = []
    for f in model.flows:
        name, items = f.fingerprint()
        items = list(
            (k, (v is not None) if k == "frozen_source_value" else v)
            for k, v in items if k != "flow_rate")
        if isinstance(f, PointFlow):
            # the source CELL's repr embeds its attribute snapshot — a
            # numeric parameter; only the COORDINATES are structural
            items = [(k, v) for k, v in items if k != "source"]
            items.append(("source_xy", f.source_xy))
        flows.append((name, tuple(sorted(items))))
    chans = tuple(sorted((k, str(v.dtype)) for k, v in space.values.items()))
    return (tuple(flows), tuple(model.offsets),
            (space.dim_x, space.dim_y), chans)


def flow_params(models: Sequence) -> tuple[np.ndarray, np.ndarray]:
    """Per-scenario numeric flow parameters as ``[B, F]`` float64 host
    arrays: rates, and frozen snapshot values (0.0 filler for flows that
    have none — frozen-ness itself is structural, see ``structure_key``).

    For nonlinear IR models the rate lanes are the PER-TERM rates (one
    lane per term — THE per-scenario IR parameter; frozens stay zero
    filler)."""
    B = len(models)
    if B and _ir_nonlinear(models[0]):
        F = len(models[0].ir_terms)
        rates = np.zeros((B, F), np.float64)
        for b, m in enumerate(models):
            rates[b] = m.term_rates()
        return rates, np.zeros((B, F), np.float64)
    F = len(models[0].flows) if B else 0
    rates = np.zeros((B, F), np.float64)
    frozens = np.zeros((B, F), np.float64)
    for b, m in enumerate(models):
        for i, f in enumerate(m.flows):
            rates[b, i] = float(f.flow_rate)
            fv = getattr(f, "frozen_source_value", None)
            if fv is not None:
                frozens[b, i] = float(fv)
    return rates, frozens


def _substituted(template_flows, rates, frozens) -> list:
    """Copies of the template flows with per-flow parameters taken from
    ``rates``/``frozens`` lanes (traced scalars inside the batched step,
    concrete floats for padding lanes). Works for dataclass flows
    (``dataclasses.replace``) and plain-attribute user subclasses."""
    out = []
    for i, f in enumerate(template_flows):
        kw = {"flow_rate": rates[i]}
        if isinstance(f, PointFlow) and f.frozen_source_value is not None:
            kw["frozen_source_value"] = frozens[i]
        if dataclasses.is_dataclass(f):
            out.append(dataclasses.replace(f, **kw))
        else:
            g = copy.copy(f)
            for k, v in kw.items():
                setattr(g, k, v)
            out.append(g)
    return out


def padding_scenarios(model, space: CellularSpace,
                      n: int) -> tuple[list[CellularSpace], list[Model]]:
    """``n`` zero scenarios structure-compatible with ``(model, space)``:
    all-zero channels and zero-rate flows. Padded lanes move nothing,
    total nothing and conserve trivially — they contribute ZERO to
    conservation checks and never appear in reports.

    IR padding: every term's contribution is ``rate * amount``, so the
    all-zero rate vector is a PROVABLE no-op for any term set — the
    property that makes zero-padding inert for arbitrary IR physics,
    not just zero-rate Diffusions."""
    if _ir_nonlinear(model):
        zvals = {k: jnp.zeros_like(v) for k, v in space.values.items()}
        zspace = CellularSpace(zvals, space.dim_x, space.dim_y)
        zmodel = model.with_rates([0.0] * len(model.ir_terms))
        return [zspace] * n, [zmodel] * n
    F = len(model.flows)
    zvals = {k: jnp.zeros_like(v) for k, v in space.values.items()}
    zspace = CellularSpace(zvals, space.dim_x, space.dim_y)
    zflows = _substituted(model.flows, [0.0] * F, [0.0] * F)
    zmodel = Model(zflows, model.time, model.time_step,
                   offsets=model.offsets)
    return [zspace] * n, [zmodel] * n


# -- the vmapped parametric step ---------------------------------------------

def make_scenario_step(model, space) -> Callable:
    """Single-scenario step ``(values, rates, frozens) -> values`` with
    TRACED per-flow parameters, mirroring ``Model.make_step``'s XLA path
    term for term (``neighbor_counts_traced`` → ``build_outflow`` →
    ``transport`` → ``point_flow_step`` on pre-step amounts), so one
    vmapped lane reproduces a ``SerialExecutor`` run of that scenario.
    Non-float FLOW channels are rejected exactly like ``make_step``;
    int/bool bystander channels (masks etc.) ride along untouched.

    Nonlinear IR models build the SAME registered lowering the serial
    dense step runs (``ir.lower.dense_apply``), with each term's rate
    read from its traced parameter lane — one lane reproduces that
    scenario's ``SerialExecutor`` run bitwise at f64."""
    offsets = model.offsets
    shape = (space.dim_x, space.dim_y)
    if _ir_nonlinear(model):
        from ..ir.lower import StepMeta, dense_apply

        model._validate_space(space)
        terms = model.ir_terms
        meta = StepMeta(shape=shape, origin=(0, 0), global_shape=shape,
                        dtype=space.dtype, offsets=tuple(offsets))
        dtype = space.dtype
        T = len(terms)

        def ir_single(values: Values, rates, frozens) -> Values:
            counts = neighbor_counts_traced(shape, offsets, (0, 0),
                                            shape, dtype)
            return dense_apply(terms, values,
                               [rates[i] for i in range(T)], meta, counts)

        return ir_single
    for f in model.flows:
        ch = space.values.get(f.attr)
        if ch is None:
            raise ValueError(
                f"flow {type(f).__name__} targets channel {f.attr!r} "
                f"which the space does not carry (has {tuple(space.values)})")
        if not jnp.issubdtype(ch.dtype, jnp.floating):
            raise TypeError(
                f"flow transport requires a floating dtype, got {ch.dtype} "
                f"for channel {f.attr!r} (integer/bool channels are "
                "supported for storage/comm/masks, not flows)")
    dtype = space.dtype
    template = list(model.flows)
    # owner filter at BUILD time from the static source coords, exactly
    # as make_step does (full grids only here, so "inside" is static)
    pt_idx = [i for i, f in enumerate(template)
              if isinstance(f, PointFlow)
              and f.local_source({f.attr: space.values[f.attr]}, (0, 0))[2]]

    def single(values: Values, rates, frozens) -> Values:
        flows = _substituted(template, rates, frozens)
        field_flows = [f for f in flows if not isinstance(f, PointFlow)]
        pt_by_attr: dict[str, list] = {}
        for i in pt_idx:
            pt_by_attr.setdefault(flows[i].attr, []).append(flows[i])
        new = dict(values)
        counts = neighbor_counts_traced(shape, offsets, (0, 0), shape,
                                        dtype)
        outflow = build_outflow(field_flows, values, (0, 0))
        for attr, o in outflow.items():
            # analysis: ignore[hardcoded-physics] — legacy FLOW path:
            # summed multi-flow outflows have no exact IR twin (a
            # one-term sum rounds differently); IR models never get here
            new[attr] = transport(values[attr], o, counts, offsets)
        # point amounts read the PRE-step values (summed-outflow
        # semantics — the serial step's exact discipline)
        for attr, pflows in pt_by_attr.items():
            locs = [f.local_source(values, (0, 0)) for f in pflows]
            xs = jnp.asarray([lx for lx, _, _ in locs])
            ys = jnp.asarray([ly for _, ly, _ in locs])
            amts = jnp.stack([f.amount(values, (0, 0)) for f in pflows])
            # analysis: ignore[hardcoded-physics] — the point-source
            # scatter is the reference workload's sparse path, outside
            # the IR's field-term grammar by design
            new[attr] = point_flow_step(new[attr], xs, ys, amts, counts,
                                        offsets)
        return new

    return single


def batched_totals(values_b: Values) -> dict[str, np.ndarray | jax.Array]:
    """Per-scenario channel totals: ``[B]`` per channel. Accumulation
    mirrors ``CellularSpace.total`` lane-wise: integer channels sum
    host-side in int64 (exact — a device float accumulation would make
    ensemble Report totals diverge from the serial path's), f64 channels
    in f64 on device, everything else (incl. bool masks) in
    f32-or-wider."""
    out = {}
    for k, v in values_b.items():
        if jnp.issubdtype(v.dtype, jnp.integer):
            out[k] = np.asarray(v).reshape(v.shape[0], -1).sum(
                axis=1, dtype=np.int64)
        elif v.dtype == jnp.float64:
            out[k] = jnp.sum(v, axis=(1, 2), dtype=jnp.float64)
        else:
            out[k] = jnp.sum(v, axis=(1, 2),
                             dtype=jnp.promote_types(v.dtype, jnp.float32))
    return out


# -- per-scenario conservation -----------------------------------------------

def conservation_thresholds(initial: dict[str, np.ndarray],
                            shape: tuple[int, int], dtype,
                            tolerance: float = 1e-3,
                            rtol: Optional[float] = None) -> np.ndarray:
    """Per-scenario allowed |Δtotal| — ``Model.conservation_threshold``'s
    formula applied lane-wise: ``tolerance + rtol * scale_i`` where
    ``scale_i`` is scenario i's largest |initial channel total|. The
    default rtol is the SHARED ``default_conservation_rtol`` bound, so
    a lane's threshold always equals its serial run's."""
    if rtol is None:
        rtol = default_conservation_rtol(shape, dtype)
    scale = np.max(np.abs(np.stack(list(initial.values()), axis=0)), axis=0)
    return tolerance + rtol * scale


def conservation_violations(initial: dict[str, np.ndarray],
                            final: dict[str, np.ndarray],
                            thresholds: np.ndarray,
                            count: int) -> tuple[np.ndarray, list[int]]:
    """(per-lane max |Δtotal| errors ``[B]``, violating lane indices
    ``< count``). Lanes at index >= ``count`` are padding and never
    counted. A NON-FINITE lane error (a NaN/Inf-poisoned lane makes its
    total NaN) is always a violation: ``NaN > threshold`` is False, so
    a plain comparison would wave the worst possible state through."""
    errs = np.max(np.abs(np.stack(
        [final[k] - initial[k] for k in initial], axis=0)), axis=0)
    head = errs[:count]
    bad = np.nonzero((head > thresholds[:count]) | ~np.isfinite(head))[0]
    return errs, [int(i) for i in bad]


def _violation_error(errs: np.ndarray, thresholds: np.ndarray, i: int,
                     nbad: Optional[int] = None,
                     count: Optional[int] = None,
                     key: Optional[str] = None,
                     model=None) -> EnsembleConservationError:
    """The one place the per-lane violation message is built. ``key``
    (the worst-violating view key) plus an IR model routes the wording
    through ``FlowIRModel.violation_message`` so a violated source/sink
    contract names its TERM identically to the serial gate."""
    if not np.isfinite(errs[i]):
        msg = (f"non-finite state in scenario {i}: its channel totals "
               "are NaN/Inf (divergence or a poisoned lane)")
    elif key is not None and hasattr(model, "violation_message"):
        msg = (f"scenario {i}: "
               + model.violation_message(key, float(errs[i]),
                                         float(thresholds[i])))
    else:
        msg = (f"mass conservation violated in scenario {i}: |Δ| = "
               f"{errs[i]:.3e} > {thresholds[i]:.3e}")
    if nbad is not None:
        msg += f" ({nbad} of {count} scenarios violated)"
    return EnsembleConservationError(msg, scenario=i)


def _worst_violation_keys(initial: dict, final: dict) -> list[str]:
    """Per-lane key with the largest |Δ| (non-finite dominates) — what
    names the violating term in IR budget-reconciliation errors."""
    ks = list(initial)
    stack = np.abs(np.stack(
        [np.asarray(final[k], np.float64) - np.asarray(initial[k],
                                                       np.float64)
         for k in ks], axis=0))
    stack = np.where(np.isfinite(stack), stack, np.inf)
    idx = np.argmax(stack, axis=0)
    return [ks[int(j)] for j in np.atleast_1d(idx)]


def check_batch_conserved(initial: dict[str, np.ndarray],
                          final: dict[str, np.ndarray],
                          thresholds: np.ndarray,
                          count: int) -> np.ndarray:
    """Enforce the contract per lane; raises ``EnsembleConservationError``
    naming the FIRST violating scenario's index. Returns the per-lane
    errors when every real lane conserves."""
    errs, bad = conservation_violations(initial, final, thresholds, count)
    if bad:
        raise _violation_error(errs, thresholds, bad[0], len(bad), count)
    return errs


# -- the batched executor ----------------------------------------------------

class EnsembleExecutor:
    """Batched execution strategy: one compiled program advances every
    scenario lane together.

    ``impl`` selects the interior engine:

    - ``"xla"`` (default): the vmapped parametric step — per-scenario
      rates/frozen snapshots as traced lanes; works for every flow
      combination the serial XLA step supports.
    - ``"pipeline"``: the pipelined-window Pallas kernel
      (``ops.pallas_stencil._pipeline_call``) applied per scenario under
      ``lax.map`` — successive kernel dispatches read INDEPENDENT lane
      buffers, the repeated-independent-dispatch pattern the kernel
      measured 1.4x fast on (round-5; VERDICT weak #5). Requires
      all-Diffusion models sharing ONE rate set across the batch (the
      kernel's rate is compile-time static), an f32/bf16 grid divisible
      into 16-row/128-col strips, and ``substeps <= 8``; raises
      ``ValueError`` otherwise (opt-in — no silent fallback).
    - ``"active"``: the active-tile engine per lane (``ops.active``,
      ISSUE 3) — each scenario skips its own quiet ocean; all-Diffusion
      batches with per-lane rates (any float dtype, f64 included).
    - ``"active_fused"``: the fused Pallas active kernel per lane
      (``ops.pallas_active``, ISSUE 8) — the active engine's skip rule
      with scalar-prefetched window streaming and in-kernel flag
      computation; same eligibility as ``"active"``. Per-lane rates are
      traced, so every pass runs the exact iterated path (tap tables
      need a concrete rate).

    ``substeps`` fuses that many model steps per compiled step call
    (kernel-fused on the pipeline path; composed singles on the XLA
    path); any remainder runs as single steps, so semantics are
    independent of the setting. Runners are cached by
    ``(batch, shape, channel dtypes, impl, substeps, structure,
    mesh token)`` — ``builds``/``cache_hits`` count misses/hits for
    the serving counters.

    ``mesh`` (xla impl only) is an ``ensemble.mesh.EnsembleMesh``:
    runners constrain the ``[B,H,W]`` carry to
    ``P("batch", "space", None)`` so GSPMD shards scenario lanes over
    the batch axis (and grid rows over the space axis) instead of
    replicating — the ISSUE 16 2-D data-parallel layout. The mesh
    token (axis extents + device ids) is part of the runner cache key,
    so resizing the mesh — or the CPU rig's
    ``--xla_force_host_platform_device_count`` — can never serve a
    stale compiled runner.
    """

    comm_size = 1

    def __init__(self, impl: str = "xla", substeps: int = 1,
                 compute_dtype=None, mesh=None):
        if impl not in ("xla", "pipeline", "active", "active_fused"):
            raise ValueError(
                f"unknown ensemble impl {impl!r} (expected 'xla', "
                "'pipeline', 'active' or 'active_fused')")
        if mesh is not None and impl != "xla":
            raise ValueError(
                f"mesh-sharded dispatch supports impl='xla' only, got "
                f"{impl!r} (the other impls carry per-lane state the "
                "batch-axis sharding contract does not cover)")
        self.impl = impl
        self.substeps = max(1, int(substeps))
        #: interior-tile math dtype for the pipeline kernel (None → f32)
        self.compute_dtype = compute_dtype
        #: ``EnsembleMesh`` (or None): the (batch, space) placement the
        #: xla runners constrain their carry to. Plain attribute — the
        #: cache key reads ``mesh.token()`` per lookup, so swapping the
        #: mesh rebuilds instead of serving a stale runner.
        self.mesh = mesh
        self.last_impl: Optional[str] = None
        #: per-run report detail (impl="active" stats); None otherwise
        self.last_backend_report: Optional[dict] = None
        #: guards the runner cache + its build/hit counters: the async
        #: loop pins all dispatching to one pump thread, but the SYNC
        #: service dispatches inline on whichever client thread filled
        #: the bucket — two racing submitters must not double-compile a
        #: runner or lose counter updates (ISSUE 9 thread-safety work)
        self._cache_lock = lockdep.lock("EnsembleExecutor._cache_lock")
        self._cache: dict = {}
        #: runner-build / cache-hit counters (the scheduler's
        #: compile-cache-hit fields read these)
        self.builds = 0
        self.cache_hits = 0

    def runner_for(self, model, espace: EnsembleSpace,
                   uniform_rates: Optional[dict] = None,
                   donate: bool = False):
        """``donate=True`` (xla impl only) builds the runner with
        ``donate_argnums=0``: the ``[B,H,W]`` state pytree is consumed
        by each call and its buffers are reused for the output — the
        copy-free carry between consecutive WINDOWS of the same
        scenario batch (ISSUE 9; the pjit donation idiom of
        SNIPPETS.md [1]/[3]). Donated and undonated runners cache under
        distinct keys (same jaxpr, different aliasing contract)."""
        if donate and self.impl != "xla":
            raise ValueError(
                f"donated dispatch supports impl='xla' only (the "
                f"'{self.impl}' runner carries stat lanes alongside the "
                "state, so the carry is not a pure [B,H,W] pytree)")
        key = (espace.batch, espace.shape, self.impl, self.substeps,
               str(self.compute_dtype) if self.compute_dtype is not None
               else None,
               structure_key(model, espace), bool(donate),
               self.mesh.token() if self.mesh is not None else None)
        if uniform_rates is not None:
            key = key + (tuple(sorted(uniform_rates.items())),)
        # build INSIDE the lock: serializing a miss is the point — two
        # racing sync-path submitters must get one build, one hit
        with self._cache_lock:
            runner = self._cache.get(key)
            if runner is not None:
                self.cache_hits += 1
                return runner
            self.builds += 1
            if self.impl == "pipeline":
                # analysis: ignore[blocking-under-lock] — serializing
                # the miss is the point (two racing sync submitters
                # must get one build, one hit); builder device work is
                # the cost of the single-build guarantee
                runner = self._build_pipeline(model, espace, uniform_rates)
            elif self.impl in ("active", "active_fused"):
                # analysis: ignore[blocking-under-lock] — serialize the
                # miss (see the pipeline branch)
                runner = self._build_active(
                    model, espace, fused=self.impl == "active_fused")
            else:
                # analysis: ignore[blocking-under-lock] — serialize the
                # miss (see the pipeline branch)
                runner = self._build_xla(model, espace, donate=donate)
            self._cache[key] = runner
            return runner

    def _build_xla(self, model, espace: EnsembleSpace,
                   donate: bool = False):
        single = make_scenario_step(model, espace)
        substeps = self.substeps
        mesh = self.mesh

        def stepk(v, rr, ff):
            for _ in range(substeps):
                v = single(v, rr, ff)
            return v

        bk = jax.vmap(stepk, in_axes=(0, 0, 0))
        b1 = (bk if substeps == 1
              else jax.vmap(single, in_axes=(0, 0, 0)))

        if mesh is not None:
            # Constrain the carry to the (batch, space) layout at entry
            # and on every loop-body output: GSPMD propagates shardings
            # through the fori_loop, but pinning the body output keeps
            # the carry from collapsing to replicated on any dtype or
            # reshape boundary the flows introduce (the idiom of
            # parallel.AutoShardedExecutor, extended with a batch axis).
            vsh = mesh.value_sharding()

            def _pin(vb):
                return {k: jax.lax.with_sharding_constraint(v, vsh)
                        for k, v in vb.items()}
        else:
            def _pin(vb):
                return vb

        def run(vb, rates_b, frozens_b, q, r):
            # q k-step calls + r single steps == num_steps; both counts
            # are TRACED scalars, so one compile serves every step count
            vb = _pin(vb)
            vb = jax.lax.fori_loop(
                0, q, lambda i, c: _pin(bk(c, rates_b, frozens_b)), vb)
            vb = jax.lax.fori_loop(
                0, r, lambda i, c: _pin(b1(c, rates_b, frozens_b)), vb)
            return vb

        # donation aliases the output onto the input buffers — the SAME
        # program (bitwise) minus the inter-window copy of the state
        return jax.jit(run, donate_argnums=0) if donate else jax.jit(run)

    def last_execute_for(self, model, espace: EnsembleSpace):
        """Batched ``Flow.execute``: ONE jitted vmapped program producing
        the ``[B, F]`` per-lane outflow sums the Reports carry — not B×F
        separate per-lane device reductions after every dispatch (that
        per-lane host-synced tail grows linearly with B and would erode
        the scenarios/s the batch program buys). Cached alongside the
        runners but outside the ``builds``/``cache_hits`` counters, which
        count STEP programs only (the serving occupancy metric)."""
        key = ("last_execute", espace.batch, espace.shape,
               structure_key(model, espace))
        with self._cache_lock:
            fn = self._cache.get(key)
            if fn is None:
                template = list(model.flows)

                def single(values: Values, rates, frozens):
                    flows = _substituted(template, rates, frozens)
                    if not flows:
                        return jnp.zeros((0,), jnp.float32)
                    return jnp.stack([jnp.sum(f.outflow(values, (0, 0)))
                                      for f in flows])

                fn = jax.jit(jax.vmap(single, in_axes=(0, 0, 0)))
                self._cache[key] = fn
            return fn

    def _build_active(self, model, espace: EnsembleSpace,
                      fused: bool = False):
        """Per-scenario ACTIVITY (ISSUE 3): each lane runs the
        active-tile whole-run stepper (``ops.active`` — pad once, carry
        the tile map, compute only active tiles, dense-fallback above
        the threshold) under ``lax.map``, so one lane's quiet ocean is
        skipped regardless of its batchmates' wavefronts, and each lane
        conds on its OWN activity (under ``vmap`` the cond would
        degenerate to computing both branches for every lane).

        All-Diffusion scenario batches only; per-lane rates ride the
        traced ``[B, F]`` parameter lanes like the XLA engine's. A lane
        with a SINGLE Diffusion per channel reproduces the serial run
        bitwise (channels fed by several flows sum rates before the
        multiply, ~1 ULP from the serial summed-outflow grouping)."""
        from ..ops import active as act

        impl_name = "active_fused" if fused else "active"
        flows = list(model.flows)
        if not flows or any(type(f) is not Diffusion for f in flows):
            raise ValueError(
                f"impl={impl_name!r} supports all-Diffusion scenario "
                "batches (the tile-skip rule is only bitwise-exact for "
                "uniform-rate linear flows); got "
                f"flows={[type(f).__name__ for f in flows]}. "
                "Use impl='xla'.")
        for f in flows:
            adt = espace.values[f.attr].dtype
            if not jnp.issubdtype(adt, jnp.floating):
                raise TypeError(
                    f"flow transport requires a floating dtype, got "
                    f"{adt} for channel {f.attr!r}")
            if adt != jnp.dtype(espace.dtype):
                raise ValueError(
                    f"impl={impl_name!r} computes every flow channel in "
                    f"the space dtype ({jnp.dtype(espace.dtype).name}); "
                    f"channel {f.attr!r} is {adt}. Use impl='xla'.")
        attr_idx: dict[str, list[int]] = {}
        for i, f in enumerate(flows):
            attr_idx.setdefault(f.attr, []).append(i)
        if fused:
            from ..ops.pallas_active import (build_fused_runner,
                                             choose_fused_k)
            from ..ops.pallas_stencil import resolve_interpret

            plan = act.plan_for(espace.shape)
            lane = build_fused_runner(
                espace.shape, attr_idx, model.offsets, espace.dtype,
                plan=plan, k=choose_fused_k(self.substeps, plan),
                traced_rates=True,
                interpret=resolve_interpret(
                    next(iter(espace.values.values()))))
        else:
            lane = act.build_active_runner(
                espace.shape, attr_idx, model.offsets, espace.dtype,
                traced_rates=True)
        substeps = self.substeps

        def run(vb, rates_b, frozens_b, q, r):
            n = q * np.int32(substeps) + r

            def one(args):
                v, rlane = args
                return lane(v, n, rlane)

            # stats ride out as [B] lanes: a batch that dense-fell-back
            # every step must not be silently labeled "active"
            # (run_ensemble folds them into backend_report — the same
            # honesty contract as the serial and sharded runners)
            return jax.lax.map(one, (vb, rates_b))

        return jax.jit(run)

    def _build_pipeline(self, model, espace: EnsembleSpace,
                        rates: Optional[dict]):
        from ..ops.pallas_stencil import (_pipeline_blocks,
                                          pallas_dense_step,
                                          resolve_interpret)

        if rates is None or not any(r != 0.0 for r in rates.values()):
            raise ValueError(
                "impl='pipeline' requires all flows to be plain Diffusion "
                "with a nonzero rate shared across the batch; got "
                f"flows={[type(f).__name__ for f in model.flows]}")
        for attr in rates:
            if jnp.dtype(espace.values[attr].dtype).itemsize > 4:
                raise ValueError(
                    "impl='pipeline' computes in f32 — f64 grids stay on "
                    f"impl='xla' (channel {attr!r} is "
                    f"{espace.values[attr].dtype})")
        if _pipeline_blocks(*espace.shape) is None or self.substeps > 8:
            raise ValueError(
                "impl='pipeline' needs a grid divisible into 16-row/"
                f"128-col strips and substeps <= 8; got {espace.shape} "
                f"substeps={self.substeps}. Use impl='xla'.")
        interp = resolve_interpret(next(iter(espace.values.values())))
        offsets = model.offsets
        cdt = self.compute_dtype

        def scen(values, ns):
            new = dict(values)
            for attr, rate in rates.items():
                if rate == 0.0:
                    continue
                new[attr] = pallas_dense_step(
                    values[attr], rate, offsets=offsets, interpret=interp,
                    nsteps=ns, compute_dtype=cdt, pipeline=True)
            return new

        def run(vb, rates_b, frozens_b, q, r):
            # lax.map, NOT vmap: each lane is its own kernel dispatch, so
            # back-to-back dispatches read independent buffers — the
            # pipelined kernel's winning pattern (module docstring)
            vb = jax.lax.fori_loop(
                0, q,
                lambda i, c: jax.lax.map(
                    lambda v: scen(v, self.substeps), c), vb)
            vb = jax.lax.fori_loop(
                0, r, lambda i, c: jax.lax.map(lambda v: scen(v, 1), c), vb)
            return vb

        return jax.jit(run)


def _uniform_rates(model, models, rates_np: np.ndarray) -> dict:
    """Validate the pipeline engine's batch-uniform-rate requirement and
    return the attr → summed-rate map (``Model.pallas_rates`` shape)."""
    if any(isinstance(f, PointFlow) for f in model.flows):
        raise ValueError(
            "impl='pipeline' supports field (Diffusion) flows only; got "
            f"flows={[type(f).__name__ for f in model.flows]}")
    rates = models[0].pallas_rates()
    if rates is None:
        raise ValueError(
            "impl='pipeline' requires all flows to be plain Diffusion "
            "(a uniform rate is what the kernel compiles in); got "
            f"flows={[type(f).__name__ for f in model.flows]}")
    if rates_np.size and not np.all(rates_np == rates_np[0:1]):
        raise ValueError(
            "impl='pipeline' requires every scenario in the batch to "
            "share one rate set (the kernel's rate is compile-time "
            "static); got differing per-scenario rates — use impl='xla'")
    return rates


@dataclasses.dataclass
class EnsembleInFlight:
    """One LAUNCHED-but-not-fetched ensemble dispatch (ISSUE 9): the
    device program is dispatched (async), nothing is blocked on, and
    every host-side artifact ``complete_ensemble`` needs travels here.
    The always-on serving loop launches batch N, assembles/launches
    batch N+1 on the host thread while N runs on-device, then completes
    N — ``run_ensemble`` is the degenerate launch-then-complete
    composition, so the synchronous path and the async path execute the
    SAME code (bitwise results by construction)."""

    executor: "EnsembleExecutor"
    model: object
    espace: EnsembleSpace
    #: the runner's raw output (dict of [B,H,W] values, or the active
    #: impls' (values, stat-lanes) tuple) — dispatched, NOT blocked on
    out: object
    rates_b: object
    frozens_b: object
    count: int
    num_steps: int
    #: per-channel [B] initial totals (device scalars / host ints)
    initial_d: dict
    #: perf_counter at dispatch, for the batch wall time
    t0: float
    #: (lane, Fault) poisons captured at LAUNCH (the scheduler's
    #: ticket→lane window is open then; applied at complete)
    poisons: list
    #: windows whose carry was verifiably donated (buffer reused, no
    #: inter-window copy) — the no-copy assertion's observable
    donated_windows: int = 0
    windows: int = 1
    #: perf_counter when the launch returned (device program enqueued):
    #: the wall bills launch + fetch, NOT the async overlap gap between
    #: them (during which this batch ran unobserved while the loop
    #: assembled its successor)
    t_launched: float = 0.0


def _window_steps(num_steps: int, windows: int) -> list[int]:
    """Split ``num_steps`` across ``windows`` runner calls (earlier
    windows take the remainder): same step sequence, so windowed
    results are bitwise-equal to the single-call dispatch."""
    windows = max(1, min(int(windows), max(num_steps, 1)))
    base, rem = divmod(num_steps, windows)
    return [base + (1 if w < rem else 0) for w in range(windows)]


def launch_ensemble(model, spaces, *, models=None, executor=None,
                    steps=None, count: Optional[int] = None,
                    windows: int = 1,
                    donate: bool = False) -> EnsembleInFlight:
    """Validate, stack, resolve/compile the runner and DISPATCH one
    ensemble batch without fetching results — the launch half of
    ``run_ensemble`` (module docstring there). Everything host-side
    (structure checks, padding-compatible stacking, runner-cache
    lookup, compile on a miss) happens here, so an async serving loop
    overlaps this work with the previous batch's device execution.

    ``windows > 1`` advances the batch in that many runner calls
    instead of one (same step sequence — bitwise identical); with
    ``donate=True`` (xla impl only) each window's carry is DONATED to
    the next, eliminating the inter-window copy of the ``[B,H,W]``
    state; ``EnsembleInFlight.donated_windows`` counts the windows
    whose input buffers were verifiably consumed (``is_deleted``) —
    the no-copy assertion the serving tests pin."""
    spaces = list(spaces)
    B = len(spaces)
    if B == 0:
        raise ValueError("run_ensemble needs at least one scenario")
    models = list(models) if models is not None else [model] * B
    if len(models) != B:
        raise ValueError(
            f"{len(models)} models for {B} spaces — one model per scenario")
    skey = structure_key(model, spaces[0])
    for i, (m, s) in enumerate(zip(models, spaces)):
        if structure_key(m, s) != skey:
            raise ValueError(
                f"scenario {i} is not batch-compatible with the template: "
                "models must share flow structure (types/attrs/sources/"
                "frozen-ness), offsets, geometry and channel dtypes; only "
                "numeric parameters (rates, frozen snapshots) may vary")
    espace = EnsembleSpace.stack(spaces)
    if executor is None:
        executor = EnsembleExecutor()
    count = B if count is None else int(count)
    num_steps = model.num_steps if steps is None else int(steps)
    windows = max(1, int(windows))
    if windows > 1 and executor.impl != "xla":
        raise ValueError(
            f"windowed dispatch supports impl='xla' only, got "
            f"{executor.impl!r} (the stat-lane carry of the active "
            "impls does not window)")
    rates_np, frozens_np = flow_params(models)
    # the uniform-rate requirement binds REAL lanes only: padding lanes
    # are all-zero VALUES, so the kernel's static shared rate keeps them
    # identically zero regardless of their (zero-rate) parameter lanes
    uniform = (None if executor.impl != "pipeline"
               else _uniform_rates(model, models, rates_np[:count]))
    mesh = getattr(executor, "mesh", None)
    if mesh is not None:
        # divisibility is validated BEFORE compiling: the scheduler pads
        # to (bucket × mesh) so it never trips this; direct callers get
        # told to pad rather than a GSPMD shape error mid-trace
        mesh.validate(espace.batch, espace.shape)
    runner = executor.runner_for(model, espace, uniform, donate=donate)
    # f64 host params: jnp.asarray keeps f64 under x64 (bit-parity with
    # the serial path's python-float rates), f32 otherwise
    rates_b = jnp.asarray(rates_np)
    frozens_b = jnp.asarray(frozens_np)
    if mesh is not None:
        # scatter the [B,H,W] SoA channels and [B,F] parameter lanes
        # onto the mesh BEFORE dispatch: each device receives exactly
        # its own scenario lanes (and row block), and the runner's
        # carry constraint keeps them there across windows — no
        # replicate-then-slice on the first call
        espace = dataclasses.replace(
            espace, values=mesh.place_values(espace.values))
        rates_b = mesh.place_lanes(rates_b)
        frozens_b = mesh.place_lanes(frozens_b)

    # initial totals are dispatched BEFORE the (possibly donating)
    # runner call: the runtime sequences the donated execution after
    # these reads, so the totals see the pre-step state. A space-cut
    # mesh reshards through totals_view first — the bitwise-at-f64
    # stat contract needs the single-device reduction order
    initial_d = batched_totals(espace.values if mesh is None
                               else mesh.totals_view(espace.values))
    # chaos seam (resilience.inject): lane poisons are CAPTURED at
    # launch (the scheduler's ticket→lane push window is open now) and
    # applied at complete — one firing per dispatch either way
    st = inject.active()
    poisons = (list(st.ensemble_poisons(st.bump("ensemble")))
               if st is not None else [])
    # analysis: ignore[naked-timer] — the launch wall anchor feeds
    # Report.wall_time_s and the billing split (busy_s/inflight_s);
    # it is the number the spans themselves are reconciled against
    t0 = _time.perf_counter()
    donated = 0
    # the EFFECTIVE window count (the split clamps to num_steps): what
    # actually ran is what the flight records — the donation audit
    # compares donated_windows against THIS, never the requested knob
    steps_list = _window_steps(num_steps, windows)
    windows = len(steps_list)
    if windows == 1:
        q, r = divmod(num_steps, executor.substeps)
        prev = espace.values
        out = runner(prev, rates_b, frozens_b, jnp.int32(q), jnp.int32(r))
        if donate and all(x.is_deleted() for x in jax.tree.leaves(prev)):
            donated = 1
    else:
        vb = espace.values
        for w_steps in steps_list:
            q, r = divmod(w_steps, executor.substeps)
            prev = vb
            vb = runner(prev, rates_b, frozens_b,
                        jnp.int32(q), jnp.int32(r))
            if donate and all(x.is_deleted()
                              for x in jax.tree.leaves(prev)):
                donated += 1
        out = vb
    return EnsembleInFlight(
        executor=executor, model=model, espace=espace, out=out,
        rates_b=rates_b, frozens_b=frozens_b, count=count,
        num_steps=num_steps, initial_d=initial_d, t0=t0,
        # analysis: ignore[naked-timer] — same billing anchor: the
        # launch-segment end the async overlap accounting needs
        t_launched=_time.perf_counter(),
        poisons=poisons, donated_windows=donated, windows=windows)


def complete_ensemble(inflight: EnsembleInFlight, *,
                      check_conservation: bool = True,
                      tolerance: float = 1e-3,
                      rtol: Optional[float] = None,
                      on_violation: str = "raise") -> list:
    """Block on a launched dispatch, fetch, and build the per-lane
    results — the completion half of ``run_ensemble`` (the return
    contract documented there). The ``fetch_nan`` chaos seam fires
    here: a poison injected at the fetch boundary, downstream of the
    device program, which the per-lane conservation machinery must
    catch exactly like a genuinely diverged lane."""
    if on_violation not in ("raise", "mark"):
        raise ValueError(f"unknown on_violation {on_violation!r}")
    executor = inflight.executor
    model = inflight.model
    espace = inflight.espace
    count = inflight.count
    num_steps = inflight.num_steps
    rates_b, frozens_b = inflight.rates_b, inflight.frozens_b

    # analysis: ignore[naked-timer] — the fetch-segment anchor of
    # the same billing split (see the wall comment below)
    fetch_t0 = _time.perf_counter()
    out = jax.tree.map(jax.block_until_ready, inflight.out)
    # the batch wall bills the HOST-OBSERVED dispatch segments: launch
    # (assembly + device enqueue) plus fetch (block + transfer). Under
    # the async loop, the gap between them is the overlap window —
    # this batch ran on-device while the pump assembled its successor —
    # and billing it would inflate busy_s/occupancy and let a healthy
    # dispatch blow its deadline on a slow NEIGHBOR's compile. In the
    # sync composition fetch starts where launch ended, so this is the
    # same launch-to-done span as ever. A genuinely hung device program
    # still shows: the hang sits inside the fetch segment.
    wall = ((inflight.t_launched - inflight.t0)
            # analysis: ignore[naked-timer] — closes the fetch
            # billing segment (see the anchor above)
            + (_time.perf_counter() - fetch_t0))
    # the active engine's runner returns ([B] fallback-event,
    # [B] active-tile) stat lanes alongside the values; fold them into
    # backend_report so a batch that dense-fell-back every step is
    # visible, not silently labeled "active" (serial/sharded contract)
    fb_arr = at_arr = ff_arr = None
    if executor.impl == "active":
        out, (fb_b, at_b) = out
        fb_arr = np.asarray(fb_b)
        at_arr = np.asarray(at_b)
    elif executor.impl == "active_fused":
        # the fused lanes also carry the [B] flags_fused counter —
        # passes whose next-step flags came out of the kernel
        out, (fb_b, at_b, ff_b) = out
        fb_arr = np.asarray(fb_b)
        at_arr = np.asarray(at_b)
        ff_arr = np.asarray(ff_b)
    # launch-captured lane poisons (lane_nan) + the fetch-boundary seam
    poisons = list(inflight.poisons)
    st = inject.active()
    if st is not None:
        f = st.take("fetch", st.bump("fetch"), kinds=("fetch_nan",))
        if f is not None:
            poisons.append((f.lane if f.lane is not None else 0, f))
    for lane, fault in poisons:
        out = inject.poison_lane_values(out, lane, fault)
    mesh = getattr(executor, "mesh", None)
    final_d = batched_totals(out if mesh is None
                             else mesh.totals_view(out))
    executor.last_impl = executor.impl
    executor.last_backend_report = None
    if fb_arr is not None:
        from ..ops.active import plan_for

        plan = plan_for(espace.shape)
        nattr = len({f.attr for f in model.flows})
        if ff_arr is not None:
            from ..ops.pallas_active import choose_fused_k, pass_count
            fused_k = choose_fused_k(executor.substeps, plan)
            passes = pass_count(num_steps, fused_k)
        else:
            fused_k, passes = None, num_steps
        denom = passes * nattr * plan.ntiles
        executor.last_backend_report = {
            "impl": executor.impl,
            "steps": num_steps,
            "lanes": count,
            #: (attr, step) dense-fallback events summed over REAL lanes
            #: (padding lanes are identically zero and never fall back)
            "fallback_steps": int(fb_arr[:count].sum()),
            "per_lane_fallback_steps": [int(x) for x in fb_arr[:count]],
            "tile": list(plan.tile),
            "tiles": plan.ntiles,
            "capacity": plan.capacity,
            "fallback_tiles": plan.fallback_tiles,
            "mean_active_fraction": (
                float(at_arr[:count].sum()) / (count * denom)
                if count and denom else None),
        }
        if ff_arr is not None:
            executor.last_backend_report.update({
                "composed_k": fused_k,
                "passes": passes,
                "flags_fused": int(ff_arr[:count].sum()),
                "per_lane_flags_fused": [int(x) for x in ff_arr[:count]],
            })

    last_exec = np.asarray(
        executor.last_execute_for(model, espace)(out, rates_b, frozens_b),
        np.float64)

    initial = {k: np.asarray(v, np.float64)
               for k, v in inflight.initial_d.items()}
    final = {k: np.asarray(v, np.float64) for k, v in final_d.items()}
    # IR models check the VIEW (summed mass minus integrated budgets,
    # plus per-term contract keys), not raw per-channel totals — a
    # declared source's drift is physics, an undeclared one a violation
    # naming the term (ir.FlowIRModel.conservation_view); flow models
    # get the identity view and the classic per-channel contract
    viewfn = getattr(model, "conservation_view", None)
    vinitial = viewfn(initial) if viewfn is not None else initial
    vfinal = viewfn(final) if viewfn is not None else final
    bad: list[int] = []
    thresholds = None
    wkeys: Optional[list[str]] = None
    if check_conservation:
        thresholds = conservation_thresholds(
            vinitial, espace.shape, espace.dtype, tolerance, rtol)
        if viewfn is not None and "mass" in vinitial:
            # the reconciliation sums every channel + budget reduction:
            # allow each its own rounding share (the serial gate's rule)
            thresholds = thresholds * max(len(initial), 1)
        errs, bad = conservation_violations(vinitial, vfinal,
                                            thresholds, count)
        if bad:
            wkeys = _worst_violation_keys(vinitial, vfinal)
            if on_violation == "raise":
                raise _violation_error(errs, thresholds, bad[0],
                                       len(bad), count,
                                       key=wkeys[bad[0]], model=model)

    out_es = dataclasses.replace(espace, values=dict(out))
    results: list = []
    badset = set(bad)
    for i in range(count):
        if i in badset:
            e = _violation_error(errs, thresholds, i,
                                 key=wkeys[i] if wkeys else None,
                                 model=model)
            # the batch's wall time rides the error too, so serving
            # counters stay honest even when every lane violated
            e.wall_time_s = wall
            results.append(e)
            continue
        sp = out_es.scenario(i)
        results.append((sp, Report(
            comm_size=1,
            rank_id=jax.process_index(),
            steps=num_steps,
            initial_total={k: float(initial[k][i]) for k in initial},
            final_total={k: float(final[k][i]) for k in final},
            last_execute=[float(x) for x in last_exec[i]],
            wall_time_s=wall,
            backend_report=(None if fb_arr is None else {
                "impl": executor.impl,
                "fallback_steps": int(fb_arr[i]),
                "mean_active_fraction": (
                    float(at_arr[i]) / denom if denom else None),
                **({} if ff_arr is None
                   else {"flags_fused": int(ff_arr[i])}),
            }),
        )))
    return results


def run_ensemble(model, spaces, *, models=None, executor=None, steps=None,
                 check_conservation: bool = True, tolerance: float = 1e-3,
                 rtol: Optional[float] = None, count: Optional[int] = None,
                 on_violation: str = "raise") -> list:
    """Step B scenarios in one device program; the engine behind
    ``Model.execute_many`` and the scheduler.

    ``models`` (default: ``model`` for every lane) supplies per-scenario
    numeric parameters; every entry must share ``model``'s structure
    (``structure_key``). ``count`` limits conservation checks and
    returned results to the first ``count`` lanes (the scheduler's
    padding protocol). ``on_violation``: ``"raise"`` raises
    ``EnsembleConservationError`` on the first bad lane; ``"mark"``
    returns that lane's error OBJECT in its result slot instead, so the
    other scenarios' results survive a bad neighbor.

    Returns a list of ``(CellularSpace, Report)`` per real lane (or an
    ``EnsembleConservationError`` in a violating lane's slot under
    ``"mark"``). Each Report carries the scenario's own totals and
    ``last_execute``; ``wall_time_s`` is the BATCH dispatch's wall time
    (shared by construction — one program stepped every lane).

    This is the synchronous composition of ``launch_ensemble`` +
    ``complete_ensemble`` (ISSUE 9): the always-on serving loop drives
    the two halves separately to overlap host assembly with device
    compute, and both paths therefore execute the same code — async
    results are bitwise-equal to this function's by construction.
    """
    if on_violation not in ("raise", "mark"):
        raise ValueError(f"unknown on_violation {on_violation!r}")
    inflight = launch_ensemble(model, spaces, models=models,
                               executor=executor, steps=steps, count=count)
    return complete_ensemble(inflight, check_conservation=check_conservation,
                             tolerance=tolerance, rtol=rtol,
                             on_violation=on_violation)
