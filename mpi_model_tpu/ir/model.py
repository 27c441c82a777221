"""FlowIRModel: a Model whose physics is a declarative term list.

Where ``Model`` holds the reference's ``Flow`` objects, ``FlowIRModel``
holds IR **terms** (``ir.terms``) and builds every step through the one
registered lowering (``ir.lower``). The executor/ensemble/serving
stack needs zero per-model step code:

- **linear models** (every term a uniform ``Transport``) expose an
  exact flows VIEW (one ``Diffusion`` per term), so the whole
  accelerated surface lights up unchanged — pallas, the composed k-step
  tap table, both active engines, the pipeline ensemble impl, sharded
  deep halos — and the dense XLA path they gate against is itself the
  IR Transport lowering (``Model.make_step`` delegates its
  all-Diffusion dense branch to ``ir.lower.dense_apply``), making the
  lowering the single source of truth the bitwise gate pins;
- **nonlinear models** (reactions, coupled channels, sources/sinks)
  lower to the dense step, the composed path at k=1 (a warning says the
  taps don't compose), and the generic active engine whose activity
  predicate is derived from the terms; ``active_fused``/``pallas``
  raise the documented incompatibility (their kernels are
  linear-stencil machines).

Conservation generalizes from "global sum is constant" to **per-term
budget reconciliation**: declared sources/sinks integrate their signed
contribution into hidden ``_b_<term>`` channels during the run, and the
gate checks (a) each budget's SIGN matches its contract and (b) the
observed total-mass drift equals the summed budgets — violations raise
``ConservationError`` naming the term instead of the drift being
asserted away.
"""

from __future__ import annotations

import math
import warnings
from typing import Callable, Mapping, Optional, Sequence, Union

import jax.numpy as jnp
import numpy as np

from ..core.cellular_space import CellularSpace
from ..models.model import ConservationError, Model, Report, \
    default_conservation_rtol
from ..ops.flow import Diffusion
from . import lower
from .terms import Term, Transport, validate_terms

Number = Union[float, np.ndarray]


class FlowIRModel(Model):
    """Orchestrates IR terms over a CellularSpace (see module docstring).

    ``terms`` is the model; each term's ``rate`` is its per-scenario
    parameter (``with_rates`` rebinds them — the ensemble engine ships
    differing rates as traced ``[B, F]`` lanes)."""

    def __init__(self, terms: Sequence[Term], time: float = 1.0,
                 time_step: float = 1.0, *,
                 offsets: Optional[Sequence[tuple[int, int]]] = None,
                 active_opts: Optional[dict] = None):
        self.ir_terms: tuple[Term, ...] = validate_terms(terms)
        #: generic active-engine plan knobs for nonlinear terms (keys
        #: ``tile``/``capacity``/``max_active_frac`` — ops.active
        #: .plan_for); the amortized linear engines take theirs from
        #: SerialExecutor(active_opts=...) as before
        self.active_opts = dict(active_opts) if active_opts else None
        # the exact flows view of a linear model: one Diffusion per
        # uniform Transport term — what routes linear IR models onto
        # every pre-existing accelerated engine with zero new code
        rates = lower.uniform_rates(self.ir_terms)
        flows = ([Diffusion(t.rate, attr=t.channel) for t in self.ir_terms]
                 if rates is not None else [])
        super().__init__(flows, time, time_step, offsets=offsets)

    # -- structure ----------------------------------------------------------

    @property
    def ir_linear(self) -> bool:
        """True when every term is a uniform Transport (the flows-view
        family served bitwise by the specialized engines)."""
        return bool(self.flows)

    def term_rates(self) -> tuple[float, ...]:
        return tuple(t.rate for t in self.ir_terms)

    def with_rates(self, rates: Sequence[float]) -> "FlowIRModel":
        """Same structure, new per-term rates (the per-scenario knob)."""
        rates = list(rates)
        if len(rates) != len(self.ir_terms):
            raise ValueError(
                f"{len(rates)} rates for {len(self.ir_terms)} terms")
        return FlowIRModel(
            [t.with_rate(r) for t, r in zip(self.ir_terms, rates)],
            self.time, self.time_step, offsets=self.offsets,
            active_opts=self.active_opts)

    def term_structure(self) -> tuple:
        """Hashable batch-compatibility identity: term structures (rates
        excluded — they are the traced parameter lanes) + offsets."""
        return (tuple(t.structure() for t in self.ir_terms),
                tuple(self.offsets))

    def _term_fingerprints(self) -> tuple:
        return tuple(t.structure() + (t.rate,) for t in self.ir_terms)

    def pallas_rates(self) -> Optional[dict[str, float]]:
        if self.ir_linear:
            return super().pallas_rates()
        return None  # nonlinear terms need the general lowering

    # -- spaces -------------------------------------------------------------

    def required_channels(self) -> frozenset[str]:
        return lower.involved_channels(self.ir_terms)

    def create_space(self, dim_x: int, dim_y: int,
                     attributes: Optional[Mapping] = None,
                     dtype=jnp.float32, **kw) -> CellularSpace:
        """``CellularSpace.create`` plus the model's hidden budget
        channels (zero-initialized accumulators for declared
        sources/sinks)."""
        attrs = dict(attributes) if attributes is not None else {
            ch: 0.0 for ch in sorted(self.required_channels())
            if not ch.startswith("_b_")}
        for b in lower.budget_channels(self.ir_terms):
            attrs.setdefault(b, 0.0)
        return CellularSpace.create(dim_x, dim_y, attrs, dtype=dtype, **kw)

    def with_budget_channels(self, space: CellularSpace) -> CellularSpace:
        """A copy of ``space`` with any missing budget channels added
        (zeroed, in the space dtype)."""
        vals = dict(space.values)
        for b in lower.budget_channels(self.ir_terms):
            if b not in vals:
                vals[b] = jnp.zeros(space.shape, space.dtype)
        return space.with_values(vals)

    def _validate_space(self, space: CellularSpace) -> None:
        missing = sorted(self.required_channels()
                         - set(space.values))
        if missing:
            raise ValueError(
                f"space is missing channels {missing} required by the "
                "model's terms (budget accumulators included) — build "
                "spaces with FlowIRModel.create_space, or add them via "
                "with_budget_channels")
        written = set().union(*(t.writes() for t in self.ir_terms))
        written |= set(lower.budget_channels(self.ir_terms))
        for ch in sorted(written):
            if not jnp.issubdtype(space.values[ch].dtype, jnp.floating):
                raise TypeError(
                    f"IR terms write channel {ch!r}, which requires a "
                    f"floating dtype (got {space.values[ch].dtype}); "
                    "int/bool channels are supported as read-only "
                    "masks/storage")

    def _meta(self, space: CellularSpace) -> lower.StepMeta:
        return lower.StepMeta(
            shape=space.shape, origin=(space.x_init, space.y_init),
            global_shape=space.global_shape, dtype=space.dtype,
            offsets=tuple(self.offsets))

    # -- step construction --------------------------------------------------

    def make_step(self, space: CellularSpace, impl: str = "xla",
                  substeps: int = 1, compute_dtype=None) -> Callable:
        if self.ir_linear:
            # linear family: the flows view runs the whole specialized
            # engine surface; its dense path is the IR Transport
            # lowering (Model.make_step delegates), so this is not a
            # second implementation
            return super().make_step(space, impl=impl, substeps=substeps,
                                     compute_dtype=compute_dtype)
        if impl in ("pallas", "active_fused", "pipeline"):
            raise ValueError(
                f"impl={impl!r} is a linear-stencil kernel; this model "
                "has nonlinear/coupled terms "
                f"({[t.name for t in self.ir_terms]}). Eligible impls: "
                "'xla'/'auto' (dense lowering), 'composed' (k forced "
                "to 1), 'active' (term-derived activity predicate).")
        if impl not in ("xla", "auto", "composed", "active"):
            raise ValueError(f"unknown step impl {impl!r}")
        substeps = int(substeps)
        if substeps < 1:
            raise ValueError(f"substeps must be >= 1, got {substeps}")
        self._validate_space(space)
        key = ("ir", space.shape, space.global_shape,
               (space.x_init, space.y_init), str(space.dtype),
               self.offsets, impl, substeps, self._term_fingerprints(),
               tuple(sorted((self.active_opts or {}).items())))
        cached = self._step_cache.get(key)
        if cached is not None:
            return cached

        meta = self._meta(space)
        rates = self.term_rates()
        single = lower.build_dense_step(self.ir_terms, meta, rates)
        if impl == "composed" and substeps > 1:
            # the documented degeneration: nonlinear terms do not
            # compose into a k-step tap table (the table is the
            # k-fold composition of a LINEAR operator), so every
            # "composed" call iterates k=1 passes
            warnings.warn(
                f"impl='composed' with nonlinear IR terms forces k=1 "
                f"for substeps={substeps}: each call runs iterated "
                "single passes, equaling the dense path. Only linear "
                "all-Transport models compose into the tap table.",
                RuntimeWarning)
        if impl == "active":
            from ..ops.active import plan_for
            opts = dict(self.active_opts or {})
            plan = plan_for(space.shape, tile=opts.get("tile"),
                            capacity=opts.get("capacity"),
                            max_active_frac=opts.get("max_active_frac",
                                                     0.25))
            single = lower.build_active_step(self.ir_terms, meta, rates,
                                             plan, single)

        if substeps == 1:
            step = single
        else:
            # compose via a TRACED loop, not Python unrolling: an
            # unrolled chain of nonlinear singles fuses across the seam
            # and XLA CPU's stripped-barrier fma contraction drifts it
            # 1 ulp from the serial fori(single) reference — the inner
            # fori body compiles as its own computation, matching the
            # executors' loop context exactly
            def step(values, _single=single):
                import jax

                return jax.lax.fori_loop(
                    0, substeps, lambda i, c: _single(c), values)

        step.impl = "active" if impl == "active" else (
            "composed" if impl == "composed" else "xla")
        step.substeps = substeps
        # the nonlinear lowering is XLA fusions, "composed" included
        step.kernel_pass = False
        step.composed_k = 1 if impl == "composed" else None
        step.composed_passes = substeps if impl == "composed" else None
        self._step_cache[key] = step
        return step

    # -- conservation: per-term budget reconciliation -----------------------

    def conservation_view(self, totals: Mapping[str, Number]
                          ) -> dict[str, Number]:
        """Map raw per-channel totals to the quantities the IR contract
        checks (works on scalars and on the ensemble's ``[B]`` lanes):

        - ``"mass"``: summed non-budget totals MINUS the integrated
          budgets — constant for a correct model (what the conserving
          terms promise);
        - ``"term:<name>"`` per declared source/sink: the contract-
          violating part of its integrated budget (a source gone
          negative / a sink gone positive), zero when honest.

        All-Transport models return the totals unchanged (the classic
        per-channel contract, bitwise-identical behavior)."""
        buds = lower.budget_channels(self.ir_terms)
        if not buds and all(isinstance(t, Transport)
                            for t in self.ir_terms):
            return dict(totals)
        mass = None
        for k, v in totals.items():
            if k in buds:
                continue
            mass = v if mass is None else mass + v
        for b in buds:
            mass = mass - totals[b]
        out: dict[str, Number] = {"mass": mass}
        for b, t in buds.items():
            v = totals[b]
            out[f"term:{t.name}"] = (np.minimum(v, 0.0)
                                     if t.conservation == "source"
                                     else np.maximum(v, 0.0))
        return out

    def budget_totals(self, space: CellularSpace) -> dict[str, float]:
        """term name -> integrated budget (host floats) — the run's
        reconciled source/sink ledger, for reports and benches."""
        return {t.name: float(space.total(b))
                for b, t in lower.budget_channels(self.ir_terms).items()}

    def _raise_if_violated(self, space: CellularSpace,
                           initial: dict, final: dict,
                           tolerance: float, rtol: Optional[float]
                           ) -> None:
        vi = self.conservation_view(initial)
        vf = self.conservation_view(final)
        if rtol is None:
            rtol = default_conservation_rtol(space.shape, space.dtype)
        scale = max(abs(float(t)) for t in initial.values())
        thresh = tolerance + rtol * scale * max(len(initial), 1)
        worst_key, worst = None, -1.0
        for k in vi:
            err = abs(float(vf[k]) - float(vi[k]))
            if not math.isfinite(err):
                worst_key, worst = k, err
                break
            if err > worst:
                worst_key, worst = k, err
        if worst_key is None or (math.isfinite(worst)
                                 and worst <= thresh):
            return
        raise ConservationError(
            self.violation_message(worst_key, worst, thresh))

    def violation_message(self, key: str, err: float,
                          thresh: float) -> str:
        """The one place IR conservation violations are worded — the
        ensemble path reuses it so serial and batched runs name terms
        identically."""
        if key.startswith("term:"):
            name = key[len("term:"):]
            term = next(t for t in self.ir_terms if t.name == name)
            return (
                f"conservation contract violated by term {name!r}: the "
                f"declared {term.conservation}'s integrated budget ran "
                f"{'negative' if term.conservation == 'source' else 'positive'}"
                f" by {err:.3e} (> {thresh:.3e}) — a "
                f"{term.conservation} must only "
                f"{'add' if term.conservation == 'source' else 'remove'}"
                " mass")
        conserving = [t.name for t in self.ir_terms
                      if t.budget_channel is None]
        return (
            f"per-term budgets do not reconcile: |Δmass − Σ budgets| = "
            f"{err:.3e} > {thresh:.3e} — a conserving term "
            f"({conserving}) leaked mass, or a source/sink moved mass "
            "it did not declare")

    def report_conservation_error(self, report: Report) -> float:
        """``Report.conservation_error`` through the IR view (what the
        CLI/bench judge for --model runs: raw per-channel drift is
        EXPECTED physics for a model with declared sources/sinks)."""
        vi = self.conservation_view(report.initial_total)
        vf = self.conservation_view(report.final_total)
        return max(abs(float(vf[k]) - float(vi[k])) for k in vi)

    def conservation_threshold(self, space: CellularSpace,
                               tolerance: float = 1e-3,
                               rtol: Optional[float] = None,
                               initial_totals: Optional[dict] = None
                               ) -> float:
        thresh = super().conservation_threshold(
            space, tolerance, rtol, initial_totals=initial_totals)
        # the reconciliation sums C channel totals + T budgets: allow
        # each reduction its own rounding share
        n = len(space.values) if initial_totals is None \
            else len(initial_totals)
        return thresh * max(n, 1)
