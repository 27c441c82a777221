"""Sharded executors: the distributed runtime of the framework.

Rebuild of the reference's distributed ``Model::execute<R>`` orchestration
(``/root/reference/src/Model.hpp:53-262``) — minus the master rank, the
string wire protocol and the hand-rolled collectives. Two strategies:

- ``AutoShardedExecutor`` — the *idiomatic XLA* path: the same global-array
  step the serial path runs, jitted with ``NamedSharding`` on its inputs;
  XLA's SPMD partitioner inserts the halo exchanges for the stencil shifts
  automatically. Zero re-expression of the model.

- ``ShardMapExecutor`` — the *explicit* path mirroring the reference's
  architecture: per-shard code with hand-placed ``ppermute`` halo exchanges
  (``parallel.halo``), scan inside ``shard_map`` so the whole time loop +
  halo traffic compiles into one XLA program over ICI. This is the path
  that extends to Pallas kernels and custom collective schedules.

Both reproduce the serial semantics exactly (tests golden-compare all three
paths); the conservation contract holds because shares crossing shard
boundaries are delivered via halos, and true grid edges see ppermute's
zero-fill (non-periodic boundary).

Point flows are SPARSE per-shard scatters (the serial path's
``point_flow_step`` economics): the owner test (``Model.hpp:176,189``)
becomes a mask instead of a rank branch, so a source sitting on a shard's
last row (the reference's deliberate default: cell (19,3) on rank 1's
stripe edge, ``Main.cpp:33``) needs no special case — its neighbor-share
rides the ordinary halo.

Every runner takes the step count as a TRACED scalar (dynamic trip
count), so supervisor chunks of any size — including the remainder chunk
— and step-count sweeps reuse one compilation per model/space geometry.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.cellular_space import CellularSpace
from ..ops.flow import PointFlow
from ..resilience import inject
from .halo import gather_from_padded, pad_with_halo_1d, pad_with_halo_2d
from .mesh import grid_spec, put_global

Values = dict[str, jax.Array]


def _check_divisible(space: CellularSpace, mesh: Mesh) -> None:
    dims = (space.dim_x, space.dim_y)
    for axis_idx, name in enumerate(mesh.axis_names[:2]):
        n = mesh.shape[name]
        if dims[axis_idx] % n != 0:
            raise ValueError(
                f"grid dim {dims[axis_idx]} along '{name}' not divisible by "
                f"mesh extent {n} (the reference's PROC_DIMX=DIMX/NWORKERS "
                f"divisibility requirement, Defines.hpp:8)")


class AutoShardedExecutor:
    """GSPMD path: global step + sharding annotations; XLA inserts halos."""

    def __init__(self, mesh: Mesh, spec: Optional[P] = None):
        self.mesh = mesh
        self.spec = grid_spec(mesh) if spec is None else spec
        #: "xla" (the GSPMD global step) or "point" (the point-subsystem
        #: fast path for all-point-flow models) — reported by CLI/bench
        self.last_impl: Optional[str] = "xla"
        self._cache: dict = {}

    @property
    def comm_size(self) -> int:
        return int(np.prod(list(self.mesh.shape.values())))

    def run_model(self, model, space: CellularSpace, num_steps: int) -> Values:
        _check_divisible(space, self.mesh)
        # all-point-flow models take the point-subsystem fast path the
        # other executors already have (round-4 VERDICT weak #3): the
        # ≤9k involved cells step in a tiny compiled loop on the global
        # view (GSPMD's global-array semantics make dynamic amounts fine
        # here, unlike shard_map), and the result is scattered onto the
        # mesh once per run
        if (num_steps > 0 and model.flows
                and all(isinstance(f, PointFlow) for f in model.flows)):
            from ..ops.point_kernel import (build_point_plans,
                                            serial_point_runner)

            key = ("pointmini", space.shape, space.global_shape,
                   (space.x_init, space.y_init), str(space.dtype),
                   model.offsets,
                   tuple(f.fingerprint() for f in model.flows))
            runner = self._cache.get(key)
            if runner is None:
                plans = build_point_plans(model.flows, space, model.offsets)
                runner = (jax.jit(serial_point_runner(
                    plans, jnp.dtype(space.dtype)))
                    if plans is not None else False)
                self._cache[key] = runner
            if runner:
                self.last_impl = "point"
                # shard FIRST: the runner's gather/scatter touch only the
                # ~9k involved cells, so running it on the sharded global
                # arrays lets XLA partition those tiny ops — the grid is
                # never materialized on one device (it may not fit there;
                # the mesh's aggregate memory is the point of GSPMD)
                sharding = NamedSharding(self.mesh, self.spec)
                values = {k: put_global(v, sharding)
                          for k, v in space.values.items()}
                return runner(values, jnp.int32(num_steps))
        self.last_impl = "xla"
        step = model.make_step(space)
        runner = self._cache.get(step)
        if runner is None:
            sharding = NamedSharding(self.mesh, self.spec)

            def _run(v, n):
                def body(i, c):
                    out = step(c)
                    # keep the carry pinned to the mesh layout across steps
                    return {k: jax.lax.with_sharding_constraint(a, sharding)
                            for k, a in out.items()}
                # n is a TRACED scalar: one compile serves any step count
                return jax.lax.fori_loop(0, n, body, v)

            runner = jax.jit(_run)
            self._cache[step] = runner
        values = {k: put_global(v, NamedSharding(self.mesh, self.spec))
                  for k, v in space.values.items()}
        return runner(values, jnp.int32(num_steps))


class ShardMapExecutor:
    """Explicit SPMD path: shard_map + ppermute halo exchange per step.

    Field flows run per shard according to their declared
    ``Flow.footprint``: ``"pointwise"`` outflows are evaluated on the bare
    shard, ``"ring1"`` outflows get one-cell halo-padded channels (their
    ``outflow_padded``), and undeclared footprints raise instead of
    silently miscomputing. Point flows of any kind are lifted to dense
    one-hot fields sharded with the grid. User flows needing global
    coordinates should precompute coordinate fields as extra attribute
    channels.

    ``step_impl`` selects the per-shard field-flow kernel, mirroring
    ``SerialExecutor``: ``"xla"`` (pad→gather stencil, works for every
    flow), ``"pallas"`` (the fused halo-mode kernels consuming the
    ppermute ghost ring — the specialized Diffusion kernel when every
    flow is a plain ``Diffusion``, else the general multi-channel field
    kernel for any POINTWISE flows (Coupled/user); requires no point
    flows and an f32/bf16 non-partition grid, raises otherwise),
    ``"composed"`` (the composed k-step filter consuming the
    ``halo_depth``-deep ring: interior tiles run ONE
    ``(2·halo_depth+1)²`` tap pass per exchange instead of
    ``halo_depth`` iterated steps — all-Diffusion models only, raises
    otherwise; see ``ops.composed_stencil``), ``"active"`` /
    ``"active_fused"`` (shard-local active-tile stepping — the XLA
    engine or the fused Pallas kernel over the same ghost-padded
    windows; ``_build_active_runner``), or ``"auto"`` (pallas when
    eligible, else xla; a Pallas failure propagates).
    """

    def __init__(self, mesh: Mesh, step_impl: str = "xla",
                 halo_mode: str = "exchange", halo_depth: int = 1,
                 compute_dtype=None):
        if len(mesh.axis_names) not in (1, 2):
            raise ValueError("ShardMapExecutor needs a 1-D or 2-D mesh")
        if step_impl not in ("xla", "pallas", "auto", "composed", "active",
                             "active_fused"):
            raise ValueError(f"unknown step impl {step_impl!r}")
        if halo_mode not in ("exchange", "zero"):
            raise ValueError(f"unknown halo mode {halo_mode!r}")
        if int(halo_depth) < 1:
            raise ValueError(f"halo_depth must be >= 1, got {halo_depth}")
        if step_impl in ("active", "active_fused") and int(halo_depth) != 1:
            raise ValueError(
                f"step_impl={step_impl!r} exchanges a one-cell ghost ring "
                f"per step; halo_depth={halo_depth} is not supported (the "
                "active set would need depth-d frontier dilation)")
        self.mesh = mesh
        self.step_impl = step_impl
        #: DIAGNOSTIC knob for measuring halo cost (benchmarks/ladder.py's
        #: halo-exchange wallclock share): "zero" replaces every ppermute
        #: ghost exchange with zero padding — identical compute shape, NO
        #: inter-shard traffic, WRONG results at shard boundaries. Never
        #: use for real runs.
        self.halo_mode = halo_mode
        #: halo_depth > 1 = DEEP-HALO execution: each collective round
        #: exchanges a depth-d ghost ring, then d local steps run on it —
        #: collective rounds drop d-fold. On the XLA path the padded
        #: shard shrinks one ring per step (any pointwise flows); on the
        #: Pallas path the ring feeds d FUSED kernel steps (one
        #: collective round and one HBM round-trip per d steps —
        #: Diffusion-only). Point flows need halo_depth=1 (they must
        #: fire between steps).
        self.halo_depth = int(halo_depth)
        #: interior-tile window math dtype for the Pallas halo kernels
        #: (None → f32; the near-ring exact path stays f32 — the same
        #: knob as ``Model.make_step(compute_dtype=...)``); the XLA
        #: shard step ignores it
        self.compute_dtype = compute_dtype
        #: kernel the last ``run_model`` actually used ("pallas"/"xla"),
        #: what "auto" resolved to — reported by the CLI/bench
        self.last_impl: Optional[str] = None
        #: per-run report detail (Report.backend_report); None until a
        #: run records one
        self.last_backend_report: Optional[dict] = None
        self._cache: dict = {}

    @property
    def comm_size(self) -> int:
        return int(np.prod(list(self.mesh.shape.values())))

    # -- execution ---------------------------------------------------------

    def _pallas_plan(self, model, space: CellularSpace):
        """Which fused halo kernel applies: ``("diffusion", rates)`` when
        every flow is a plain Diffusion (the specialized kernel with the
        closed-form interior fast path), ``("composed", rates)`` for the
        same shape under ``step_impl="composed"`` (interior tiles run
        ONE composed (2·depth+1)² tap pass per depth-deep exchange,
        ``ops.composed_stencil``), ``("field", flows)`` when every
        field flow is pointwise (the general multi-channel kernel —
        Coupled/user flows), or None → the XLA shard step. Raises for an
        explicit ``step_impl='pallas'``/``'composed'`` that can't be
        honored."""
        if self.step_impl == "xla":
            return None
        has_point = any(isinstance(f, PointFlow) for f in model.flows)
        # f64 shards stay on the XLA shard step: the halo kernels compute
        # in f32 internally (no silent precision downgrade under "auto");
        # point flows must fire between steps, which the fused kernels
        # cannot interleave
        base_ok = (not has_point and not space.is_partition
                   and model.pallas_dtype_ok(space))
        if self.step_impl == "composed":
            rates = model.pallas_rates() if base_ok else None
            if rates and any(r != 0.0 for r in rates.values()):
                return ("composed", rates)
            if rates is not None and not any(r != 0.0
                                             for r in rates.values()):
                raise ValueError(
                    "step_impl='composed' has nothing to compose: every "
                    "Diffusion rate is 0.0 (no field transport). Use "
                    "'xla' or 'auto' for a no-op field step.")
            raise ValueError(
                "step_impl='composed' requires all field flows to be "
                "plain Diffusion (a uniform rate is what composes into "
                "an explicit tap table) on a full (non-partition) "
                "f32/bf16 grid with no point flows; got "
                f"flows={[type(f).__name__ for f in model.flows]}, "
                f"is_partition={space.is_partition}, "
                f"dtype={space.dtype}. Use 'xla', 'pallas' or 'auto'.")
        if base_ok:
            rates = model.pallas_rates()
            # empty/all-zero rates = no field transport: nothing for the
            # kernel to do — don't claim "pallas" ran (see make_step).
            # The general field kernel applies only when some flow NEEDS
            # it (rates is None — a non-Diffusion pointwise flow), never
            # as a no-op fallback for zero-rate Diffusions.
            if rates is not None:
                if rates and any(r != 0.0 for r in rates.values()):
                    return ("diffusion", rates)
            else:
                field_flows = tuple(f for f in model.flows
                                    if not isinstance(f, PointFlow))
                if field_flows and all(
                        getattr(f, "footprint", "unknown") == "pointwise"
                        for f in field_flows):
                    return ("field", field_flows)
        if self.step_impl == "pallas":
            raise ValueError(
                "step_impl='pallas' requires all field flows to be "
                "POINTWISE (Diffusion/Coupled/...) on a full "
                "(non-partition) f32/bf16 grid with no point flows (the "
                "kernels compute in f32; f64 runs the XLA shard step); "
                "got "
                f"flows={[type(f).__name__ for f in model.flows]}, "
                f"is_partition={space.is_partition}, "
                f"dtype={space.dtype}. Use 'xla' or 'auto'.")
        return None

    def run_model(self, model, space: CellularSpace, num_steps: int) -> Values:
        # chaos seam (resilience.inject): one module-global read when no
        # plan is armed; "halo" arms the trace-time ghost-ring
        # perturbation for exactly this chunk (the perturbed runner is
        # cached under a distinct build token, so the clean cache is
        # never poisoned)
        st = inject.active()
        if st is None:
            return self._run_inner(model, space, num_steps)
        idx = st.bump("executor")
        fault = st.take("executor", idx, kinds=("exc", "nan", "halo"))
        if fault is None:
            return self._run_inner(model, space, num_steps)
        if fault.kind == "exc":
            # call index in the message: distinct signatures per
            # injection (see SerialExecutor.run_model)
            raise inject.InjectedFault(
                f"injected executor fault on call {idx} (sharded "
                f"{num_steps}-step chunk)")
        if fault.kind == "halo":
            with st.halo_window(fault):
                return self._run_inner(model, space, num_steps)
        out = self._run_inner(model, space, num_steps)
        return inject.poison_values(out, fault, st.plan)

    def _run_inner(self, model, space: CellularSpace,
                   num_steps: int) -> Values:
        _check_divisible(space, self.mesh)
        #: per-run report detail (Report.backend_report) — reset so a
        #: previous run's composed record never leaks forward
        self.last_backend_report = None
        # origin is part of the identity: the compiled runners bake
        # row0/col0 and the boundary mask from it, so two same-shaped
        # partitions at different origins must not share a runner. The
        # STEP COUNT is deliberately NOT part of it: runners take the
        # count as a traced scalar (dynamic trip count), so a supervisor
        # sweeping chunk sizes or a remainder chunk reuses one compile.
        # the trailing inject.build_token() is None except while a halo
        # fault is armed — a perturbed build lives under its own key and
        # can never serve (or be served by) a clean chunk
        key = (space.shape, space.global_shape,
               (space.x_init, space.y_init), str(space.dtype),
               tuple(space.values), model.offsets,
               tuple(f.fingerprint() for f in model.flows),
               inject.build_token())
        spec = grid_spec(self.mesh)
        sharding = NamedSharding(self.mesh, spec)
        put = partial(put_global, sharding=sharding)
        values = {k: put(v) for k, v in space.values.items()}
        n = jnp.int32(num_steps)

        from ..utils.tracing import get_tracer

        # nonlinear Flow IR models (ISSUE 11): the general registered
        # lowering in its ghost-ring context — the model's max term
        # FOOTPRINT drives the required halo depth (1 for the current
        # grammar: transport reads the Moore ring), instead of trusting
        # a hand-set knob. Linear IR models never reach this branch
        # (their Diffusion flows view rides every specialized path
        # below, bitwise).
        if (getattr(model, "ir_terms", None) is not None
                and not model.ir_linear):
            if self.step_impl not in ("xla", "auto"):
                raise ValueError(
                    f"step_impl={self.step_impl!r} is a linear-stencil "
                    "engine; this model's nonlinear IR terms "
                    f"({[t.name for t in model.ir_terms]}) run the "
                    "general lowering — use step_impl='xla'/'auto'.")
            from ..ir.lower import max_footprint

            need = max(1, max_footprint(model.ir_terms))
            if self.halo_depth != need:
                raise ValueError(
                    f"this model's terms read a footprint-"
                    f"{max_footprint(model.ir_terms)} stencil: the "
                    f"required halo depth is {need}, got halo_depth="
                    f"{self.halo_depth} (nonlinear terms do not compose "
                    "into deep-halo chunks — the tap table is a linear "
                    "object)")
            # the flow-based key's fingerprint component is EMPTY for a
            # nonlinear IR model (flows=[]), and the runner bakes the
            # term rates concretely — the terms' own fingerprints must
            # be part of the identity or two models sharing a geometry
            # would silently share one compiled physics
            ikey = ("ir", model._term_fingerprints()) + key
            runner = self._cache.get(ikey)
            if runner is None:
                with get_tracer().span("shardmap.build", impl="ir"):
                    runner = self._build_ir_runner(model, space)
                self._cache[ikey] = runner
            self.last_impl = "xla"
            return runner(values, n)

        # all-FROZEN-point-flow models (the reference's live workload)
        # step only the ≤9k involved cells per shard — constant per-step
        # deltas mean NO halo traffic at all; owned entries scatter back
        # once per run. Bitwise equal to the halo path.
        if (self.halo_depth == 1
                and self.step_impl in ("xla", "auto", "active",
                                       "active_fused")
                and model.flows
                and all(isinstance(f, PointFlow) for f in model.flows)):
            mkey = ("pointmini",) + key
            runner = self._cache.get(mkey)
            if runner is None:
                from ..ops.point_kernel import build_point_plans

                plans = build_point_plans(model.flows, space, model.offsets)
                if plans is not None and all(p.frozen_only
                                             for p in plans.values()):
                    with get_tracer().span("shardmap.build",
                                           impl="point-subsystem"):
                        runner = self._build_point_runner(space, plans)
                else:
                    # cache the ineligible verdict too: a dynamic point
                    # flow must not re-pay plan construction every chunk
                    runner = False
                self._cache[mkey] = runner
            if runner:
                # "point" = the zero-collective subsystem path; distinct
                # from "xla" so its liveness is assertable (dryrun/tests)
                self.last_impl = "point"
                return runner(values, n)

        # shard-local active sets (ISSUE 3): each shard tracks its OWN
        # tile activity — the one-cell ppermute ghost ring both feeds
        # the tile windows and activates edge tiles (ghost_flags), so
        # cross-shard frontier arrival is seen one step early, exactly
        # like the interior dilation. The per-shard dense fallback
        # consumes the same exchanged ring (the exchange sits OUTSIDE
        # the cond: collectives must run on every shard every step).
        if self.step_impl in ("active", "active_fused"):
            fused = self.step_impl == "active_fused"
            akey = (self.step_impl, key)
            entry = self._cache.get(akey)
            if entry is None:
                with get_tracer().span("shardmap.build",
                                       impl=self.step_impl):
                    entry = self._build_active_runner(model, space,
                                                      fused=fused)
                self._cache[akey] = entry
            runner, plan, nattr, nshards = entry
            out, stats = runner(values, n)
            if fused:
                fb, at, ff = stats
            else:
                (fb, at), ff = stats, None
            self.last_impl = self.step_impl
            ntiles = plan.ntiles * nshards
            self.last_backend_report = {
                "impl": self.step_impl,
                "steps": int(num_steps),
                "shards": nshards,
                #: (shard, attr, step) triples that ran the per-shard
                #: dense fallback — psum'd, so an all-shards-dense run
                #: reads steps*nattr*nshards, not a silent "active"
                "fallback_steps": int(fb),
                "tile": list(plan.tile),
                "tiles": ntiles,
                "tiles_per_shard": plan.ntiles,
                "capacity": plan.capacity,
                "fallback_tiles": plan.fallback_tiles,
                "mean_active_fraction": (
                    float(at) / (num_steps * nattr * ntiles)
                    if num_steps and nattr else None),
            }
            if fused:
                #: (shard, attr, step) triples whose flags came out of
                #: the kernel (psum'd) — fallbacks recompute flags in
                #: XLA, so flags_fused + fallback_steps == the triple
                #: total (the observability satellite's counter)
                self.last_backend_report["flags_fused"] = int(ff)
            return out

        # one build/cache protocol for both depths: the fused Pallas
        # kernel when eligible (deep halos compose with it — a depth-d
        # ring feeds d fused steps per exchange: one collective round
        # AND one HBM round-trip per d steps), else the XLA shard step
        # (deep or pad-gather)
        deep = self.halo_depth > 1
        entry = self._cache.get(key)
        if entry is None:
            kind, prunner, out = self._run_pallas(
                model, space, num_steps, values,
                label="pallas-deep" if deep else "pallas")
            if prunner is not None:
                self._cache[key] = (kind, prunner)
                self.last_impl = kind
                self._record_backend_report(kind, num_steps)
                return out
            with get_tracer().span("shardmap.build",
                                   impl="deep-halo" if deep else "xla",
                                   depth=self.halo_depth):
                runner = (self._build_deep_runner(model, space) if deep
                          else self._build_runner(model, space))
            entry = ("xla", runner)
            self._cache[key] = entry
        kind, runner = entry
        #: the kernel the last run actually used (what "auto" resolved
        #: to) — the CLI/bench report it so a user never believes they
        #: measured a configuration that never ran
        self.last_impl = kind
        self._record_backend_report(kind, num_steps)
        return runner(values, n)

    def _record_backend_report(self, kind: str, num_steps: int) -> None:
        """Composed auto-k visibility (ISSUE 3 satellite): the sharded
        composed path's k IS ``halo_depth`` and the remainder chunk
        (``num_steps % k``) composes at its own depth — both recorded
        in ``Report.backend_report`` so a depth that buys no
        composition is observable."""
        if kind != "composed":
            return
        d = self.halo_depth
        self.last_backend_report = {
            "impl": "composed",
            "composed_k": d,
            "full_chunks": num_steps // d,
            "remainder_chunk_depth": num_steps % d,
        }

    def _run_pallas(self, model, space, num_steps, values, *, label):
        """Build + first-run the Pallas runner when the model is
        eligible. Returns ``(kind, runner, first_output)`` — ``kind`` is
        the honest ``last_impl`` label ("pallas" or "composed") — or
        ``(None, None, None)`` when ineligible. A build, compile or
        device failure propagates, under ``"auto"`` too.
        ``block_until_ready`` makes async device faults surface HERE,
        not in the caller after a broken runner got cached."""
        from ..utils.tracing import get_tracer

        plan = self._pallas_plan(model, space)
        if plan is None:
            return None, None, None
        kind = "composed" if plan[0] == "composed" else "pallas"
        if kind == "composed":
            label = f"composed-depth{self.halo_depth}"
        tracer = get_tracer()
        with tracer.span("shardmap.build", impl=label,
                         depth=self.halo_depth):
            prunner = self._build_pallas_runner(model, space, plan)
        with tracer.span("shardmap.compile+first_run", impl=label):
            out = jax.block_until_ready(
                prunner(values, jnp.int32(num_steps)))
        return kind, prunner, out

    def _shard_geometry(self, space: CellularSpace):
        """(names, nx, ny, local_h, local_w): this mesh's axis names,
        extents, and per-shard block dims (1-D meshes: ny = 1, columns
        un-split) — the geometry every runner builder needs."""
        names = self.mesh.axis_names
        nx = self.mesh.shape[names[0]]
        ny = self.mesh.shape[names[1]] if len(names) > 1 else 1
        return names, nx, ny, space.dim_x // nx, space.dim_y // ny

    def _build_point_runner(self, space: CellularSpace, plans):
        """shard_map wrapper for the frozen point-subsystem runner: each
        shard derives its window offset from ``axis_index`` and updates
        only the involved cells it owns — zero collectives."""
        from jax import lax

        from ..ops.point_kernel import shard_point_runner

        mesh = self.mesh
        names, nx, ny, local_h, local_w = self._shard_geometry(space)
        spec = grid_spec(mesh)
        run = shard_point_runner(plans, jnp.dtype(space.dtype),
                                 local_h, local_w)

        def shard_fn(values, n):
            off_x = lax.axis_index(names[0]) * np.int32(local_h)
            off_y = (lax.axis_index(names[1]) * np.int32(local_w)
                     if len(names) > 1 else jnp.int32(0))
            return run(values, off_x, off_y, n)

        sharded = shard_map(shard_fn, mesh=mesh, in_specs=(spec, P()),
                                out_specs=spec)
        return jax.jit(sharded)

    def _build_deep_runner(self, model, space: CellularSpace):
        """Deep-halo execution: one depth-d ghost exchange per d local
        steps, for ANY pointwise field flows (Diffusion, Coupled, user
        flows). All channels are padded; each step evaluates every flow's
        own ``outflow()`` on the pre-step padded values (summed-outflow
        semantics), masks outflows to the partition (affine flows must
        not manufacture mass on ghost cells), and applies the exact
        per-cell-count transport on a region shrinking one ring per step
        — mirroring ``ops.stencil.transport``'s expression term-for-term.
        All-Diffusion models reproduce the serial path BITWISE (the
        uniform-rate expression compiles to the same contraction);
        general flows match to ~1 ULP (XLA FMA grouping of the summed
        outflow differs between compilations). Collective rounds (the
        0.64-0.81 halo share measured in BASELINE configs 2-3) drop
        d-fold."""
        from jax import lax

        depth = self.halo_depth
        field_flows = [f for f in model.flows
                       if not isinstance(f, PointFlow)]
        has_point = any(isinstance(f, PointFlow) for f in model.flows)
        all_pointwise = bool(field_flows) and all(
            getattr(f, "footprint", "unknown") == "pointwise"
            for f in field_flows)
        if not all_pointwise or has_point:
            raise ValueError(
                "halo_depth > 1 requires POINTWISE field flows and no "
                "point flows (a point flow must fire between steps, "
                "which deep-halo chunks cannot interleave); got "
                f"flows={[type(f).__name__ for f in model.flows]}. "
                "Use halo_depth=1 for general flows.")
        # all-Diffusion models take the uniform-rate expression whose
        # compiled graph matches the serial path BITWISE; general
        # pointwise flows take the summed-outflow form, which XLA's FMA
        # contraction may round differently by ~1 ULP
        uniform_rates = model.pallas_rates()
        # the general chunk pads and MASKS every channel in the flow
        # dtype, which would silently float-ify int/bool storage
        # channels (e.g. a land-water mask); the uniform chunk touches
        # only its own rate-carrying float attrs, so bystanders are
        # fine there
        if uniform_rates is None:
            nonfloat = sorted(
                k for k, v in space.values.items()
                if not jnp.issubdtype(v.dtype, jnp.floating))
            if nonfloat:
                raise ValueError(
                    f"halo_depth > 1 with general pointwise flows pads/"
                    f"masks every channel in the flow dtype; non-float "
                    f"channels {nonfloat} are not supported on this path "
                    "— use halo_depth=1 (or an all-Diffusion model)")

        mesh = self.mesh
        names, nx, ny, local_h, local_w = self._shard_geometry(space)
        # only EXCHANGED dimensions bound the depth — on a 1-D mesh the
        # columns are zero-padded, not shipped, so any width is fine
        exchanged_min = local_h if len(names) == 1 else min(local_h, local_w)
        if depth > exchanged_min:
            raise ValueError(
                f"halo_depth={depth} exceeds the shard extent "
                f"({local_h}x{local_w}) — the exchanged slab cannot be "
                "deeper than the shard")
        offsets = model.offsets
        gshape = space.global_shape
        x_init, y_init = space.x_init, space.y_init
        dtype = space.dtype
        D = depth
        spec = grid_spec(mesh)

        if self.halo_mode == "zero":
            def pad_deep(z, d):  # diagnostic: no traffic (see __init__)
                return jnp.pad(z, d)
        elif len(names) == 1:
            def pad_deep(z, d):
                return pad_with_halo_1d(z, names[0], nx, depth=d)
        else:
            def pad_deep(z, d):
                return pad_with_halo_2d(z, names[0], names[1], nx, ny,
                                        depth=d)

        def shard_fn(values, n):
            row0 = np.int32(x_init) + lax.axis_index(names[0]) * np.int32(
                local_h)
            col0 = (np.int32(y_init)
                    + lax.axis_index(names[1]) * np.int32(local_w)
                    if len(names) > 1 else jnp.int32(y_init))
            # mask and true neighbor counts over the DEPTH-padded region,
            # from global coords (hoisted: one computation per compile,
            # sliced per chunk/step). The mask is the PARTITION bounds,
            # not the grid bounds: a standalone partition drops shares at
            # its interior edges EVERY step (reference-worker semantics,
            # see Model.execute), so ghost cells beyond the partition
            # must be re-zeroed each sub-step; for a full grid the two
            # coincide. Counts stay global-true (grid-edge topology).
            PH, PW = local_h + 2 * D, local_w + 2 * D
            rowg = (row0 - np.int32(D)) + lax.broadcasted_iota(
                jnp.int32, (PH, PW), 0)
            colg = (col0 - np.int32(D)) + lax.broadcasted_iota(
                jnp.int32, (PH, PW), 1)
            maskD_b = ((rowg >= np.int32(x_init))
                       & (rowg < np.int32(x_init) + np.int32(space.dim_x))
                       & (colg >= np.int32(y_init))
                       & (colg < np.int32(y_init) + np.int32(space.dim_y)))
            maskD = maskD_b.astype(dtype)
            from ..ops.stencil import neighbor_counts_traced
            cntD = jnp.maximum(
                neighbor_counts_traced(
                    (PH, PW), offsets,
                    (row0 - np.int32(D), col0 - np.int32(D)), gshape, dtype),
                jnp.asarray(1, dtype))

            def transport_step(cur, of, cnt_s, m, s, hs, ws):
                share = of / cnt_s
                inflow = None
                for dx, dy in offsets:
                    t = share[1 + dx:hs - 1 + dx, 1 + dy:ws - 1 + dy]
                    inflow = t if inflow is None else inflow + t
                return ((cur[1:hs - 1, 1:ws - 1]
                         - of[1:hs - 1, 1:ws - 1] + inflow)
                        * m[s + 1:s + hs - 1, s + 1:s + ws - 1])

            def chunk_uniform(c, d):
                """All-Diffusion: per-attr uniform-rate expression —
                compiles to the serial path's exact contraction (BITWISE
                parity); flow-less channels are never padded/exchanged."""
                off = D - d
                m = maskD[off:PH - off, off:PW - off]
                cnt = cntD[off:PH - off, off:PW - off]
                new = dict(c)
                for attr, rate in uniform_rates.items():
                    if rate == 0.0:
                        continue
                    cur = pad_deep(c[attr], d) * m
                    for s in range(d):
                        hs, ws = cur.shape
                        cur = transport_step(cur, rate * cur,
                                             cnt[s:s + hs, s:s + ws],
                                             m, s, hs, ws)
                    new[attr] = cur
                return new

            def chunk_general(c, d):
                """General pointwise flows: every channel rides the
                padded region (modulators are read by other flows'
                outflows at the shrinking shapes); ~1 ULP vs serial
                (XLA FMA grouping of the summed outflow)."""
                off = D - d
                m = maskD[off:PH - off, off:PW - off]
                mb = maskD_b[off:PH - off, off:PW - off]
                cnt = cntD[off:PH - off, off:PW - off]
                cur = {k: pad_deep(v, d) * m for k, v in c.items()}
                for s in range(d):
                    hs, ws = next(iter(cur.values())).shape
                    cnt_s = cnt[s:s + hs, s:s + ws]
                    mb_s = mb[s:s + hs, s:s + ws]
                    # the region's [0,0] sits d-s cells before the shard
                    # origin — origin-reading pointwise flows need it
                    org_s = (row0 - np.int32(d - s), col0 - np.int32(d - s))
                    # all outflows read the PRE-step values; the
                    # where-SELECT (bitwise passthrough in-partition)
                    # masks ghost cells so affine outflow(0) != 0 flows
                    # don't manufacture mass there
                    outflows = {}
                    for f in field_flows:
                        o = jnp.where(mb_s, f.outflow(cur, org_s),
                                      jnp.asarray(0, dtype))
                        outflows[f.attr] = (outflows[f.attr] + o
                                            if f.attr in outflows else o)
                    new = {}
                    for k2, cw in cur.items():
                        of = outflows.get(k2)
                        if of is None:
                            new[k2] = cw[1:hs - 1, 1:ws - 1]
                            continue
                        new[k2] = transport_step(cw, of, cnt_s, m, s,
                                                 hs, ws)
                    cur = new
                return cur

            chunk = (chunk_uniform if uniform_rates is not None
                     else chunk_general)

            # n is a TRACED scalar (dynamic trip count): one compile
            # serves every step count. q full-depth chunks, then a
            # lax.switch over the D possible remainder depths.
            q = n // D
            out = lax.fori_loop(0, q, lambda i, c: chunk(c, D), values)
            if D > 1:
                branches = [lambda c: c] + [
                    (lambda d: lambda c: chunk(c, d))(d)
                    for d in range(1, D)]
                out = lax.switch(n - q * D, branches, out)
            return out

        sharded = shard_map(shard_fn, mesh=mesh, in_specs=(spec, P()),
                                out_specs=spec)
        return jax.jit(sharded)

    def _build_pallas_runner(self, model, space: CellularSpace, plan: tuple):
        """Per-shard fused Pallas kernel fed by the ppermute ghost ring —
        the config-5 architecture (SURVEY §7 'Pallas at 16384²'): the
        fast kernel and the distributed runtime in one compiled step.
        ``plan`` selects the kernel (``_pallas_plan``): ``"diffusion"``
        runs the specialized per-channel kernel, ``"field"`` the general
        multi-channel kernel (Coupled/user pointwise flows — ALL
        channels exchange rings, since outflows read modulators on ghost
        cells). With ``halo_depth = d > 1`` the ring is exchanged d
        cells deep and the kernel fuses d flow steps per invocation —
        one collective round AND one HBM round-trip per d steps."""
        from jax import lax

        from ..ops.pallas_stencil import (
            mesh_interpret, pallas_field_halo_step, pallas_halo_step,
        )
        from .halo import exchange_ring, zero_ring

        kind, payload = plan
        mesh = self.mesh
        # resolve interpret from the MESH platform, not ambient config:
        # inside shard_map the values are tracers, and the default
        # backend/device can disagree with where the mesh actually runs
        # (round-3 VERDICT weak #1 — both failure directions)
        interpret = mesh_interpret(mesh)
        names, nx, ny, local_h, local_w = self._shard_geometry(space)
        ax = names[0]
        ay = names[1] if len(names) > 1 else None
        gshape = (space.dim_x, space.dim_y)
        offsets = model.offsets
        spec = grid_spec(mesh)
        depth = self.halo_depth
        if depth > (local_h if ay is None else min(local_h, local_w)):
            raise ValueError(
                f"halo_depth={depth} exceeds the shard extent "
                f"({local_h}x{local_w})")

        def ring_of(z, ns):
            return (zero_ring(z, ns) if self.halo_mode == "zero"
                    else exchange_ring(z, ax, nx, ay, ny, depth=ns))

        def shard_fn(values, n):
            row0 = lax.axis_index(ax) * np.int32(local_h)
            col0 = (lax.axis_index(ay) * np.int32(local_w) if ay
                    else jnp.int32(0))
            origin = jnp.stack([row0, col0]).astype(jnp.int32)

            cdt = self.compute_dtype
            if kind == "diffusion":
                def chunk(c, ns):
                    """ns fused steps after one depth-``ns`` exchange
                    (the remainder chunk ships only the rings it
                    consumes); flow-less channels never exchange."""
                    new = dict(c)
                    for attr, rate in payload.items():
                        if rate == 0.0:
                            continue
                        new[attr] = pallas_halo_step(
                            c[attr], ring_of(c[attr], ns), origin, gshape,
                            rate, offsets, interpret=interpret, nsteps=ns,
                            compute_dtype=cdt)
                    return new
            elif kind == "composed":
                from ..ops.composed_stencil import composed_halo_step

                def chunk(c, ns):
                    """ns flow steps as ONE composed (2·ns+1)² pass per
                    depth-``ns`` exchange — interior tiles run the tap
                    filter, near-global-edge tiles the exact iterated
                    path (remainder chunks compose at their own ns)."""
                    new = dict(c)
                    for attr, rate in payload.items():
                        if rate == 0.0:
                            continue
                        new[attr] = composed_halo_step(
                            c[attr], ring_of(c[attr], ns), origin, gshape,
                            rate, ns, offsets, interpret=interpret,
                            compute_dtype=cdt)
                    return new
            else:
                def chunk(c, ns):
                    """One depth-``ns`` exchange of EVERY channel, then
                    ns fused multi-channel steps in one kernel call."""
                    rings = {k: ring_of(v, ns) for k, v in c.items()}
                    return pallas_field_halo_step(
                        c, rings, origin, gshape, payload, offsets,
                        interpret=interpret, nsteps=ns, compute_dtype=cdt)

            # dynamic trip count (n traced): q full-depth fused chunks,
            # then a switch over the possible remainder depths — each
            # branch instantiates the kernel at its own (static) nsteps
            q = n // depth
            out = lax.fori_loop(0, q, lambda i, c: chunk(c, depth), values)
            if depth > 1:
                branches = [lambda c: c] + [
                    (lambda d: lambda c: chunk(c, d))(d)
                    for d in range(1, depth)]
                out = lax.switch(n - q * depth, branches, out)
            return out

        # check_vma=False: pallas_call's out_shape carries no
        # varying-mesh-axes metadata, which the checker would demand
        sharded = shard_map(shard_fn, mesh=mesh, in_specs=(spec, P()),
                                out_specs=spec, check_vma=False)
        return jax.jit(sharded)

    def _build_active_runner(self, model, space: CellularSpace,
                             fused: bool = False):
        """Shard-local active-tile stepping (``ops.active``): per shard,
        per step — one ppermute value exchange (the ghost ring), tile
        activity = ring-1 dilation of the shard's nonzero-tile map OR'd
        with ghost-strip activations, then either the compacted
        active-set pass (windows read the padded shard, counts from
        GLOBAL coordinates) or, above the capacity/activity threshold,
        the per-shard dense step consuming the same ring. Exchanging
        VALUES instead of shares keeps the result bitwise equal to the
        share-exchanging XLA shard step: a ghost cell's share is
        recomputed here from the same operands with the same expression
        the owning shard uses.

        ``fused=True`` (``step_impl="active_fused"``, ISSUE 8) swaps the
        XLA gather/compute for the scalar-prefetched Pallas pass
        (``ops.pallas_active.fused_active_pass``): windows stream the
        SAME ghost-padded shard — ghost-flag activation, counts-from-
        global-coordinates and the value-exchange bitwise argument all
        carry over unchanged — and the next tile map comes from the
        kernel's in-VMEM flags.

        Returns ``(runner, plan, nattr, nshards)``; the runner yields
        ``(values, (fallback_events, active_tiles_total))`` — plus a
        ``flags_fused`` counter under ``fused`` — with the counters
        psum'd across shards (one cheap collective per run), mirroring
        the serial runner's stats so a sharded run that dense-fell-back
        every step is visible in ``Report.backend_report``, not
        silently labeled "active"."""
        from jax import lax

        from ..ops import active as act
        from ..ops.stencil import neighbor_counts_traced

        impl_name = "active_fused" if fused else "active"
        rates = model.pallas_rates()
        live = {a: r for a, r in (rates or {}).items() if r != 0.0}
        has_point = any(isinstance(f, PointFlow) for f in model.flows)
        if rates is None or not live or has_point:
            raise ValueError(
                f"step_impl={impl_name!r} requires all field flows to be "
                "plain Diffusion with a nonzero rate and no point flows "
                "(the tile-skip rule is only bitwise-exact for "
                "uniform-rate linear flows); got "
                f"flows={[type(f).__name__ for f in model.flows]}. "
                "Use step_impl='xla' or 'auto'.")
        for a in live:
            adt = space.values[a].dtype
            if not jnp.issubdtype(adt, jnp.floating):
                raise TypeError(
                    f"flow transport requires a floating dtype, got "
                    f"{adt} for channel {a!r}")
            if adt != jnp.dtype(space.dtype):
                raise ValueError(
                    f"step_impl={impl_name!r} computes every flow channel "
                    f"in the space dtype ({jnp.dtype(space.dtype).name}); "
                    f"channel {a!r} is {adt}. Use step_impl='xla'.")
        mesh = self.mesh
        names, nx, ny, local_h, local_w = self._shard_geometry(space)
        plan = act.plan_for((local_h, local_w))
        th, tw = plan.tile
        offsets = model.offsets
        gshape = space.global_shape
        x_init, y_init = space.x_init, space.y_init
        dtype = space.dtype
        spec = grid_spec(mesh)

        if self.halo_mode == "zero":
            def pad(z):  # diagnostic: no inter-shard traffic
                return jnp.pad(z, 1)
        elif len(names) == 1:
            def pad(z):
                return pad_with_halo_1d(z, names[0], nx)
        else:
            def pad(z):
                return pad_with_halo_2d(z, names[0], names[1], nx, ny)

        if fused:
            from ..ops.pallas_active import fused_active_pass
            from ..ops.pallas_stencil import mesh_interpret
            interp = mesh_interpret(mesh)

        def shard_fn(values, n):
            row0 = np.int32(x_init) + lax.axis_index(names[0]) * np.int32(
                local_h)
            col0 = (np.int32(y_init)
                    + lax.axis_index(names[1]) * np.int32(local_w)
                    if len(names) > 1 else jnp.int32(y_init))
            # true neighbor counts over the PADDED shard from global
            # coords (hoisted per compile); off-grid ghosts clamp to 1 —
            # their value is ppermute's zero fill anyway
            counts_pad = jnp.maximum(
                neighbor_counts_traced(
                    (local_h + 2, local_w + 2), offsets,
                    (row0 - np.int32(1), col0 - np.int32(1)), gshape,
                    dtype),
                jnp.asarray(1, dtype))

            def step_attr(vals_a, tmap, upd, rate):
                # per-step cond here (unlike the serial runner's
                # while-nest): the ghost exchange is a collective that
                # must run on every shard every step, so consecutive
                # active steps cannot be batched past it — the cond's
                # buffer-copy tax is paid on the (smaller) per-shard
                # arrays and accepted. The tile map is CARRIED, not
                # rebuilt from the shard values (the serial runner's
                # measured lesson: a full-array nonzero reduction per
                # step costs a third of the step); the active branch
                # derives the exact next map from its own per-lane
                # flags, the dense branch re-scans only on fallback
                # EVENTS.
                padded = pad(vals_a)  # collective — OUTSIDE the cond
                flags = (act.dilate_tile_map(tmap)
                         | act.ghost_flags(padded, plan))
                count = jnp.sum(flags, dtype=jnp.int32)
                pred = count > np.int32(plan.fallback_tiles)

                def dense_branch(args):
                    p, u = args
                    new = act.dense_from_ghost_padded(
                        p, rate, counts_pad, offsets, dtype)
                    return (new, act.tile_nonzero_map(new, plan), u,
                            jnp.zeros((), jnp.int32))

                def active_branch(args, _tmap=tmap):
                    p, u = args
                    ids, cnt = act.compact_tile_ids(flags, plan)
                    if fused:
                        # the scalar-prefetched kernel pass: same
                        # ghost-padded windows, flags computed in-VMEM
                        selfnz = _tmap.reshape(-1)[ids].astype(jnp.int32)
                        origin_vec = jnp.stack([row0, col0]).astype(
                            jnp.int32)
                        p2, anyf = fused_active_pass(
                            p, ids, cnt, selfnz, rate, plan, origin_vec,
                            gshape, offsets, dtype, k=1, ring=1,
                            taps=None, interpret=interp)
                        u2 = u
                    else:
                        p2, u2, anyf = act.active_pass(
                            p, u, ids, cnt, rate, plan, (row0, col0),
                            gshape, offsets, dtype)
                    return (p2[1:-1, 1:-1],
                            act.next_tile_map(anyf, ids, cnt, plan), u2,
                            jnp.ones((), jnp.int32))

                nv, ntm, nu, fs = lax.cond(pred, dense_branch,
                                           active_branch, (padded, upd))
                return nv, ntm, nu, pred, count, fs

            # the fused branch scatters in-kernel and never touches the
            # carried update buffer — a scalar placeholder keeps the
            # cond/loop carries shape-shared without allocating the
            # [capacity, th, tw] buffer (~64 MB/attr at bench scale)
            # the XLA branch actually needs
            upd0 = {a: (jnp.zeros((), dtype) if fused
                        else jnp.zeros((plan.capacity, th, tw), dtype))
                    for a in live}
            # one full-shard nonzero scan per RUN seeds the carried maps
            tmap0 = {a: act.tile_nonzero_map(values[a], plan)
                     for a in live}

            def body(i, carry):
                vals, tmaps, upds, fb, at, ff = carry
                new_v, new_t, new_u = dict(vals), dict(tmaps), dict(upds)
                for a, r in live.items():
                    (new_v[a], new_t[a], new_u[a], p, c, fs) = step_attr(
                        vals[a], tmaps[a], upds[a], r)
                    # serial-runner stats semantics (ops.active): fb
                    # counts dense-fallback EVENTS, at sums the dilated
                    # active-tile counts, ff the kernel-flagged steps —
                    # here per (shard, attr, step)
                    fb = fb + p.astype(jnp.int32)
                    at = at + c.astype(jnp.float32)
                    ff = ff + fs
                return new_v, new_t, new_u, fb, at, ff

            # the update buffers and counters end each step varying over
            # the mesh, so they start that way
            ref = next(iter(values.values()))
            upd0, fb0, at0, ff0 = jax.tree.map(
                lambda x: act.vary_like(x, ref),
                (upd0, jnp.int32(0), jnp.float32(0), jnp.int32(0)))
            # n is a TRACED scalar: one compile serves every step count
            out, _, _, fb, at, ff = lax.fori_loop(
                0, n, body, (values, tmap0, upd0, fb0, at0, ff0))
            # one collective for all counters (psum over the tuple)
            fb, at, ff = lax.psum((fb, at, ff), names)
            if fused:
                return out, (fb, at, ff)
            return out, (fb, at)

        stat_spec = (P(), P(), P()) if fused else (P(), P())
        sharded = shard_map(shard_fn, mesh=mesh, in_specs=(spec, P()),
                            out_specs=(spec, stat_spec),
                            check_vma=not fused)
        return jax.jit(sharded), plan, len(live), nx * ny

    def _build_ir_runner(self, model, space: CellularSpace):
        """Per-shard runner for nonlinear Flow IR models: one ppermute
        VALUE exchange per step for the channels some ring-1 term reads
        (the term footprints say which — budget/pointwise channels never
        ship), then the SAME registered lowering the serial dense step
        runs, in its ghost-padded context (``ir.lower.padded_apply``).
        Value-exchange keeps the result bitwise equal to the serial
        step: a ghost cell's outflow/share is recomputed here from the
        same operands with the same expression the owning shard uses
        (the ``ops.active`` discipline), and ghost cells beyond the
        partition are masked to zero — the serial zero-pad semantics."""
        from jax import lax

        from ..ir.lower import StepMeta, involved_channels, padded_apply
        from ..ops.stencil import neighbor_counts_traced

        model._validate_space(space)
        terms = model.ir_terms
        rates = model.term_rates()
        missing = sorted(involved_channels(terms) - set(space.values))
        if missing:
            raise ValueError(f"space is missing IR channels {missing}")
        mesh = self.mesh
        names, nx, ny, local_h, local_w = self._shard_geometry(space)
        offsets = tuple(model.offsets)
        gshape = space.global_shape
        x_init, y_init = space.x_init, space.y_init
        dtype = space.dtype
        spec = grid_spec(mesh)
        ring_chs = sorted(set().union(
            *(t.reads() for t in terms if t.footprint >= 1)) or set())

        if self.halo_mode == "zero":
            def pad(z):  # diagnostic: no inter-shard traffic
                return jnp.pad(z, 1)
        elif len(names) == 1:
            def pad(z):
                return pad_with_halo_1d(z, names[0], nx)
        else:
            def pad(z):
                return pad_with_halo_2d(z, names[0], names[1], nx, ny)

        def shard_fn(values, n):
            row0 = np.int32(x_init) + lax.axis_index(names[0]) * np.int32(
                local_h)
            col0 = (np.int32(y_init)
                    + lax.axis_index(names[1]) * np.int32(local_w)
                    if len(names) > 1 else jnp.int32(y_init))
            meta = StepMeta(shape=(local_h, local_w), origin=(row0, col0),
                            global_shape=gshape, dtype=dtype,
                            offsets=offsets)
            PH, PW = local_h + 2, local_w + 2
            # partition-bounds mask over the padded shard (ghosts beyond
            # the true grid/partition shed nothing — the serial
            # zero-pad's bitwise twin) + global-true clamped counts
            rowg = (row0 - np.int32(1)) + lax.broadcasted_iota(
                jnp.int32, (PH, PW), 0)
            colg = (col0 - np.int32(1)) + lax.broadcasted_iota(
                jnp.int32, (PH, PW), 1)
            mask_pb = ((rowg >= np.int32(x_init))
                       & (rowg < np.int32(x_init) + np.int32(space.dim_x))
                       & (colg >= np.int32(y_init))
                       & (colg < np.int32(y_init) + np.int32(space.dim_y)))
            counts_pad = jnp.maximum(
                neighbor_counts_traced(
                    (PH, PW), offsets,
                    (row0 - np.int32(1), col0 - np.int32(1)), gshape,
                    dtype),
                jnp.asarray(1, dtype))

            def body(i, c):
                padded = {k: pad(c[k]) for k in ring_chs}
                return padded_apply(terms, c, padded, rates, meta,
                                    counts_pad, mask_pb)

            # n is a TRACED scalar: one compile serves every step count
            return lax.fori_loop(0, n, body, values)

        sharded = shard_map(shard_fn, mesh=mesh, in_specs=(spec, P()),
                            out_specs=spec)
        return jax.jit(sharded)

    def _build_runner(self, model, space: CellularSpace):
        mesh = self.mesh
        names, nx, ny, local_h, local_w = self._shard_geometry(space)
        offsets = model.offsets
        field_flows = [f for f in model.flows if not isinstance(f, PointFlow)]
        spec = grid_spec(mesh)

        # Footprint enforcement (round-2 VERDICT weak #4): a flow whose
        # outflow reads neighbors would silently miscompute per shard, so
        # undeclared footprints are refused here, and declared ring1 flows
        # get halo-padded channels instead.
        undeclared = sorted({type(f).__name__ for f in field_flows
                             if f.footprint not in ("pointwise", "ring1")})
        if undeclared:
            raise ValueError(
                f"ShardMapExecutor cannot prove flows {undeclared} are "
                "shardable: declare footprint='pointwise' (outflow reads "
                "only the cell's own channels) or footprint='ring1' + "
                "outflow_padded (reads the 3x3 neighborhood; inputs are "
                "halo-exchanged). Undeclared flows run correctly under "
                "SerialExecutor and AutoShardedExecutor.")
        any_ring1 = any(f.footprint == "ring1" for f in field_flows)

        if self.halo_mode == "zero":
            def pad(z):  # diagnostic: no inter-shard traffic (see __init__)
                return jnp.pad(z, 1)
        elif len(names) == 1:
            def pad(z):
                return pad_with_halo_1d(z, names[0], nx)
        else:
            def pad(z):
                return pad_with_halo_2d(z, names[0], names[1], nx, ny)

        # global bounds / origin: the sharded space may itself be a
        # partition of a larger grid — boundary topology follows the TRUE
        # grid edges, exactly like the numpy counts did
        gshape = space.global_shape
        x_init, y_init = space.x_init, space.y_init
        dtype = space.dtype

        point_flows = [f for f in model.flows if isinstance(f, PointFlow)]

        def point_outflows(outflows, values, row0, col0):
            """SPARSE per-shard point-flow outflows: one O(1) scatter per
            flow into the shard owning the source (everyone else's masked
            amount is 0), replacing the former dense one-hot rate fields
            — no O(grid) extra operand, no per-step field multiply (the
            serial path's ``point_flow_step`` economics, sharded). The
            owner test (``Model.hpp:176``) is the ``inside`` mask;
            cross-shard delivery still rides the ordinary share halo."""
            for f in point_flows:
                x, y = f.source_xy  # static global coords
                lx = jnp.int32(x) - row0
                ly = jnp.int32(y) - col0
                inside = ((lx >= 0) & (lx < local_h)
                          & (ly >= 0) & (ly < local_w))
                lxc = jnp.clip(lx, 0, local_h - 1)
                lyc = jnp.clip(ly, 0, local_w - 1)
                if f.frozen_source_value is not None:
                    amt = jnp.asarray(f.flow_rate * f.frozen_source_value,
                                      dtype=dtype)
                else:
                    amt = jnp.asarray(f.flow_rate, dtype=dtype) \
                        * values[f.attr][lxc, lyc]
                amt = jnp.where(inside, amt, jnp.zeros((), dtype))
                base = outflows.get(f.attr)
                if base is None:
                    base = jnp.zeros((local_h, local_w), dtype)
                outflows[f.attr] = base.at[lxc, lyc].add(amt)
            return outflows

        def local_step(values, counts, row0, col0):
            new = dict(values)
            origin = (row0, col0)
            padded_vals = (
                {k: pad(v) for k, v in values.items()} if any_ring1 else None)
            outflows: dict[str, jax.Array] = {}
            for f in field_flows:
                if f.footprint == "ring1":
                    o = f.outflow_padded(padded_vals, origin)
                else:
                    # origin is the shard's global offset (traced) — the
                    # serial path passes the space's origin the same way
                    o = f.outflow(values, origin)
                outflows[f.attr] = outflows.get(f.attr, 0.0) + o
            outflows = point_outflows(outflows, values, row0, col0)
            for attr, outflow in outflows.items():
                share = outflow / counts
                # analysis: ignore[hardcoded-physics] — the legacy
                # share-exchanging flow shard step (general flows +
                # point scatters); nonlinear IR models run
                # _build_ir_runner's registered lowering instead
                inflow = gather_from_padded(pad(share), offsets)
                new[attr] = values[attr] - outflow + inflow
            return new

        def shard_fn(values, n):
            from jax import lax

            from ..ops.stencil import neighbor_counts_traced
            row0 = np.int32(x_init) + lax.axis_index(names[0]) * np.int32(local_h)
            col0 = (np.int32(y_init) + lax.axis_index(names[1]) * np.int32(local_w)
                    if len(names) > 1 else jnp.int32(y_init))
            # per-shard counts as traced iota arithmetic — no O(grid)
            # host array, no extra sharded operand (mirrors make_step)
            counts = neighbor_counts_traced((local_h, local_w), offsets,
                                            (row0, col0), gshape, dtype)

            # n is a TRACED scalar: every step count runs one compile
            return lax.fori_loop(
                0, n, lambda i, c: local_step(c, counts, row0, col0), values)

        sharded = shard_map(
            shard_fn, mesh=mesh,
            in_specs=(spec, P()),
            out_specs=spec)
        return jax.jit(sharded)
