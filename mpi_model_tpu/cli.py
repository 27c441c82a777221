"""Command-line driver: the Python counterpart of the reference's
``Main.cpp`` (and of ``native/src/main.cpp``).

The reference hardcodes everything at compile time — a 100x100 grid, an
``Exponencial`` flow at cell (19,3) with snapshot value 2.2 and rate 0.1,
``Model(…, 10.0, 0.2)``, 6 mpirun ranks (``/root/reference/src/Main.cpp:
17-52``, ``Defines.hpp:5-13``) — and accepts but ignores ``argv``. Here
the same scenario is the DEFAULT of a real flag surface:

    python -m mpi_model_tpu.cli run                       # the reference run
    python -m mpi_model_tpu.cli run --flow=diffusion --dimx=1024 \\
        --mesh=2x4 --halo-depth=4 --steps=100             # sharded
    python -m mpi_model_tpu.cli run --checkpoint-dir=ckpts \\
        --checkpoint-every=10 --steps=100                 # supervised+resumable
    python -m mpi_model_tpu.cli info                      # devices/backends

``run`` wires the whole framework: Model/flows, serial or shard_map
executors (with multi-step fusion and deep halos), the resilience
supervisor when checkpointing is on, the reference-parity output dump
(``--output``), and Chrome-trace export (``--trace``).
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
from typing import Optional


def _parse_grid2(text, flag):
    """'N' or 'LxC' → (lines, columns), positive."""
    try:
        parts = [int(v) for v in text.lower().split("x")]
        if len(parts) == 1:  # "N" = 1-D row stripes (Model.hpp:62-76)
            parts.append(1)
        lines, columns = parts
        if lines < 1 or columns < 1:
            raise ValueError
    except ValueError:
        raise SystemExit(
            f"{flag}={text!r} is not N or LxC with positive extents "
            f"(e.g. {flag}=4, {flag}=2x4)")
    return lines, columns


def _parse_chaos(args):
    """``--chaos=KIND[:N]`` → a seeded FaultPlan, or None. Kinds: ``exc``
    (executor exception on chunk N), ``nan`` (NaN written into the state
    after chunk N), ``halo`` (ghost-ring perturbation during chunk N —
    sharded runs only), ``torn`` (the checkpoint written at step N is
    torn on disk — requires --checkpoint-dir; with the delta layout the
    unpinned fault tears whichever record step N wrote), and the
    delta-chain targets ``torn-keyframe``/``torn-delta``/``torn-chain``
    (tear that specific record kind / the chain manifest — require
    --checkpoint-layout=delta)."""
    if args.chaos is None:
        return None
    from .resilience.inject import Fault, FaultPlan

    spec = args.chaos
    kind, _, at_s = spec.partition(":")
    known = ("exc", "nan", "halo", "torn", "torn-keyframe", "torn-delta",
             "torn-chain")
    if kind not in known:
        raise SystemExit(
            f"--chaos={spec!r}: unknown kind {kind!r} (expected "
            "exc|nan|halo|torn|torn-keyframe|torn-delta|torn-chain, "
            "optionally ':N' for the chunk/step to fire at)")
    try:
        at = int(at_s) if at_s else None
    except ValueError:
        raise SystemExit(f"--chaos={spec!r}: {at_s!r} is not an integer")
    sharded = args.mesh is not None or args.rectangular is not None
    if kind == "halo" and not sharded:
        raise SystemExit(
            "--chaos=halo perturbs the ghost-ring exchange; add "
            "--mesh=LxC (serial runs have no halos)")
    if kind.startswith("torn"):
        if args.checkpoint_dir is None:
            raise SystemExit(
                f"--chaos={kind} tears a written checkpoint; add "
                "--checkpoint-dir=DIR")
        part = kind.partition("-")[2] or None
        if part is not None and args.checkpoint_layout != "delta":
            raise SystemExit(
                f"--chaos={kind} targets a delta-chain "
                f"{'manifest' if part == 'chain' else part + ' record'}, "
                f"which --checkpoint-layout={args.checkpoint_layout} "
                "never writes; use --checkpoint-layout=delta (or plain "
                "--chaos=torn for this layout's files)")
        # commit records are json — corrupt them (truncation at a byte
        # offset is the data-record tear)
        tear = (Fault("torn", at=at, channel="chain", tear="corrupt",
                      offset=2)
                if part == "chain"
                else Fault("torn", at=at, channel=part, tear="truncate",
                           offset=64))
        return FaultPlan((tear,), seed=args.chaos_seed)
    return FaultPlan((Fault(kind, at=at),), seed=args.chaos_seed)


def _cache_spec(args, default):
    """Map ``--compile-cache`` to the service knob: unset → ``default``
    ("auto": ``utils.compile_cache``'s placement rule),
    ``off``/``none`` → explicitly disabled, a directory → that
    directory unless ``$JAX_COMPILATION_CACHE_DIR`` is set; an EMPTY
    value is an error, not a silent flip (the errors-not-silent-no-ops
    rule)."""
    v = args.compile_cache
    if v is None:
        return default
    if v.strip().lower() in ("off", "none"):
        return None
    if not v.strip():
        raise SystemExit(
            "--compile-cache needs a directory (or 'off' to disable "
            "the persistent cache explicitly)")
    return v


def _compute_dtype(args):
    if args.compute_dtype is None:
        return None
    import jax.numpy as jnp

    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[
        args.compute_dtype]


def _build_model(args):
    import jax.numpy as jnp

    from . import (
        Attribute, Cell, CellularSpace, Coupled, Diffusion, Exponencial,
        Model, ModelRectangular,
    )

    dtype = {"float32": jnp.float32, "float64": jnp.float64,
             "bfloat16": jnp.bfloat16}[args.dtype]
    if args.model is not None:
        # the Flow IR registry (ISSUE 11): terms + the one registered
        # lowering — every engine/executor/serving path below consumes
        # the model with zero per-model step code
        from .ir import build_model as build_ir_model

        model, space = build_ir_model(
            args.model, args.dimx, args.dimy, dtype=dtype,
            time=args.time, time_step=args.time_step)
        return space, model
    init_spec = args.init
    if args.flow == "exponencial":
        sx, sy = (int(v) for v in args.source.split(","))
        flow = Exponencial(Cell(sx, sy, Attribute(99, args.value)),
                           args.rate)
    elif args.flow == "diffusion":
        flow = Diffusion(args.rate)
    elif args.flow == "coupled":
        # the config-4 workload shape: N diffusing channels chained by
        # coupled flows (channel i sheds mass modulated by channel i+1)
        # — at --channels=2 this is the BASELINE config-4 flow SET
        # (Diffusion(a) + Coupled(a|b) + Diffusion(b); the ladder's
        # second diffusion uses rate 0.2 where the CLI applies --rate to
        # every diffusion), the multi-attribute case the fused FIELD
        # kernel exists for
        if args.channels < 2:
            raise SystemExit("--flow=coupled needs --channels >= 2 "
                             "(one channel has nothing to modulate — "
                             "use --flow=diffusion)")
        names = [f"c{i}" for i in range(args.channels)]
        flow = [Diffusion(args.rate, attr=nm) for nm in names]
        flow += [Coupled(flow_rate=args.rate / 2, attr=names[i],
                         modulator=names[i + 1])
                 for i in range(len(names) - 1)]
        init_spec = {nm: args.init for nm in names}
    else:
        raise SystemExit(f"unknown --flow={args.flow!r} "
                         "(expected exponencial|diffusion|coupled)")
    space = CellularSpace.create(args.dimx, args.dimy, init_spec,
                                 dtype=dtype)
    if args.rect_grid is not None:
        lines, columns = args.rect_grid
        model = ModelRectangular(flow, args.time, args.time_step,
                                 lines=lines, columns=columns,
                                 step_impl=args.impl,
                                 halo_depth=args.halo_depth,
                                 compute_dtype=_compute_dtype(args))
    else:
        model = Model(flow, args.time, args.time_step)
    return space, model


def _pick_devices(n: int, hint_flag: str):
    import jax

    devices = jax.devices()
    if len(devices) < n:
        raise SystemExit(
            f"{hint_flag} needs {n} devices; have {len(devices)} "
            f"{devices[0].platform} device(s) (hint: XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} "
            "JAX_PLATFORMS=cpu for a virtual CPU mesh)")
    return devices[:n]


def _build_executor(args, model):
    if args.rect_grid is not None:
        # ModelRectangular owns its executor: a ShardMapExecutor over
        # the lines × columns block mesh, which also becomes the
        # owner_of / per-block-output geometry source of truth
        lines, columns = args.rect_grid
        return model.default_executor(
            devices=_pick_devices(lines * columns, "--rectangular"))

    if args.mesh is None:
        from .models.model import SerialExecutor

        return SerialExecutor(step_impl=args.impl, substeps=args.substeps,
                              compute_dtype=_compute_dtype(args))

    lines, columns = _parse_grid2(args.mesh, "--mesh")
    n = lines * columns
    devices = _pick_devices(n, f"--mesh={args.mesh}")

    from .parallel import (AutoShardedExecutor, ShardMapExecutor, make_mesh,
                           make_mesh_2d)

    if lines == 1 or columns == 1:
        mesh = make_mesh(n, devices=devices)
    else:
        mesh = make_mesh_2d(lines, columns, devices=devices)
    if args.executor == "gspmd":
        # the GSPMD path: the global XLA step with sharding annotations —
        # XLA inserts the halo collectives. Slower than the explicit
        # ppermute path on the measured ladder (BASELINE config 3) but
        # runs ANY flow unchanged, including footprint="unknown" user
        # flows ShardMapExecutor refuses.
        return AutoShardedExecutor(mesh)
    return ShardMapExecutor(mesh, step_impl=args.impl,
                            halo_depth=args.halo_depth,
                            compute_dtype=_compute_dtype(args))


def _parse_ensemble_mesh(spec):
    """``--ensemble-mesh B`` or ``BxS`` → the scheduler's mesh spec
    (batch extent, or (batch, space) pair). The concrete mesh resolves
    against the running process's devices — or, with
    --serve-transport=process, against each CHILD's (possibly
    --serve-member-env-pinned) device set."""
    if spec is None:
        return None
    s = str(spec).lower().replace("×", "x")
    try:
        if "x" in s:
            b, sp = s.split("x", 1)
            b, sp = int(b), int(sp)
            if b < 1 or sp < 1:
                raise ValueError
            return (b, sp)
        b = int(s)
        if b < 1:
            raise ValueError
        return b
    except ValueError:
        raise SystemExit(
            f"--ensemble-mesh expects a batch extent B or BxS "
            f"(batch x space devices), got {spec!r}")


def _parse_member_env(pairs):
    """Repeatable ``--serve-member-env KEY=VAL`` → the env dict laid
    over every spawned member (per-slot pinning is API-level:
    ``FleetSupervisor(member_env=[{...}, {...}])``)."""
    if not pairs:
        return None
    env = {}
    for p in pairs:
        if "=" not in p:
            raise SystemExit(
                f"--serve-member-env expects KEY=VAL, got {p!r}")
        k, v = p.split("=", 1)
        if not k:
            raise SystemExit(
                f"--serve-member-env expects a non-empty KEY, got {p!r}")
        env[k] = v
    return env


def _run_ensemble(args, space, model) -> int:
    """``--ensemble B``: B copies of the configured scenario through the
    full serving stack (EnsembleService → bucketed scheduler → batched
    engine), so the CLI reports what a deployment would see: per-scenario
    conservation, scenarios/s, batch occupancy and compile-cache hits.
    Conservation is judged here (status + exit code), not raised
    mid-flight — the CLI's contract everywhere else."""
    import time as _time

    from .ensemble import EnsembleService, buckets_for

    B = args.ensemble
    steps = args.steps if args.steps is not None else model.num_steps
    svc = EnsembleService(
        model, steps=steps, impl=args.ensemble_impl,
        substeps=args.substeps, buckets=buckets_for(B),
        compute_dtype=_compute_dtype(args), check_conservation=False,
        compile_cache=_cache_spec(args, "auto"),
        mesh=_parse_ensemble_mesh(args.ensemble_mesh))
    t0 = _time.perf_counter()
    try:
        tickets = [svc.submit(space) for _ in range(B)]
        svc.flush()
        outs = [svc.result(t) for t in tickets]
    except (TypeError, ValueError) as e:
        # engine ineligibility (e.g. --ensemble-impl=pipeline on a
        # non-Diffusion flow or a non-strip grid) is CLI misuse, not a
        # crash: the flag-surface discipline, not a raw traceback
        raise SystemExit(f"ensemble run failed: {e}")
    wall = _time.perf_counter() - t0
    st = svc.stats()

    thresh = model.conservation_threshold(space)
    # IR models judge the budget-reconciled view, not raw channel drift
    errfn = getattr(model, "report_conservation_error", None)
    errs = [errfn(rep) if errfn is not None
            else rep.conservation_error() for _, rep in outs]
    err = max(errs)
    conserved = bool(err <= thresh)
    initial = {k: sum(rep.initial_total[k] for _, rep in outs)
               for k in outs[0][1].initial_total}
    final = {k: sum(rep.final_total[k] for _, rep in outs)
             for k in outs[0][1].final_total}
    result = {
        "backend": "ensemble",
        "ranks": 1,
        "ensemble": B,
        "steps": steps,
        "initial": initial,
        "final": final,
        "conservation_error": err,
        "conserved": conserved,
        "wall_s": wall,
        "impl": args.ensemble_impl,
        "substeps": args.substeps,
        "mesh": st["mesh"],
        "scenarios_per_s": st["scenarios_per_s"],
        "batch_occupancy": st["batch_occupancy"],
        "compile_cache_hits": st["compile_cache_hits"],
        "dispatches": st["dispatches"],
        # self-healing honesty (ISSUE 5): zeros on a clean run, but the
        # row always says how many scenarios were recovered/quarantined
        "recovered_failures": st["recovered_failures"],
        "quarantined": st["quarantined"],
        "solo_retries": st["solo_retries"],
    }
    if args.json:
        print(json.dumps(result, allow_nan=False))
    else:
        status = "CONSERVED" if conserved else "VIOLATED"
        sps = st["scenarios_per_s"]
        rate = f"{sps:.1f} scenarios/s, " if sps else ""
        print(f"backend=ensemble impl={args.ensemble_impl} B={B} "
              f"steps={steps} max|delta|={err:.3e} {status} "
              f"({wall:.2f}s, {rate}"
              f"occupancy={st['batch_occupancy']:.2f}, "
              f"{st['dispatches']} dispatches)")
    return 0 if conserved else 1


def _run_serve(args, space, model) -> int:
    """``--serve``: drive the always-on async dispatch loop (ISSUE 9)
    with an open-loop arrival process — ``--serve-scenarios`` copies of
    the configured scenario arriving at ``--arrival-rate`` per second
    (0/unset = open throttle) against a ``--max-queue``-bounded
    admission queue with optional per-ticket ``--deadline-s``.
    ``--serve-services N`` (ISSUE 10) shards the same arrival stream
    over an N-member ``FleetSupervisor`` (structure-affine routing,
    member fencing + restart, per-member attribution in the JSON row).
    Reports the serving ledger (served/failed/expired/shed — complete
    by construction, exit 1 if not), sustained scenarios/s, p50/p99
    queue latency and device occupancy."""
    from .ensemble import (AsyncEnsembleService, FleetSupervisor,
                           buckets_for, run_soak)

    steps = args.steps if args.steps is not None else model.num_steps
    n = args.serve_scenarios
    svc_kw = dict(
        steps=steps, impl=args.ensemble_impl,
        substeps=args.substeps, buckets=buckets_for(8),
        max_queue=args.max_queue, compute_dtype=_compute_dtype(args),
        deadline_s=args.deadline_s, retry="solo",
        compile_cache=_cache_spec(args, "auto"),
        # ISSUE 14: capacity-aware paging — overload hibernates to the
        # vault instead of shedding (both flags or neither, validated)
        residency_budget=args.residency_budget,
        hibernate_dir=args.hibernate_dir,
        # ISSUE 16: the (batch × space) ensemble mesh — an int/pair
        # spec, so over process transport each CHILD resolves it
        # against its own (possibly pinned) device set
        mesh=_parse_ensemble_mesh(args.ensemble_mesh))
    if args.status:
        # --status is the "I am watching this soak" flag: flight dumps
        # (the ring cut beside every fence/quarantine/HibernationError)
        # land on disk next to the snapshot so a post-mortem finds them
        # even if this process died with its in-memory dumps
        from .obs.flight import FlightRecorder, set_recorder

        set_recorder(FlightRecorder(
            dump_dir=args.status + ".flight.d"))
    fleet_mode = (args.serve_services > 1
                  or args.serve_transport != "inproc")
    if fleet_mode:
        # process transport always runs under the fleet supervisor —
        # someone must heartbeat, fence and respawn the children
        svc = FleetSupervisor(model, services=args.serve_services,
                              member_transport=args.serve_transport,
                              member_env=_parse_member_env(
                                  args.serve_member_env),
                              **svc_kw)
    else:
        svc = AsyncEnsembleService(model, **svc_kw)
    rate = args.arrival_rate if args.arrival_rate else 1e9
    with svc:
        rep = run_soak(svc, [(space, None, None)] * n,
                       arrival_rate_hz=rate,
                       snapshot_path=args.status,
                       snapshot_interval_s=args.status_interval_s,
                       status_port=args.status_port)
    if args.trace:
        # serve mode: the merged ticket-flight trace (member spans
        # arrived over heartbeats, labeled m<slot>g<gen>)
        from .utils.tracing import get_tracer

        get_tracer().export_chrome(args.trace)
    result = {
        "backend": "serve",
        "impl": args.ensemble_impl,
        "steps": steps,
        "max_queue": args.max_queue,
        "deadline_s": args.deadline_s,
        "services": args.serve_services,
        "transport": args.serve_transport,
        "telemetry_snapshot": args.status,
        "trace": args.trace,
        **{k: rep[k] for k in (
            "offered", "served", "failed", "expired", "shed",
            "ledger_complete", "wall_s", "sustained_scenarios_per_s",
            "occupancy", "latency_p50_s", "latency_p99_s",
            "batch_occupancy", "dispatches", "solo_retries",
            "recovered_failures", "quarantined", "loop_faults")},
    }
    if args.residency_budget is not None:
        # ISSUE 14 observability: the paging ledger + gauges (wakes,
        # hibernations, wake-latency percentiles, residency cut)
        st = svc.stats()
        for k in ("hibernations", "rehibernations", "wakes",
                  "wake_faults", "wake_latency_p50_s",
                  "wake_latency_p99_s", "resident_scenarios",
                  "resident_bytes", "residency_budget",
                  "hibernated_scenarios", "hibernated_bytes"):
            result[k] = st.get(k)
        if fleet_mode:
            result["wakes_by_member"] = st.get("wakes_by_member")
    if fleet_mode:
        result["member_faults"] = rep["member_faults"]
        result["readmitted"] = rep["readmitted"]
        # per-member attribution (the service_id satellite): enough for
        # an operator to see which member served what; process
        # transport adds the wire observability (ISSUE 13)
        result["members"] = [
            {k: s[k] for k in ("service_id", "scenarios", "dispatches",
                               "pending", "gen")}
            for s in rep["services"]]
        if args.serve_transport in ("process", "tcp"):
            st = svc.stats()
            for k in ("respawns", "heartbeats", "heartbeat_misses",
                      "wire_errors", "wire_bytes_in", "wire_bytes_out"):
                result[k] = st[k]
    if args.json:
        print(json.dumps(result, allow_nan=False))
    else:
        sps = rep["sustained_scenarios_per_s"]
        p99 = rep["latency_p99_s"]
        p99_s = "n/a" if p99 is None else f"{p99:.4f}s"
        fleet_note = (f" services={args.serve_services}"
                      if args.serve_services > 1 else "")
        print(f"backend=serve impl={args.ensemble_impl}{fleet_note} "
              f"served={rep['served']}/{rep['offered']} "
              f"shed={rep['shed']} expired={rep['expired']} "
              f"failed={rep['failed']} "
              f"({sps:.1f} scenarios/s sustained, "
              f"p99={p99_s}, "
              f"occupancy={rep['occupancy']:.2f})")
    return 0 if rep["ledger_complete"] else 1


def cmd_run(args) -> int:
    import time as _time

    from .utils.compile_cache import configure_compile_cache
    from .utils.tracing import get_tracer

    # arm the persistent compilation cache BEFORE anything compiles
    configure_compile_cache(_cache_spec(args, "auto"))

    # inapplicable flag combinations are errors, not silent no-ops — a
    # user must not believe they benchmarked a configuration that never
    # ran
    sharded = args.mesh is not None or args.rectangular is not None
    if args.model is not None:
        if args.flow is not None:
            raise SystemExit(
                "--model runs a registered Flow IR model; --flow builds "
                "a hand-wired scenario — pick one")
        if args.rectangular is not None:
            raise SystemExit(
                "--model runs the standard Model orchestration; "
                "--rectangular drives the flow-based ModelRectangular "
                "demo — use --mesh=LxC for sharded IR runs")
        if (args.rate != 0.1 or args.source != "19,3"
                or args.value != 2.2):
            raise SystemExit(
                "--rate/--source/--value configure hand-built flows; a "
                "registry model's coefficients are its term rates "
                "(registry defaults) — drop them or use --flow")
        nonlinear = args.model != "diffusion"
        if nonlinear and args.impl in ("pallas", "active_fused"):
            raise SystemExit(
                f"--impl={args.impl} is a linear-stencil kernel; "
                f"--model={args.model} has nonlinear/coupled terms. "
                "Eligible: --impl=xla/auto (dense lowering), composed "
                "(k forced to 1, warns), active (term-derived activity "
                "predicate)")
        if nonlinear and args.ensemble_impl in ("pipeline", "active",
                                                "active_fused"):
            raise SystemExit(
                f"--ensemble-impl={args.ensemble_impl} batches "
                "all-Diffusion lanes; nonlinear IR models run the "
                "vmapped general lowering — use --ensemble-impl=xla")
        if args.impl == "composed" and args.substeps > 1 and nonlinear:
            # allowed, but the degeneration is loud: the tap table is a
            # linear object, so composed falls to k=1 (a RuntimeWarning
            # fires at build). Keep the combo legal — the warning is
            # the documented contract — but say it up front on the CLI.
            print("note: nonlinear terms do not compose; "
                  "--impl=composed will run k=1 iterated passes",
                  file=sys.stderr)
    args.flow = args.flow if args.flow is not None else "exponencial"
    if not sharded and args.halo_depth != 1:
        raise SystemExit(
            "--halo-depth applies to sharded execution; add --mesh=LxC "
            "or --rectangular=LxC")
    if sharded and args.substeps != 1:
        raise SystemExit(
            "--substeps applies to the serial executor; for sharded runs "
            "use --halo-depth for the analogous fusion")
    if args.rectangular is not None and args.mesh is not None:
        raise SystemExit(
            "--rectangular IS the mesh (a lines x columns block "
            "decomposition); drop --mesh")
    if args.executor == "gspmd":
        if args.rectangular is not None:
            raise SystemExit(
                "--rectangular always runs the explicit block-halo "
                "ShardMapExecutor (its owner map IS that mesh); for the "
                "GSPMD path use --mesh=LxC --executor=gspmd")
        if args.mesh is None:
            raise SystemExit("--executor=gspmd is a sharded path; add "
                             "--mesh=LxC")
        if args.impl in ("pallas", "composed"):
            raise SystemExit(
                "--executor=gspmd runs the global XLA step (XLA inserts "
                "the collectives); the Pallas/composed kernels need "
                "--executor=shardmap")
        if args.halo_depth != 1 or args.compute_dtype is not None:
            raise SystemExit(
                "--halo-depth/--compute-dtype tune the explicit "
                "ShardMapExecutor; --executor=gspmd delegates both to XLA")
    if args.executor == "shardmap" and not sharded:
        raise SystemExit("--executor=shardmap needs --mesh=LxC")
    if args.executor == "serial" and sharded:
        raise SystemExit("--executor=serial contradicts "
                         "--mesh/--rectangular")
    if args.channels != 2 and args.flow != "coupled":
        raise SystemExit("--channels applies to --flow=coupled")
    if args.serve:
        if args.ensemble is not None:
            raise SystemExit(
                "--serve runs the always-on async loop over an arrival "
                "process; --ensemble runs one synchronous batch — pick "
                "one")
        if sharded:
            raise SystemExit(
                "--serve batches whole scenarios through the ensemble "
                "engine (the batch axis replaces the mesh axes); drop "
                "--mesh/--rectangular")
        if args.chaos is not None:
            raise SystemExit(
                "--chaos drives the single-run supervised path; serve-"
                "mode chaos is driven from the API (resilience.inject "
                "armed around run_soak — see bench.bench_service)")
        if args.checkpoint_dir is not None or args.output is not None:
            raise SystemExit(
                "--serve does not compose with --checkpoint-dir/"
                "--output (supervised/dump runs are single-scenario)")
        if args.impl != "auto":
            raise SystemExit(
                "--impl selects the single-run kernel; serve mode uses "
                "--ensemble-impl=xla|pipeline|active|active_fused")
        if args.serve_scenarios < 1:
            raise SystemExit(
                f"--serve-scenarios={args.serve_scenarios} needs >= 1")
        if args.serve_services < 1:
            raise SystemExit(
                f"--serve-services={args.serve_services} needs >= 1")
        if args.max_queue < 1:
            raise SystemExit(f"--max-queue={args.max_queue} needs >= 1")
        if args.arrival_rate is not None and args.arrival_rate < 0:
            raise SystemExit(
                f"--arrival-rate={args.arrival_rate} must be >= 0 "
                "(0 = open throttle)")
        if args.deadline_s is not None and args.deadline_s <= 0:
            raise SystemExit(
                f"--deadline-s={args.deadline_s} must be positive")
        if (args.residency_budget is None) != (args.hibernate_dir is None):
            raise SystemExit(
                "scenario tiering needs BOTH --residency-budget and "
                "--hibernate-dir (or neither)")
        if args.serve_member_env and args.serve_transport not in (
                "process", "tcp"):
            raise SystemExit(
                "--serve-member-env pins a spawned CHILD's environment "
                "(device visibility); it needs "
                "--serve-transport=process or =tcp")
        if args.residency_budget is not None \
                and args.residency_budget < 1:
            raise SystemExit(
                f"--residency-budget={args.residency_budget} needs "
                ">= 1 byte")
    else:
        for flag, val, default in (
                ("--arrival-rate", args.arrival_rate, None),
                ("--deadline-s", args.deadline_s, None),
                ("--max-queue", args.max_queue, 64),
                ("--serve-scenarios", args.serve_scenarios, 64),
                ("--serve-services", args.serve_services, 1),
                ("--serve-transport", args.serve_transport, "inproc"),
                ("--serve-member-env", args.serve_member_env or None, None),
                ("--residency-budget", args.residency_budget, None),
                ("--hibernate-dir", args.hibernate_dir, None),
                ("--status", args.status, None),
                ("--status-interval-s", args.status_interval_s, 5.0),
                ("--status-port", args.status_port, None)):
            if val != default:
                raise SystemExit(
                    f"{flag} configures the always-on serving loop; "
                    "add --serve")
    if args.ensemble is not None:
        if args.ensemble < 1:
            raise SystemExit(f"--ensemble={args.ensemble} needs B >= 1")
        if args.chaos is not None:
            raise SystemExit(
                "--chaos drives the single-run supervised path; it does "
                "not compose with --ensemble (drive ensemble chaos from "
                "the API: resilience.inject + EnsembleScheduler("
                "retry='solo'))")
        if sharded:
            raise SystemExit(
                "--ensemble batches B whole scenarios into one device "
                "program (the batch axis replaces the mesh axes); drop "
                "--mesh/--rectangular")
        if args.checkpoint_dir is not None:
            raise SystemExit(
                "--ensemble does not compose with --checkpoint-dir "
                "(supervised/checkpointed runs are single-scenario)")
        if args.output is not None:
            raise SystemExit(
                "--output writes one scenario's dump; it does not "
                "compose with --ensemble")
        if args.impl != "auto":
            raise SystemExit(
                "--impl selects the single-run kernel; ensemble runs "
                "use --ensemble-impl=xla|pipeline|active|active_fused")
    elif args.ensemble_impl != "xla" and not args.serve:
        raise SystemExit("--ensemble-impl applies to ensemble/serve "
                         "runs; add --ensemble=B or --serve")
    if args.ensemble_mesh is not None:
        if args.ensemble is None and not args.serve:
            raise SystemExit(
                "--ensemble-mesh shards the ensemble batch axis over "
                "devices; add --ensemble=B or --serve (for the spatial "
                "mesh of a single run use --mesh=LxC)")
        if args.ensemble_impl != "xla":
            raise SystemExit(
                "--ensemble-mesh requires --ensemble-impl=xla (the "
                "other engines carry per-lane state the batch-axis "
                "sharding contract does not cover)")
    if args.owner_of is not None and args.rectangular is None:
        raise SystemExit(
            "--owner-of reports the 2-D block owner map; add "
            "--rectangular=LxC")
    if args.compute_dtype is not None and args.impl == "xla":
        raise SystemExit(
            "--compute-dtype tunes the Pallas kernels' interior math; "
            "--impl=xla never runs them (use --impl=pallas or auto)")
    args.rect_grid = (_parse_grid2(args.rectangular, "--rectangular")
                      if args.rectangular is not None else None)

    space, model = _build_model(args)
    if args.serve:
        return _run_serve(args, space, model)
    if args.ensemble is not None:
        return _run_ensemble(args, space, model)
    executor = _build_executor(args, model)
    steps = args.steps if args.steps is not None else model.num_steps
    initial = {k: float(space.total(k)) for k in space.values}

    t0 = _time.perf_counter()
    events = []
    failure = None
    out = None
    ranks = getattr(executor, "comm_size", 1)
    if args.async_checkpoints and args.checkpoint_layout != "sharded":
        raise SystemExit(
            "--async-checkpoints requires --checkpoint-layout=sharded")
    if args.checkpoint_dir is None and (args.async_checkpoints
                                        or args.checkpoint_layout != "full"):
        raise SystemExit(
            "--checkpoint-layout/--async-checkpoints configure "
            "checkpointing; add --checkpoint-dir=DIR")
    if args.keyframe_every is not None:
        if args.checkpoint_layout != "delta":
            raise SystemExit(
                "--keyframe-every sets the delta chain's keyframe "
                "cadence; it does nothing for "
                f"--checkpoint-layout={args.checkpoint_layout} (use "
                "--checkpoint-layout=delta)")
        if args.keyframe_every < 1:
            raise SystemExit(
                f"--keyframe-every={args.keyframe_every} must be >= 1 "
                "(1 = every save is a keyframe)")
    chaos_plan = _parse_chaos(args)
    injected = 0
    if args.checkpoint_dir or chaos_plan is not None:
        import contextlib

        from .io import CheckpointManager
        from .resilience import SimulationFailure, supervised_run
        from .resilience import inject

        # --chaos without --checkpoint-dir still runs SUPERVISED (the
        # in-memory rollback path); a manager adds durability on top
        manager = (CheckpointManager(args.checkpoint_dir,
                                     layout=args.checkpoint_layout,
                                     async_writes=args.async_checkpoints,
                                     keyframe_every=(args.keyframe_every
                                                     or 8))
                   if args.checkpoint_dir else None)
        arm = (inject.armed(chaos_plan) if chaos_plan is not None
               else contextlib.nullcontext())
        arm_state = None
        try:
            with arm as st:
                arm_state = st
                res = supervised_run(
                    model, space, manager,
                    steps=steps, every=args.checkpoint_every,
                    max_failures=args.max_failures, executor=executor,
                    on_event=events.append)
        except SimulationFailure as e:
            failure = str(e)
            events = e.events
        else:
            out = res.space
            # run-global baseline: survives resume via the checkpoint
            initial = res.initial_totals or initial
        # the fired-fault log outlives disarm — reported even when the
        # run failed (the row must say what chaos was actually injected)
        injected = len(arm_state.fired) if arm_state is not None else 0
    else:
        # conservation judged HERE (status line + exit code), not raised
        # mid-flight — the CLI's contract is a conserved=false record
        out, report = model.execute(space, executor, steps=steps,
                                    check_conservation=False)
        ranks = report.comm_size
    wall = _time.perf_counter() - t0

    # the kernel that ACTUALLY ran (what "auto" resolved to) — without
    # this the user could believe they benchmarked a configuration that
    # never ran. --rectangular IS a
    # sharded run (a lines x columns block mesh), so the backend label
    # and the halo_depth/substeps applicability follow `sharded`, not
    # --mesh alone.
    impl_used = getattr(executor, "last_impl", None)
    run_cfg = {"impl": impl_used,
               "halo_depth": args.halo_depth if sharded else None,
               "substeps": args.substeps if not sharded else None,
               "rectangular": args.rectangular}

    if failure is not None:
        result = {"backend": "sharded" if sharded else "serial",
                  "ranks": ranks, "steps": steps, "conserved": False,
                  "error": failure, "recovered_failures": len(events),
                  "injected_faults": injected,
                  "wall_s": wall, **run_cfg}
        print(json.dumps(result) if args.json
              else f"FAILED after {len(events)} failure(s): {failure}")
        return 1

    if args.output:
        if args.rectangular:
            # per-BLOCK dump + master merge following the executed
            # lines x columns mesh (the output stage the reference's 2-D
            # variant left commented out, ModelRectangular.hpp:235-270)
            merged = model.write_output(args.output, out)
        else:
            from .io import write_output

            merged = write_output(args.output, out, comm_size=max(ranks, 1))
        print(f"output written to {merged}", file=sys.stderr)
    if args.owner_of is not None:
        x, y = (int(v) for v in args.owner_of.split(","))
        print(json.dumps({
            "cell": [x, y],
            "owner": model.owner_of(x, y, out),
            "partitions": [p.describe() for p in model.partitions(out)],
        }))
    if args.trace:
        get_tracer().export_chrome(args.trace)
        print(f"trace written to {args.trace}", file=sys.stderr)

    # full-run drift against the run-global initial totals (a per-chunk
    # report would understate drift on checkpointed runs). IR models
    # are judged through their conservation VIEW: declared source/sink
    # drift is physics, reconciled against the integrated budgets —
    # raw per-channel drift would mislabel every --model run VIOLATED
    final = {k: float(out.total(k)) for k in out.values}
    viewfn = getattr(model, "conservation_view", None)
    vi = viewfn(initial) if viewfn is not None else initial
    vf = viewfn(final) if viewfn is not None else final
    err = max(abs(float(vf[k]) - float(vi[k])) for k in vi)
    thresh = model.conservation_threshold(space, initial_totals=initial)
    result = {
        "backend": "sharded" if sharded else "serial",
        "ranks": ranks,
        "steps": steps,
        "initial": initial,
        "final": final,
        "conservation_error": err,
        "conserved": bool(err <= thresh),
        "recovered_failures": len(events),
        "injected_faults": injected,
        "wall_s": wall,
        **run_cfg,
    }
    if args.json:
        print(json.dumps(result, allow_nan=False))
    else:
        status = "CONSERVED" if result["conserved"] else "VIOLATED"
        print(f"backend={result['backend']} impl={impl_used} "
              f"ranks={result['ranks']} "
              f"steps={steps} initial={result['initial']} "
              f"final={result['final']} |delta|={err:.3e} {status} "
              f"({wall:.2f}s, {len(events)} recovered failures)")
    return 0 if result["conserved"] else 1


def cmd_info(args) -> int:
    import jax

    from . import __version__

    from .utils import chip_peaks

    info = {
        "version": __version__,
        "jax_backend": jax.default_backend(),
        "devices": [f"{d.platform}:{d.id}" for d in jax.devices()],
        "device_kind": getattr(jax.devices()[0], "device_kind", None),
        "chip_peaks": chip_peaks(),  # None for unknown parts
        "cpu_devices": len(jax.devices("cpu")),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
    }
    try:
        from .native import build_native

        info["native_library"] = build_native()
    except (OSError, RuntimeError, subprocess.SubprocessError) as e:
        # toolchain optional: no cmake/ninja (OSError), a failed
        # configure/build (SubprocessError), or a loader refusal
        # (RuntimeError) all mean "no native library here"
        info["native_library"] = f"unavailable: {e}"
    print(json.dumps(info, indent=2 if not args.json else None))
    return 0


def main(argv: Optional[list[str]] = None) -> int:
    # `analyze` forwards its whole tail to the analysis CLI (argparse
    # REMAINDER cannot pass through leading --flags, so peel it here)
    tail = sys.argv[1:] if argv is None else list(argv)
    if tail[:1] == ["analyze"]:
        from .analysis import main as analyze_main
        return analyze_main(tail[1:])

    ap = argparse.ArgumentParser(
        prog="python -m mpi_model_tpu.cli",
        description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)

    run = sub.add_parser("run", help="run a simulation (reference "
                         "scenario by default)")
    # reference defaults: Main.cpp:25,32-33 / Defines.hpp:5-6
    run.add_argument("--dimx", type=int, default=100)
    run.add_argument("--dimy", type=int, default=100)
    run.add_argument("--init", type=float, default=1.0)
    run.add_argument("--flow", default=None,
                     choices=["exponencial", "diffusion", "coupled"],
                     help="hand-built flow scenario (default: the "
                     "reference's exponencial run); mutually exclusive "
                     "with --model")
    run.add_argument("--model", default=None,
                     choices=["diffusion", "gray_scott", "sir",
                              "predator_prey"],
                     help="run a registered Flow IR model (ISSUE 11): "
                     "declarative terms lowered once for every engine "
                     "— 'gray_scott' reaction-diffusion, 'sir' "
                     "contagion, 'predator_prey' Lotka-Volterra, or "
                     "the linear 'diffusion' re-expression (bitwise "
                     "with --flow=diffusion). Composes with --impl, "
                     "--ensemble and --serve; conservation is judged "
                     "by per-term budget reconciliation")
    run.add_argument("--channels", type=int, default=2,
                     help="channel count for --flow=coupled (a CHAIN of "
                     "N diffusing channels, each but the last shedding "
                     "mass modulated by the next — the config-4 "
                     "multi-attribute workload shape)")
    run.add_argument("--source", default="19,3",
                     help="point-flow source cell x,y")
    run.add_argument("--rate", type=float, default=0.1)
    run.add_argument("--value", type=float, default=2.2,
                     help="frozen snapshot value of the point source")
    run.add_argument("--time", type=float, default=10.0)
    run.add_argument("--time-step", type=float, default=0.2)
    run.add_argument("--steps", type=int, default=1,
                     help="step count (default 1 = the reference's live "
                     "behavior; pass --steps=-1 for time/time_step)")
    run.add_argument("--dtype", default="float32",
                     choices=["float32", "float64", "bfloat16"])
    run.add_argument("--impl", default="auto",
                     choices=["xla", "pallas", "auto", "composed",
                              "active", "active_fused"],
                     help="field-flow kernel: 'composed' runs the "
                     "k-step composed tap filter (uniform-rate "
                     "Diffusion only; pair with --substeps=k serially "
                     "or --halo-depth=k sharded); 'active' runs the "
                     "active-tile engine (compute only tiles whose "
                     "ring-1 neighborhood holds mass — bitwise-exact "
                     "skipping for uniform-rate Diffusion, dense "
                     "fallback above the activity threshold); "
                     "'active_fused' runs the fused Pallas active "
                     "kernel (scalar-prefetched sparse streaming with "
                     "in-kernel activity flags; --substeps=k composes "
                     "k flow steps per tile-resident pass)")
    run.add_argument("--compute-dtype", default=None,
                     choices=["float32", "bfloat16"],
                     help="Pallas interior-tile math dtype (default f32; "
                     "bfloat16 trades interior precision for VPU "
                     "throughput; the near-ring exact path stays f32)")
    run.add_argument("--substeps", type=int, default=1,
                     help="fused steps per compiled call (serial executor)")
    run.add_argument("--compile-cache", default=None, metavar="DIR|off",
                     help="arm the JAX persistent compilation cache at "
                     "DIR (created if missing): every kernel/runner "
                     "compile on this machine is paid once and reused "
                     "across processes — a restarted run or service "
                     "skips straight to execution (ROADMAP direction "
                     "5). Ensemble/serve runs arm a per-user default "
                     "cache even without this flag; pass 'off' to "
                     "disable that explicitly")
    run.add_argument("--ensemble", type=int, default=None, metavar="B",
                     help="step B independent copies of the scenario as "
                     "ONE batched device program through the ensemble "
                     "serving stack (bucketed scheduler + per-scenario "
                     "conservation); reports scenarios/s, batch "
                     "occupancy and compile-cache hits")
    run.add_argument("--ensemble-impl", default="xla",
                     choices=["xla", "pipeline", "active",
                              "active_fused"],
                     help="ensemble interior engine: 'xla' (vmapped "
                     "parametric step — any flows, per-scenario rates), "
                     "'pipeline' (the pipelined-window Pallas kernel "
                     "per lane — all-Diffusion, one shared rate, grid "
                     "divisible into 16x128 strips), 'active' (the "
                     "active-tile engine per lane — all-Diffusion, "
                     "per-scenario rates and per-scenario activity), "
                     "or 'active_fused' (the fused Pallas active "
                     "kernel per lane)")
    run.add_argument("--serve", action="store_true",
                     help="drive the always-on async serving loop "
                     "(ISSUE 9): --serve-scenarios copies of the "
                     "configured scenario arrive open-loop at "
                     "--arrival-rate/s against a bounded admission "
                     "queue; reports sustained scenarios/s, p50/p99 "
                     "queue latency, occupancy and the complete "
                     "served/shed/expired/failed ledger")
    run.add_argument("--serve-scenarios", type=int, default=64,
                     metavar="N",
                     help="scenarios offered to the serving loop "
                     "(default 64)")
    run.add_argument("--serve-services", type=int, default=1,
                     metavar="N",
                     help="shard the arrival stream over N supervised "
                     "always-on services (ISSUE 10 FleetSupervisor: "
                     "structure-affine routing, member fencing + "
                     "restart, per-member attribution); default 1 = "
                     "the single async loop")
    run.add_argument("--serve-transport", default="inproc",
                     choices=("inproc", "process", "tcp"),
                     help="fleet member transport (ISSUE 13): "
                     "'inproc' (default) runs members as in-process "
                     "services; 'process' spawns each member as its "
                     "own OS process behind the CRC-framed wire "
                     "protocol (heartbeat health, fence + respawn on "
                     "a killed member, per-member device pinning via "
                     "the child environment); 'tcp' (ISSUE 20) is "
                     "'process' over an authenticated TCP socket — a "
                     "per-member shared secret rides the child env "
                     "(MMTPU_WIRE_SECRET, never argv) and both sides "
                     "run an HMAC challenge-response before the first "
                     "frame, with jitter-tolerant deadline defaults")
    run.add_argument("--serve-member-env", action="append", default=None,
                     metavar="KEY=VAL",
                     help="with --serve-transport=process: lay KEY=VAL "
                     "over every spawned member's environment before "
                     "exec (repeatable) — the device-pinning contract "
                     "(e.g. JAX_PLATFORMS, CUDA_VISIBLE_DEVICES, "
                     "XLA_FLAGS); per-slot pins are API-level "
                     "(FleetSupervisor(member_env=[{...}, ...]))")
    run.add_argument("--ensemble-mesh", default=None, metavar="B[xS]",
                     help="shard the ensemble batch axis over a device "
                     "mesh (ISSUE 16): B = scenario lanes split over B "
                     "devices; BxS adds an S-way space axis inside "
                     "every lane (2-D batch x space layout). Dispatches "
                     "pad to (bucket x B) with inert zero scenarios; "
                     "with --serve-transport=process each member "
                     "resolves the mesh against its own (possibly "
                     "--serve-member-env-pinned) devices. Requires "
                     "--ensemble-impl=xla")
    run.add_argument("--arrival-rate", type=float, default=None,
                     metavar="HZ",
                     help="open-loop arrival rate in scenarios/s "
                     "(unset/0 = open throttle: submit as fast as "
                     "admission allows)")
    run.add_argument("--deadline-s", type=float, default=None,
                     help="per-ticket deadline: a scenario still "
                     "queued past this expires with a complete "
                     "FailureEvent instead of being served late")
    run.add_argument("--max-queue", type=int, default=64,
                     help="admission-queue bound: submissions beyond "
                     "this shed with ServiceOverloaded (default 64)")
    run.add_argument("--residency-budget", type=int, default=None,
                     metavar="BYTES",
                     help="scenario-tiering residency budget (ISSUE "
                     "14): scenario state bytes allowed resident; "
                     "overload beyond it HIBERNATES scenarios to "
                     "--hibernate-dir (keyframe+delta chains, TJ1 "
                     "lifecycle journal) and wakes them as capacity "
                     "frees — sheds happen only when the hibernation "
                     "tier itself is exhausted")
    run.add_argument("--hibernate-dir", default=None, metavar="DIR",
                     help="vault directory for the hibernation tier "
                     "(required with --residency-budget)")
    run.add_argument("--mesh", default=None,
                     help="LxC device mesh for sharded execution "
                     "(e.g. 4x1, 2x4); omit for serial")
    run.add_argument("--executor", default="auto",
                     choices=["auto", "serial", "shardmap", "gspmd"],
                     help="'auto' = serial without --mesh, shardmap with "
                     "it; 'gspmd' = AutoShardedExecutor (global XLA step, "
                     "XLA inserts the halo collectives — runs ANY flow, "
                     "including footprint='unknown' user flows the "
                     "explicit shardmap path refuses)")
    run.add_argument("--rectangular", default=None, metavar="LxC",
                     help="run ModelRectangular over a lines x columns "
                     "2-D block mesh (the reference's rectangular demo); "
                     "--output writes per-BLOCK rank files")
    run.add_argument("--owner-of", default=None, metavar="X,Y",
                     help="with --rectangular: print the block-owner "
                     "rank of global cell (X,Y) and the partition map")
    run.add_argument("--halo-depth", type=int, default=1,
                     help="ghost-ring depth d: one exchange per d steps")
    run.add_argument("--checkpoint-dir", default=None)
    run.add_argument("--checkpoint-every", type=int, default=1)
    run.add_argument("--checkpoint-layout", default="full",
                     choices=("full", "sharded", "delta"),
                     help="'sharded' = per-process O(shard) files, no "
                          "full-grid gather (io/sharded.py); 'delta' = "
                          "incremental chain: periodic keyframes + "
                          "dirty-tile delta records, restore replays "
                          "the chain (io/delta.py) — a snapshot costs "
                          "O(dirty tiles), not O(grid)")
    run.add_argument("--keyframe-every", type=int, default=None,
                     help="delta layout: records per chain segment "
                          "(1 keyframe + N-1 deltas; default 8; 1 = "
                          "every save is a keyframe)")
    run.add_argument("--async-checkpoints", action="store_true",
                     help="overlap checkpoint writes with compute "
                          "(requires --checkpoint-layout=sharded)")
    run.add_argument("--max-failures", type=int, default=3)
    run.add_argument("--chaos", default=None, metavar="KIND[:N]",
                     help="arm a deterministic fault plan against the "
                     "supervised run and prove it heals: exc|nan inject "
                     "an executor exception / NaN state at chunk N, "
                     "halo perturbs one ghost exchange (sharded runs), "
                     "torn tears the checkpoint written at step N "
                     "(with --checkpoint-dir); the run reports "
                     "injected_faults and recovered_failures")
    run.add_argument("--chaos-seed", type=int, default=0,
                     help="seed for the fault plan's derived "
                     "perturbation values (reproducible chaos)")
    run.add_argument("--output", default=None,
                     help="write the reference-parity per-rank dump + "
                     "merged output file to this directory")
    run.add_argument("--trace", default=None,
                     help="write a Chrome trace of the run's phases "
                     "(serve mode: the merged multi-process ticket "
                     "trace, member spans labeled m<slot>g<gen>)")
    run.add_argument("--status", default=None, metavar="PATH",
                     help="dump the unified telemetry-plane snapshot "
                     "(obs.fleet_snapshot: serving stats + per-member "
                     "cuts + tiering residency + tracer rollups + "
                     "flight-recorder ledger, one versioned JSON "
                     "document) to PATH — during a --serve soak every "
                     "--status-interval-s, plus a final cut; validate "
                     "or scrape it with python -m mpi_model_tpu.obs. "
                     "Also arms on-disk flight-recorder dumps under "
                     "PATH.flight.d/")
    run.add_argument("--status-interval-s", type=float, default=5.0,
                     metavar="S",
                     help="seconds between --status snapshot dumps "
                     "during a soak (default 5)")
    run.add_argument("--status-port", type=int, default=None,
                     metavar="PORT",
                     help="with --serve: serve the live telemetry "
                     "plane over HTTP for the soak's duration (ISSUE "
                     "20) — GET /metrics is a Prometheus text "
                     "exposition of the serving counters, GET "
                     "/snapshot the full obs.fleet_snapshot JSON "
                     "document; binds 127.0.0.1:PORT (0 = ephemeral)")
    run.add_argument("--json", action="store_true")
    run.set_defaults(fn=cmd_run)

    info = sub.add_parser("info", help="print device/backend info")
    info.add_argument("--json", action="store_true")
    info.set_defaults(fn=cmd_info)

    sub.add_parser(
        "analyze", add_help=False,
        help="static analysis: AST lint + jaxpr contract audit "
        "(all flags pass through to python -m mpi_model_tpu.analysis)")

    args = ap.parse_args(argv)
    steps = getattr(args, "steps", None)
    if steps == -1:
        args.steps = None  # -1 = the time/time_step schedule
    elif steps is not None and steps < -1:
        # anything else negative would fail deep inside lax.scan with an
        # opaque shape error — reject it at the flag surface
        raise SystemExit(
            f"--steps={steps} is invalid: pass a non-negative step count "
            "or -1 for the time/time_step schedule")
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
