"""Chip smoke: drive the system's main paths once on a TPU and check them.

``python chip_smoke.py`` needs one chip and runs five phases in this one
process (it spawns nothing: a chip belongs to one process):

- ``dense``: ``Model([Diffusion(0.1)])`` on a 16384² bf16 grid through
  ``SerialExecutor(step_impl="auto", substeps=4)`` for 32 steps; the
  Pallas kernel must run, and one fused chunk at that geometry is held
  to the XLA step;
- ``field``: the ``__graft_entry__`` flagship model (Diffusion + Coupled
  + Diffusion, two channels) at 8192² f32 through
  ``make_step(impl="auto")``; the field kernel must run, held to the XLA
  step;
- ``ir``: Gray-Scott from the Flow IR registry at 4096² through
  ``model.execute`` with the per-term budget reconciliation on;
- ``serve``: ``EnsembleService(model, steps=8)`` answers 8 submitted
  2048² Diffusion scenarios with different rates; one lane is held to a
  ``SerialExecutor`` run;
- ``reference``: the reference scenario at f32, ``steps=1``: total 10000
  and source cell 0.78.

``python chip_smoke.py --chips 4`` runs only the sharded path and its
comparator: Diffusion on a 24576² f32 grid over a 2×2 mesh of four
chips (``ShardMapExecutor(step_impl="pallas", halo_depth=4)``, 16 steps)
against the same run on one chip.

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}``. Without a TPU, or when any phase
fails, the script exits non-zero and prints no result. Data comes from
``--seed``. The phases are plain functions of a size, so
``tests/test_chip_smoke.py`` runs them at tiny sizes on the CPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

#: max |err| allowed against the XLA step, by dtype, per fused step
#: (bench.py's gate tiers)
STEP_TOL = {"float32": 1e-5, "bfloat16": 0.04}


def _device_line(n: int) -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": n}


def _peak_bytes():
    """Process-wide device peak so far (None where the backend keeps no
    memory statistics, as the CPU does)."""
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _random_space(grid: int, channels, dtype, seed: int):
    """A grid of values uniform in [0.5, 1.5), made on the device."""
    import jax
    import jax.numpy as jnp

    from mpi_model_tpu import CellularSpace

    keys = jax.random.split(jax.random.key(seed), len(channels))
    values = {c: jax.random.uniform(k, (grid, grid), jnp.float32,
                                    0.5, 1.5).astype(dtype)
              for c, k in zip(channels, keys)}
    return CellularSpace(values=values, dim_x=grid, dim_y=grid)


def _max_err(a, b) -> float:
    import jax.numpy as jnp

    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def _timed(fn):
    """(result, seconds) with the result's device work finished."""
    import jax

    t = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t


def _interpreted(space) -> bool:
    from mpi_model_tpu.ops.pallas_stencil import resolve_interpret

    return resolve_interpret(next(iter(space.values.values())))


def _line(phase, size, dtype, impl, interpret, first_s, run_s, check,
          **extra) -> dict:
    # compile seconds: the first call (compile + run) less a warm call
    return {"phase": phase, "size": size, "dtype": dtype, "impl": impl,
            "interpret": interpret,
            "compile_s": round(first_s - run_s, 3), "run_s": run_s,
            "check": check, **extra, "peak_bytes_in_use": _peak_bytes()}


def phase_dense(grid: int = 16384, steps: int = 32, substeps: int = 4,
                dtype: str = "bfloat16", seed: int = 0) -> dict:
    import jax

    from mpi_model_tpu import Diffusion, Model
    from mpi_model_tpu.models.model import SerialExecutor

    space = _random_space(grid, ("value",), dtype, seed)
    model = Model([Diffusion(0.1)])
    ex = SerialExecutor(step_impl="auto", substeps=substeps)
    run = lambda: model.execute(space, ex, steps=steps)[0].values  # noqa: E731
    _, first = _timed(run)
    _, warm = _timed(run)
    if ex.last_impl != "pallas":
        raise AssertionError(f"auto ran {ex.last_impl!r}, not the Pallas "
                             "kernel")
    # one fused chunk at this geometry against substeps XLA steps
    fused = model.make_step(space, impl="auto", substeps=substeps)
    xla = model.make_step(space, impl="xla", substeps=substeps)
    got = jax.jit(fused)(dict(space.values))["value"]
    err = _max_err(got, jax.jit(xla)(dict(space.values))["value"])
    tol = STEP_TOL[dtype] * substeps
    if not err <= tol:
        raise AssertionError(f"fused chunk vs XLA: max|err|={err} > {tol}")
    return _line("dense", f"{grid}x{grid}", dtype, ex.last_impl,
                 _interpreted(space), first, warm,
                 f"fused chunk vs XLA max|err|={err} <= {tol}",
                 steps=steps, substeps=substeps)


def phase_field(grid: int = 8192, seed: int = 1) -> dict:
    import jax

    from __graft_entry__ import flagship_model

    space = _random_space(grid, ("a", "b"), "float32", seed)
    model = flagship_model()
    step = model.make_step(space, impl="auto")
    if step.impl != "pallas":
        raise AssertionError(f"auto chose {step.impl!r}, not the field "
                             "kernel")
    values = dict(space.values)
    jstep = jax.jit(step)
    got, first = _timed(lambda: jstep(values))
    got, warm = _timed(lambda: jstep(values))
    want = jax.jit(model.make_step(space, impl="xla"))(values)
    err = max(_max_err(got[c], want[c]) for c in ("a", "b"))
    tol = STEP_TOL["float32"]
    if not err <= tol:
        raise AssertionError(f"field kernel vs XLA: max|err|={err} > {tol}")
    return _line("field", f"{grid}x{grid}x2", "float32", step.impl,
                 _interpreted(space), first, warm,
                 f"field kernel vs XLA max|err|={err} <= {tol}")


def phase_ir(grid: int = 4096, steps: int = 32) -> dict:
    import jax.numpy as jnp
    import numpy as np

    import mpi_model_tpu as mm
    from mpi_model_tpu.models.model import SerialExecutor

    model, space = mm.build_model("gray_scott", grid)
    ex = SerialExecutor()
    # check_conservation=True: the per-term budget reconciliation raises
    # on a violated or mis-signed budget
    run = lambda: model.execute(space, ex, steps=steps)  # noqa: E731
    _, first = _timed(lambda: run()[0].values)
    (out, rep), warm = _timed(run)
    finite = all(bool(jnp.all(jnp.isfinite(v))) for v in out.values.values())
    if not finite:
        raise AssertionError("Gray-Scott produced non-finite values")
    budgets = model.budget_totals(out)
    return _line("ir", f"{grid}x{grid}", str(space.dtype), ex.last_impl,
                 False, first, warm,
                 f"budget reconciliation passed, finite; budgets="
                 f"{ {k: float(np.float64(v)) for k, v in budgets.items()} }",
                 steps=steps)


def phase_serve(grid: int = 2048, scenarios: int = 8, steps: int = 8,
                seed: int = 2) -> dict:
    import mpi_model_tpu as mm
    from mpi_model_tpu import Diffusion, Model

    rates = [0.05 + 0.02 * i for i in range(scenarios)]
    models = [Model([Diffusion(r)]) for r in rates]
    spaces = [_random_space(grid, ("value",), "float32", seed + i)
              for i in range(scenarios)]
    svc = mm.EnsembleService(models[0], steps=steps)

    def serve():
        tickets = [svc.submit(s, model=m) for s, m in zip(spaces, models)]
        svc.flush()
        return [svc.result(t)[0].values["value"] for t in tickets]

    _, first = _timed(serve)
    outs, warm = _timed(serve)
    want, _ = models[-1].execute(spaces[-1], steps=steps)
    err = _max_err(outs[-1], want.values["value"])
    tol = STEP_TOL["float32"] * steps
    if not err <= tol:
        raise AssertionError(f"served lane vs SerialExecutor: "
                             f"max|err|={err} > {tol}")
    return _line("serve", f"{scenarios}x{grid}x{grid}", "float32",
                 svc.scheduler.executor.impl, False, first, warm,
                 f"lane {scenarios - 1} vs SerialExecutor max|err|={err} "
                 f"<= {tol}", scenarios_per_s=scenarios / warm)


def phase_reference() -> dict:
    import jax.numpy as jnp
    import numpy as np

    import mpi_model_tpu as mm

    from mpi_model_tpu.models.model import SerialExecutor

    space = mm.CellularSpace.create(100, 100, 1.0, dtype=jnp.float32)
    model = mm.Model(mm.Exponencial(mm.Cell(19, 3, mm.Attribute(99, 2.2)),
                                    0.1), 10.0, 0.2)
    ex = SerialExecutor()
    run = lambda: model.execute(space, ex, steps=1)[0].values  # noqa: E731
    _, first = _timed(run)
    out, warm = _timed(run)
    v = np.asarray(out["value"])
    total, src = float(v.astype(np.float64).sum()), float(v[19, 3])
    # f32: 1e-2 per 1e6 cells, at least one ulp of the total
    tol = max(1e-2 * v.size / 1e6, float(np.spacing(np.float32(1e4))))
    if not (abs(total - 1e4) <= tol and abs(src - 0.78) <= 1e-6):
        raise AssertionError(f"reference: total={total} (tol {tol}), "
                             f"source cell={src}")
    return _line("reference", "100x100", "float32", ex.last_impl, False,
                 first, warm, f"total={total} within {tol} of 10000, "
                 f"source cell={src}")


def phase_sharded(grid: int = 24576, steps: int = 16, depth: int = 4,
                  seed: int = 3, devices=None) -> dict:
    """The 2×2-mesh ShardMapExecutor run against the same run on one
    chip, on values and totals."""
    import jax

    from mpi_model_tpu import Diffusion, Model
    from mpi_model_tpu.models.model import SerialExecutor
    from mpi_model_tpu.parallel import ShardMapExecutor, make_mesh_2d

    devices = list(devices if devices is not None else jax.devices())[:4]
    if len(devices) < 4:
        raise RuntimeError(f"the sharded phase needs 4 devices, have "
                           f"{len(devices)}")
    model = Model([Diffusion(0.1)])
    with jax.default_device(devices[0]):
        space = _random_space(grid, ("value",), "float32", seed)
        one = SerialExecutor(step_impl="pallas", substeps=depth)
        (want, wrep), one_s = _timed(
            lambda: model.execute(space, one, steps=steps))
        ex = ShardMapExecutor(make_mesh_2d(2, 2, devices=devices),
                              step_impl="pallas", halo_depth=depth)
        run = lambda: model.execute(space, ex, steps=steps)  # noqa: E731
        _, first = _timed(run)
        (got, rep), warm = _timed(run)
    shard_devs = {s.device for s in got.values["value"].addressable_shards}
    if len(shard_devs) != 4:
        raise AssertionError(f"shards sit on {len(shard_devs)} devices")
    if ex.last_impl != "pallas" or one.last_impl != "pallas":
        raise AssertionError(f"impls: sharded {ex.last_impl!r}, one chip "
                             f"{one.last_impl!r}")
    err = _max_err(jax.device_put(got.values["value"], devices[0]),
                   want.values["value"])
    tol = STEP_TOL["float32"] * steps
    dtot = abs(rep.final_total["value"] - wrep.final_total["value"])
    ttol = model.conservation_threshold(space)
    if not (err <= tol and dtot <= ttol):
        raise AssertionError(f"sharded vs one chip: max|err|={err} (tol "
                             f"{tol}), |dtotal|={dtot} (tol {ttol})")
    return _line("sharded", f"{grid}x{grid}", "float32", ex.last_impl,
                 False, first, warm,
                 f"vs one chip max|err|={err} <= {tol}, |dtotal|={dtot} "
                 f"<= {ttol}, 4 shards on 4 devices",
                 mesh="2x2", halo_depth=depth, steps=steps,
                 one_chip_s=one_s)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found — JAX's devices are "
              f"{devs[0].platform} ({len(devs)}); this script runs only "
              "on a TPU and has no CPU fallback", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"found {len(devs)}", file=sys.stderr)
        return 2
    from mpi_model_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache("auto")
    if args.chips == 4:
        phases = {"sharded": lambda seed: phase_sharded(seed=seed,
                                                        devices=devs)}
    else:
        phases = {
            "dense": lambda seed: phase_dense(seed=seed),
            "field": lambda seed: phase_field(seed=seed + 1),
            "ir": lambda seed: phase_ir(),
            "serve": lambda seed: phase_serve(seed=seed + 2),
            "reference": lambda seed: phase_reference(),
        }
    failed = []
    for name, fn in phases.items():
        try:
            print(json.dumps(fn(args.seed)), flush=True)
        # analysis: ignore[broad-except] — phase boundary: report every
        # phase's failure, then exit non-zero
        except Exception as e:
            failed.append(name)
            print(json.dumps({"phase": name, "ok": False,
                              "error": f"{type(e).__name__}: {e}"[:2000]}),
                  flush=True)
    if failed:
        print(f"chip_smoke: phases failed: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": _device_line(args.chips)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
