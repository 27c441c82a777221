"""Readings that the limits of ``correct`` are set from.

    python3 cellbench/calibrate.py --workload <name> --seeds 1,2,3 \\
        --control-seeds 11,12,13 [--seconds 3] [--out FILE]

For every seed, in one process: the cell's set-up, a short window at
the cell's own load, and the numbers compared, as a run makes them. For
every control seed also each control's numbers, in the order of the
configuration's ``control``: the reference in a lower precision put in
the program's place on the same sampled input (``"kind":
"reference"``), or the program with its own lower-precision path
switched on (``"kind": "program"``). The benchmark's own runs never run
this. One JSON line per seed; needs the chips the cell asks for.
"""

from __future__ import annotations

import os
import sys
import time

if __package__ in (None, ""):
    # as cellbench/run.py: the checkout's root first, the cache in it
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(sys.path[0],
                                                           ".jax_cache")

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402


def readings(cell, seed: int, seconds: float, devices, control: bool):
    """``{"program": numbers, "controls": [numbers, ...]}`` of one seed
    (no controls unless ``control``)."""
    from cellbench import compare, harness

    out = {"seed": seed}
    system = harness.build(cell, seed, devices)
    harness.warm(cell, system)
    try:
        win, _ = harness.window(cell, system, seed, seconds, False)
    finally:
        if hasattr(system, "close"):
            system.close()
    out["program"] = win.check()
    out["failed"] = win.failed
    ctls = compare.controls(cell.config) if control else []
    out["controls"] = [win.check(store=c["store"])
                       if c["kind"] == "reference" else None for c in ctls]
    del system, win
    gc.collect()
    for i, c in enumerate(ctls):
        if c["kind"] != "program":
            continue
        system = harness.build(cell, seed, devices,
                               executor_overrides=c["executor"])
        harness.warm(cell, system)
        win, _ = harness.window(cell, system, seed, seconds, False)
        out["controls"][i] = win.check()
        del system, win
        gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from cellbench import run, spec

    cell = spec.load_cell(run.ROOT, args.workload)
    devices = run.find_chips(cell.chips)
    if devices is None:
        return 3
    devices = devices[:cell.chips]
    plan = [(int(s), False) for s in args.seeds.split(",") if s]
    plan += [(int(s), True) for s in args.control_seeds.split(",") if s]
    sink = open(args.out, "a") if args.out else None
    try:
        for seed, control in plan:
            t = time.perf_counter()
            row = readings(cell, seed, args.seconds, devices, control)
            row["workload"] = cell.name
            row["s"] = time.perf_counter() - t
            line = json.dumps(row)
            print(line, flush=True)
            if sink:
                sink.write(line + "\n")
                sink.flush()
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
