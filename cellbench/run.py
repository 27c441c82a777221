"""Run one cell of the benchmark on the chips of this machine.

    python3 cellbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

from the root of a checkout. The cell's configuration, traffic and
metrics are found by the names in ``BENCHMARK.json``. With ``--trace 0``
the result line holds the cell's end-to-end metrics; with ``--trace 1``
the per-layer metrics read from the device trace of the window. Without
a TPU, or with fewer chips than the cell asks for, the run exits non-zero
and prints no result. The last lines of standard error give each number
compared with its limit; the last line of standard output is the result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: JAX's persistent compilation cache: a fixed path inside the checkout,
#: so that only a checkout's first run of a cell compiles
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

if __package__ in (None, ""):
    # run as a script: import the benchmark and the program from the
    # checkout's root, not from this directory (whose module names would
    # shadow the stdlib's), and place the cache before JAX is imported
    sys.path[0] = ROOT
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_chips(n: int):
    """The TPUs of this machine, or None (with the reason on stderr)
    when there is no TPU or fewer than ``n``."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"run: no TPU found (JAX's devices are {devs[0].platform}); "
              "the benchmark does not run elsewhere", file=sys.stderr)
        return None
    if len(devs) < n:
        print(f"run: the cell needs {n} chips, found {len(devs)}",
              file=sys.stderr)
        return None
    return devs


def main(argv=None) -> int:
    args = parse(argv)
    from cellbench import harness, spec

    cell = spec.load_cell(ROOT, args.workload)
    t = time.perf_counter()
    import jax

    devices = find_chips(cell.chips)
    if devices is None:
        return 3
    split = {"backend_s": time.perf_counter() - t}
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    harness.run(cell, args.seed, args.seconds, bool(args.trace), T_PROCESS,
                devices, split)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Exception:  # the run's boundary: report, print no result
        traceback.print_exc()
        code = 1
    sys.exit(code)
