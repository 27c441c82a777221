"""Probe of the vector unit's f32 peak: the compute roof of the stencil
and IR rooflines.

The figure is not published the way the MXU's is, so it is measured:
independent chains ``x = x * a + b`` on a block that stays in VMEM,
several block shapes and chain counts, each kernel timed from the
device trace. Every multiply and every add counts as one operation, as
the ops files of the roofline metrics count them. The highest rate of
the variants is the peak; ``cellbench/roofline.py`` records it.

    python3 cellbench/probe.py [--repeats 5]

needs a TPU and prints one JSON line per variant and a summary line.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

if __package__ in (None, ""):
    # run as a script: import the package from the checkout's root, not
    # this directory (whose module names would shadow the stdlib's)
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (rows, cols, chains, unroll) of the block each grid step keeps in VMEM
VARIANTS = ((8, 128, 8, 8), (8, 128, 16, 8), (16, 128, 8, 8),
            (16, 128, 12, 4), (32, 128, 6, 4), (32, 128, 8, 4),
            (8, 256, 8, 8), (8, 512, 4, 8), (16, 256, 4, 8),
            (64, 128, 2, 8))
ITERS = 2048
BLOCKS = 512


def probe_kernel(rows: int, cols: int, chains: int, unroll: int,
                 iters: int = ITERS, blocks: int = BLOCKS,
                 interpret: bool = False):
    """``(fn, x, ops)``: a jitted kernel over ``blocks`` grid steps, its
    input, and the multiplies and adds one call performs."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        a = jnp.float32(0.999999)
        b = jnp.float32(1e-6)
        x = x_ref[...]
        cs = tuple(x + jnp.float32(j) for j in range(chains))

        def body(_, cs):
            # unrolled by hand: Mosaic lowers only unroll=1 loops
            for _ in range(unroll):
                cs = tuple(c * a + b for c in cs)
            return cs

        cs = jax.lax.fori_loop(0, iters // unroll, body, cs)
        acc = cs[0]
        for c in cs[1:]:
            acc = acc + c
        o_ref[...] = acc

    shape = (blocks * rows, cols)
    spec = pl.BlockSpec((rows, cols), lambda i: (i, 0))
    fn = jax.jit(pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
        grid=(blocks,), in_specs=[spec], out_specs=spec,
        interpret=interpret))
    x = jnp.ones(shape, jnp.float32)
    ops = 2.0 * rows * cols * chains * (iters // unroll * unroll) * blocks
    return fn, x, ops


def measure(repeats: int = 5) -> dict:
    """Run every variant ``repeats`` times under the profiler; the rate
    of a call is its ops over the device time of its ops."""
    import jax

    from cellbench import trace as tr

    rows = []
    for rows_, cols, chains, unroll in VARIANTS:
        fn, x, ops = probe_kernel(rows_, cols, chains, unroll)
        jax.block_until_ready(fn(x))
        rates = []
        for _ in range(repeats):
            with tr.capture() as cap:
                with jax.profiler.TraceAnnotation(tr.WINDOW):
                    jax.block_until_ready(fn(x))
            t = tr.load(cap[0])
            tr.discard(cap)
            dev = sorted(t.ops)[0]
            secs = tr.op_time_ns(t, dev, lambda n: True) * 1e-9
            rates.append(ops / secs)
        row = {"rows": rows_, "cols": cols, "chains": chains,
               "unroll": unroll, "ops": ops,
               "median_ops_per_s": statistics.median(rates),
               "min": min(rates), "max": max(rates)}
        print(json.dumps(row), flush=True)
        rows.append(row)
    best = max(rows, key=lambda r: r["median_ops_per_s"])
    return {"peak_ops_per_s": best["median_ops_per_s"], "best": best,
            "device_kind": jax.devices()[0].device_kind}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("probe: no TPU found; the peak is measured only on the chip",
              file=sys.stderr)
        return 2
    print(json.dumps(measure(args.repeats)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
