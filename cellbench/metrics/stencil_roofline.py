"""Share of the roofline that the Pallas stencil kernels reach.

Work of the window, counted from the model, not the implementation.
One Moore-8 Diffusion step costs each cell 11 operations:

    outflow = rate * v             1 multiply
    share   = outflow / count      1 divide
    inflow  = sum of 8 shares      7 adds
    v'      = v - outflow + inflow 2 adds

and each call reads and writes the state once (its bytes twice). The
time is the device time of the Pallas kernels (Mosaic custom calls) in
the window, summed over the chips; the work is shared evenly among
them. Nothing to read (no kernel ran, or the model is not Diffusion):
None.
"""

from cellbench import roofline
from cellbench import trace as tr

OPS_PER_CELL_STEP = 1 + 1 + 7 + 2


def read(ctx):
    if ctx.trace is None or ctx.config["model"]["kind"] != "diffusion":
        return None
    devs = sorted(ctx.trace.ops)
    kernel_s = sum(tr.op_time_ns(ctx.trace, d, tr.is_kernel)
                   for d in devs) * 1e-9
    if kernel_s <= 0:
        return None
    w = ctx.work
    ops = OPS_PER_CELL_STEP * w["cells"] * w["steps_per_call"] * w["calls"]
    nbytes = 2.0 * w["state_bytes"] * w["calls"]
    per_chip, _ = roofline.least_time_s(ops / len(devs), nbytes / len(devs),
                                        ctx.kind)
    return 100.0 * per_chip * len(devs) / kernel_s
