"""The whole step's share of the chip's peak: the least time the window's
work needs (the larger of its operations over the compute peak and its
bytes over HBM, counted as the kernel rooflines count them), over the
time the device was busy in the window: the union of every op it ran,
summed over the chips. Where a kernel roofline times only its kernel,
this times the whole step on the device (the kernel, the layout copies
around it, the totals' reductions), so it bounds any kernel's gain, and
still reads when a later change takes a kernel off the path. The time
the device sat idle is ``device_idle_pct``'s.

The operations per cell per step are the ``OPS_PER_CELL_STEP`` of the
reader that the configuration names under ``ops_counted_by``; each call
reads and writes the state once. No such key, or no trace: None.
"""

from cellbench import roofline, spec
from cellbench import trace as tr


def read(ctx):
    counter = ctx.config.get("ops_counted_by")
    if ctx.trace is None or counter is None:
        return None
    devs = sorted(ctx.trace.ops)
    busy_s = sum(tr.busy_ns(ctx.trace, d) for d in devs) * 1e-9
    if busy_s <= 0:
        return None
    per_cell = spec.metric_module(ctx.root, counter).OPS_PER_CELL_STEP
    w = ctx.work
    ops = per_cell * w["cells"] * w["steps_per_call"] * w["calls"]
    nbytes = 2.0 * w["state_bytes"] * w["calls"]
    per_chip, _ = roofline.least_time_s(ops / len(devs), nbytes / len(devs),
                                        ctx.kind)
    return 100.0 * per_chip * len(devs) / busy_s
