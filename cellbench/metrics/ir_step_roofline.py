"""Share of the roofline that the Flow IR's lowering of Gray-Scott
reaches (XLA fusions; no Pallas kernel runs on this path).

Work of the window, counted term by term from the model's equations
(``cellbench/references/gray_scott.py``), per cell per step:

    Transport u (Du)     11  (as one Moore-8 Diffusion step)
    Transport v (Dv)     11
    reaction v*v*u        4  (2 multiplies; subtract from u, add to v)
    feed F*(1-u)          4  (1 subtract, 1 multiply; add to u and to
                              its budget)
    kill (F+k)*v          3  (1 multiply, F+k folded; subtract from v
                              and from its budget)

and each call reads and writes its four state channels once. The time
is the device's busy time in the window (every op the entry point ran:
the step loop and the budget reductions). Nothing to read (not this
model): None.
"""

from cellbench import roofline
from cellbench import trace as tr

OPS_PER_CELL_STEP = 11 + 11 + 4 + 4 + 3


def read(ctx):
    m = ctx.config["model"]
    if ctx.trace is None or m.get("name") != "gray_scott":
        return None
    devs = sorted(ctx.trace.ops)
    busy_s = sum(tr.busy_ns(ctx.trace, d) for d in devs) * 1e-9
    if busy_s <= 0:
        return None
    w = ctx.work
    ops = OPS_PER_CELL_STEP * w["cells"] * w["steps_per_call"] * w["calls"]
    nbytes = 2.0 * w["state_bytes"] * w["calls"]
    per_chip, _ = roofline.least_time_s(ops / len(devs), nbytes / len(devs),
                                        ctx.kind)
    return 100.0 * per_chip * len(devs) / busy_s
