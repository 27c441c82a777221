"""Share of the window in which a collective (the halo ring's
collective-permutes, the totals' all-reduce) runs on a chip and no
other op does: the exchange the compute does not hide. The highest of
the chips. No collective in the trace: None.
"""

from cellbench import trace as tr


def read(ctx):
    if ctx.trace is None:
        return None
    devs = sorted(ctx.trace.ops)
    if not any(tr.is_collective(n) for d in devs
               for n, _, _ in ctx.trace.device_ops(d)):
        return None
    return 100.0 * max(tr.exposed_collective_share(ctx.trace, d)
                       for d in devs)
