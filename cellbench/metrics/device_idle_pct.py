"""Share of the window in which no op ran on the device: 1 minus the
union of the device ops' intervals over the window, averaged over the
chips."""

from cellbench import trace as tr


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * tr.idle_share(ctx.trace)
