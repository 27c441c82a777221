"""Share of the window in which the chip runs no op while the service's
pump is inside the served fetch path's host reads: the batched totals
and last values (``ensemble.fetch.totals``) and the slicing of the
batch into per-lane results (``ensemble.fetch.lanes``). At most
``device_idle_pct``. Read from the program's span ring placed on the
trace's clock (``cellbench/program_spans.py``). No such span in the
ring, or no placement: None.
"""

from cellbench import program_spans

SPANS = ("ensemble.fetch.totals", "ensemble.fetch.lanes")


def read(ctx):
    return program_spans.idle_under(ctx.trace, SPANS)
