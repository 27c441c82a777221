"""Share of the window in which a chip runs no op while the host is
inside one of ``Model.execute``'s host reads of the device: the
per-channel totals before the run (``model.totals.initial``), after it
(``model.totals.final``) and the flows' last values
(``model.flows.last_execute``). The mean over chips; at most
``device_idle_pct``. Read from the program's span ring placed on the
trace's clock (``cellbench/program_spans.py``). No such span in the
ring, or no placement: None.
"""

from cellbench import program_spans

SPANS = ("model.totals.initial", "model.totals.final",
         "model.flows.last_execute")


def read(ctx):
    return program_spans.idle_under(ctx.trace, SPANS)
