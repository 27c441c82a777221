"""Share of the dispatched batch lanes that held a scenario, over the
dispatches that completed in the window (the scheduler's counters:
scenarios over lanes, padding lanes included). No dispatch: None.
"""


def read(ctx):
    lanes = ctx.counters.get("lanes", 0)
    if not lanes:
        return None
    return 100.0 * ctx.counters["scenarios"] / lanes
