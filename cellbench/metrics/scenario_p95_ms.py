"""The 95th percentile of the latencies of every scenario completed in
the window, from submission to result in the client's hand (host
clock, all scenarios, not a bounded sample).

A per-layer reading, not an end-to-end metric: the closed loop keeps
the service at its capacity, where the tail swings between two modes
(one batch time of waiting or one and a half) from run to run; the
completion rate is the cell's end-to-end metric. No scenario
completed: None.
"""

from cellbench import traffic


def read(ctx):
    lat = ctx.work.get("latencies_s")
    if not lat:
        return None
    return 1e3 * traffic.p95(lat)
