"""The 95th percentile, over every scenario submitted in the window, of
its wait in the queue: from the start of its ``ensemble.submit`` span to
the start of the first ``ensemble.assemble`` span whose ``tickets``
hold it (the program's span ring, one clock). The scenarios of the
window are the ``ensemble.submit`` spans that ``program_spans`` pairs
with the window's ``cellbench.submit`` annotations. Beside
``scenario_p95_ms``, which adds the service time. No such span in the
ring, or no placement: None.
"""

from cellbench import program_spans, traffic


def read(ctx):
    if ctx.trace is None:
        return None
    spans = program_spans.ring()
    if not any(s.name == "ensemble.submit" for s in spans):
        return None
    on_trace = program_spans.placed(ctx.trace, spans)
    if on_trace is None:
        return None
    w0, w1 = ctx.trace.window
    assembled = {}
    for p in on_trace:
        if p.name == "ensemble.assemble":
            for t in p.meta.get("tickets", ()):
                assembled.setdefault(t, []).append(p.start)
    waits = []
    for p in on_trace:
        if p.name != "ensemble.submit" or not w0 <= p.start < w1:
            continue
        later = [a for a in assembled.get(p.meta.get("ticket"), ())
                 if a >= p.start]
        if later:
            waits.append((min(later) - p.start) * 1e-9)
    if not waits:
        return None
    return 1e3 * traffic.p95(waits)
