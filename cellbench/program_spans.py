"""The program's own spans, placed on a traced window's clock.

The program records its spans in a ring of its own
(``mpi_model_tpu.utils.tracing``, ``perf_counter`` seconds); the
reduced trace keeps only the benchmark's ``cellbench.*`` annotations
(profiler nanoseconds). The two clocks are joined by pairing. Each
benchmark annotation of one kind encloses exactly one program span of
one name (``ANCHORS``), and from the window's first anchor to the end
of the run nothing else opens such a span (not the check's sample, the
check or the drain), so the last K spans of that name in the ring pair
in order with the window's K annotations.

A program span opens after its annotation opens and closes before it
closes, so each pair bounds the offset from both sides: the start
difference from below, the end difference from above. The offset is
the middle of the range that every pair allows. Where a thread waits
(for a lock, or for the interpreter) between an annotation and its
span, its pair only widens the range, so such waits do not bias the
fit; the median of the start differences would place every span early
by the median delay (45–70 µs on a TPU v5e host). The fit's residual
is how far it places an anchor outside its own annotation: zero where
the range is not empty, half the width by which its bounds cross where
it is (the clocks run apart). A rate term is fitted only where the offset alone leaves a
residual over ``TOLERANCE_NS``; a residual over it after that, or fewer
anchors in the ring than annotations in the trace, places nothing.

Readers call ``idle_under``/``placed``; a ring without the spans a
reader reads gives None, never 0.
"""

from __future__ import annotations

import dataclasses
import os
import statistics
from typing import Optional

from cellbench import trace as tr

#: program span name -> the benchmark annotation that encloses each one
ANCHORS = {"model.execute": tr.PREFIX + "call",
           "ensemble.submit": tr.PREFIX + "submit"}

#: the largest residual a placement is accepted with
TOLERANCE_NS = 1e6


@dataclasses.dataclass
class Placed:
    """A program span on the trace's clock (nanoseconds)."""

    name: str
    start: float
    end: float
    meta: dict


def ring() -> list:
    """The spans this process's program tracer holds."""
    from mpi_model_tpu.utils.tracing import get_tracer

    pid = os.getpid()
    return [s for s in get_tracer().spans if s.pid == pid]


def _ns(span) -> tuple[float, float]:
    return span.start_s * 1e9, (span.start_s + span.duration_s) * 1e9


def residual_ns(pairs: list, clock) -> float:
    """How far ``clock`` places a ring span outside its annotation, at
    most over ``pairs`` of ``((a0, a1), (s0, s1))``."""
    worst = 0.0
    for (a0, a1), (s0, s1) in pairs:
        worst = max(worst, a0 - clock(s0), clock(s1) - a1)
    return worst


def _clock(pairs: list, ref: float, rate: float):
    """The clock of slope ``1 + rate`` (about ``ref``) whose offset is
    the middle of the range every pair allows."""
    lo = max(a0 - s0 - rate * (s0 - ref) for (a0, _), (s0, _) in pairs)
    hi = min(a1 - s1 - rate * (s1 - ref) for (_, a1), (_, s1) in pairs)
    off = (lo + hi) / 2

    def clock(t):
        return t + off + rate * (t - ref)

    return clock


def fit(pairs: list):
    """``clock(ring_ns) -> trace_ns`` from annotation/span pairs, or None
    where no offset (and rate) places every anchor inside its
    annotation to within ``TOLERANCE_NS``."""
    if not pairs:
        return None
    ref = pairs[0][1][0]
    clock = _clock(pairs, ref, 0.0)
    if residual_ns(pairs, clock) <= TOLERANCE_NS:
        return clock
    h = len(pairs) // 2
    if h < 2:
        return None
    x = [s0 for _, (s0, _) in pairs]
    d = [a0 - s0 for (a0, _), (s0, _) in pairs]
    span = statistics.median(x[h:]) - statistics.median(x[:h])
    if span <= 0:
        return None
    rate = (statistics.median(d[h:]) - statistics.median(d[:h])) / span
    clock = _clock(pairs, ref, rate)
    if residual_ns(pairs, clock) <= TOLERANCE_NS:
        return clock
    return None


def placed(trace, spans: Optional[list] = None) -> Optional[list]:
    """Every span of ``spans`` (default: the ring) on ``trace``'s clock,
    or None where the trace holds no anchor annotation, the ring fewer
    anchors than it, or the fit fails."""
    spans = ring() if spans is None else spans
    for name, annotation in ANCHORS.items():
        marks = sorted((s, e) for n, s, e in trace.host if n == annotation)
        if not marks:
            continue
        anchors = [s for s in spans if s.name == name]
        if len(anchors) < len(marks):
            return None
        anchors = sorted(anchors[-len(marks):], key=lambda s: s.start_s)
        clock = fit([(m, _ns(s)) for m, s in zip(marks, anchors)])
        if clock is None:
            return None
        out = []
        for s in spans:
            s0, s1 = _ns(s)
            out.append(Placed(s.name, clock(s0), clock(s1), s.meta))
        return out
    return None


def clip(trace, intervals) -> list:
    """``intervals`` cut to the window's spans, as a disjoint cover."""
    out = []
    for s, e in intervals:
        for w0, w1 in trace.spans:
            if e > w0 and s < w1:
                out.append((max(s, w0), min(e, w1)))
    return tr.union(out)


def idle_under(trace, names: tuple, spans: Optional[list] = None
               ) -> Optional[float]:
    """Share of the window (%) in which a chip runs no op while the host
    is inside a program span named in ``names``, the mean over chips.
    None where there is no trace, the ring holds no such span, or the
    placement fails."""
    if trace is None:
        return None
    spans = ring() if spans is None else spans
    if not any(s.name in names for s in spans):
        return None
    on_trace = placed(trace, spans)
    if on_trace is None:
        return None
    host = clip(trace, [(p.start, p.end) for p in on_trace
                        if p.name in names])
    w = trace.window_s * 1e9
    devs = sorted(trace.ops)
    idle = [sum(e - s for s, e in tr.subtract(
        host, tr.union((s, e) for _, s, e in trace.device_ops(d))))
        for d in devs]
    return 100.0 * sum(idle) / len(idle) / w
