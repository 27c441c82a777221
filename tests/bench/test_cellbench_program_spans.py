"""The program's span ring placed on a traced window's clock
(``cellbench/program_spans.py``) and the readers built on it: on
hand-made intervals with a known offset, and on tiny traced cells on
the CPU."""

import io
import json
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from cellbench import harness, program_spans, spec
from cellbench import trace as tr
from mpi_model_tpu.utils.tracing import Span, Tracer, set_tracer

from cellbench_fixtures import cpu_peaks, tiny_root

OFFSET_NS = 7.25e12
MS = 1e6


def _span(name, start_ns, end_ns, **meta):
    return Span(name=name, start_s=start_ns * 1e-9,
                duration_s=(end_ns - start_ns) * 1e-9, thread=1, depth=0,
                meta=meta)


def _calls(n, delay_ns=(20e3,), drift=0.0):
    """``n`` back-to-back calls of 100 ms: the trace's ``cellbench.call``
    annotations, and the ring's ``model.execute`` spans (each opened
    ``delay_ns`` after its annotation, cycling, on a clock
    ``OFFSET_NS`` behind and running ``drift`` slower) with a totals
    read of 1 ms at each end."""
    host, ring = [], []
    for i in range(n):
        a0 = 1e9 + i * 101 * MS
        a1 = a0 + 100 * MS
        host.append(("cellbench.call", a0, a1))
        s0 = a0 + delay_ns[i % len(delay_ns)]
        s1 = a1 - 10e3

        def back(t):
            return (t - OFFSET_NS) * (1 - drift)

        ring.append(_span("model.totals.initial", back(s0), back(s0 + MS)))
        ring.append(_span("model.totals.final", back(s1 - MS), back(s1)))
        ring.append(_span("model.execute", back(s0), back(s1)))
    w0, w1 = host[0][1] - MS, host[-1][2] + MS
    host.append((tr.WINDOW, w0, w1))
    # the device runs through each call but the two totals reads
    ops = {0: [("%fusion", a0 + 1.5 * MS, a1 - 1.5 * MS)
               for _, a0, a1 in host[:-1]]}
    return tr.Trace(ops=ops, host=host, window=(w0, w1)), ring


@pytest.mark.parametrize("delays", [
    (20e3,),
    # a thread now and then waits for the interpreter lock before its
    # span opens: a one-sided tail of the start differences
    (15e3, 22e3, 18e3, 5.2 * MS, 21e3),
])
def test_the_offset_is_recovered(delays):
    trace, ring = _calls(12, delay_ns=delays)
    placed = program_spans.placed(trace, ring)
    ex = [p for p in placed if p.name == "model.execute"]
    for (_, a0, a1), p, d in zip(trace.host, ex,
                                 [delays[i % len(delays)]
                                  for i in range(12)]):
        # each span lands where it was, to half the smallest delays
        # (15-20 us at the start, 10 us at the end)
        assert abs(p.start - (a0 + d)) <= 6e3
        assert a0 <= p.start and p.end <= a1


def test_a_drifting_clock_takes_a_rate_term():
    # 4000 ppm over 1.2 s: no offset alone places every call inside its
    # annotation to within the tolerance
    trace, ring = _calls(12, drift=4e-3)
    pairs = [((a0, a1), (s.start_s * 1e9, (s.start_s + s.duration_s) * 1e9))
             for (_, a0, a1), s in zip(
                 trace.host, [s for s in ring if s.name == "model.execute"])]
    offset_only = program_spans._clock(pairs, pairs[0][1][0], 0.0)
    assert program_spans.residual_ns(pairs, offset_only) > \
        program_spans.TOLERANCE_NS
    clock = program_spans.fit(pairs)
    assert clock is not None
    assert program_spans.residual_ns(pairs, clock) <= 20e3


def test_nothing_is_placed_when_the_counts_or_the_residual_are_off():
    trace, ring = _calls(12)
    # fewer anchors in the ring than annotations in the trace
    short = [s for s in ring if s.name != "model.execute"] + \
        [s for s in ring if s.name == "model.execute"][:11]
    assert program_spans.placed(trace, short) is None
    # anchors that cannot be made to fit their annotations: every other
    # call 3 ms longer on the ring's clock
    bad = [Span(s.name, s.start_s, s.duration_s + (i % 2) * 3e-3, 1, 0)
           if s.name == "model.execute" else s
           for i, s in enumerate(ring)]
    assert program_spans.placed(trace, bad) is None
    # no anchor annotation in the trace
    bare = tr.Trace(ops=trace.ops, host=[h for h in trace.host
                                         if h[0] == tr.WINDOW],
                    window=trace.window)
    assert program_spans.placed(bare, ring) is None
    assert program_spans.idle_under(bare, ("model.totals.initial",),
                                    ring) is None


def test_idle_under_counts_the_idle_device_inside_the_spans():
    trace, ring = _calls(12)
    got = program_spans.idle_under(
        trace, ("model.totals.initial", "model.totals.final"), ring)
    # each call: the device idles 1.5 ms at each end, and each totals
    # span, 1 ms long, lies inside that idle time
    want = 100.0 * 12 * 2 * MS / (trace.window[1] - trace.window[0])
    assert got == pytest.approx(want, rel=1e-3)
    assert 0 < got < 100.0 * tr.idle_share(trace)
    # a ring without the spans read: None, not 0
    assert program_spans.idle_under(trace, ("ensemble.fetch.totals",),
                                    ring) is None


# -- tiny traced cells on the CPU ---------------------------------------------

CELLS = {"diffusion16k-bf16-horizon": ("model.execute", "totals_idle_pct.grid",
                                       "device_idle_pct.grid"),
         "diffusion2k-sweep-closed16": ("ensemble.submit",
                                        "fetch_idle_pct.sweep",
                                        "device_idle_pct.sweep")}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Each cell run traced once with a fresh program tracer: the result
    line, the tracer, the ring's cursor when the window opened and the
    window's reduced trace."""
    root = tiny_root(Path(tmp_path_factory.mktemp("tiny")))
    mp = pytest.MonkeyPatch()
    cpu_peaks(mp)
    out = {}
    real = harness.window
    try:
        for workload in CELLS:
            box = {}

            def window(*a, box=box, real=real, **k):
                box["cursor"] = box["tracer"].spans_since(2**62)[0]
                win, trace = real(*a, **k)
                box["trace"] = trace
                return win, trace

            mp.setattr(harness, "window", window)
            box["tracer"] = Tracer()
            prev = set_tracer(box["tracer"])
            try:
                cell = spec.load_cell(root, workload)
                buf = io.StringIO()
                harness.run(cell, 2**33 + 9, 0.4, True, time.perf_counter(),
                            jax.devices(), {}, out=buf, err=io.StringIO())
            finally:
                set_tracer(prev)
            box["line"] = json.loads(buf.getvalue().strip().splitlines()[-1])
            out[workload] = box
    finally:
        mp.undo()
    return out


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_only_the_window_opens_anchors_after_it_starts(traced, workload):
    box = traced[workload]
    anchor = CELLS[workload][0]
    after = [s for s in box["tracer"].spans
             if s.name == anchor and s.seq > box["cursor"]]
    marks = [h for h in box["trace"].host
             if h[0] == program_spans.ANCHORS[anchor]]
    # the sample, the check and the drain open none
    assert len(after) == len(marks) == box["line"]["attempted"] > 0


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_a_reader_reads_inside_the_idle_share(traced, workload):
    _, name, idle = CELLS[workload]
    m = traced[workload]["line"]["metrics"]
    assert m[name]["unit"] == "%"
    assert 0.0 <= m[name]["value"] <= m[idle]["value"]


def test_the_queue_wait_reads_under_the_latency(traced):
    m = traced["diffusion2k-sweep-closed16"]["line"]["metrics"]
    assert 0.0 <= m["queue_wait_p95_ms"]["value"] \
        <= m["scenario_p95_ms"]["value"]


@pytest.mark.parametrize("name", ["totals_idle_pct.grid",
                                  "fetch_idle_pct.sweep",
                                  "queue_wait_p95_ms"])
def test_a_ring_without_the_new_spans_reads_none(traced, name,
                                                 monkeypatch):
    workload = ("diffusion16k-bf16-horizon" if name.startswith("totals")
                else "diffusion2k-sweep-closed16")
    box = traced[workload]
    spans = box["tracer"].spans
    ctx = SimpleNamespace(trace=box["trace"])
    reader = spec.metric_reader(str(Path(__file__).parents[2]), name)
    # the ring as recorded reads a number
    monkeypatch.setattr(program_spans, "ring", lambda: spans)
    assert reader(ctx) is not None
    # the ring of a program that records only the older spans: None
    older = {"model.execute", "executor.run", "model.report",
             "ensemble.assemble", "ensemble.launch", "ensemble.fetch"}
    monkeypatch.setattr(program_spans, "ring",
                        lambda: [s for s in spans if s.name in older])
    assert reader(ctx) is None
