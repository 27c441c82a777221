"""The one traffic generator: it reads a mix's parameters and drives a
system in one of two loops.

- ``horizon``: one long run, stepped by back-to-back calls of
  ``steps_per_call`` flow steps, each call's output the next one's
  input. A rate is all the cell-updates of the window over the wall
  time of its calls (all of it but the check's copy of one call).
- ``closed``: ``clients`` callers, each submitting its next scenario as
  soon as the result of its last one is in hand. Each scenario's
  rate is drawn from ``rates`` and its input from a pool of ``pool``
  grids, both by the seed, so every seed sends the same work. A
  scenario's latency runs from its submission to its result in the
  client's hand; the window counts the scenarios completed in it.

Both loops take the clock as an argument, so the tests drive them on a
fake one. ``annotate(name)`` returns a context manager around each
call (the traced run's host spans); it defaults to doing nothing.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Any, Callable, Optional

import numpy as np


def no_span(_name):
    return contextlib.nullcontext()


@dataclasses.dataclass
class HorizonResult:
    calls: int
    elapsed_s: float
    state: Any
    #: what ``take`` kept of the call the seed drew
    sample: Any
    #: each call's report, in order (references, nothing copied)
    reports: list


def _whole(state, out, report):
    return state, out, report


def horizon(call: Callable, state, seconds: float, *, sample_at: float,
            take: Callable = _whole,
            clock: Callable[[], float] = time.perf_counter,
            annotate=no_span) -> HorizonResult:
    """Call ``call(state) -> (state, report)`` back to back until the
    calls have taken ``seconds``. Of the first call that starts at or
    after ``sample_at`` of the window (a fraction drawn from the seed),
    ``take(input, output, report)`` keeps what the correctness check
    needs; its time is not the program's and is left out. Every call's
    report is kept for the window's counters."""
    sample = None
    sampled = False
    calls = 0
    elapsed = 0.0
    reports = []
    while True:
        start = elapsed
        t = clock()
        with annotate("call"):
            out, report = call(state)
        elapsed += clock() - t
        if not sampled and start >= sample_at * seconds:
            with annotate("sample"):
                sample, sampled = take(state, out, report), True
        state = out
        calls += 1
        reports.append(report)
        if elapsed >= seconds:
            break
    return HorizonResult(calls, elapsed, state, sample, reports)


@dataclasses.dataclass
class ClosedResult:
    attempted: int
    failed: int
    #: latencies (s) of the scenarios completed inside the window
    latencies: list
    #: when each of them completed, in seconds from the window's start
    finished: list
    window_s: float
    #: {request index: (request, result)} of the scenarios ``keep`` chose
    kept: dict
    #: completed after the window closed (drained, not counted)
    drained: int
    errors: list


def closed_loop(submit: Callable, wait: Callable, poll: Callable,
                request: Callable[[int], Any], *, clients: int,
                seconds: float, keep: Callable[[int], bool],
                clock: Callable[[], float] = time.perf_counter,
                annotate=no_span, on_window_end=None) -> ClosedResult:
    """Keep ``clients`` scenarios in flight for ``seconds``.

    ``submit(req) -> ticket``; ``wait(ticket)`` blocks until the result
    is in hand and returns it (raising the scenario's error);
    ``poll(ticket)`` returns it or None without blocking. The oldest
    ticket is waited on, then every other one is polled, so results
    that arrive together are taken together. After the window no new
    scenario is sent and the ones in flight are drained.
    ``on_window_end()`` runs once, when the window closes."""
    inflight: collections.OrderedDict = collections.OrderedDict()
    n = 0
    t0 = clock()
    end = t0 + seconds

    def send():
        nonlocal n
        req = request(n)
        with annotate("submit"):
            ticket = submit(req)
        inflight[ticket] = (n, req, clock())
        n += 1

    for _ in range(clients):
        send()
    latencies: list = []
    finished: list = []
    kept: dict = {}
    errors: list = []
    failed = drained = 0
    closed = False
    while inflight:
        done = []
        first = next(iter(inflight))
        with annotate("wait"):
            try:
                done.append((first, wait(first), None))
            except Exception as e:  # the scenario's own error: counted
                done.append((first, None, e))
        for t in list(inflight)[1:]:
            try:
                r = poll(t)
            except Exception as e:  # the scenario's own error: counted
                done.append((t, None, e))
                continue
            if r is not None:
                done.append((t, r, None))
        now = clock()
        if not closed and now >= end:
            closed = True
            if on_window_end is not None:
                on_window_end()
        for t, res, err in done:
            i, req, sent = inflight.pop(t)
            if err is not None:
                failed += 1
                errors.append(f"{type(err).__name__}: {err}"[:300])
            elif now <= end:
                latencies.append(now - sent)
                finished.append(now - t0)
                if keep(i):
                    kept[i] = (req, res)
            else:
                drained += 1
            if not closed:
                send()
    return ClosedResult(attempted=len(latencies) + failed + drained,
                        failed=failed, latencies=latencies,
                        finished=finished, window_s=seconds, kept=kept,
                        drained=drained, errors=errors)


def p95(values: list) -> float:
    """The 95th percentile of all ``values`` (linear interpolation)."""
    return float(np.percentile(np.asarray(values, np.float64), 95))


def scenario_plan(traffic: dict, seed: int):
    """``request(i) -> (pool index, rate)`` and ``keep(i)`` for a closed
    mix: the same sizes for every seed, in a seeded order. ``keep``
    picks about one scenario in ``sample_every`` for the check."""
    rates = list(traffic["rates"])
    pool = int(traffic["pool"])
    every = int(traffic["sample_every"])
    salt = np.random.default_rng(int(seed) % 2**63).integers(0, 2**62)

    def draw(i: int, stream: int) -> int:
        r = np.random.default_rng([int(salt), stream, i])
        return int(r.integers(0, 2**31))

    def request(i: int):
        return draw(i, 0) % pool, rates[draw(i, 1) % len(rates)]

    def keep(i: int) -> bool:
        return draw(i, 2) % every == 0

    return request, keep
