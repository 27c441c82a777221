"""One run of one cell: set-up, the measured window, the check, the
metrics and the result line.

``run`` is the whole of a run after the chip has been found, so the
tests drive it on the CPU at tiny sizes with the timed path broken
underneath and see ``correct`` come out false.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import sys
import time
from types import SimpleNamespace
from typing import Optional

import numpy as np

from cellbench import compare, spec, systems, traffic
from cellbench import trace as tr


def _annotator(on: bool):
    if not on:
        return traffic.no_span
    import jax

    return lambda name: jax.profiler.TraceAnnotation(tr.PREFIX + name)


def _peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Window:
    """What a window left behind for the check and the metrics."""

    attempted: int
    failed: int
    metrics: dict          # end-to-end name -> value
    work: dict             # what the roofline readers count
    counters: dict         # program counters over the window (spec.py)
    check: callable        # (store=None) -> the numbers compared
    errors: list
    #: a few numbers about the window itself, printed on stderr
    notes: dict = dataclasses.field(default_factory=dict)


def _grid_window(cell, system, seed, seconds, annotate) -> Window:
    """Back-to-back calls; the check holds one call the seed drew."""
    import jax.numpy as jnp

    cfg = cell.config
    h, w = cfg["grid"]
    state_bytes = sum(v.size * jnp.dtype(v.dtype).itemsize
                      for v in system.space.values.values())
    rng = np.random.default_rng([int(seed) % 2**63, 3])

    def take(inp, out, report):
        return system.sampler(inp.values, out.values), report

    res = traffic.horizon(system.call, system.space, seconds,
                          sample_at=float(rng.uniform(0.0, 0.9)), take=take,
                          annotate=annotate)
    work = {"calls": res.calls, "steps_per_call": system.steps,
            "cells": h * w, "state_bytes": state_bytes,
            "elapsed_s": res.elapsed_s}
    cuts, report = res.sample
    counters = program_counters(res.reports)
    notes = dict(counters, elapsed_s=res.elapsed_s)
    # the program's state goes before the reference runs
    system.space = None
    del res

    def check(store=None):
        return compare.grid_numbers(cfg, system.steps, cuts, report,
                                    store=store)

    rate = work["calls"] * system.steps * h * w / work["elapsed_s"]
    return Window(attempted=work["calls"], failed=0,
                  metrics={"cell_updates_per_s": rate}, work=work,
                  counters=counters, check=check, errors=[], notes=notes)


def program_counters(reports: list) -> dict:
    """``calls``, and for each number (an ``int`` or ``float``, not a
    ``bool``) in the calls' ``backend_report``, its sum over the calls
    as ``report.<key>``. Strings, lists and None are left out."""
    out = {"calls": len(reports)}
    for r in reports:
        for k, v in (r.backend_report or {}).items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out["report." + k] = out.get("report." + k, 0) + v
    return out


def _closed_window(cell, system, seed, seconds, annotate) -> Window:
    tf = cell.traffic
    request, keep = traffic.scenario_plan(tf, seed)
    before = system.counters()
    marks = {}

    def window_end():
        marks["after"] = system.counters()

    res = traffic.closed_loop(
        system.submit, system.wait, system.poll, request,
        clients=int(tf["clients"]), seconds=seconds, keep=keep,
        annotate=annotate, on_window_end=window_end)
    after = marks.get("after", system.counters())
    counters = {k: after[k] - before[k] for k in before}
    kept = [(system.pool[req[0]].values, req[1], out)
            for i, (req, out) in sorted(res.kept.items())]
    n = len(res.latencies)
    metrics = {"scenarios_per_s": n / res.window_s}
    cfg = cell.config

    def check(store=None):
        if not kept:
            return {"max_abs_err": float("nan")}
        return compare.scenario_numbers(cfg, system.steps, kept,
                                        system.devices[0], store=store)

    return Window(attempted=res.attempted, failed=res.failed,
                  metrics=metrics,
                  work={"scenarios": n, "steps": system.steps,
                        "latencies_s": res.latencies},
                  counters=counters, check=check, errors=res.errors,
                  notes={"completed": n, "drained": res.drained,
                         "kept": len(kept), **counters,
                         "latency_max_ms": 1e3 * max(res.latencies,
                                                     default=0.0),
                         "per_second": np.bincount(
                             np.asarray(res.finished, int),
                             minlength=int(seconds)).tolist()})


def build(cell, seed: int, devices, executor_overrides=None):
    """The system a cell runs; ``executor_overrides`` switch on a path of
    the program's own (the control), whose conservation gate is then
    off so that it gives a reading rather than an exception."""
    tf = cell.traffic
    if tf["loop"] == "closed":
        return systems.ServiceSystem(cell.config, tf, seed, devices,
                                     root=cell.root)
    if tf["loop"] == "horizon":
        return systems.GridSystem(cell.config, seed, devices,
                                  tf["steps_per_call"],
                                  executor_overrides=executor_overrides,
                                  check_conservation=not executor_overrides,
                                  root=cell.root)
    raise ValueError(f"unknown traffic loop {tf['loop']!r}")


def warm(cell, system) -> dict:
    """Compile or load every shape the window uses, and run each once.
    Returns the split: ``load_s`` (the first call of each shape, which
    compiles or loads it from the cache), ``cuts_s`` (the same for the
    check's cut programs), ``compile_s`` (the part of both that
    compiled rather than loaded) and ``warm_s`` (a second call)."""
    import jax

    counts = {"traced": 0, "compile_s": 0.0}
    t = time.perf_counter()
    with _compiles(counts):
        if isinstance(system, systems.ServiceSystem):
            system.warm()
            return {"load_s": time.perf_counter() - t,
                    "compile_s": counts["compile_s"]}
        out, _ = system.call(system.space)
        jax.block_until_ready(out.values)
        system.space = out
        split = {"load_s": time.perf_counter() - t}
        t = time.perf_counter()
        system.sampler = compare.Sampler(cell.config, system.steps,
                                         system.devices[0])
        system.sampler.compile(out.values)
    split.update(cuts_s=time.perf_counter() - t,
                 compile_s=counts["compile_s"])
    t = time.perf_counter()
    out, _ = system.call(system.space)
    jax.block_until_ready(out.values)
    system.space = out
    split["warm_s"] = time.perf_counter() - t
    want = cell.config.get("expect_impl")
    if want is not None and system.impl != want:
        raise RuntimeError(f"the executor ran {system.impl!r}, not the "
                           f"{want!r} path this configuration measures")
    return split


@contextlib.contextmanager
def _host_load(notes: dict):
    """The process's CPU seconds while the body runs, and the collections
    of Python's garbage collector and their seconds, by generation:
    printed on stderr, to tell where a slow window lost its time."""
    n, spent, began = [0, 0, 0], [0.0, 0.0, 0.0], []

    def watch(phase, info):
        if phase == "start":
            began.append(time.perf_counter())
        elif began:
            g = info["generation"]
            n[g] += 1
            spent[g] += time.perf_counter() - began.pop()

    gc.callbacks.append(watch)
    cpu = time.process_time()
    try:
        yield notes
    finally:
        gc.callbacks.remove(watch)
        notes.update(cpu_s=time.process_time() - cpu, gc_n=n, gc_s=spent)


@contextlib.contextmanager
def _compiles(counts: dict):
    """Count the programs traced while the body runs, and the seconds of
    compiling them or loading them from the cache (a window should see
    none: set-up warms every shape)."""
    import jax
    from jax._src import dispatch

    def listen(event, duration, **_):
        if event == dispatch.JAXPR_TRACE_EVENT:
            counts["traced"] += 1
        elif event == dispatch.BACKEND_COMPILE_EVENT:
            counts["compile_s"] += duration

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield counts
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def window(cell, system, seed: int, seconds: float, traced: bool):
    """The measured window; with ``traced``, under the profiler. Returns
    ``(Window, Trace or None)``."""
    import jax

    fn = _closed_window if isinstance(system, systems.ServiceSystem) \
        else _grid_window
    annotate = _annotator(traced)
    counts = {"traced": 0, "compile_s": 0.0}
    if not traced:
        with _compiles(counts), _host_load(counts):
            w = fn(cell, system, seed, seconds, annotate)
        w.notes.update(counts)
        return w, None
    with tr.capture() as cap:
        with jax.profiler.TraceAnnotation(tr.WINDOW), _compiles(counts), \
                _host_load(counts):
            w = fn(cell, system, seed, seconds, annotate)
    w.notes.update(counts)
    try:
        t = tr.load(cap[0])
    finally:
        tr.discard(cap)
    return w, t


def per_layer(cell, win: Window, trace, kind: str) -> dict:
    ctx = SimpleNamespace(trace=trace, kind=kind, config=cell.config,
                          traffic=cell.traffic, work=win.work,
                          counters=win.counters, chips=cell.chips,
                          root=cell.root)
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(cell.root, m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run(cell, seed: int, seconds: float, traced: bool, t_process: float,
        devices, split: dict, out=sys.stdout, err=sys.stderr,
        executor_overrides=None) -> dict:
    """Set up, measure, check; print the split, the compared numbers and
    the result line, and return the result. ``executor_overrides`` (the
    tests' planted faults) switch on another path of the program."""
    import jax

    devices = list(devices)[:cell.chips]
    t = time.perf_counter()
    system = build(cell, seed, devices, executor_overrides)
    jax.block_until_ready(system.arrays())
    split["state_s"] = time.perf_counter() - t
    split.update(warm(cell, system))
    # every window starts from a collected heap, whatever set-up left
    t = time.perf_counter()
    gc.collect()
    split["gc_s"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_process
    split["setup_s"] = setup_s
    print("setup split: " + json.dumps(split), file=err, flush=True)

    try:
        win, trace = window(cell, system, seed, seconds, traced)
    finally:
        if isinstance(system, systems.ServiceSystem):
            system.close()
    peak = _peak_bytes(devices)
    gc.collect()
    numbers = win.check()
    limits = cell.config["limits"]
    correct = compare.judge(numbers, limits) and win.failed == 0
    for e in win.errors[:5]:
        print(f"failed scenario: {e}", file=err)
    print("window: " + json.dumps(win.notes), file=err)

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if traced:
        metrics = per_layer(cell, win, trace, d0.device_kind)
        busy = [tr.busy_ns(trace, d) * 1e-9 for d in sorted(trace.ops)]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = trace.window_s
    else:
        values = dict(win.metrics, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in values}
    compared = {k: {"value": v, "limit": limits[k]}
                for k, v in numbers.items()}
    result = {"correct": bool(correct), "attempted": win.attempted,
              "failed": win.failed, "metrics": metrics, "device": device}
    if traced:
        result["breakdown"] = tr.breakdown(trace)
    result["compared"] = compared
    for k, v in compared.items():
        print(f"compared {k} = {v['value']!r} limit {v['limit']!r}",
              file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return result
