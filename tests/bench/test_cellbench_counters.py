"""The program's counters in a grid cell: a sparse-front cell added as
files alone (a ``squares`` initial state, the active-tile engines, a
reader of ``report.*`` counters) runs correct and reads what its engine
did; the same cell with a planted fault reads not correct; and the
horizon loop keeps each call's report by one list append, calling
nothing else that it did not call before."""

import collections
import functools
import io
import json
import sys
import time
from pathlib import Path

import jax
import pytest

from cellbench import harness, spec, traffic

from cellbench_fixtures import copy_benchmark, cpu_peaks, tiny_root
from test_cellbench_faults import _state_unchanged

SEED = 2**33 + 9
STEPS_PER_CALL = 3
#: calls in a window on the fake clock below
CALLS = 3


def _add_sparse(root: Path, step_impl: str) -> None:
    """A sparse-front configuration, a traffic mix and a reader of the
    program's counters, as new files and entries."""
    (root / "cellbench" / "configs" / "sparse.json").write_text(json.dumps({
        "name": "sparse", "model": {"kind": "diffusion", "rate": 0.1},
        "grid": [128, 128], "dtype": "float32",
        "init": {"value": {"maker": "squares", "centres": [[0.5, 0.5]],
                           "area": 0.01, "value": [0.5, 2.0]}},
        "executor": {"kind": "serial", "step_impl": step_impl,
                     "active_opts": {"tile": [8, 8],
                                     "max_active_frac": 0.5}},
        "expect_impl": step_impl, "reference": "diffusion",
        "control": {"kind": "reference", "store": "bfloat16"},
        "limits": {"max_abs_err": 1e-4}, "tiny": {}}))
    (root / "cellbench" / "traffic" / "horizon3.json").write_text(
        json.dumps({"loop": "horizon", "steps_per_call": STEPS_PER_CALL}))
    (root / "cellbench" / "metrics" / "active_tile_pct.py").write_text(
        "def read(ctx):\n"
        "    c = ctx.counters\n"
        "    if 'report.mean_active_fraction' not in c:\n"
        "        return None\n"
        "    return 100.0 * c['report.mean_active_fraction'] / c['calls']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "sparse", "source": "x",
                             "file": "cellbench/configs/sparse.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "sparse.h3", "config": "sparse",
                               "traffic": "horizon3", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][0]["workloads"].append("sparse.h3")
    bench["per_layer"].append({"name": "active_tile_pct", "unit": "%",
                               "better": "lower",
                               "source": "program_counter",
                               "layer": "kernels",
                               "moves": "cell_updates_per_s",
                               "workloads": ["sparse.h3"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def _sparse_cell(tmp: Path, step_impl: str):
    src = copy_benchmark(tmp / "src")
    _add_sparse(src, step_impl)
    return spec.load_cell(tiny_root(tmp / "tiny", src), "sparse.h3")


def _fake_clock(monkeypatch, dt=0.01):
    """Each read of the horizon loop's clock moves it ``dt`` on, so every
    call takes ``dt`` and a window of ``CALLS * dt`` holds ``CALLS``."""
    t = iter(range(10**6))
    monkeypatch.setattr(traffic, "horizon", functools.partial(
        traffic.horizon, clock=lambda: dt * next(t)))


def _run(cell, traced):
    out, err = io.StringIO(), io.StringIO()
    harness.run(cell, SEED, CALLS * 0.01 - 1e-6, traced, time.perf_counter(),
                jax.devices(), {}, out=out, err=err)
    notes = next(line for line in err.getvalue().splitlines()
                 if line.startswith("window: "))
    return (json.loads(out.getvalue().strip().splitlines()[-1]),
            json.loads(notes[len("window: "):]))


@pytest.mark.parametrize("step_impl", ["active", "active_fused"])
def test_a_sparse_cell_added_as_files_reads_its_engines_counters(
        tmp_path, monkeypatch, step_impl):
    cpu_peaks(monkeypatch)
    _fake_clock(monkeypatch)
    line, notes = _run(_sparse_cell(tmp_path, step_impl), traced=True)
    assert line["correct"] is True, line["compared"]
    assert notes["calls"] == line["attempted"] == CALLS
    assert notes["report.steps"] == CALLS * STEPS_PER_CALL
    assert notes["report.fallback_steps"] == 0
    assert 0 < notes["report.mean_active_fraction"] / CALLS < 1
    # strings and lists of the report are left out
    assert "report.impl" not in notes and "report.tile" not in notes
    pct = line["metrics"]["active_tile_pct"]
    assert pct["unit"] == "%" and 0 < pct["value"] < 100


def test_the_sparse_cell_with_its_state_unchanged_is_not_correct(
        tmp_path, monkeypatch):
    _fake_clock(monkeypatch)
    _state_unchanged(monkeypatch)
    line, notes = _run(_sparse_cell(tmp_path, "active"), traced=False)
    assert notes["report.steps"] == CALLS * STEPS_PER_CALL
    assert line["correct"] is False, line["compared"]


def test_counters_sum_numbers_and_leave_out_the_rest():
    class Rep:
        def __init__(self, backend_report):
            self.backend_report = backend_report

    got = harness.program_counters([
        Rep({"steps": 32, "frac": 0.25, "impl": "active", "tile": [8, 8],
             "fused": True, "k": None}),
        Rep(None),
        Rep({"steps": 32, "frac": 0.5})])
    assert got == {"calls": 3, "report.steps": 64, "report.frac": 0.75}


#: what the horizon loop called, directly, before it kept the reports:
#: per call, the clock twice, the (untraced) annotation and its context
#: manager, and the program; once, the check's ``take`` (under its own
#: annotation) and the result's constructor
def parent_calls(n):
    return collections.Counter(
        perf_counter=2 * n, no_span=n + 1, __enter__=n + 1, __exit__=n + 1,
        call=n, take=1, __init__=1)


def test_the_horizon_loop_adds_one_append_and_nothing_else(tmp_path):
    root = tiny_root(tmp_path)
    cell = spec.load_cell(root, "grayscott8k-ir-horizon")
    system = harness.build(cell, SEED, jax.devices()[:1])
    harness.warm(cell, system)
    code = traffic.horizon.__code__
    seen = collections.Counter()

    def profile(frame, event, arg):
        if event == "c_call" and frame.f_code is code:
            seen[arg.__name__] += 1
        elif (event == "call" and frame.f_back is not None
              and frame.f_back.f_code is code):
            seen[frame.f_code.co_name] += 1

    sys.setprofile(profile)
    try:
        win, _ = harness.window(cell, system, SEED, 0.05, False)
    finally:
        sys.setprofile(None)
    n = win.attempted
    assert n >= 1 and win.counters["calls"] == n
    assert seen == parent_calls(n) + collections.Counter(append=n)
