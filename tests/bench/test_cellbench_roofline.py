"""Roofline arithmetic, the peak table, and the operation counts that the
roofline readers take from the models' equations."""

from types import SimpleNamespace

import pytest

from cellbench import roofline, spec
from cellbench import trace as tr

from cellbench_fixtures import REPO

KIND = "TPU v5 lite"


def test_every_peak_names_its_source():
    for kind, peaks in roofline.PEAKS.items():
        assert {"hbm_bytes_per_s", "vpu_f32_ops_per_s"} <= set(peaks)
        for name, (value, source) in peaks.items():
            assert value > 0 and source.strip(), (kind, name)
    v5e = roofline.PEAKS[KIND]
    assert v5e["hbm_bytes_per_s"][0] == 819e9
    assert v5e["vpu_f32_ops_per_s"][1].startswith("measured")


def test_least_time_takes_the_larger_bound():
    t, bound = roofline.least_time_s(4.0e12, 1.0, KIND)
    assert bound == "compute"
    assert t == pytest.approx(4.0e12 / roofline.peak(KIND,
                                                     "vpu_f32_ops_per_s"))
    t, bound = roofline.least_time_s(1.0, 819e9, KIND)
    assert (t, bound) == (pytest.approx(1.0), "memory")


def test_an_unknown_chip_has_no_peak():
    with pytest.raises(KeyError, match="no peak"):
        roofline.least_time_s(1.0, 1.0, "TPU v9 imaginary")
    with pytest.raises(KeyError, match="no peak"):
        roofline.least_time_s(1.0, 1.0, KIND, compute="no_such_peak")


def _reader(name):
    return spec.metric_reader(str(REPO), name)


def _module(name):
    import importlib.util

    p = REPO / "cellbench" / "metrics" / f"{name}.py"
    s = importlib.util.spec_from_file_location("m_" + name, p)
    m = importlib.util.module_from_spec(s)
    s.loader.exec_module(m)
    return m


def test_operation_counts_follow_the_equations():
    # Moore-8 Diffusion: rate*v, /count, 7 adds over 8 shares, 2 updates
    assert _module("stencil_roofline").OPS_PER_CELL_STEP == 11
    # Gray-Scott: two transports, reaction 4, feed 4, kill 3
    assert _module("ir_step_roofline").OPS_PER_CELL_STEP == 33


def _ctx(ops, model, cells=1 << 20, steps=32, calls=10, state_bytes=None):
    trace = tr.Trace(ops=ops, host=[], window=(0.0, 1e9))
    work = {"cells": cells, "steps_per_call": steps, "calls": calls,
            "state_bytes": state_bytes or 2 * cells}
    return SimpleNamespace(trace=trace, kind=KIND, work=work,
                           config={"model": model}, counters={}, chips=1)


KERNEL = ('%_pallas_step.3 = bf16[8]{0} custom-call(bf16[8]{0} %c), '
          'custom_call_target="tpu_custom_call"')
FUSION = "%f = f32[8]{0} fusion(f32[8]{0} %a)"


def test_stencil_roofline_reads_the_kernels_only():
    ctx = _ctx({0: [(KERNEL, 0.0, 2e8), (FUSION, 2e8, 9e8)]},
               {"kind": "diffusion", "rate": 0.1})
    ops = 11 * (1 << 20) * 32 * 10
    want = 100 * roofline.least_time_s(ops, 2 * 2 * (1 << 20) * 10,
                                       KIND)[0] / 0.2
    assert _reader("stencil_roofline")(ctx) == pytest.approx(want)


def test_stencil_roofline_splits_the_work_over_chips():
    one = _ctx({0: [(KERNEL, 0.0, 4e8)]}, {"kind": "diffusion"})
    four = _ctx({d: [(KERNEL, 0.0, 1e8)] for d in range(4)},
                {"kind": "diffusion"})
    r = _reader("stencil_roofline")
    assert r(four) == pytest.approx(r(one))


def test_roofline_readers_with_nothing_to_read_return_none():
    r = _reader("stencil_roofline")
    assert r(_ctx({0: [(FUSION, 0.0, 1e8)]}, {"kind": "diffusion"})) is None
    assert r(_ctx({0: [(KERNEL, 0.0, 1e8)]},
                  {"kind": "flow_ir", "name": "gray_scott"})) is None
    ir = _reader("ir_step_roofline")
    assert ir(_ctx({0: [(KERNEL, 0.0, 1e8)]}, {"kind": "diffusion"})) is None
    ctx = _ctx({0: [(FUSION, 0.0, 1e8)]}, {"kind": "diffusion"})
    ctx.trace = None
    assert r(ctx) is None and _reader("device_idle_pct.grid")(ctx) is None


def test_ir_roofline_reads_the_busy_time():
    ctx = _ctx({0: [(FUSION, 0.0, 5e8), (FUSION, 2e8, 6e8)]},
               {"kind": "flow_ir", "name": "gray_scott"},
               state_bytes=16 * (1 << 20))
    ops = 33 * (1 << 20) * 32 * 10
    want = 100 * roofline.least_time_s(ops, 2 * 16 * (1 << 20) * 10,
                                       KIND)[0] / 0.6
    assert _reader("ir_step_roofline")(ctx) == pytest.approx(want)


def test_step_mfu_times_the_whole_step():
    r = _reader("step_mfu.grid")
    # busy 0.9 s: the kernel's 0.2 s and the fusion's 0.7 s
    ctx = _ctx({0: [(KERNEL, 0.0, 2e8), (FUSION, 2e8, 9e8)]},
               {"kind": "diffusion", "rate": 0.1})
    ctx.root = str(REPO)
    ctx.config["ops_counted_by"] = "stencil_roofline"
    ops = 11 * (1 << 20) * 32 * 10
    want = 100 * roofline.least_time_s(ops, 2 * 2 * (1 << 20) * 10,
                                       KIND)[0] / 0.9
    assert r(ctx) == pytest.approx(want)
    assert r(ctx) < _reader("stencil_roofline")(ctx)
    # four chips share the work; their busy times add up
    four = _ctx({d: [(KERNEL, 0.0, 1e8), (FUSION, 1e8, 2.25e8)]
                 for d in range(4)}, {"kind": "diffusion"})
    four.root = str(REPO)
    four.config["ops_counted_by"] = "stencil_roofline"
    assert r(four) == pytest.approx(want)
    # the counts are those of the reader the configuration names
    gs = _ctx({0: [(FUSION, 0.0, 5e8), (FUSION, 2e8, 6e8)]},
              {"kind": "flow_ir", "name": "gray_scott"},
              state_bytes=16 * (1 << 20))
    gs.root = str(REPO)
    gs.config["ops_counted_by"] = "ir_step_roofline"
    assert r(gs) == pytest.approx(_reader("ir_step_roofline")(gs))
    # no counts named, or no trace: nothing to read
    other = _ctx({0: [(FUSION, 0.0, 1e8)]}, {"kind": "flow_ir",
                                             "name": "sir"})
    other.root = str(REPO)
    assert r(other) is None
    ctx.trace = None
    assert r(ctx) is None


def test_batch_fill_and_idle_readers():
    ctx = _ctx({0: [(FUSION, 0.0, 2.5e8)]}, {"kind": "diffusion"})
    ctx.counters = {"scenarios": 30, "lanes": 40, "dispatches": 5}
    assert _reader("batch_fill_pct")(ctx) == pytest.approx(75.0)
    assert _reader("device_idle_pct.sweep")(ctx) == pytest.approx(75.0)
    ctx.counters = {"scenarios": 0, "lanes": 0}
    assert _reader("batch_fill_pct")(ctx) is None


def test_p95_reader_takes_every_latency():
    ctx = _ctx({0: []}, {"kind": "diffusion"})
    ctx.work = {"latencies_s": [i / 1000 for i in range(1, 101)]}
    assert _reader("scenario_p95_ms")(ctx) == pytest.approx(95.05)
    ctx.work = {"latencies_s": []}
    assert _reader("scenario_p95_ms")(ctx) is None


def test_halo_reader_needs_a_collective():
    r = _reader("halo_exposed_pct")
    cp = "%cp = f32[8]{0} collective-permute-done(f32[8]{0} %s)"
    assert r(_ctx({0: [(FUSION, 0.0, 1e8)]}, {"kind": "diffusion"})) is None
    ctx = _ctx({0: [(cp, 0.0, 1e8)], 1: [(cp, 0.0, 3e8), (FUSION, 0, 1e8)]},
               {"kind": "diffusion"})
    assert r(ctx) == pytest.approx(20.0)
