"""The reduction from a device trace to numbers: on a trace recorded on
a v5e chip (two 8-step calls of 1024² bf16 Diffusion through the Pallas
kernel, each under a ``cellbench.call`` annotation) and on hand-made
intervals."""

from pathlib import Path

import pytest

from cellbench import trace as tr

RECORDED = Path(__file__).resolve().parent / "data" / "dense1k.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return tr.load(str(RECORDED))


def test_recorded_trace_has_one_device_and_the_window(recorded):
    assert sorted(recorded.ops) == [0]
    w0, w1 = recorded.window
    assert 0 < recorded.window_s < 1
    names = [n for n, _, _ in recorded.host]
    assert names.count("cellbench.window") == 1
    assert names.count("cellbench.call") == 2
    # device ops sit on the host's clock, inside the window
    ops = recorded.ops[0]
    assert min(s for _, s, _ in ops) >= w0
    assert max(e for _, _, e in ops) <= w1


def test_recorded_kernel_time_and_idle_share(recorded):
    kernel = tr.op_time_ns(recorded, 0, tr.is_kernel)
    busy = tr.busy_ns(recorded, 0)
    w = recorded.window[1] - recorded.window[0]
    # 2 calls x 2 fused 4-step passes of the Pallas kernel
    assert sum(tr.is_kernel(n) for n, _, _ in recorded.ops[0]) == 4
    assert 0 < kernel <= busy < w
    assert tr.idle_share(recorded) == pytest.approx(1 - busy / w)


def test_recorded_breakdown(recorded):
    b = tr.breakdown(recorded)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    names = [n for n, _ in b["device_ops"]]
    assert any(n.endswith("custom-call") for n in names)
    # loops enclose other ops and are left out of the list
    assert not any(n.endswith(" while") for n in names)
    assert {g[0] for g in b["idle_gaps"]} <= {"call", "outside any span"}
    secs = [s for _, s in b["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)


@pytest.mark.parametrize("name, op", [
    ("%_pallas_step.3 = bf16[16384,16384]{1,0:T(8,128)(2,1)} custom-call("
     "bf16[16384,16384]{1,0:T(8,128)(2,1)} %copy.11), custom_call_target="
     "\"tpu_custom_call\"", "custom-call"),
    ("%while.2 = (s32[]{:T(128)}, f32[8192,8192]{1,0:T(8,128)}) while((s32[]"
     "{:T(128)}, f32[8192,8192]{1,0:T(8,128)}) %tuple.36), condition=%c",
     "while"),
    ("%copy-start.1 = (pred[8192]{0:T(1024)(128)(4,1)S(1)}, u32[]{:S(2)}) "
     "copy-start(pred[8192]{0:T(1024)(128)(4,1)} %fusion.28)", "copy-start"),
    ("%collective-permute-done.3 = f32[4,24576]{1,0} collective-permute-done("
     "(f32[4,24576]{1,0}) %collective-permute-start.3)",
     "collective-permute-done"),
    ("ppermute.72", "ppermute"),
])
def test_opcode(name, op):
    assert tr.opcode(name) == op


def test_kinds_of_ops():
    k = ('%k.1 = f32[8]{0} custom-call(f32[8]{0} %a), '
         'custom_call_target="tpu_custom_call"')
    assert tr.is_kernel(k) and not tr.is_collective(k)
    assert tr.is_collective("%cp.1 = f32[8]{0} collective-permute-start("
                            "f32[8]{0} %a)")
    assert tr.is_collective("%ar = f32[] all-reduce(f32[] %a), to_apply=%s")
    assert not tr.is_collective("%f = f32[8]{0} fusion(f32[8]{0} %a)")
    assert tr.is_control("%w = (s32[]) while((s32[]) %t), body=%b")


def _trace(ops, host=(), window=(0.0, 100.0)):
    return tr.Trace(ops=ops, host=[("cellbench.window", *window)]
                    + list(host), window=window)


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.covered([(0, 10), (5, 15), (20, 21)]) == 16
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                       (7, 10)]
    assert tr.subtract([(0, 10)], [(0, 10)]) == []


def test_ops_are_clipped_to_the_window():
    t = _trace({0: [("%f = f32[] fusion()", -10.0, 10.0),
                    ("%g = f32[] fusion()", 90.0, 120.0),
                    ("%h = f32[] fusion()", 150.0, 160.0)]})
    assert [(s, e) for _, s, e in t.device_ops(0)] == [(0.0, 10.0),
                                                      (90.0, 100.0)]
    assert tr.busy_ns(t, 0) == 20.0


def test_the_checks_own_span_is_left_out_of_the_window():
    f = "%f = f32[8]{0} fusion(f32[8]{0} %a)"
    t = _trace({0: [(f, 30.0, 70.0)]},
               host=[("cellbench.sample", 40.0, 60.0)])
    assert t.spans == [(0.0, 40.0), (60.0, 100.0)]
    assert t.window_s == pytest.approx(80e-9)
    assert tr.busy_ns(t, 0) == 20.0
    assert tr.idle_share(t) == pytest.approx(0.75)
    assert all(g[0] != "sample" for g in tr.breakdown(t)["idle_gaps"])


def test_idle_share_averages_the_chips():
    t = _trace({0: [("%f = f32[] fusion()", 0.0, 50.0)],
                1: [("%f = f32[] fusion()", 0.0, 100.0)]})
    assert tr.idle_share(t) == pytest.approx(0.25)


def test_exposed_collective_share():
    cp = "%cp = f32[8]{0} collective-permute-done(f32[8]{0} %s)"
    f = "%f = f32[8]{0} fusion(f32[8]{0} %a)"
    loop = "%w = (s32[]) while((s32[]) %t), body=%b"
    t = _trace({0: [(loop, 0.0, 100.0), (cp, 10.0, 30.0), (f, 20.0, 40.0)],
                1: [(cp, 50.0, 90.0)]})
    # device 0: 10..20 exposed (the loop that encloses both is no compute)
    assert tr.exposed_collective_share(t, 0) == pytest.approx(0.10)
    assert tr.exposed_collective_share(t, 1) == pytest.approx(0.40)


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    f = "%f = f32[8]{0} fusion(f32[8]{0} %a)"
    t = _trace({0: [(f, 0.0, 40.0), (f, 60.0, 100.0)]},
               host=[("cellbench.call", 0.0, 100.0),
                     ("cellbench.wait", 45.0, 70.0)])
    b = tr.breakdown(t)
    assert b["idle_gaps"] == [["wait", pytest.approx(20e-9)]]
    assert b["device_ops"] == [["%f fusion", pytest.approx(80e-9)]]


def test_a_trace_without_one_window_is_refused():
    class Plane:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Event:
        def __init__(self, name, s, e):
            self.name, self.start_ns, self.end_ns = name, s, e
            self.stats = []

    class Data:
        planes = [Plane("/device:TPU:0", [Line("XLA Ops", [
            Event("%f = f32[] fusion()", 1.0, 2.0)])]),
            Plane("/host:CPU", [Line("python3", [])])]

    with pytest.raises(ValueError, match="window"):
        tr.from_profile(Data())
    Data.planes[1].lines[0].events.append(Event("cellbench.window", 0, 5))
    t = tr.from_profile(Data())
    assert t.window == (0, 5) and list(t.ops) == [0]
