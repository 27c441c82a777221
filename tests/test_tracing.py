"""Tracing subsystem: nested/threaded span recording, aggregation,
Chrome trace export, the framework's own phase instrumentation
(Model.execute / ShardMapExecutor / the served fetch path), and the
spans' annotations in a profiler capture."""

import json
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from mpi_model_tpu import CellularSpace, Diffusion, Model
from mpi_model_tpu.utils import Tracer, get_tracer, set_tracer, trace_span


def test_nested_spans_depth_and_duration():
    tr = Tracer()
    with tr.span("outer", job=1):
        with tr.span("inner"):
            pass
    inner, outer = tr.spans
    assert (inner.name, outer.name) == ("inner", "outer")
    assert inner.depth == 1 and outer.depth == 0
    assert 0 <= inner.duration_s <= outer.duration_s
    assert outer.meta == {"job": 1}
    # inner lies within outer
    assert outer.start_s <= inner.start_s
    assert (inner.start_s + inner.duration_s
            <= outer.start_s + outer.duration_s + 1e-9)


def test_span_recorded_on_exception():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")
    assert [s.name for s in tr.spans] == ["boom"]


def test_summary_aggregates():
    tr = Tracer()
    for _ in range(3):
        with tr.span("a"):
            pass
    with tr.span("b"):
        pass
    s = tr.summary()
    assert s["a"]["count"] == 3 and s["b"]["count"] == 1
    assert s["a"]["total_s"] >= s["a"]["max_s"] >= s["a"]["mean_s"] >= 0


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("a"):
        tr.instant("marker")
    assert tr.spans == []


def test_ring_buffer_bounds_memory():
    tr = Tracer(max_spans=5)
    for i in range(9):
        tr.instant("m", i=i)
    spans = tr.spans
    assert len(spans) == 5
    assert tr.dropped == 4
    assert [s.meta["i"] for s in spans] == [4, 5, 6, 7, 8]  # oldest dropped
    tr.clear()
    assert tr.spans == [] and tr.dropped == 0


def test_thread_safety_and_per_thread_nesting():
    tr = Tracer()
    # barrier keeps all 8 threads alive at once — thread idents are reused
    # after a thread exits, which would collapse the uniqueness check
    barrier = threading.Barrier(8)

    def work(i):
        barrier.wait(timeout=30)
        with tr.span("outer", i=i):
            with tr.span("inner", i=i):
                pass
        barrier.wait(timeout=30)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tr.spans
    assert len(spans) == 16
    # nesting depth is per-thread: every inner is depth 1, outer depth 0
    for s in spans:
        assert s.depth == (1 if s.name == "inner" else 0)
    assert len({s.thread for s in spans}) == 8


def test_chrome_export(tmp_path):
    tr = Tracer()
    with tr.span("phase", detail="x"):
        pass
    tr.instant("mark")
    path = tr.export_chrome(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    # a truncated trace must say so IN the artifact (ISSUE 15 satellite)
    assert doc["dropped"] == 0
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(spans) == 2
    for e in spans:
        assert set(e) >= {"name", "ph", "ts", "dur", "pid", "tid"}
    assert metas and metas[0]["name"] == "process_name"
    # span args carry the trace-context ids beside the user meta
    assert spans[0]["args"]["detail"] == "x"
    assert spans[0]["args"]["trace_id"] and spans[0]["args"]["span_id"]


def test_model_execute_emits_phases():
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        space = CellularSpace.create(8, 8, 1.0, dtype=jnp.float64)
        model = Model(Diffusion(0.1), 2.0, 1.0)
        model.execute(space)
    finally:
        set_tracer(prev)
    ex = next(s for s in tr.spans if s.name == "model.execute")
    assert ex.meta["steps"] == 2
    assert ex.meta["executor"] == "SerialExecutor"
    # one child span per host read of the device, in order, each nested
    # directly inside model.execute
    kids = sorted((s for s in tr.spans if s.parent_id == ex.span_id),
                  key=lambda s: s.start_s)
    assert [s.name for s in kids] == [
        "model.totals.initial", "executor.enqueue", "executor.block",
        "model.totals.final", "model.flows.last_execute"]
    assert all(s.depth == ex.depth + 1 for s in kids)
    for a, b in zip(kids, kids[1:]):
        assert a.start_s + a.duration_s <= b.start_s
    assert kids[0].start_s >= ex.start_s
    assert (kids[-1].start_s + kids[-1].duration_s
            <= ex.start_s + ex.duration_s)
    # the totals spans count the scalars read; one flow's last value
    assert kids[0].meta == {"channels": 1} == kids[3].meta
    assert kids[4].meta == {"flows": 1}


def test_shardmap_executor_emits_build_phase(eight_devices):
    from mpi_model_tpu.parallel import ShardMapExecutor, make_mesh

    tr = Tracer()
    prev = set_tracer(tr)
    try:
        mesh = make_mesh(4, devices=eight_devices[:4])
        space = CellularSpace.create(16, 16, 1.0, dtype=jnp.float64)
        model = Model(Diffusion(0.1), 1.0, 1.0)
        out, _ = model.execute(space, ShardMapExecutor(mesh))
        assert np.isfinite(np.asarray(out.values["value"])).all()
    finally:
        set_tracer(prev)
    builds = [s for s in tr.spans if s.name == "shardmap.build"]
    assert len(builds) == 1 and builds[0].meta["impl"] == "xla"


def test_trace_span_uses_current_default():
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        with trace_span("x"):
            pass
        assert get_tracer() is tr
    finally:
        set_tracer(prev)
    assert [s.name for s in tr.spans] == ["x"]


def test_trace_context_ids_nest_and_propagate():
    tr = Tracer()
    with tr.span("outer") as meta:
        meta["k"] = 1
        ctx = tr.current()
        with tr.span("inner"):
            pass
    outer = next(s for s in tr.spans if s.name == "outer")
    inner = next(s for s in tr.spans if s.name == "inner")
    assert outer.meta == {"k": 1}  # values set inside the block land
    assert outer.span_id == ctx.span_id
    assert outer.parent_id is None
    assert inner.trace_id == outer.trace_id
    assert inner.parent_id == outer.span_id
    assert inner.span_id != outer.span_id


def test_attach_adopts_a_remote_context():
    from mpi_model_tpu.utils.tracing import TraceContext

    tr = Tracer()
    with tr.span("root"):
        wire_meta = tr.current().to_meta()  # what crosses the frame
    ctx = TraceContext.from_meta(wire_meta)
    with tr.attach(ctx):
        with tr.span("remote-child"):
            pass
    root = next(s for s in tr.spans if s.name == "root")
    child = next(s for s in tr.spans if s.name == "remote-child")
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    # None-safe: a frame without trace meta attaches nothing
    assert TraceContext.from_meta(None) is None
    assert TraceContext.from_meta({"trace_id": 1}) is None
    with tr.attach(None):
        assert tr.current() is None


def test_explicit_parent_overrides_thread_context():
    tr = Tracer()
    with tr.span("ticket-submit"):
        ticket_ctx = tr.current()
    with tr.span("pump-iteration"):
        with tr.span("dispatch", parent=ticket_ctx):
            pass
    dispatch = next(s for s in tr.spans if s.name == "dispatch")
    submit = next(s for s in tr.spans if s.name == "ticket-submit")
    assert dispatch.parent_id == submit.span_id
    assert dispatch.trace_id == submit.trace_id


def test_spans_since_and_ingest_roundtrip():
    tr = Tracer()
    with tr.span("a"):
        pass
    cur, delta = tr.spans_since(0)
    assert [d["name"] for d in delta] == ["a"]
    cur2, delta2 = tr.spans_since(cur)
    assert delta2 == [] and cur2 == cur
    with tr.span("b"):
        pass
    _, delta3 = tr.spans_since(cur)
    assert [d["name"] for d in delta3] == ["b"]
    # ingest into another tracer: same-pid spans are SKIPPED (the
    # loopback transport shares the process tracer — shipping them
    # back must not duplicate), foreign pids merge in labeled
    tr2 = Tracer()
    assert tr2.ingest(delta) == 0
    foreign = [dict(d, pid=999_999) for d in delta]
    assert tr2.ingest(foreign, label="m3g1") == 1
    s = tr2.spans[0]
    assert s.pid == 999_999 and s.name == "a"
    events = tr2.chrome_events()
    names = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert "m3g1" in names


def test_summary_surfaces_dropped_and_percentiles():
    tr = Tracer(max_spans=2)
    for _ in range(4):
        with tr.span("x"):
            pass
    s = tr.summary()
    assert s["__tracer__"] == {"dropped": 2, "recorded": 2}
    assert s["x"]["count"] == 2
    assert 0 <= s["x"]["p50_s"] <= s["x"]["p99_s"] <= s["x"]["max_s"]
    # the chrome artifact says it too
    import tempfile, os
    path = os.path.join(tempfile.mkdtemp(), "t.json")
    tr.export_chrome(path)
    with open(path) as f:
        assert json.load(f)["dropped"] == 2


def _annotations(capture_dir):
    """The ``mm.*`` host events of a profiler capture: (name, start,
    end), in start order."""
    import glob
    import os

    import jax

    path = glob.glob(os.path.join(capture_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    data = jax.profiler.ProfileData.from_file(path)
    return sorted(((e.name, e.start_ns, e.end_ns)
                   for plane in data.planes for line in plane.lines
                   for e in line.events if e.name.startswith("mm.")),
                  key=lambda e: e[1])


def test_spans_land_in_a_profiler_capture_as_nested_annotations(tmp_path):
    import jax

    tr, off = Tracer(), Tracer(enabled=False)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tr.span("outer", job=1):
            with tr.span("inner"):
                jnp.ones(4).block_until_ready()
        with off.span("quiet"):
            pass
    finally:
        jax.profiler.stop_trace()
    got = _annotations(str(tmp_path))
    # the name only (meta stays in the ring); a disabled tracer: nothing
    assert [n for n, _, _ in got] == ["mm.outer", "mm.inner"]
    (_, o0, o1), (_, i0, i1) = got
    assert o0 <= i0 < i1 <= o1
    inner, outer = tr.spans
    # each annotation encloses its span: the same lengths, to the
    # annotation's own cost
    assert i1 - i0 >= inner.duration_s * 1e9
    assert o1 - o0 >= outer.duration_s * 1e9


def test_complete_ensemble_emits_fetch_spans_in_order():
    from mpi_model_tpu.ensemble import EnsembleService

    tr = Tracer()
    prev = set_tracer(tr)
    try:
        svc = EnsembleService(Model(Diffusion(0.1), 2.0, 1.0),
                              steps=2, max_batch=2)
        tickets = [svc.submit(CellularSpace.create(8, 8, 1.0,
                                                   dtype=jnp.float64))
                   for _ in range(2)]
        for t in tickets:
            svc.result(t)
    finally:
        set_tracer(prev)
    fetch = next(s for s in tr.spans if s.name == "ensemble.fetch")
    kids = sorted((s for s in tr.spans if s.parent_id == fetch.span_id),
                  key=lambda s: s.start_s)
    assert [s.name for s in kids] == [
        "ensemble.fetch.block", "ensemble.fetch.totals",
        "ensemble.fetch.lanes"]
    assert all(s.depth == fetch.depth + 1
               and s.trace_id == fetch.trace_id for s in kids)
    for a, b in zip(kids, kids[1:]):
        assert a.start_s + a.duration_s <= b.start_s
    assert kids[1].meta == {"lanes": 2} == kids[2].meta


def test_submit_span_parents_its_dispatch_spans():
    from mpi_model_tpu.ensemble import EnsembleService

    tr = Tracer()
    prev = set_tracer(tr)
    try:
        svc = EnsembleService(Model(Diffusion(0.1), 2.0, 1.0),
                              steps=2, max_batch=1)
        t = svc.submit(CellularSpace.create(8, 8, 1.0, dtype=jnp.float64))
        svc.result(t)
    finally:
        set_tracer(prev)
    sub = next(s for s in tr.spans if s.name == "ensemble.submit")
    # no caller span: the submit span roots the ticket's trace
    assert sub.meta == {"ticket": t} and sub.parent_id is None
    for name in ("ensemble.assemble", "ensemble.launch", "ensemble.fetch"):
        sp = next(s for s in tr.spans if s.name == name)
        assert sp.parent_id == sub.span_id
        assert sp.trace_id == sub.trace_id
        assert sp.start_s >= sub.start_s + sub.duration_s


def test_supervised_run_emits_chunk_and_failure_spans():
    from mpi_model_tpu import CellularSpace, Diffusion, Model, supervised_run
    from mpi_model_tpu.models.model import SerialExecutor

    class OnceFaulty:
        comm_size = 1

        def __init__(self):
            self.n = 0
            self.inner = SerialExecutor()

        def run_model(self, m, s, k):
            self.n += 1
            if self.n == 2:
                raise RuntimeError("injected")
            return self.inner.run_model(m, s, k)

    tr = Tracer()
    prev = set_tracer(tr)
    try:
        space = CellularSpace.create(8, 8, 1.0, dtype=jnp.float64)
        res = supervised_run(Model(Diffusion(0.1), 4.0, 1.0), space,
                             steps=4, every=2, executor=OnceFaulty())
    finally:
        set_tracer(prev)
    assert res.recovered_failures == 1
    names = [s.name for s in tr.spans]
    assert names.count("supervise.chunk") == 3  # 2 good + 1 failed attempt
    fails = [s for s in tr.spans if s.name == "supervise.failure"]
    assert len(fails) == 1 and fails[0].meta["kind"] == "exception"
