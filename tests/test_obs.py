"""Ticket-flight observability (ISSUE 15): trace-context propagation
through the serving stack (including across the loopback wire), the
unified telemetry plane (snapshot schema + Prometheus exposition), the
flight recorder, and post-mortem timeline reconstruction — with the
subprocess-free loopback hard-stop row as the in-tier-1 acceptance leg
(every served ticket reconstructs a complete, gap-annotated timeline;
an in-flight-at-kill ticket shows an explicit uncertainty record)."""

import json
import warnings

import numpy as np
import jax.numpy as jnp
import pytest

from mpi_model_tpu import CellularSpace, Diffusion, Model, obs
from mpi_model_tpu.ensemble import AsyncEnsembleService, FleetSupervisor
from mpi_model_tpu.ensemble.member_proc import spawn_loopback_member
from mpi_model_tpu.obs.flight import FlightRecorder, set_recorder
from mpi_model_tpu.obs.postmortem import spans_from_chrome
from mpi_model_tpu.resilience import inject
from mpi_model_tpu.resilience.inject import Fault, FaultPlan
from mpi_model_tpu.utils.metrics import LatencyReservoir
from mpi_model_tpu.utils.tracing import Tracer, set_tracer


def scen_space(i, g=16, dtype=jnp.float64):
    rng = np.random.default_rng((61, i, g))
    v = jnp.asarray(rng.uniform(0.5, 2.0, (g, g)), dtype)
    return CellularSpace.create(g, g, 1.0, dtype=dtype).with_values(
        {"value": v})


def scen_model(i=0):
    return Model(Diffusion(0.05 + 0.01 * i), 4.0, 1.0)


@pytest.fixture
def fresh_obs():
    """A private tracer + flight recorder for the test (the process
    defaults are shared state; tests must not read each other's
    spans/dumps)."""
    tr, rec = Tracer(), FlightRecorder()
    prev_tr, prev_rec = set_tracer(tr), set_recorder(rec)
    try:
        yield tr, rec
    finally:
        set_tracer(prev_tr)
        set_recorder(prev_rec)


# -- LatencyReservoir (the dedup satellite) -----------------------------------

def test_latency_reservoir_bounded_and_percentiles():
    r = LatencyReservoir(maxlen=4)
    for v in (5.0, 1.0, 2.0, 3.0, 4.0):  # the 5.0 ages out
        r.record(v)
    assert len(r) == 4
    snap = r.snapshot("lat")
    assert snap["lat_n"] == 4
    assert snap["lat_p50_s"] in (2.0, 3.0)
    assert snap["lat_p99_s"] == 4.0
    assert LatencyReservoir.percentile_of([], 0.5) is None
    empty = LatencyReservoir().snapshot("x")
    assert empty == {"x_n": 0, "x_p50_s": None, "x_p99_s": None}


def test_counter_reservoirs_share_the_implementation():
    from mpi_model_tpu.utils.metrics import ThroughputCounter

    c = ThroughputCounter()
    assert isinstance(c._latencies, LatencyReservoir)
    assert isinstance(c._wake_latencies, LatencyReservoir)
    c.record_latency(0.25)
    c.record_wake_latency(0.5)
    s = c.snapshot()
    assert s["latency_p50_s"] == 0.25 and s["latency_n"] == 1
    assert s["wake_latency_p99_s"] == 0.5 and s["wake_latency_n"] == 1


# -- flight recorder ----------------------------------------------------------

def test_flight_recorder_ring_is_bounded_per_service():
    rec = FlightRecorder(capacity=3)
    for i in range(5):
        rec.record("submit", service_id="m0g0", ticket=i)
    ring = rec.snapshot("m0g0")
    assert [e["ticket"] for e in ring] == [2, 3, 4]


def test_flight_recorder_dump_merges_service_and_fleet_rings(tmp_path):
    rec = FlightRecorder(dump_dir=str(tmp_path))
    rec.record("submit", service_id=None, ticket=1)   # fleet ring
    rec.record("dispatch", service_id="m0g0", ticket=1)
    d = rec.dump("quarantine", service_id="m0g0", ticket=1)
    kinds = [e["kind"] for e in d["events"]]
    assert kinds == ["submit", "dispatch"]  # time-ordered, both rings
    assert d["path"] is not None
    with open(d["path"]) as fh:
        on_disk = json.load(fh)
    assert on_disk["reason"] == "quarantine"
    assert rec.dumps[-1] is d


def test_flight_recorder_dump_list_is_bounded():
    rec = FlightRecorder(max_dumps=2)
    for i in range(4):
        rec.dump(f"r{i}")
    assert [d["reason"] for d in rec.dumps] == ["r2", "r3"]


def test_quarantine_dumps_the_flight_recorder(fresh_obs):
    """A scenario whose solo retry also fails quarantines — and the
    flight recorder dumps beside its FailureEvent, ring holding the
    ticket's lifecycle run-up."""
    _, rec = fresh_obs
    svc = AsyncEnsembleService(scen_model(), steps=4, start=False,
                               retry="solo")
    with inject.armed(FaultPlan(
            (Fault("lane_nan", lane=0, once=False),))):
        t = svc.submit(scen_space(0))
        with pytest.raises(Exception):
            svc.result(t)
    svc.stop()
    assert any(d["reason"] == "quarantine" for d in rec.dumps)
    d = next(d for d in rec.dumps if d["reason"] == "quarantine")
    assert any(e["kind"] == "submit" and e["ticket"] == t
               for e in d["events"])


# -- the telemetry plane ------------------------------------------------------

def test_snapshot_validates_for_service_and_fleet(fresh_obs, tmp_path):
    svc = AsyncEnsembleService(scen_model(), steps=4, start=False)
    t = svc.submit(scen_space(0))
    svc.result(t)
    svc.stop()
    doc = obs.fleet_snapshot(svc)
    obs.validate_snapshot(doc)
    assert doc["stats"]["scenarios"] == 1
    assert doc["tracer"]["dropped"] == 0
    assert "ensemble.launch" in doc["tracer"]["stages"]

    fleet = FleetSupervisor(scen_model(), services=2, steps=4,
                            start=False)
    t = fleet.submit(scen_space(0))
    fleet.result(t)
    fleet.stop()
    path = str(tmp_path / "snap.json")
    doc2 = obs.write_snapshot(path, fleet)
    obs.validate_snapshot(doc2)
    with open(path) as fh:
        obs.validate_snapshot(json.load(fh))
    assert doc2["stats"]["members"] == 2
    # the per-stage rollup carries reservoir-style percentiles
    st = doc2["tracer"]["stages"]["fleet.submit"]
    assert st["count"] == 1 and st["p50_s"] >= 0


def test_snapshot_schema_gate_names_the_missing_field():
    with pytest.raises(ValueError, match="schema"):
        obs.validate_snapshot({"stats": {}})
    doc = {"schema": obs.SCHEMA, "generated_unix_s": 0.0,
           "stats": {"dispatches": 0}, "tracer": {},
           "flight_recorder": {}}
    with pytest.raises(ValueError, match="scenarios"):
        obs.validate_snapshot(doc)


def test_prometheus_exposition_covers_counters_per_member(fresh_obs):
    fleet = FleetSupervisor(scen_model(), services=2, steps=4,
                            start=False)
    t = fleet.submit(scen_space(0))
    fleet.result(t)
    st = fleet.stats()
    fleet.stop()
    text = obs.prometheus_text(st)
    from mpi_model_tpu.utils.metrics import ThroughputCounter

    # every ThroughputCounter counter that made it into the cut is
    # exposed (the scrape contract)
    for name in ThroughputCounter.COUNTERS:
        if name in st:
            assert f"mpi_model_tpu_{name}" in text, name
    assert "# TYPE mpi_model_tpu_scenarios counter" in text
    assert 'service_id="m0g0"' in text and 'service_id="m1g0"' in text
    # gauges typed as gauges
    assert "# TYPE mpi_model_tpu_pending gauge" in text


def test_run_soak_dumps_snapshots_on_an_interval(fresh_obs, tmp_path):
    from mpi_model_tpu.ensemble import run_soak

    clock = {"t": 0.0}

    def fake_sleep(dt):
        clock["t"] += dt

    path = str(tmp_path / "soak-snap.json")
    svc = AsyncEnsembleService(scen_model(), steps=4, start=False,
                               clock=lambda: clock["t"])
    scen = [(scen_space(i), None, None) for i in range(6)]
    rep = run_soak(svc, scen, arrival_rate_hz=1.0,
                   clock=lambda: clock["t"], sleep=fake_sleep,
                   snapshot_path=path, snapshot_interval_s=2.0)
    svc.stop()
    assert rep["ledger_complete"] and rep["served"] == 6
    assert rep["telemetry_snapshot"] == path
    with open(path) as fh:
        doc = json.load(fh)
    obs.validate_snapshot(doc)
    assert doc["stats"]["scenarios"] == 6  # the final cut


# -- trace-context propagation ------------------------------------------------

def _submit_parent(tr, span):
    """The ``ensemble.submit`` span that parents a dispatch span: the
    scheduler's span of the ticket, itself under the caller's span."""
    return next(s for s in tr.spans if s.name == "ensemble.submit"
                and s.span_id == span.parent_id)


def test_dispatch_spans_parent_under_fleet_submit_span(fresh_obs):
    tr, _ = fresh_obs
    fleet = FleetSupervisor(scen_model(), services=2, steps=4,
                            start=False)
    t = fleet.submit(scen_space(0))
    fleet.result(t)
    fleet.stop()
    sub = next(s for s in tr.spans if s.name == "fleet.submit")
    assert sub.meta["ticket"] == t
    for name in ("ensemble.assemble", "ensemble.launch",
                 "ensemble.fetch"):
        sp = next(s for s in tr.spans if s.name == name)
        assert sp.trace_id == sub.trace_id
        esub = _submit_parent(tr, sp)
        assert esub.parent_id == sub.span_id
        assert esub.meta["ticket"] in sp.meta["tickets"]
        assert t in sp.meta["tickets"]


def test_dispatch_spans_parent_across_the_loopback_wire(fresh_obs):
    """The cross-process half of the tentpole, subprocess-free: the
    trace context crosses the wire IN the submit frame's meta (encode →
    CRC → decode → attach), so member-side dispatch spans parent under
    the fleet-side submit span even though the submission was admitted
    by a MemberServer reading frames off a socketpair."""
    tr, _ = fresh_obs
    fleet = FleetSupervisor(scen_model(), services=2, steps=4,
                            start=False, member_transport="process",
                            member_spawner=spawn_loopback_member)
    t = fleet.submit(scen_space(0))
    fleet.result(t)
    fleet.stop()
    sub = next(s for s in tr.spans if s.name == "fleet.submit")
    launch = next(s for s in tr.spans if s.name == "ensemble.launch")
    assert launch.trace_id == sub.trace_id
    assert _submit_parent(tr, launch).parent_id == sub.span_id


def test_wake_spans_join_the_tickets_trace(fresh_obs, tmp_path):
    """A ticket that hibernates and wakes keeps ONE trace: the
    tiering.hibernate/tiering.wake spans parent under its submit
    span."""
    tr, _ = fresh_obs
    nb = int(scen_space(0).values["value"].nbytes)
    fleet = FleetSupervisor(scen_model(), services=1, steps=4,
                            start=False, max_queue=1,
                            residency_budget=nb,
                            hibernate_dir=str(tmp_path / "vault"))
    t0 = fleet.submit(scen_space(0))
    t1 = fleet.submit(scen_space(1))  # no room: hibernates
    assert fleet.result(t0) is not None
    assert fleet.result(t1) is not None
    fleet.stop()
    subs = {s.meta.get("ticket"): s for s in tr.spans
            if s.name == "fleet.submit"}
    wake = next(s for s in tr.spans if s.name == "tiering.wake")
    hib = next(s for s in tr.spans if s.name == "tiering.hibernate")
    assert hib.trace_id == subs[t1].trace_id
    assert wake.trace_id == subs[t1].trace_id
    assert wake.meta["source"].startswith("chain")


# -- post-mortem timelines ----------------------------------------------------

def test_timeline_of_a_two_ticket_run_is_gap_free(fresh_obs, tmp_path):
    tr, _ = fresh_obs
    jd = str(tmp_path / "journal")
    fleet = FleetSupervisor(scen_model(), services=2, steps=4,
                            start=False, journal_dir=jd)
    ts = [fleet.submit(scen_space(i)) for i in range(2)]
    for t in ts:
        fleet.result(t)
    fleet.stop()
    for t in ts:
        tl = obs.timeline(t, journal_dir=jd, spans=tr.spans)
        assert tl.complete and not tl.gaps
        kinds = [e.kind for e in tl.events]
        # the fleet's submit SPAN opens, the member scheduler's submit
        # span inside it, then the journal's submit record is appended —
        # the three lead the timeline, in that order
        assert kinds[:3] == ["fleet.submit", "ensemble.submit", "submit"]
        assert "served" in kinds
        assert "ensemble.launch" in kinds  # spans joined by trace id
        # ordered: every stamped event's t_wall is non-decreasing
        stamped = [e.t_wall for e in tl.events if e.t_wall is not None]
        assert stamped == sorted(stamped)


def test_timeline_from_exported_chrome_trace(fresh_obs, tmp_path):
    """The offline join: the same timeline reconstructs from the
    export_chrome artifact as from the live span list."""
    tr, _ = fresh_obs
    jd = str(tmp_path / "journal")
    fleet = FleetSupervisor(scen_model(), services=1, steps=4,
                            start=False, journal_dir=jd)
    t = fleet.submit(scen_space(0))
    fleet.result(t)
    fleet.stop()
    trace_path = str(tmp_path / "trace.json")
    tr.export_chrome(trace_path)
    spans = spans_from_chrome(trace_path)
    assert spans and all(s["trace_id"] for s in spans)
    tl = obs.timeline(t, journal_dir=jd, spans=trace_path)
    assert tl.complete
    assert any(e.kind == "ensemble.fetch" for e in tl.events)


def test_timeline_uncertainty_for_in_flight_at_kill(fresh_obs,
                                                    tmp_path):
    """A ticket in flight at a hard kill: BEFORE recovery its timeline
    says explicitly where it was ('in flight on mXgY'), never a silent
    gap; AFTER recovery serves it, the timeline is complete."""
    jd = str(tmp_path / "journal")
    fleet = FleetSupervisor(scen_model(), services=1, steps=4,
                            start=False, journal_dir=jd,
                            max_wait_s=1e9, max_batch=8)
    t = fleet.submit(scen_space(0))  # queued, never pumped
    fleet.abandon()                  # the simulated process kill
    tl = obs.timeline(t, journal_dir=jd)
    assert not tl.complete
    assert tl.gaps and tl.gaps[0].kind == "uncertainty"
    assert "in flight on m0g0" in tl.gaps[0].detail
    # recovery re-admits and serves it; the journal now closes the story
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        r2 = FleetSupervisor.recover(jd, scen_model(), services=1,
                                     steps=4, start=False)
        r2.result(t)
        r2.stop()
    tl2 = obs.timeline(t, journal_dir=jd)
    assert tl2.complete and not tl2.gaps
    kinds = [e.kind for e in tl2.events]
    assert "readmit" in kinds and "served" in kinds


def test_timeline_unknown_ticket_says_so(tmp_path):
    jd = str(tmp_path / "journal")
    fleet = FleetSupervisor(scen_model(), services=1, steps=4,
                            start=False, journal_dir=jd)
    fleet.result(fleet.submit(scen_space(0)))
    fleet.stop()
    tl = obs.timeline(999, journal_dir=jd)
    assert not tl.complete
    assert tl.gaps and "no verified submit record" in tl.gaps[0].detail


def test_tiering_journal_joins_the_timeline(fresh_obs, tmp_path):
    nb = int(scen_space(0).values["value"].nbytes)
    jd = str(tmp_path / "journal")
    vault = str(tmp_path / "vault")
    fleet = FleetSupervisor(scen_model(), services=1, steps=4,
                            start=False, journal_dir=jd, max_queue=1,
                            residency_budget=nb, hibernate_dir=vault)
    t0 = fleet.submit(scen_space(0))
    t1 = fleet.submit(scen_space(1))  # hibernates
    fleet.result(t0)
    fleet.result(t1)
    fleet.stop()
    tl = obs.timeline(t1, journal_dir=jd, vault_dir=vault)
    assert tl.complete
    srcs = {(e.source, e.kind) for e in tl.events}
    assert ("tiering", "hibernate") in srcs
    assert ("tiering", "wake") in srcs
    assert ("journal", "served") in srcs


# -- the acceptance leg: loopback hard-stop (subprocess-free kill -9) ---------

def test_loopback_hard_stop_timelines_complete_and_trace_merged(
        fresh_obs, tmp_path):
    """The in-tier-1 half of the ISSUE 15 acceptance: a journaled
    loopback-wire fleet loses a member to the proc_kill hard stop
    mid-serving; after fencing + respawn + re-admission every served
    ticket reconstructs a COMPLETE timeline (the fence visible as its
    readmit record), the merged Chrome trace carries member-side
    dispatch spans parented under fleet-side submit spans that crossed
    the wire, and the flight recorder dumped beside the fence."""
    tr, rec = fresh_obs
    clock = {"t": 0.0}
    jd = str(tmp_path / "journal")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        fleet = FleetSupervisor(
            scen_model(), services=2, steps=4, start=False,
            member_transport="process",
            member_spawner=spawn_loopback_member, retry="solo",
            journal_dir=jd, clock=lambda: clock["t"],
            heartbeat_deadline_s=1.0, max_wait_s=1e9, max_batch=8)
        tickets = [fleet.submit(scen_space(i)) for i in range(4)]
        fleet.tick()
        victim = next(s["service_id"]
                      for s in fleet.stats()["services"]
                      if s["pending"] > 0)
        with inject.armed(FaultPlan(
                (Fault("proc_kill", channel=victim),))):
            fleet.pump_once()
            clock["t"] = 2.0
            fleet.pump_once()
            outs = [fleet.result(t) for t in tickets]
        stats = fleet.stats()
        fleet.stop()
    assert len(outs) == 4 and stats["respawns"] >= 1

    # (1) 100% of served tickets reconstruct complete timelines; the
    # fenced member's tickets show the handoff, not a silent gap
    trace_path = str(tmp_path / "merged-trace.json")
    tr.export_chrome(trace_path)
    readmits = 0
    for t in tickets:
        tl = obs.timeline(t, journal_dir=jd, spans=trace_path)
        assert tl.complete, tl.to_dict()
        readmits += sum(1 for e in tl.events if e.kind == "readmit")
    assert readmits >= 1  # the kill is visible in some ticket's story

    # (2) the merged trace: member-side dispatch spans parented under
    # the fleet-side submit spans whose context crossed the wire
    sub_ids = {s.span_id for s in tr.spans if s.name == "fleet.submit"}
    launches = [s for s in tr.spans if s.name == "ensemble.launch"]
    assert launches
    assert all(_submit_parent(tr, s).parent_id in sub_ids
               for s in launches)

    # (3) the flight recorder dumped beside the fence's FailureEvent
    assert any(d["reason"] == "fence" and d["service_id"] == victim
               for d in rec.dumps)
    fence_dump = next(d for d in rec.dumps if d["reason"] == "fence")
    assert any(e["kind"] == "fence" for e in fence_dump["events"])


# -- the obs CLI --------------------------------------------------------------

def test_obs_cli_validate_prom_timeline(fresh_obs, tmp_path, capsys):
    from mpi_model_tpu.obs.__main__ import main

    jd = str(tmp_path / "journal")
    fleet = FleetSupervisor(scen_model(), services=1, steps=4,
                            start=False, journal_dir=jd)
    t = fleet.submit(scen_space(0))
    fleet.result(t)
    snap = str(tmp_path / "snap.json")
    obs.write_snapshot(snap, fleet)
    fleet.stop()

    assert main(["validate", snap]) == 0
    assert "validates" in capsys.readouterr().out

    assert main(["prom", snap]) == 0
    assert "mpi_model_tpu_scenarios" in capsys.readouterr().out

    assert main(["timeline", str(t), "--journal", jd, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["complete"] and doc["ticket"] == t

    # an unresolved ticket exits 1 (the scriptable post-mortem gate)
    assert main(["timeline", "12345", "--journal", jd]) == 1


# -- ISSUE 19 golden pin: the lifecycle refactor changed no verdict ----------

def _golden_journal(dirname, records, tear=None):
    from mpi_model_tpu.ensemble.journal import TicketJournal, journal_path

    dirname.mkdir(parents=True, exist_ok=True)
    path = journal_path(str(dirname))
    j = TicketJournal(path)
    if tear is None:
        for kind, meta in records:
            j.append(kind, meta)
    else:
        plan = FaultPlan((Fault("journal_torn", at=tear, offset=3,
                                tear="truncate"),))
        with inject.armed(plan):
            for kind, meta in records:
                j.append(kind, meta)
    j.close()
    return path


def _norm_tl(tl, path):
    """to_dict with the tmpdir-dependent journal path canonicalised
    (the ONLY run-dependent byte in any verdict)."""
    return json.loads(json.dumps(tl.to_dict()).replace(path, "<journal>"))


_GOLDEN_META = [
    ("submit", {"ticket": 0, "service_id": "m0g0", "steps": 4,
                "t_wall": 10.0}),
    ("submit", {"ticket": 1, "service_id": "m1g0", "steps": 4,
                "t_wall": 11.0}),
    ("served", {"ticket": 0, "service_id": "m0g0", "steps": 4,
                "t_wall": 12.0}),
    ("served", {"ticket": 1, "service_id": "m1g0", "steps": 4,
                "t_wall": 13.0}),
]

_TORN_NOTE = {
    "detail": "some records carry no t_wall stamp (pre-ISSUE-15 "
              "journal) — their order is record-index order, not "
              "clock order",
    "kind": "ordering-note", "order": float("-inf"),
    "service_id": None, "source": "reconstruction", "t_wall": None}
_TORN_TAIL = {
    "detail": "<journal> had an unverifiable suffix — events "
              "after the verified prefix are unknown",
    "kind": "journal-torn-tail", "order": 2.5,
    "service_id": None, "source": "journal", "t_wall": None}
_NO_SUBMIT = {
    "detail": "no verified submit record for this ticket — the "
              "journal predates it, lost its tail, or the ticket id "
              "is from another fleet",
    "kind": "uncertainty", "order": float("-inf"),
    "service_id": None, "source": "reconstruction", "t_wall": None}


def _jev(kind, order, t_wall, sid, detail):
    return {"detail": detail, "kind": kind, "order": order,
            "service_id": sid, "source": "journal", "t_wall": t_wall}


def test_golden_verdicts_exactly_once(tmp_path):
    """ISSUE 19 acceptance: driving replay/audit/timeline off the
    declared lifecycle machine produced byte-identical verdicts — this
    pin holds the refactor (and all future ones) to that bar."""
    from mpi_model_tpu.ensemble.journal import audit_journal, replay
    from mpi_model_tpu.obs.postmortem import reconstruct

    path = _golden_journal(tmp_path / "once", _GOLDEN_META)
    audit = audit_journal(path)
    audit.pop("path")
    assert audit == {
        "duplicate_terminals": [], "epochs": [],
        "kinds": {"served": 2, "submit": 2},
        "ok": True, "records": 4, "shed": 0,
        "stale_epoch_records": [], "submits": 2,
        "terminal": 2, "torn": False, "unresolved": []}
    st = replay(path)
    assert (sorted(st.submits), sorted(st.terminal),
            st.duplicate_terminals, st.shed, st.torn) == (
        [0, 1], [0, 1], [], 0, False)
    jd = str(tmp_path / "once")
    assert _norm_tl(reconstruct(0, journal_dir=jd), path) == {
        "complete": True, "gaps": [], "ticket": 0, "trace_id": None,
        "events": [_jev("submit", 0, 10.0, "m0g0", "steps=4"),
                   _jev("served", 2, 12.0, "m0g0", "steps=4")]}
    assert _norm_tl(reconstruct(1, journal_dir=jd), path) == {
        "complete": True, "gaps": [], "ticket": 1, "trace_id": None,
        "events": [_jev("submit", 1, 11.0, "m1g0", "steps=4"),
                   _jev("served", 3, 13.0, "m1g0", "steps=4")]}


def test_golden_verdicts_torn_tail(tmp_path):
    from mpi_model_tpu.ensemble.journal import audit_journal, replay
    from mpi_model_tpu.obs.postmortem import reconstruct

    path = _golden_journal(tmp_path / "torn", _GOLDEN_META[:1]
                           + [("served", dict(_GOLDEN_META[2][1],
                                              t_wall=11.0)),
                              ("submit", dict(_GOLDEN_META[1][1],
                                              t_wall=12.0))],
                           tear=2)
    audit = audit_journal(path)
    audit.pop("path")
    assert audit == {
        "duplicate_terminals": [], "epochs": [],
        "kinds": {"served": 1, "submit": 1},
        "ok": True, "records": 2, "shed": 0,
        "stale_epoch_records": [], "submits": 1,
        "terminal": 1, "torn": True, "unresolved": []}
    st = replay(path)
    assert (sorted(st.submits), sorted(st.terminal), st.torn) == (
        [0], [0], True)
    jd = str(tmp_path / "torn")
    assert _norm_tl(reconstruct(0, journal_dir=jd), path) == {
        "complete": True, "gaps": [], "ticket": 0, "trace_id": None,
        "events": [_jev("submit", 0, 10.0, "m0g0", "steps=4"),
                   _jev("served", 1, 11.0, "m0g0", "steps=4"),
                   _TORN_NOTE, _TORN_TAIL]}
    assert _norm_tl(reconstruct(1, journal_dir=jd), path) == {
        "complete": False, "gaps": [_NO_SUBMIT], "ticket": 1,
        "trace_id": None,
        "events": [_NO_SUBMIT, _TORN_NOTE, _TORN_TAIL]}


def test_golden_verdicts_duplicate_terminal(tmp_path):
    from mpi_model_tpu.ensemble.journal import audit_journal, replay
    from mpi_model_tpu.obs.postmortem import reconstruct

    path = _golden_journal(tmp_path / "dup", [
        _GOLDEN_META[0],
        ("served", dict(_GOLDEN_META[2][1], t_wall=11.0)),
        ("quarantined", {"ticket": 0, "service_id": "m0g0", "steps": 4,
                         "error": "ValueError", "detail": "boom",
                         "t_wall": 12.0})])
    audit = audit_journal(path)
    audit.pop("path")
    assert audit == {
        "duplicate_terminals": [0], "epochs": [],
        "kinds": {"quarantined": 1, "served": 1, "submit": 1},
        "ok": False, "records": 3, "shed": 0,
        "stale_epoch_records": [], "submits": 1,
        "terminal": 1, "torn": False, "unresolved": []}
    assert replay(path).duplicate_terminals == [0]
    jd = str(tmp_path / "dup")
    assert _norm_tl(reconstruct(0, journal_dir=jd), path) == {
        "complete": False, "gaps": [], "ticket": 0, "trace_id": None,
        "events": [
            _jev("submit", 0, 10.0, "m0g0", "steps=4"),
            _jev("served", 1, 11.0, "m0g0", "steps=4"),
            _jev("quarantined", 2, 12.0, "m0g0",
                 "error=ValueError; detail=boom; steps=4")]}
