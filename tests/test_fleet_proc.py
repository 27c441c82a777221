"""Multi-process fleet tests (ISSUE 13 tentpole): the member surface
behind the wire protocol. Tier-1 rows run ``member_transport=
"process"`` over the IN-MEMORY loopback transport (a real
``MemberServer`` on a thread over a real socketpair — same codec,
framing, chaos seams and client path as a spawned child, zero
subprocesses), covering: the bitwise process==inproc acceptance gate,
the full PR 10/12 fleet chaos matrix re-run on the wire (lockdep-armed
against the static acquisition graph), the member_kill-then-wedge and
torn-journal-recovery rows, the NEW wire seams (proc_kill /
heartbeat_loss / wire_torn → fence, respawn gen+1, ticket recovery),
and the heartbeat/RSS/wire-bytes observability. REAL spawned-process
rows — including an actual ``kill -9`` — are marked ``slow``."""

import os
import signal
import threading
import time
import warnings

import numpy as np
import jax.numpy as jnp
import pytest

from mpi_model_tpu import CellularSpace, Diffusion, Model
from mpi_model_tpu.ensemble import (
    EnsembleService,
    FleetSupervisor,
    ServiceOverloaded,
    run_soak,
)
from mpi_model_tpu.ensemble.journal import (audit_journal, journal_path,
                                            replay)
from mpi_model_tpu.ensemble.member_proc import (ProcessMemberClient,
                                                spawn_loopback_member)
from mpi_model_tpu.resilience import inject, lockdep
from mpi_model_tpu.resilience.inject import Fault, FaultPlan

RNG = np.random.default_rng(41)
BASE = RNG.uniform(0.5, 2.0, (16, 16))


def scen_space(i, g=16, dtype=jnp.float64):
    rng = np.random.default_rng((97, i, g))
    v = jnp.asarray(rng.uniform(0.5, 2.0, (g, g)), dtype)
    return CellularSpace.create(g, g, 1.0, dtype=dtype).with_values(
        {"value": v})


def scen_model(i=0):
    return Model(Diffusion(0.05 + 0.01 * i), 4.0, 1.0)


def proc_fleet(model=None, **kw):
    kw.setdefault("services", 2)
    kw.setdefault("steps", 4)
    kw.setdefault("retry", "solo")
    return FleetSupervisor(model or scen_model(), start=False,
                           member_transport="process",
                           member_spawner=spawn_loopback_member, **kw)


_ALLOWED_GRAPH = None


def _allowed_graph():
    global _ALLOWED_GRAPH
    if _ALLOWED_GRAPH is None:
        from mpi_model_tpu.analysis.concurrency import static_lock_graph

        _ALLOWED_GRAPH = static_lock_graph()
    return _ALLOWED_GRAPH


# -- the acceptance gate: process-mode == inproc, bitwise ---------------------

def test_process_fleet_bitwise_equal_inproc_and_sync_f64():
    """The ISSUE 13 acceptance bar: the same scenario set through a
    process-transport fleet (every state crossing the wire twice) and
    through the synchronous scheduler AND an inproc fleet — every
    served state bitwise-identical at f64, on the same arrival order."""
    model = scen_model()
    spaces = [scen_space(i) for i in range(6)]
    models = [scen_model(i) for i in range(6)]
    sync = EnsembleService(model, steps=4)
    ts = [sync.submit(spaces[i], model=models[i]) for i in range(6)]
    sync.flush()
    want = [sync.result(t)[0] for t in ts]

    inproc = FleetSupervisor(model, services=3, steps=4, start=False)
    ti = [inproc.submit(spaces[i], model=models[i]) for i in range(6)]
    got_inproc = [inproc.result(t)[0] for t in ti]
    inproc.stop()

    fleet = proc_fleet(model, services=3)
    tp = [fleet.submit(spaces[i], model=models[i]) for i in range(6)]
    got_proc = [fleet.result(t)[0] for t in tp]
    st = fleet.stats()
    fleet.stop()
    for i in range(6):
        np.testing.assert_array_equal(
            np.asarray(got_proc[i].values["value"]),
            np.asarray(want[i].values["value"]))
        np.testing.assert_array_equal(
            np.asarray(got_proc[i].values["value"]),
            np.asarray(got_inproc[i].values["value"]))
    assert st["member_transport"] == "process"
    assert st["scenarios"] == 6 and st["pending"] == 0


def test_report_and_conservation_totals_cross_the_wire():
    fleet = proc_fleet(services=1)
    t = fleet.submit(scen_space(0))
    space, report = fleet.result(t)
    fleet.stop()
    assert report.steps == 4
    assert report.backend_report.get("service_id") == "m0g0"
    want = float(jnp.sum(scen_space(0).values["value"]))
    assert abs(report.initial_total["value"] - want) < 1e-9
    assert abs(report.final_total["value"] - want) < 1e-6


# -- the PR 10/12 chaos matrix, re-run across the wire ------------------------

FLEET_MATRIX = {
    "lane_nan_transient": (
        (Fault("lane_nan", lane=0, at=0, once=True),), {},
        dict(min_recovered=1, quarantined=0)),
    "lane_nan_sticky": (
        (Fault("lane_nan", lane=0, once=False),), {},
        dict(min_quarantined=1)),
    "batch_exc": (
        (Fault("batch_exc", at=0),), {},
        dict(min_recovered=1, quarantined=0)),
    "hang": (
        (Fault("hang", at=0, seconds=5.0),),
        dict(dispatch_deadline_s=1.0, clock=None),
        dict(min_recovered=1, quarantined=0)),
    "thread_exc": (
        (Fault("thread_exc", at=0),), {},
        dict(min_loop_faults=1, quarantined=0)),
    "slow_compile": (
        (Fault("slow_compile", at=0, seconds=5.0),),
        dict(dispatch_deadline_s=1.0, clock=None),
        dict(min_recovered=1, quarantined=0)),
    "fetch_nan": (
        (Fault("fetch_nan", at=0, lane=0, once=True),), {},
        dict(min_recovered=1, quarantined=0)),
    "queue_full": (
        (Fault("queue_full", at=0),), {},
        dict(quarantined=0, fleet_shed=0)),
}


@pytest.mark.parametrize("kind", sorted(FLEET_MATRIX))
def test_process_fleet_matrix_every_ticket_resolves(kind):
    """The full PR 10 fleet matrix with every member behind the wire —
    and lockdep-armed (ISSUE 12): chaos included, zero inversions, and
    every observed acquisition order already proven by the static
    graph. Whatever the fault does member-side, every fleet ticket
    resolves to a counted outcome through the codec."""
    faults, extra, expect = FLEET_MATRIX[kind]
    extra = dict(extra)
    if "clock" in extra:
        clock = {"t": 0.0}
        extra["clock"] = lambda: clock["t"]
    served = failed = 0
    with lockdep.armed(allowed=_allowed_graph()) as witness:
        fleet = proc_fleet(**extra)
        with inject.armed(FaultPlan(faults)) as st, \
                warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            tickets = [fleet.submit(scen_space(i)) for i in range(4)]
            for t in tickets:
                try:
                    fleet.result(t)
                    served += 1
                # analysis: ignore[broad-except] — the matrix LEDGER:
                # every non-served outcome must be counted, whatever
                # chaos threw across the wire
                except Exception:
                    failed += 1
        stats = fleet.stats()
        fleet.stop()
    assert witness.edges, f"{kind}: the witness saw no acquisitions"
    witness.assert_clean()
    assert st.fired, f"{kind}: fault never fired"
    assert served + failed == 4
    assert stats["pending"] == 0
    if "quarantined" in expect:
        assert stats["quarantined"] == expect["quarantined"]
    if "min_quarantined" in expect:
        assert stats["quarantined"] >= expect["min_quarantined"]
    if "min_recovered" in expect:
        assert stats["recovered_failures"] >= expect["min_recovered"]
    if "min_loop_faults" in expect:
        assert stats["loop_faults"] >= expect["min_loop_faults"]
    if "fleet_shed" in expect:
        assert stats["shed"] == expect["fleet_shed"]


def test_process_fleet_member_kill_then_wedge():
    """PR 10's hardest supervision row on the wire: a kill fences the
    member holding the queue, then a wedge fences the member holding
    the next wave — both through the codec, both with a complete
    ledger and kind="member" events, lockdep-armed."""
    clock = {"t": 0.0}
    with lockdep.armed(allowed=_allowed_graph()) as witness:
        fleet = proc_fleet(supervision_deadline_s=1.0,
                           clock=lambda: clock["t"])
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            tickets = [fleet.submit(scen_space(i)) for i in range(3)]
            fleet.tick()  # beat: refresh the telemetry cut
            victim = next(s["service_id"]
                          for s in fleet.stats()["services"]
                          if s["pending"] > 0)
            with inject.armed(FaultPlan(
                    (Fault("member_kill", channel=victim),))) as st1:
                outs = [fleet.result(t) for t in tickets]
            wave2 = [fleet.submit(scen_space(i), steps=3)
                     for i in range(3)]
            fleet.tick()  # beat: refresh telemetry for the new wave
            wedged = next(s["service_id"]
                          for s in fleet.stats()["services"]
                          if s["pending"] > 0)
            with inject.armed(FaultPlan(
                    (Fault("member_wedge", channel=wedged,
                           once=False),))) as st2:
                fleet.pump_once()
                clock["t"] = 2.0
                fleet.pump_once()
                clock["t"] = 4.0
                fleet.pump_once()
                outs2 = [fleet.result(t) for t in wave2]
        stats = fleet.stats()
        fleet.stop()
    witness.assert_clean()
    assert {f["kind"] for f in st1.fired} == {"member_kill"}
    assert "member_wedge" in {f["kind"] for f in st2.fired}
    assert len(outs) == 3 and len(outs2) == 3
    assert stats["member_faults"] == 2 and stats["pending"] == 0
    assert stats["respawns"] >= 1  # the killed member came back gen+1
    assert {e.service_id for e in fleet.member_log} == {victim, wedged}


def test_process_fleet_journal_torn_recovery(tmp_path):
    """Crash + torn journal + recovery, with process members on both
    sides of the crash: the torn suffix is lost, the verified prefix
    recovers, re-admitted tickets serve on FRESH member processes, and
    the replay audit stays exactly-once."""
    jdir = str(tmp_path)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        fleet = proc_fleet(journal_dir=jdir, max_wait_s=1e9, max_batch=8)
        tickets = [fleet.submit(scen_space(i)) for i in range(4)]
        with inject.armed(FaultPlan(
                (Fault("journal_torn", at=4, offset=5,
                       tear="truncate"),))) as st:
            fleet.submit(scen_space(4))  # this submit's record tears
        assert st.fired
        fleet.abandon()  # the crash: nothing drains, nothing harvests
        state = replay(journal_path(jdir))
        assert state.torn
        assert len(state.submits) == 4  # the torn 5th submit is lost
        r2 = FleetSupervisor.recover(
            jdir, scen_model(), services=2, steps=4, retry="solo",
            start=False, member_transport="process",
            member_spawner=spawn_loopback_member)
        for t in tickets:
            space, report = r2.result(t)
            assert space.values["value"].shape == (16, 16)
        r2.stop()
    audit = audit_journal(journal_path(jdir))
    assert audit["ok"] and not audit["unresolved"]


# -- the NEW wire seams -------------------------------------------------------

def test_proc_kill_fences_respawns_and_recovers_tickets():
    """The loopback ``proc_kill``: the member's serve thread is
    hard-stopped mid-stream (the in-memory stand-in for SIGKILL — the
    real one is the slow row below). The supervisor classifies the
    dead wire, fences, respawns gen+1 and re-admits from its stored
    state; every ticket still resolves."""
    clock = {"t": 0.0}
    fleet = proc_fleet(services=2, clock=lambda: clock["t"],
                       heartbeat_deadline_s=1.0, max_wait_s=1e9,
                       max_batch=8)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        tickets = [fleet.submit(scen_space(i)) for i in range(4)]
        fleet.tick()
        victim = next(s["service_id"]
                      for s in fleet.stats()["services"]
                      if s["pending"] > 0)
        with inject.armed(FaultPlan(
                (Fault("proc_kill", channel=victim),))) as st:
            fleet.pump_once()   # the kill lands on a wire RPC
            clock["t"] = 2.0    # age past the heartbeat deadline
            fleet.pump_once()
            outs = [fleet.result(t) for t in tickets]
    stats = fleet.stats()
    fleet.stop()
    assert st.fired and st.fired[0]["kind"] == "proc_kill"
    assert len(outs) == 4
    assert stats["member_faults"] >= 1
    assert stats["respawns"] >= 1
    assert stats["readmitted"] >= 1
    assert stats["wire_errors"] >= 1
    assert stats["pending"] == 0
    live = {s["service_id"] for s in stats["services"]}
    assert victim not in live  # gen+1 replaced it


def test_heartbeat_loss_fences_after_missed_deadline():
    """A sticky channel-pinned heartbeat_loss: the member itself is
    healthy — only the failure detector path is exercised. Once the
    missed-beat age crosses the deadline on the injectable clock, the
    member is fenced and its replacement (new id, un-faulted) serves
    the re-admitted work."""
    clock = {"t": 0.0}
    fleet = proc_fleet(services=2, clock=lambda: clock["t"],
                       heartbeat_deadline_s=1.0, max_wait_s=1e9,
                       max_batch=8)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        tickets = [fleet.submit(scen_space(i)) for i in range(4)]
        fleet.tick()
        victim = next(s["service_id"]
                      for s in fleet.stats()["services"]
                      if s["pending"] > 0)
        with inject.armed(FaultPlan(
                (Fault("heartbeat_loss", channel=victim,
                       once=False),))) as st:
            fleet.pump_once()
            clock["t"] = 2.0
            fleet.pump_once()
            outs = [fleet.result(t) for t in tickets]
    stats = fleet.stats()
    fleet.stop()
    assert {f["kind"] for f in st.fired} == {"heartbeat_loss"}
    assert len(outs) == 4
    assert stats["heartbeat_misses"] >= 1
    assert stats["member_faults"] >= 1 and stats["respawns"] >= 1
    assert stats["pending"] == 0
    assert any("missed heartbeats" in e.detail
               for e in fleet.member_log)


def test_wire_torn_mid_stream_is_a_member_fault_not_a_ticket_loss():
    """A torn frame on one member's wire (CRC-failing corrupt tear):
    the codec raises its typed error, the fleet classifies a MEMBER
    fault — fence, respawn, re-admit — and the client still gets every
    result; no ticket resolves with a wire error."""
    clock = {"t": 0.0}
    fleet = proc_fleet(services=2, clock=lambda: clock["t"],
                       heartbeat_deadline_s=1.0, max_wait_s=1e9,
                       max_batch=8)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        tickets = [fleet.submit(scen_space(i)) for i in range(4)]
        fleet.tick()
        victim = next(s["service_id"]
                      for s in fleet.stats()["services"]
                      if s["pending"] > 0)
        with inject.armed(FaultPlan(
                (Fault("wire_torn", channel=victim, offset=2,
                       nbytes=8, tear="corrupt"),))) as st:
            fleet.pump_once()
            clock["t"] = 2.0
            fleet.pump_once()
            outs = [fleet.result(t) for t in tickets]
    stats = fleet.stats()
    fleet.stop()
    assert {f["kind"] for f in st.fired} == {"wire_torn"}
    assert len(outs) == 4          # every ticket served, none errored
    assert stats["wire_errors"] >= 1
    assert stats["pending"] == 0


# -- soak + observability -----------------------------------------------------

def test_process_fleet_soak_ledger_complete_lockdep_armed():
    """The fake-clock open-loop soak through a wire fleet, lockdep
    armed: complete ledger, zero silent drops, the witness clean
    against the static graph."""
    clock = {"t": 0.0}

    def fake_sleep(dt):
        clock["t"] += dt

    scen = [(scen_space(i), None, None) for i in range(8)]
    with lockdep.armed(allowed=_allowed_graph()) as witness:
        fleet = proc_fleet(services=2, clock=lambda: clock["t"])
        rep = run_soak(fleet, scen, arrival_rate_hz=50.0,
                       clock=lambda: clock["t"], sleep=fake_sleep)
        fleet.stop()
    witness.assert_clean()
    assert rep["ledger_complete"] and rep["served"] == 8
    assert rep["member_faults"] == 0


def test_wire_observability_in_stats():
    fleet = proc_fleet(services=2)
    t = fleet.submit(scen_space(0))
    fleet.result(t)
    st = fleet.stats()
    per = st["services"]
    fleet.stop()
    assert st["member_transport"] == "process"
    assert st["heartbeats"] >= 2 and st["heartbeat_misses"] == 0
    assert st["wire_bytes_in"] > 0 and st["wire_bytes_out"] > 0
    assert st["respawns"] == 0 and st["wire_errors"] == 0
    for s in per:
        assert s["transport"] == "process"
        assert s["wire_bytes_in"] >= 0 and s["wire_bytes_out"] >= 0
        assert s["heartbeat_age_s"] >= 0.0
        assert s["member_pid"] == os.getpid()  # loopback: same process
        assert s["rss_bytes"] is None or s["rss_bytes"] > 0


def test_dead_member_wire_bytes_absorbed_into_fleet_stats():
    clock = {"t": 0.0}
    fleet = proc_fleet(services=2, clock=lambda: clock["t"],
                       heartbeat_deadline_s=1.0, max_wait_s=1e9,
                       max_batch=8)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        tickets = [fleet.submit(scen_space(i)) for i in range(2)]
        fleet.tick()
        before = fleet.stats()["wire_bytes_in"]
        victim = next(s["service_id"]
                      for s in fleet.stats()["services"]
                      if s["pending"] > 0)
        with inject.armed(FaultPlan(
                (Fault("proc_kill", channel=victim),))):
            fleet.pump_once()
            clock["t"] = 2.0
            fleet.pump_once()
            [fleet.result(t) for t in tickets]
    after = fleet.stats()["wire_bytes_in"]
    fleet.stop()
    assert after >= before  # the dead member's bytes were not dropped


# -- guards / proxies ---------------------------------------------------------

def test_process_transport_refuses_unserializable_models():
    class Opaque:
        pass

    class WeirdFlow(Diffusion):
        pass

    f = WeirdFlow(0.05)
    f.extra = Opaque()  # still a dataclass; scalar fields — fine
    with pytest.raises(ValueError, match="unknown member_transport"):
        FleetSupervisor(scen_model(), member_transport="carrier-pigeon")

    from mpi_model_tpu.ensemble.journal import model_meta

    class NonDC:
        pass

    m = scen_model()
    m2 = Model(Diffusion(0.05), 4.0, 1.0)
    m2.flows = [NonDC()]
    assert model_meta(m2) is None
    with pytest.raises(ValueError, match="wire recipe"):
        FleetSupervisor(m2, member_transport="process",
                        member_spawner=spawn_loopback_member,
                        start=False)
    assert model_meta(m) is not None


def test_wire_migration_is_crc_verified_end_to_end():
    """drain-before-retire across the wire: a queued ticket extracted
    from one process member, re-submitted on another, serves bitwise."""
    model = scen_model()
    sync = EnsembleService(model, steps=4)
    ts = sync.submit(scen_space(0))
    sync.flush()
    want = sync.result(ts)[0]
    fleet = proc_fleet(services=2, max_wait_s=1e9, max_batch=8,
                       policy=None)
    t = fleet.submit(scen_space(0))
    with fleet._cv:
        route = fleet._route[t]
        src = route.member
        dst = next(m for m in fleet._members.values() if m is not src)
        new_mt = src.service.scheduler.migrate_ticket(
            route.member_ticket, dst.service.scheduler)
        route.member, route.member_ticket = dst, new_mt
    got = fleet.result(t)[0]
    st = fleet.stats()
    fleet.stop()
    np.testing.assert_array_equal(np.asarray(got.values["value"]),
                                  np.asarray(want.values["value"]))
    assert st["pending"] == 0


def test_journal_cli_main_runs_the_audit(tmp_path, capsys):
    """The inspection CLI (ISSUE 13 satellite), driven in-process:
    record stream + exactly-once audit, json and human modes."""
    from mpi_model_tpu.ensemble import journal as journal_mod

    jdir = str(tmp_path)
    fleet = proc_fleet(journal_dir=jdir)
    tickets = [fleet.submit(scen_space(i)) for i in range(3)]
    for t in tickets:
        fleet.result(t)
    fleet.stop()
    rc = journal_mod.main([jdir])
    out = capsys.readouterr().out
    assert rc == 0
    assert "exactly-once: OK" in out
    assert "submit" in out and "served" in out
    rc = journal_mod.main([jdir, "--json"])
    out = capsys.readouterr().out
    import json as _json

    audit = _json.loads(out)
    assert audit["ok"] and audit["submits"] == 3 and not audit["torn"]
    assert journal_mod.main([str(tmp_path / "nope")]) == 2


# -- heartbeat telemetry cache (ISSUE 15 satellite: PR 13 regression) ---------

def test_heartbeat_telemetry_cache_reuses_idle_and_invalidates():
    """The MemberServer stats cache: idle beats re-serve the cached
    cut (no latency-reservoir sort per beat); the moment a counter
    moves mid-soak, the state signature changes and the next beat
    ships a FRESH cut reflecting the served work."""
    client = spawn_loopback_member(
        scen_model(), service_id="m9g0",
        member_kwargs=dict(steps=4, retry="solo"))
    server = client._server
    assert client.heartbeat()
    cut1 = server._stats_cached
    assert client.stats()["scenarios"] == 0
    assert client.heartbeat()
    # idle: the cached object is re-served, not recomputed
    assert server._stats_cached is cut1
    t = client.submit(scen_space(0))
    while client.poll(t) is None:
        client.pump_once(force=True)
    assert client.heartbeat()
    # counters moved: the signature invalidated, the cut is fresh
    assert server._stats_cached is not cut1
    assert client.stats()["scenarios"] == 1
    client.close()


def test_fence_respawn_never_serves_a_retired_generations_cut():
    """After proc_kill fences m<slot>g0 and the fleet respawns
    m<slot>g1, the replacement's heartbeat telemetry must be ITS OWN
    fresh cut (zero scenarios), never the retired generation's cached
    one — while the fleet aggregate still carries the dead member's
    absorbed work."""
    clock = {"t": 0.0}
    fleet = proc_fleet(services=2, clock=lambda: clock["t"],
                       heartbeat_deadline_s=1.0, max_wait_s=1e9,
                       max_batch=8)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        tickets = [fleet.submit(scen_space(i)) for i in range(4)]
        # serve everything once so BOTH generations' cuts differ
        outs = [fleet.result(t) for t in tickets]
        assert len(outs) == 4
        victim = next(s["service_id"]
                      for s in fleet.stats()["services"]
                      if s["scenarios"] > 0)
        slot = int(victim[1:victim.index("g")])
        with inject.armed(FaultPlan(
                (Fault("proc_kill", channel=victim),))):
            fleet.pump_once()
            clock["t"] = 2.0
            fleet.pump_once()
    stats = fleet.stats()
    assert stats["respawns"] >= 1
    replacement = next(s for s in stats["services"]
                       if s["service_id"] == f"m{slot}g1")
    # the replacement's telemetry cut is its own: a fresh service with
    # zero served scenarios, not the retired generation's cache
    assert replacement["scenarios"] == 0
    assert replacement["dispatches"] == 0
    # ...while the fleet-level aggregate absorbed the dead member's
    # work (nothing vanished with the fence)
    assert stats["scenarios"] == 4
    fleet.stop()


# -- real spawned processes (slow) --------------------------------------------

def _wait_until(pred, timeout_s=120.0):
    """Condition-wait without wall-clock sleeps in test code (the
    wall-clock-in-test rule): Event.wait paces the poll."""
    ev = threading.Event()
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        ev.wait(0.05)
    return False


@pytest.mark.slow
def test_real_process_members_serve_and_survive_kill_dash_nine(tmp_path):
    """THE acceptance row: real spawned member processes, a REAL
    ``kill -9`` on the member holding the queue mid-stream — the
    supervisor fences on the dead wire/missed heartbeats, respawns
    gen+1, re-admits from the journal-backed fleet state, every ticket
    serves, and the replay audit is exactly-once."""
    jdir = str(tmp_path)
    model = scen_model()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        fleet = FleetSupervisor(
            model, services=2, steps=400, start=True,
            member_transport="process", journal_dir=jdir,
            heartbeat_deadline_s=0.5, tick_interval_s=0.05,
            rpc_deadline_s=60.0, max_wait_s=0.0, max_batch=1,
            retry="solo")
        tickets = [fleet.submit(scen_space(i, g=32, dtype=jnp.float32))
                   for i in range(6)]
        assert _wait_until(lambda: any(
            s["pending"] > 0 and s.get("member_pid")
            for s in fleet.stats()["services"]))
        victim = next(s for s in fleet.stats()["services"]
                      if s["pending"] > 0)
        os.kill(victim["member_pid"], signal.SIGKILL)  # the real thing
        outs = [fleet.result(t, timeout=300) for t in tickets]
        st = fleet.stats()
        fleet.stop()
    assert len(outs) == 6
    assert st["respawns"] >= 1 and st["member_faults"] >= 1
    assert victim["service_id"] not in {
        s["service_id"] for s in st["services"]}
    audit = audit_journal(journal_path(jdir))
    assert audit["ok"] and not audit["unresolved"]
    assert audit["submits"] == 6


@pytest.mark.slow
def test_real_process_results_bitwise_equal_inproc():
    model = scen_model()
    spaces = [scen_space(i, dtype=jnp.float64) for i in range(3)]
    inproc = FleetSupervisor(model, services=2, steps=4, start=False)
    ti = [inproc.submit(s) for s in spaces]
    want = [inproc.result(t)[0] for t in ti]
    inproc.stop()
    fleet = FleetSupervisor(model, services=2, steps=4, start=True,
                            member_transport="process",
                            heartbeat_deadline_s=30.0,
                            rpc_deadline_s=120.0)
    tp = [fleet.submit(s) for s in spaces]
    got = [fleet.result(t, timeout=300)[0] for t in tp]
    fleet.stop()
    for i in range(3):
        np.testing.assert_array_equal(
            np.asarray(got[i].values["value"]),
            np.asarray(want[i].values["value"]))


# -- member_env device pinning + the mesh wire spec (ISSUE 16) ----------------

def test_mesh_spec_crosses_the_wire_loopback():
    """The ``(batch, space)`` mesh spec is a member KWARG: it crosses
    the wire as plain extents and the member resolves it against its
    OWN device set — served results stay bitwise-equal to the meshless
    inproc fleet, and the member's stats cut reports the mesh."""
    model = scen_model()
    spaces = [scen_space(i) for i in range(4)]
    inproc = FleetSupervisor(model, services=1, steps=4, start=False)
    want = [inproc.result(inproc.submit(s))[0] for s in spaces]
    inproc.stop()
    fleet = proc_fleet(model, services=1, mesh=2)
    tp = [fleet.submit(s) for s in spaces]
    got = [fleet.result(t, timeout=300)[0] for t in tp]
    st = fleet.stats()
    fleet.stop()
    for i in range(4):
        np.testing.assert_array_equal(
            np.asarray(got[i].values["value"]),
            np.asarray(want[i].values["value"]))
    assert st["services"][0]["mesh"] == {
        "batch": 2, "space": 1, "devices": 2}


@pytest.mark.slow
def test_member_env_pins_each_real_members_device_set():
    """ISSUE 16 satellite: two REAL spawned members with DISJOINT
    device-visibility envs (the CPU rig's pin is the forced host
    device count; silicon uses CUDA_VISIBLE_DEVICES/TPU_VISIBLE_CHIPS)
    — each child's telemetry must report exactly the device set its
    slot's pin allows, while the fleet serves correctly through both."""
    model = scen_model()
    spaces = [scen_space(i, dtype=jnp.float64) for i in range(4)]
    inproc = FleetSupervisor(model, services=2, steps=4, start=False)
    want = [inproc.result(inproc.submit(s))[0] for s in spaces]
    inproc.stop()
    fleet = FleetSupervisor(
        model, services=2, steps=4, start=True,
        member_transport="process",
        heartbeat_deadline_s=30.0, rpc_deadline_s=120.0,
        member_env=[
            {"XLA_FLAGS": "--xla_force_host_platform_device_count=2"},
            {"XLA_FLAGS": "--xla_force_host_platform_device_count=3"},
        ])
    try:
        # backend telemetry rides the heartbeat cut — wait for both
        # children's first beats to land
        assert _wait_until(lambda: all(
            s.get("backend") for s in fleet.stats()["services"]))
        by_slot = {s["slot"]: s["backend"]
                   for s in fleet.stats()["services"]}
        assert by_slot[0]["platform"] == "cpu"
        assert by_slot[0]["device_count"] == 2   # slot 0's pin
        assert by_slot[1]["device_count"] == 3   # slot 1's pin
        tp = [fleet.submit(s) for s in spaces]
        got = [fleet.result(t, timeout=300)[0] for t in tp]
    finally:
        fleet.stop()
    for i in range(4):
        np.testing.assert_array_equal(
            np.asarray(got[i].values["value"]),
            np.asarray(want[i].values["value"]))


# -- scenario tiering across the wire (ISSUE 14) ------------------------------

def test_tiering_pages_and_wakes_across_the_wire_bitwise():
    """The paging tier with PROCESS members (loopback): admissions
    beyond the residency budget hibernate at the FLEET level, wake
    FIFO, and their placements cross the wire like any submission —
    every served state bitwise-equal to the synchronous scheduler,
    zero sheds, wakes attributed per member."""
    from mpi_model_tpu.ensemble import scenario_nbytes

    import tempfile

    model = scen_model()
    spaces = [scen_space(i) for i in range(6)]
    models = [scen_model(i) for i in range(6)]
    sync = EnsembleService(model, steps=4)
    ts = [sync.submit(spaces[i], model=models[i]) for i in range(6)]
    sync.flush()
    want = [np.asarray(sync.result(t)[0].values["value"]) for t in ts]

    one = scenario_nbytes(spaces[0])
    fleet = proc_fleet(model, services=2,
                       residency_budget=2 * one + 1,
                       hibernate_dir=tempfile.mkdtemp(prefix="wire-tier-"))
    tp = [fleet.submit(spaces[i], model=models[i]) for i in range(6)]
    st = fleet.stats()
    assert st["hibernated_scenarios"] == 4 and st["shed"] == 0
    for i, t in enumerate(tp):
        out, _rep = fleet.result(t)
        np.testing.assert_array_equal(
            np.asarray(out.values["value"]), want[i])
    st = fleet.stats()
    assert st["wakes"] == 4 and st["shed"] == 0
    assert sum(st["wakes_by_member"].values()) == 4
    fleet.stop()


def test_tiering_wake_survives_proc_kill_fence():
    """A hibernated ticket belongs to no member: the loopback
    ``proc_kill`` fencing one member while scenarios sleep changes
    nothing — wakes land on the survivor/replacement and everything
    serves with zero sheds."""
    from mpi_model_tpu.ensemble import scenario_nbytes

    import tempfile

    clock = {"t": 0.0}
    model = scen_model()
    one = scenario_nbytes(scen_space(0))
    fleet = proc_fleet(model, services=2, clock=lambda: clock["t"],
                       heartbeat_deadline_s=1.0,
                       residency_budget=one + 1,
                       hibernate_dir=tempfile.mkdtemp(prefix="wire-tk-"))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        tickets = [fleet.submit(scen_space(i)) for i in range(4)]
        assert fleet.stats()["hibernated_scenarios"] >= 2
        fleet.tick()   # heartbeat: refresh the cached telemetry cut
        victim = next(s["service_id"]
                      for s in fleet.stats()["services"]
                      if s["pending"] > 0)
        with inject.armed(FaultPlan(
                (Fault("proc_kill", channel=victim),))):
            fleet.pump_once()   # the kill lands on a wire RPC
            clock["t"] = 2.0    # age past the heartbeat deadline
            fleet.pump_once()
            outs = [fleet.result(t) for t in tickets]
    stats = fleet.stats()
    fleet.stop()
    assert len(outs) == 4
    assert stats["respawns"] >= 1
    assert stats["shed"] == 0
    assert stats["wakes"] >= 2


def test_spawn_refuses_a_member_that_asks_for_the_held_tpu(monkeypatch,
                                                           tmp_path):
    """A TPU belongs to one process: when this process holds it, a
    process member whose environment asks for it is refused at spawn
    (before any child starts), while a CPU-pinned member is not."""
    from jax._src import xla_bridge

    from mpi_model_tpu.ensemble import member_proc

    monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                        lambda: True)
    monkeypatch.setattr(xla_bridge, "backends",
                        lambda: {"tpu": object(), "cpu": object()})
    for env in ({"JAX_PLATFORMS": "tpu"}, {}):
        with pytest.raises(RuntimeError, match="holds the TPU"):
            member_proc.check_chip_free(env)
    member_proc.check_chip_free({"JAX_PLATFORMS": "cpu"})
    model = Model(Diffusion(0.1), 1.0, 1.0)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(RuntimeError, match="holds the TPU"):
        member_proc.spawn_process_member(
            model, service_id="m0g0", member_kwargs={"steps": 1})
