"""Always-on async serving tests (ISSUE 9 tentpole): bitwise parity of
the async dispatch loop against the synchronous scheduler (the f64
acceptance gate), the no-copy donation assertion on consecutive
windows, bounded-admission shedding with depth/retry-after, per-ticket
deadline expiry as complete FailureEvents, the health-gated intake,
retry budgets, thread-safe snapshot-consistent counters, and the CLI
``--serve`` surface. Every latency-sensitive path runs on the
injectable clock — zero wall-clock sleeps in this module."""

import json
import threading

import numpy as np
import jax.numpy as jnp
import pytest

from mpi_model_tpu import CellularSpace, Diffusion, Model
from mpi_model_tpu.ensemble import (
    AsyncEnsembleService,
    EnsembleExecutor,
    EnsembleService,
    ServiceOverloaded,
    TicketExpired,
    complete_ensemble,
    launch_ensemble,
    run_soak,
)
from mpi_model_tpu.models.model import SerialExecutor
from mpi_model_tpu.resilience import inject
from mpi_model_tpu.resilience.inject import Fault, FaultPlan
from mpi_model_tpu.utils.metrics import ThroughputCounter

RNG = np.random.default_rng(21)
BASE = RNG.uniform(0.5, 2.0, (16, 16))


def scen_space(i, g=16):
    v = jnp.asarray(np.roll(BASE, 3 * i, axis=0)[:g, :g], jnp.float64)
    return CellularSpace.create(g, g, 1.0, dtype=jnp.float64).with_values(
        {"value": v})


def scen_model(i=0):
    return Model(Diffusion(0.05 + 0.01 * i), 4.0, 1.0)


# -- the f64 acceptance gate: async == sync, bitwise --------------------------

def test_async_served_results_bitwise_equal_sync_f64():
    """The acceptance bar: the same scenario set through the always-on
    loop (threaded, windowed, donated) and through the synchronous
    scheduler — every served state bitwise-identical at f64."""
    model = scen_model()
    spaces = [scen_space(i) for i in range(5)]
    models = [scen_model(i) for i in range(5)]
    sync = EnsembleService(model, steps=4)
    ts = [sync.submit(spaces[i], model=models[i]) for i in range(5)]
    sync.flush()
    want = [sync.result(t) for t in ts]
    with AsyncEnsembleService(model, steps=4, windows=2) as svc:
        ta = [svc.submit(spaces[i], model=models[i]) for i in range(5)]
        got = [svc.result(t, timeout=120) for t in ta]
    for i in range(5):
        np.testing.assert_array_equal(
            np.asarray(got[i][0].values["value"]),
            np.asarray(want[i][0].values["value"]))
        assert got[i][1].steps == 4
    st = svc.stats()
    assert st["scenarios"] == 5 and st["pending"] == 0
    assert st["latency_n"] == 5


def test_windowed_dispatch_matches_single_call_bitwise():
    """windows=k is the same step sequence as one call — bitwise (the
    donation path must never change the math)."""
    model, spaces = scen_model(), [scen_space(i) for i in range(3)]
    one = launch_ensemble(model, spaces, steps=6,
                          executor=EnsembleExecutor())
    win = launch_ensemble(model, spaces, steps=6, windows=3, donate=True,
                          executor=EnsembleExecutor())
    a = complete_ensemble(one)
    b = complete_ensemble(win)
    for (sa, _), (sb, _) in zip(a, b):
        np.testing.assert_array_equal(np.asarray(sa.values["value"]),
                                      np.asarray(sb.values["value"]))


# -- donation: the no-copy assertion ------------------------------------------

def test_donation_consumes_carry_between_windows():
    """The acceptance invariant: with donate=True every window's input
    buffers are CONSUMED (is_deleted) — the [B,H,W] state moved between
    windows without a copy. Undonated launches must not consume."""
    model, spaces = scen_model(), [scen_space(i) for i in range(2)]
    flight = launch_ensemble(model, spaces, steps=4, windows=2,
                             donate=True, executor=EnsembleExecutor())
    assert flight.windows == 2
    assert flight.donated_windows == 2  # every carry donated, no copy
    complete_ensemble(flight)
    plain = launch_ensemble(model, spaces, steps=4, windows=2,
                            donate=False, executor=EnsembleExecutor())
    assert plain.donated_windows == 0
    complete_ensemble(plain)


def test_service_dispatch_log_records_donation():
    model = scen_model()
    svc = AsyncEnsembleService(model, steps=4, windows=2, start=False)
    svc.submit(scen_space(0))
    while svc.pump_once(force=True):
        pass
    entries = [d for d in svc.scheduler.dispatch_log if "windows" in d]
    assert entries and all(d["donated_windows"] == d["windows"] == 2
                           for d in entries)


def test_donate_rejected_for_stat_lane_impls():
    model, spaces = scen_model(), [scen_space(0)]
    with pytest.raises(ValueError, match="impl='xla'"):
        launch_ensemble(model, spaces, steps=2, donate=True,
                        executor=EnsembleExecutor(impl="active"))
    with pytest.raises(ValueError, match="windows"):
        from mpi_model_tpu.ensemble import EnsembleScheduler

        EnsembleScheduler(impl="active", windows=2)


# -- the double-buffered pump -------------------------------------------------

def test_pump_once_overlaps_launch_with_previous_completion():
    """Iteration i launches batch i and THEN completes batch i-1 — the
    double buffer, observable deterministically in manual mode."""
    model = scen_model()
    svc = AsyncEnsembleService(model, steps=2, max_batch=1, start=False)
    a = svc.submit(scen_space(0))
    b = svc.submit(scen_space(1), steps=3)  # its own structure group
    assert svc.pump_once() is True      # launches A; nothing to complete
    assert svc.poll(a) is None          # A in flight, not fetched
    assert svc.pump_once() is True      # launches B, completes A
    assert svc.poll(a) is not None
    assert svc.poll(b) is None
    assert svc.pump_once() is True      # completes B
    assert svc.poll(b) is not None
    assert svc.pump_once() is False     # idle
    svc.stop()


def test_stop_drains_every_ticket():
    model = scen_model()
    svc = AsyncEnsembleService(model, steps=2, start=False)
    tickets = [svc.submit(scen_space(i)) for i in range(5)]
    svc.stop()  # manual-mode drain: everything resolves
    for t in tickets:
        assert svc.poll(t) is not None
    assert svc.stats()["pending"] == 0


# -- bounded admission / load shedding ----------------------------------------

def test_overload_sheds_with_depth_and_retry_after():
    model = scen_model()
    svc = AsyncEnsembleService(model, steps=2, max_queue=2, start=False)
    svc.submit(scen_space(0))
    svc.submit(scen_space(1))
    with pytest.raises(ServiceOverloaded, match="queue full") as ei:
        svc.submit(scen_space(2))
    assert ei.value.queue_depth == 2
    assert ei.value.retry_after_s >= 0.0
    st = svc.stats()
    assert st["shed"] == 1 and st["pending"] == 2
    svc.stop()
    assert svc.stats()["shed"] == 1  # shedding never resolves to a ticket


def test_concurrent_submitters_respect_the_queue_bound():
    """Admission + enqueue are atomic under the scheduler lock: many
    threads racing submit() can never overfill the bounded queue."""
    model = scen_model()
    svc = AsyncEnsembleService(model, steps=2, max_queue=4, start=False)
    outcomes = []
    lock = threading.Lock()

    def client(i):
        try:
            t = svc.submit(scen_space(i % 3))
            with lock:
                outcomes.append(("ok", t))
        except ServiceOverloaded:
            with lock:
                outcomes.append(("shed", None))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(10)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    admitted = [o for o in outcomes if o[0] == "ok"]
    assert len(admitted) == 4                  # exactly the bound
    assert len(outcomes) == 10
    assert svc.stats()["shed"] == 6
    svc.stop()
    assert svc.stats()["pending"] == 0


# -- per-ticket deadlines (injectable clock, zero sleeps) ---------------------

def test_ticket_deadline_expires_with_complete_failure_event():
    clock = {"t": 0.0}
    model = scen_model()
    svc = AsyncEnsembleService(model, steps=4, deadline_s=1.0,
                               max_wait_s=1e9, max_batch=8,
                               clock=lambda: clock["t"], start=False)
    t = svc.submit(scen_space(0))
    clock["t"] = 0.5
    svc.pump_once()                       # not due, not expired
    assert svc.poll(t) is None
    clock["t"] = 1.5                      # past the 1.0s deadline
    svc.pump_once()
    with pytest.raises(TicketExpired, match="expired") as ei:
        svc.poll(t)
    err = ei.value
    assert err.ticket == t
    ev = err.failure_event
    assert ev.kind == "expired" and ev.ticket == t
    assert ev.classification == "deterministic"
    st = svc.stats()
    assert st["expired"] == 1
    assert [e.ticket for e in svc.scheduler.expired_log] == [t]
    # the expiry is in the dispatch log too — the observable ledger
    assert any(d.get("expired_ticket") == t
               for d in svc.scheduler.dispatch_log)
    svc.stop()


def test_deadline_not_applied_to_dispatched_work():
    """A ticket that makes it INTO a dispatch before its deadline is
    served normally (dispatch_deadline_s bounds the dispatch; the
    ticket deadline bounds the queue wait)."""
    clock = {"t": 0.0}
    model = scen_model()
    svc = AsyncEnsembleService(model, steps=4, deadline_s=1.0,
                               clock=lambda: clock["t"], start=False)
    t = svc.submit(scen_space(0))
    clock["t"] = 0.9
    svc.pump_once()                      # launched before expiry
    clock["t"] = 5.0                     # deadline passes while in flight
    svc.pump_once()                      # completes — still served
    assert svc.poll(t) is not None
    assert svc.stats()["expired"] == 0
    svc.stop()


def test_queue_latency_percentiles_on_injectable_clock():
    clock = {"t": 0.0}
    model = scen_model()
    svc = AsyncEnsembleService(model, steps=2, max_wait_s=1e9,
                               max_batch=8,
                               clock=lambda: clock["t"], start=False)
    t = svc.submit(scen_space(0))
    clock["t"] = 2.5
    while svc.pump_once(force=True):
        pass
    assert svc.poll(t) is not None
    st = svc.stats()
    assert st["latency_n"] == 1
    assert st["latency_p50_s"] == pytest.approx(2.5)
    assert st["latency_p99_s"] == pytest.approx(2.5)
    svc.stop()


# -- health-gated intake ------------------------------------------------------

def test_degradation_mid_fall_gates_intake_until_clean_dispatch():
    """After a ladder rung degrades, admission sheds while backlog
    remains unproven; the first CLEAN completion reopens intake."""
    model = scen_model()
    svc = AsyncEnsembleService(
        model, steps=4, impl="active", retry="none", degrade_after=1,
        max_wait_s=1e9, max_batch=2, start=False)
    plan = FaultPlan((Fault("batch_exc", at=0),))
    with inject.armed(plan):
        a = svc.submit(scen_space(0))
        b = svc.submit(scen_space(1))         # fills the A/B group
        c = svc.submit(scen_space(2), steps=3)  # its own group, queued
        with pytest.warns(RuntimeWarning, match="degraded to 'xla'"):
            svc.pump_once()                   # A/B dispatch fails → gate up
        assert svc.scheduler.intake_gated
        with pytest.raises(ServiceOverloaded, match="health-gated"):
            svc.submit(scen_space(3))
        assert svc.stats()["shed"] == 1
        svc.pump_once(force=True)             # launches C (clean engine)
        with pytest.raises(ServiceOverloaded, match="health-gated"):
            svc.submit(scen_space(3))         # still mid-fall: C in flight
        svc.pump_once()                       # completes C → gate down
        assert not svc.scheduler.intake_gated
        t = svc.submit(scen_space(3))         # intake reopened
        assert isinstance(t, int)
    for bad in (a, b):
        with pytest.raises(inject.InjectedFault):
            svc.poll(bad)
    assert svc.poll(c) is not None
    svc.stop()


def test_idle_degraded_service_accepts_a_probe():
    """Liveness: the gate must not wedge an idle service — with no
    backlog the next submission is the health probe."""
    model = scen_model()
    svc = AsyncEnsembleService(model, steps=4, impl="active",
                               retry="none", degrade_after=1,
                               max_batch=1, start=False)
    with inject.armed(FaultPlan((Fault("batch_exc", at=0),))):
        a = svc.submit(scen_space(0))
        with pytest.warns(RuntimeWarning, match="degraded"):
            svc.pump_once()
        with pytest.raises(inject.InjectedFault):
            svc.poll(a)
        assert svc.scheduler.intake_gated
        t = svc.submit(scen_space(1))  # depth 0 → probe admitted
        assert isinstance(t, int)
    svc.stop()
    assert svc.poll(t) is not None


# -- retry budgets ------------------------------------------------------------

def test_retry_budget_caps_solo_amplification():
    """Three sticky-poisoned scenarios in one batch with budget 1: one
    solo runs (and fails → quarantine), the other two quarantine
    DIRECTLY with the budget exhaustion in their event detail — k
    failed lanes no longer cost k extra dispatches."""
    model = scen_model()
    svc = AsyncEnsembleService(model, steps=4, retry="solo",
                               retry_budget=1, max_batch=3, start=False)
    plan = FaultPlan(tuple(
        Fault("lane_nan", ticket=i, once=False) for i in range(3)))
    with inject.armed(plan):
        tickets = [svc.submit(scen_space(i)) for i in range(3)]
        while svc.pump_once(force=True):
            pass
        for t in tickets:
            with pytest.raises(Exception):
                svc.poll(t)
    st = svc.stats()
    assert st["solo_retries"] == 1          # the budget, exactly
    assert st["quarantined"] == 3           # every lane still resolved
    starved = [e for e in svc.scheduler.quarantine_log
               if "retry budget" in e.detail]
    assert len(starved) == 2
    entry = next(d for d in svc.scheduler.dispatch_log
                 if "retry_budget_exhausted" in d)
    assert len(entry["retry_budget_exhausted"]) == 2
    assert len(entry["retried_solo"]) == 1
    svc.stop()


# -- thread-safe counters -----------------------------------------------------

def test_throughput_counter_bump_validates_names():
    c = ThroughputCounter()
    c.bump("shed")
    c.bump("expired", 2)
    with pytest.raises(ValueError, match="unknown counter"):
        c.bump("typo_counter")
    snap = c.snapshot()
    assert snap["shed"] == 1 and snap["expired"] == 2


def test_concurrent_bumps_never_lose_updates():
    c = ThroughputCounter()

    def worker():
        for _ in range(500):
            c.bump("shed")
            c.record_latency(0.001)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = c.snapshot()
    assert snap["shed"] == 2000
    assert snap["latency_n"] == 2000
    assert snap["latency_p50_s"] == pytest.approx(0.001)


def test_threaded_service_stats_are_consistent():
    """Concurrent submitters against the live loop: every ticket
    resolves and the final snapshot reconciles exactly."""
    model = scen_model()
    results = []
    lock = threading.Lock()
    with AsyncEnsembleService(model, steps=2, max_queue=64) as svc:

        def client(i):
            t = svc.submit(scen_space(i % 4))
            out = svc.result(t, timeout=120)
            with lock:
                results.append(out)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        st = svc.stats()
    assert len(results) == 12
    assert st["scenarios"] == 12 and st["pending"] == 0
    assert st["latency_n"] == 12
    assert st["shed"] == 0 and st["expired"] == 0


# -- the soak driver ----------------------------------------------------------

def test_run_soak_ledger_is_complete_on_fake_clock():
    """Open-loop soak fully on the injectable clock (sleep advances it;
    zero wall sleeps): the ledger accounts for every offered scenario."""
    clock = {"t": 0.0}

    def fake_sleep(dt):
        clock["t"] += dt

    model = scen_model()
    svc = AsyncEnsembleService(model, steps=2, max_queue=3, start=False,
                               clock=lambda: clock["t"])
    scen = [(scen_space(i % 3), None, None) for i in range(7)]
    rep = run_soak(svc, scen, arrival_rate_hz=1000.0,
                   clock=lambda: clock["t"], sleep=fake_sleep)
    svc.stop()
    assert rep["offered"] == 7
    assert rep["ledger_complete"] is True
    assert rep["served"] + rep["failed"] + rep["expired"] + rep["shed"] \
        == 7
    assert rep["shed"] >= 1  # max_queue=3 with no pump during arrivals
    assert rep["sustained_scenarios_per_s"] is not None


def test_run_soak_rejects_bad_rate():
    model = scen_model()
    svc = AsyncEnsembleService(model, steps=2, start=False)
    with pytest.raises(ValueError, match="positive"):
        run_soak(svc, [], arrival_rate_hz=0.0)
    svc.stop()


# -- compile-cache default (ROADMAP direction 5 remainder) --------------------

def test_scheduler_arms_persistent_compile_cache_by_default(tmp_path,
                                                            monkeypatch):
    from mpi_model_tpu.ensemble import EnsembleScheduler
    from mpi_model_tpu.utils.compile_cache import CHECKOUT_CACHE, cache_dir

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cache_dir() == CHECKOUT_CACHE
    assert EnsembleScheduler().compile_cache == CHECKOUT_CACHE
    pinned = EnsembleScheduler(compile_cache=str(tmp_path / "p"))
    assert pinned.compile_cache == str(tmp_path / "p")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path / "cc"))
    assert cache_dir() == str(tmp_path / "cc")
    sch = EnsembleScheduler()
    assert sch.compile_cache == str(tmp_path / "cc")
    # explicit None disables; an explicit dir gives way to the env var
    assert EnsembleScheduler(compile_cache=None).compile_cache is None
    pinned = EnsembleScheduler(compile_cache=str(tmp_path / "p"))
    assert pinned.compile_cache == str(tmp_path / "cc")
    # the service surfaces the armed dir
    svc = EnsembleService(scen_model(), steps=1,
                          compile_cache=str(tmp_path / "cc"))
    assert svc.compile_cache == str(tmp_path / "cc")


# -- CLI ----------------------------------------------------------------------

def test_cli_serve_json(capsys):
    from mpi_model_tpu import cli

    rc = cli.main(["run", "--dimx=16", "--dimy=16", "--flow=diffusion",
                   "--steps=2", "--serve", "--serve-scenarios=6",
                   "--json"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert out["backend"] == "serve"
    assert out["served"] == 6 and out["ledger_complete"] is True
    assert out["shed"] == 0 and out["expired"] == 0
    for k in ("sustained_scenarios_per_s", "latency_p50_s",
              "latency_p99_s", "occupancy"):
        assert k in out


def test_cli_serve_flag_validation():
    from mpi_model_tpu import cli

    for argv in (["run", "--serve", "--ensemble=2"],
                 ["run", "--serve", "--mesh=2x1"],
                 ["run", "--serve", "--chaos=nan"],
                 ["run", "--serve", "--checkpoint-dir=/tmp/x"],
                 ["run", "--serve", "--impl=pallas"],
                 ["run", "--serve", "--serve-scenarios=0"],
                 ["run", "--serve", "--max-queue=0"],
                 ["run", "--serve", "--deadline-s=0"],
                 ["run", "--serve", "--arrival-rate=-1"],
                 ["run", "--arrival-rate=5"],
                 ["run", "--deadline-s=2"],
                 ["run", "--max-queue=8"],
                 ["run", "--serve-scenarios=9"]):
        with pytest.raises(SystemExit):
            cli.main(argv)


# -- bench/ladder surfaces ----------------------------------------------------

def test_bench_service_quick():
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    import bench

    row = bench.bench_service(grid=32, B=3, steps=2, n_scenarios=12,
                              windows=2)
    assert row["ledger_complete"] is True
    assert row["served"] + row["failed"] + row["shed"] + row["expired"] \
        == 12
    assert row["donation_ok"] is True
    # the chaos plan actually fired through the soak
    assert "thread_exc" in row["chaos_fired"]
    assert "queue_full" in row["chaos_fired"]
    for k in ("sustained_scenarios_per_s", "latency_p50_s",
              "latency_p99_s", "occupancy", "sync_occupancy"):
        assert k in row


def test_ladder_config9_quick():
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks.ladder import config9

    row = config9(quick=True)
    assert row["config"] == 9
    assert row["ledger_complete"] is True
    for k in ("sustained_scenarios_per_s", "latency_p50_s",
              "latency_p99_s", "occupancy", "shed", "expired"):
        assert k in row


# -- review-hardening regressions ---------------------------------------------

def test_dispatch_deadline_ignores_async_overlap_gap():
    """A healthy dispatch must not blow its deadline on time spent
    running UNOBSERVED while the loop assembled its successor: the
    deadline bills launch + fetch segments only (injectable clock)."""
    clock = {"t": 0.0}
    model = scen_model()
    svc = AsyncEnsembleService(model, steps=2, max_batch=1,
                               dispatch_deadline_s=1.0,
                               clock=lambda: clock["t"], start=False)
    a = svc.submit(scen_space(0))
    b = svc.submit(scen_space(1), steps=3)
    svc.pump_once()                 # launches A
    clock["t"] = 50.0               # the overlap window: A on-device
    svc.pump_once()                 # launches B, completes A
    assert svc.poll(a) is not None  # served, NOT DispatchTimeout
    svc.pump_once()
    assert svc.poll(b) is not None
    assert svc.stats()["impl_faults"] == 0
    svc.stop()


def test_finish_unwind_resolves_tickets_before_reraising():
    """An exception escaping finish_flight (e.g. warnings-as-errors in
    the fan-out) must resolve the flight's tickets via fail_flight —
    never an eternally pending ticket."""
    model = scen_model()
    svc = AsyncEnsembleService(model, steps=2, start=False)
    a = svc.submit(scen_space(0))
    svc.pump_once()                 # launches A
    real = svc.scheduler.finish_flight

    def boom(flight):
        raise RuntimeError("fan-out interrupted")

    svc.scheduler.finish_flight = boom
    with pytest.raises(RuntimeError, match="fan-out interrupted"):
        svc.pump_once()
    svc.scheduler.finish_flight = real
    with pytest.raises(RuntimeError, match="fan-out interrupted"):
        svc.poll(a)                 # resolved with the error, not None
    assert svc.stats()["pending"] == 0
    svc.stop()


def test_flight_records_effective_window_count():
    """steps < windows clamps the split; the flight must record what
    RAN so the donation audit can't produce a false copy alarm."""
    model, spaces = scen_model(), [scen_space(0)]
    flight = launch_ensemble(model, spaces, steps=1, windows=4,
                             donate=True, executor=EnsembleExecutor())
    assert flight.windows == 1          # effective, not the request
    assert flight.donated_windows == 1  # == windows: audit clean
    complete_ensemble(flight)


def test_cli_compile_cache_off_and_empty():
    from mpi_model_tpu import cli

    # empty value is an error, not a silent flip to the default
    with pytest.raises(SystemExit, match="compile-cache"):
        cli.main(["run", "--dimx=8", "--dimy=8", "--flow=diffusion",
                  "--steps=1", "--ensemble=2", "--compile-cache="])
    # 'off' disables explicitly and the run still serves
    rc = cli.main(["run", "--dimx=8", "--dimy=8", "--flow=diffusion",
                   "--steps=1", "--ensemble=2", "--compile-cache=off",
                   "--json"])
    assert rc == 0
