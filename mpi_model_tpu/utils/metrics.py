"""Timing/metrics helpers shared by bench.py and benchmarks/ladder.py.

The reference never measures its own speed (SURVEY §5: no timers
anywhere), so the framework carries its own instrumentation. The core
primitive is MARGINAL step timing: per-step cost is measured as
``(t(s2) - t(s1)) / (s2 - s1)`` between two scan lengths, so fixed
per-call dispatch cancels, with completion forced by an on-device
reduction fetched to host.
"""

from __future__ import annotations

import collections
import time
from typing import Callable, Optional

from ..resilience import lockdep

Values = dict


def marginal_step_time(step: Callable, values: Values, s1: int = 50,
                       s2: int = 250, reps: int = 2,
                       donate: bool = True) -> float:
    """Seconds per step of ``step`` (a Values→Values function), measured
    marginally between scan lengths ``s1`` and ``s2`` with donated carry
    buffers (SURVEY §7.6) and best-of-``reps`` timing."""
    import jax
    import jax.numpy as jnp

    times = {}
    for steps in (s1, s2):
        def run_fn(v, _steps=steps):
            def body(c, _):
                return step(c), None
            out, _ = jax.lax.scan(body, v, None, length=_steps)
            # force completion: tiny reduction fetched to host after
            # the scan
            return out, jnp.sum(
                jax.tree.leaves(out)[0].astype(jnp.float32))
        # donation consumes the input, so each rep runs on a fresh
        # on-device copy made outside the timed region
        run = jax.jit(run_fn, donate_argnums=0 if donate else ())
        fresh = jax.tree.map(jnp.copy, values)
        out, s = run(fresh)
        _ = float(s)  # warmup / compile
        best = float("inf")
        for _ in range(reps):
            fresh = jax.tree.map(jnp.copy, values)
            t0 = time.perf_counter()
            out, s = run(fresh)
            _ = float(s)
            best = min(best, time.perf_counter() - t0)
        times[steps] = best
    return (times[s2] - times[s1]) / (s2 - s1)


def _scan_runners(step: Callable, values: Values, lengths: tuple,
                  donate: bool = True) -> dict:
    """Build and WARM one donated-scan runner per scan length (compile
    happens here, never inside a timed region): length → jitted
    ``values -> (out, scalar)``; fetching the scalar forces
    completion."""
    import jax
    import jax.numpy as jnp

    runners = {}
    for steps in lengths:
        def run_fn(v, _steps=steps):
            def body(c, _):
                return step(c), None
            out, _ = jax.lax.scan(body, v, None, length=_steps)
            return out, jnp.sum(
                jax.tree.leaves(out)[0].astype(jnp.float32))
        run = jax.jit(run_fn, donate_argnums=0 if donate else ())
        fresh = jax.tree.map(jnp.copy, values)
        _, s = run(fresh)
        _ = float(s)  # warmup / compile
        runners[steps] = run
    return runners


def _marginal_sample(runners: dict, values: Values, s1: int,
                     s2: int) -> float:
    """One marginal per-step estimate from pre-warmed runners: both
    scan lengths timed back-to-back so chip-state drift hits the two
    arms of the estimate together."""
    import time as _time

    import jax
    import jax.numpy as jnp

    ts = {}
    for steps in (s1, s2):
        fresh = jax.tree.map(jnp.copy, values)
        t0 = _time.perf_counter()
        _, s = runners[steps](fresh)
        _ = float(s)
        ts[steps] = _time.perf_counter() - t0
    return (ts[s2] - ts[s1]) / (s2 - s1)


def marginal_step_trials(step: Callable, values: Values, s1: int = 10,
                         s2: int = 60, trials: int = 5,
                         donate: bool = True) -> list[float]:
    """``trials`` independent marginal per-step estimates (seconds).

    The two scan lengths are timed back-to-back WITHIN each trial, so
    chip-state drift hits both arms of one marginal estimate together;
    the runners are built and warmed once (one compile), then every
    trial is pure timing. Callers take the MEDIAN and report the min/max
    spread."""
    runners = _scan_runners(step, values, (s1, s2), donate)
    return [_marginal_sample(runners, values, s1, s2)
            for _ in range(trials)]


def marginal_runner_trials(make_output: Callable[[int], object],
                           s1: int = 10, s2: int = 40,
                           trials: int = 3) -> list[float]:
    """``trials`` marginal per-step estimates for an arbitrary runner
    (``make_output(num_steps)`` must block until the work is done): the
    runner-shaped counterpart of ``marginal_step_trials``, with the same
    back-to-back-within-a-trial discipline. Call ``make_output(s1)``
    once yourself first if warmup/compile must not pollute trial 1 —
    this function times every call it makes."""
    import time as _time

    out: list[float] = []
    for _ in range(trials):
        ts = {}
        for steps in (s1, s2):
            t0 = _time.perf_counter()
            make_output(steps)
            ts[steps] = _time.perf_counter() - t0
        out.append((ts[s2] - ts[s1]) / (s2 - s1))
    return out


def interleaved_ab(steps: dict, values: Values, *, s1: int = 5,
                   s2: int = 25, reps: int = 4,
                   spread: bool = False) -> dict:
    """Interleaved A/B medians: one marginal sample per arm per round,
    arms alternating so chip-state drift hits every arm of a round
    together (speedup claims are only made when they survive
    interleaving).

    EVERY arm's two scan-length runners are built and warmed up front
    (one compile per arm per length, the same once-only protocol as
    ``marginal_step_trials``) — the rounds are then pure timing, so
    ``reps`` can be raised to settle a claim without re-paying ``reps``
    jit compilations per arm (the round-5 harness re-jitted both scan
    lengths every round, which both wasted minutes and let compile-side
    state leak into the later rounds' timings).

    ``steps`` maps arm name → step function; returns arm name → median
    marginal seconds per step call, or — with ``spread=True`` — arm
    name → ``{value, spread_lo, spread_hi}`` so callers can test
    whether an A/B gap clears the cross-round spread."""
    runners = {name: _scan_runners(step, values, (s1, s2))
               for name, step in steps.items()}
    times: dict = {name: [] for name in steps}
    for _ in range(reps):
        for name in steps:
            times[name].append(
                _marginal_sample(runners[name], values, s1, s2))
    if spread:
        return {name: median_spread(ts) for name, ts in times.items()}
    import statistics

    return {name: statistics.median(ts) for name, ts in times.items()}


def positive_spread(samples: list[float], scale: float) -> dict:
    """{lo, hi} of ``scale / t`` over the POSITIVE samples — a noise
    transient can make an individual marginal estimate non-positive,
    and such samples carry no spread information (a negative per-step
    time inverts into a negative throughput bound). Null fields when
    none survive. The one implementation behind every cups/scenarios-
    per-second spread the bench and ladder publish."""
    pos = [s for s in samples if s > 0]
    return {"lo": scale / max(pos) if pos else None,
            "hi": scale / min(pos) if pos else None}


def median_spread(samples: list[float]) -> dict:
    """{value: median, spread_lo: min, spread_hi: max} of the samples —
    the shape BENCH/ladder rows report so successive rounds don't read
    noise as regressions."""
    import statistics

    return {"value": statistics.median(samples),
            "spread_lo": min(samples), "spread_hi": max(samples)}


#: latency samples kept for the percentile fields — bounded so a
#: long-lived service cannot grow the reservoir forever (at 64Ki samples
#: the p50/p99 of the RECENT traffic is what the snapshot reports, which
#: is what an operator watching a live service wants anyway)
LATENCY_RESERVOIR = 65536


class LatencyReservoir:
    """THE bounded percentile reservoir (ISSUE 15 satellite): one
    implementation behind the queue-latency and wake-latency fields
    that used to be two copy-pasted deque+sort blocks, and behind the
    tracer's per-stage span rollups.

    Bounded (the most recent ``maxlen`` samples — what an operator
    watching a live service wants anyway) and locked with its own LEAF
    lock (a plain ``threading.Lock``; nothing is ever acquired under
    it, and callers holding their own locks read percentiles BEFORE
    taking them, so the reservoir adds no acquisition-graph edges)."""

    def __init__(self, maxlen: int = LATENCY_RESERVOIR):
        import threading

        self._lock = threading.Lock()
        self._samples: collections.deque = collections.deque(
            maxlen=int(maxlen))

    def record(self, seconds: float) -> None:
        with self._lock:
            self._samples.append(float(seconds))

    def __len__(self) -> int:
        with self._lock:
            return len(self._samples)

    @staticmethod
    def percentile_of(sorted_samples: list, q: float):
        """Nearest-rank percentile over an already-sorted list (None
        when empty) — the one percentile definition every p50/p99 the
        package publishes shares."""
        if not sorted_samples:
            return None
        i = min(int(round(q * (len(sorted_samples) - 1))),
                len(sorted_samples) - 1)
        return sorted_samples[i]

    def snapshot(self, prefix: str = "latency") -> dict:
        """One consistent percentile cut:
        ``{<prefix>_n, <prefix>_p50_s, <prefix>_p99_s}``."""
        with self._lock:
            samples = sorted(self._samples)
        return {
            f"{prefix}_n": len(samples),
            f"{prefix}_p50_s": self.percentile_of(samples, 0.50),
            f"{prefix}_p99_s": self.percentile_of(samples, 0.99),
        }


class ThroughputCounter:
    """Monotonic serving counters for the ensemble engine (scheduler /
    service): scenarios served, dispatches, dispatched lanes (incl.
    bucket padding), busy wall seconds, runner-cache hits.

    THREAD-SAFE (ISSUE 9 satellite): the async serving loop mutates
    these counters from the dispatch thread while clients read
    ``snapshot()`` (and bump shed counters) from their own threads, so
    every mutation goes through a method that takes the single internal
    lock, and ``snapshot()`` is taken under the same lock — the returned
    dict is one consistent cut, never a torn read (e.g. ``scenarios``
    from before a dispatch with ``busy_s`` from after it). Counters are
    never written by attribute assignment from outside; use
    ``record_dispatch`` / ``record_latency`` / ``bump``.

    ``snapshot()`` derives the serving metrics the bench/CLI publish:
    ``scenarios_per_s`` (scenarios / busy seconds — DISPATCH wall only,
    so queueing latency from a max-wait policy is not billed as
    compute), ``batch_occupancy`` (real lanes / dispatched lanes — how
    much of each padded bucket did real work),
    ``compile_cache_hit_rate`` (dispatches that reused a built runner)
    and the queue-latency percentiles ``latency_p50_s``/``latency_p99_s``
    (submit-to-served by the scheduler's clock, over the most recent
    ``LATENCY_RESERVOIR`` served scenarios).

    The self-healing counters (ISSUE 5) make recovery observable, never
    silent: ``solo_retries`` (failed scenarios re-dispatched alone),
    ``recovered_failures`` (scenarios whose solo retry succeeded — the
    fault was the batch's, not theirs), ``quarantined`` (scenarios whose
    solo retry failed too — deterministic scenario faults, isolated with
    their ``FailureEvent``) and ``impl_faults`` (whole-dispatch failures
    feeding the degradation ladder). ISSUE 9 adds the overload/deadline
    ledger: ``shed`` (submissions refused at admission —
    ``ServiceOverloaded``) and ``expired`` (tickets whose deadline
    passed before dispatch — resolved as ``TicketExpired`` with a
    complete ``FailureEvent``, never silently dropped).
    """

    #: the integer counters bump() accepts — a typo'd name must fail
    #: loudly, not silently count into a new attribute nothing reads
    COUNTERS = ("dispatches", "scenarios", "lanes", "cache_hits",
                "solo_retries", "recovered_failures", "quarantined",
                "impl_faults", "shed", "expired", "loop_faults",
                "member_faults", "readmitted", "scale_ups", "scale_downs",
                "respawns", "heartbeats", "heartbeat_misses",
                "wire_errors", "hibernations", "rehibernations",
                "wakes", "wake_faults", "supervisor_kills",
                "stale_epoch_rejections")

    def __init__(self):
        # lockdep factory (ISSUE 12): plain Lock disarmed, witnessed
        # when the order witness is armed — the counter lock is a LEAF
        # of the static acquisition graph (bump/snapshot call nothing)
        self._lock = lockdep.lock("ThroughputCounter._lock")
        self.dispatches = 0
        self.scenarios = 0
        self.lanes = 0
        self.busy_s = 0.0
        #: launch-to-complete span per dispatch, summed — the time a
        #: dispatch was OUTSTANDING (device had work in flight). Under
        #: the async loop this exceeds busy_s (which bills only the
        #: host-observed launch+fetch segments): inflight_s/wall is the
        #: serving occupancy metric; busy_s feeds scenarios_per_s.
        #: Synchronously the two coincide.
        self.inflight_s = 0.0
        self.cache_hits = 0
        self.solo_retries = 0
        self.recovered_failures = 0
        self.quarantined = 0
        self.impl_faults = 0
        #: submissions refused at admission (bounded queue / health gate)
        self.shed = 0
        #: tickets whose per-ticket deadline passed before dispatch
        self.expired = 0
        #: dispatch-loop iterations that raised and were supervised
        #: (the loop stays alive; the fault is counted, never silent)
        self.loop_faults = 0
        #: fleet members fenced (dead pump / wedge / ladder bottom) —
        #: each carries a kind="member" FailureEvent (ISSUE 10)
        self.member_faults = 0
        #: tickets re-admitted to a healthy member after their member
        #: was fenced or a crash-restart recovery replayed the journal
        self.readmitted = 0
        #: autoscaling actions (fleet supervisor)
        self.scale_ups = 0
        self.scale_downs = 0
        #: ISSUE 13 (multi-process fleet): members respawned in place
        #: (fence → gen+1), heartbeat RPCs sent / missed, and wire
        #: failures classified as member faults
        self.respawns = 0
        self.heartbeats = 0
        self.heartbeat_misses = 0
        self.wire_errors = 0
        #: ISSUE 14 (scenario tiering): scenarios paged to the
        #: hibernation tier (rehibernations = the subset that had
        #: already hibernated once — their chain writes are deltas),
        #: scenarios woken back to residency, and wakes that could not
        #: restore their chain (fell back to the journal or resolved
        #: as a HibernationError — never a silent fresh start)
        self.hibernations = 0
        self.rehibernations = 0
        self.wakes = 0
        self.wake_faults = 0
        #: ISSUE 20 (supervisor failover): injected supervisor kills
        #: (the ``supervisor_kill`` chaos seam turning this supervisor
        #: into a zombie) and journal appends the epoch fence refused
        #: because a standby had already taken the stream over
        self.supervisor_kills = 0
        self.stale_epoch_rejections = 0
        #: the queue-latency and wake-latency reservoirs share ONE
        #: implementation (ISSUE 15 satellite): bounded, self-locked
        #: LatencyReservoir — wake latency is the wall seconds each
        #: wake spent materializing its scenario (chain restore +
        #: resubmit), the paging cost a client actually observes
        self._latencies = LatencyReservoir()
        self._wake_latencies = LatencyReservoir()

    def record_dispatch(self, scenarios: int, bucket: int, wall_s: float,
                        cache_hit: bool,
                        inflight_s: Optional[float] = None) -> None:
        with self._lock:
            self.dispatches += 1
            self.scenarios += int(scenarios)
            self.lanes += int(bucket)
            self.busy_s += float(wall_s)
            self.inflight_s += float(wall_s if inflight_s is None
                                     else inflight_s)
            if cache_hit:
                self.cache_hits += 1

    def bump(self, name: str, n: int = 1) -> None:
        """Increment one named counter under the lock — the ONLY
        sanctioned way to mutate a counter from outside (attribute
        ``+=`` from another thread is a lost-update race)."""
        if name not in self.COUNTERS:
            raise ValueError(
                f"unknown counter {name!r} (expected one of "
                f"{self.COUNTERS})")
        with self._lock:
            setattr(self, name, getattr(self, name) + int(n))

    def busy_per_scenario(self) -> Optional[float]:
        """busy seconds per served scenario (None before any serve) —
        a two-read O(1) accessor for hot paths (admission's retry-after
        estimate) that must not pay ``snapshot()``'s reservoir sort."""
        with self._lock:
            return self.busy_s / self.scenarios if self.scenarios else None

    def record_latency(self, seconds: float) -> None:
        """One served scenario's submit-to-served latency (scheduler
        clock), feeding the p50/p99 snapshot fields. The reservoir
        carries its own leaf lock — the counter lock is not taken."""
        self._latencies.record(seconds)

    def record_wake_latency(self, seconds: float) -> None:
        """One wake's wall seconds (hibernation-chain restore through
        resubmission — ``time.perf_counter`` spans, real even under a
        fake scheduler clock), feeding the ``wake_latency_p50_s``/
        ``wake_latency_p99_s`` snapshot fields."""
        self._wake_latencies.record(seconds)

    def snapshot(self) -> dict:
        # percentile cuts are read BEFORE the counter lock: the
        # reservoirs are their own (leaf-) locked objects, so taking
        # them under the counter lock would add an acquisition edge
        # for no atomicity gain (a latency sample racing a counter
        # bump was never one transaction to begin with)
        lat = self._latencies.snapshot("latency")
        wlat = self._wake_latencies.snapshot("wake_latency")
        with self._lock:
            return {
                "dispatches": self.dispatches,
                "scenarios": self.scenarios,
                "scenarios_per_s": (self.scenarios / self.busy_s
                                    if self.busy_s > 0 else None),
                "batch_occupancy": (self.scenarios / self.lanes
                                    if self.lanes else None),
                "compile_cache_hits": self.cache_hits,
                "compile_cache_hit_rate": (self.cache_hits / self.dispatches
                                           if self.dispatches else None),
                "busy_s": self.busy_s,
                "inflight_s": self.inflight_s,
                "solo_retries": self.solo_retries,
                "recovered_failures": self.recovered_failures,
                "quarantined": self.quarantined,
                "impl_faults": self.impl_faults,
                "shed": self.shed,
                "expired": self.expired,
                "loop_faults": self.loop_faults,
                "member_faults": self.member_faults,
                "readmitted": self.readmitted,
                "scale_ups": self.scale_ups,
                "scale_downs": self.scale_downs,
                "respawns": self.respawns,
                "heartbeats": self.heartbeats,
                "heartbeat_misses": self.heartbeat_misses,
                "wire_errors": self.wire_errors,
                "hibernations": self.hibernations,
                "rehibernations": self.rehibernations,
                "wakes": self.wakes,
                "wake_faults": self.wake_faults,
                "supervisor_kills": self.supervisor_kills,
                "stale_epoch_rejections": self.stale_epoch_rejections,
                **lat,
                **wlat,
            }


def marginal_runner_time(make_output: Callable[[int], object],
                         s1: int = 10, s2: int = 50,
                         reps: int = 2) -> float:
    """Marginal per-step seconds for an arbitrary runner: calls
    ``make_output(num_steps)`` (which must block until the work is truly
    done and may be a subprocess run) at two step counts."""
    times = {}
    for steps in (s1, s2):
        make_output(steps)  # warmup / compile / page-in
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            make_output(steps)
            best = min(best, time.perf_counter() - t0)
        times[steps] = best
    return (times[s2] - times[s1]) / (s2 - s1)
