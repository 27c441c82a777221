"""Scenario queue with bucketed batching — the compile-cache-aware
dispatch policy of the ensemble engine.

Submissions queue per STRUCTURE GROUP: everything that must match for
two scenarios to ride one compiled program (``batch.structure_key`` —
flow structure, offsets, geometry, channel dtypes) plus the step count,
which every lane of one dispatch shares (the count itself is traced, so
it never costs a compile — it is a grouping key only).

A group flushes when it reaches ``max_batch`` scenarios, when its oldest
submission has waited ``max_wait_s`` (checked at every ``pump``/
``poll``), or on ``pump(force=True)``; due groups flush OLDEST-FIRST
(the flush-on-max-wait ordering contract, tested). Each dispatch pads
its k real scenarios up to the smallest configured BUCKET >= k with
zero scenarios (``batch.padding_scenarios`` — zero values, zero rates:
padded lanes contribute nothing to conservation or reports), so the
runner cache — keyed by ``(bucket, shape, dtype, impl, substeps,
structure)`` — sees a handful of batch shapes instead of one per
traffic pattern: any load is served with at most ``len(buckets)``
compiles per structure. The JAX persistent compilation cache rides
UNDER the runner cache by default (``compile_cache="auto"`` →
``utils.compile_cache.cache_dir()``; pass ``None`` to disable):
a restarted process re-uses every executable this checkout already
built, so cold-start costs one cache read, not one compile, per bucket
(ROADMAP direction 5).

``clock`` is injectable (tests drive the max-wait policy — and the
dispatch deadline, via the chaos harness's ``hang`` fault — with a fake
clock); wall times for the throughput counters always come from
``time.perf_counter``.

Self-healing (ISSUE 5): with ``retry="solo"`` a failed scenario is
re-dispatched ALONE once to distinguish a scenario fault from a batch
fault — a solo success means the batch (impl/dispatch level) was at
fault and the scenario's result is recovered; a solo failure means the
scenario itself is poisoned and it is QUARANTINED with a
``FailureEvent`` (batchmates are never retried — their results, good or
bad, stand). Repeated impl-level faults engage the degradation ladder:
``active_fused`` → ``active`` → ``xla`` and ``pipeline`` → ``xla``
(each rung after ``degrade_after`` fresh faults; the fused kernel
first sheds only its Pallas layer, keeping the activity win), reported
through ``stats()``/``backend_report`` rather than silently.
``dispatch_deadline_s`` bounds a dispatch by the injectable
clock: an overrun (a hung dispatch) is a ``DispatchTimeout`` handled
through the same retry/quarantine machinery.

Always-on serving (ISSUE 9): the dispatch path is split into LAUNCH
(assemble, pad, resolve/compile the runner, dispatch the device
program — ``_launch_batch`` → ``batch.launch_ensemble``) and COMPLETE
(non-blocking fetch, conservation, result fan-out — ``finish_flight``
→ ``batch.complete_ensemble``), so ``service.AsyncEnsembleService``'s
pump thread can assemble batch N+1 while batch N runs on-device; the
synchronous path composes the same two halves back-to-back, so async
results are bitwise-equal by construction. The scheduler is
THREAD-SAFE: every shared-state mutation happens under the single
``_lock`` (enforced by the ``unguarded-shared-mutation`` analysis
rule), dispatch device work runs OUTSIDE the lock, and ``stats()`` is
one consistent cut. New robustness policy knobs:

- ``ticket_deadline_s`` — per-ticket deadline by the injectable clock:
  a ticket still QUEUED past its deadline is resolved as a
  ``TicketExpired`` error carrying a complete ``FailureEvent``
  (kind="expired") — never a silent drop.
- ``retry_budget`` — caps TOTAL solo retries: under sustained faults
  the solo-retry machinery would otherwise amplify every failed batch
  into k extra dispatches; once the budget is spent, failed lanes
  quarantine directly (counted, with the budget exhaustion in the
  event detail).
- ``intake_gated`` — raised while the degradation ladder is mid-fall
  (a rung just degraded and no dispatch has completed cleanly since);
  the async service refuses admission (``ServiceOverloaded``) while
  gated, so a failing engine drains instead of accreting backlog.
- ``windows``/``donate`` — advance each dispatch in ``windows`` runner
  calls with the ``[B,H,W]`` state DONATED between consecutive windows
  (``donate_argnums`` — the pjit idiom of SNIPPETS.md [1]/[3]): the
  inter-window copy is eliminated, asserted via ``donated_windows`` in
  the dispatch log.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import time
import warnings
from typing import Callable, Optional, Sequence

from ..core.cellular_space import CellularSpace
from ..obs.flight import get_recorder
from ..resilience import inject, lockdep
from ..utils.metrics import ThroughputCounter
from ..utils.tracing import get_tracer
from .batch import (EnsembleExecutor, complete_ensemble, launch_ensemble,
                    padding_scenarios, structure_key)

#: default bucket ladder: pad k scenarios up to the smallest entry >= k
DEFAULT_BUCKETS = (1, 2, 4, 8)


class DispatchTimeout(RuntimeError):
    """A dispatch overran ``dispatch_deadline_s`` by the scheduler's
    (injectable) clock — the serving layer's view of a hung dispatch.
    Its results are discarded; the affected tickets are retried solo or
    failed, per the retry policy."""


class TicketExpired(RuntimeError):
    """A QUEUED ticket's ``ticket_deadline_s`` passed before it was
    dispatched (ISSUE 9): the scenario was never run, the client gets
    this error from ``poll`` with a complete ``FailureEvent``
    (kind="expired") attached — a deadline miss is an observable
    outcome, never a silent drop."""


class TicketNotMigratable(RuntimeError):
    """``migrate_ticket`` found the ticket PENDING but not QUEUED — it
    is inside a claimed/launched dispatch (ISSUE 10 satellite: with an
    async pump running concurrently this is a normal state, not a bug).
    Migrating it would risk a double dispatch, so the caller is told to
    wait for the in-flight resolution (or re-admit from its own copy of
    the state once the source member is known dead) instead."""


def buckets_for(n: int) -> tuple[int, ...]:
    """Power-of-two bucket ladder covering batches up to ``n``."""
    out = [1]
    while out[-1] < n:
        out.append(out[-1] * 2)
    return tuple(out)


@dataclasses.dataclass
class _Pending:
    ticket: int
    space: CellularSpace
    model: object
    steps: int
    submitted_at: float
    #: the TraceContext current at submission (ISSUE 15) — dispatch
    #: spans (assemble/launch/fetch) parent under it, so a member-side
    #: span chains back to the fleet-side submit span even across the
    #: wire (the context crossed in the submit frame's meta)
    trace: Optional[object] = None


@dataclasses.dataclass
class _Flight:
    """One launched dispatch the scheduler is tracking: the device-side
    half lives in ``inflight`` (``batch.EnsembleInFlight``); the
    scheduler-side bookkeeping (which tickets, which bucket, the
    dispatch-seam firing index for the ``hang`` fault, any injected
    compile-hang seconds) rides here until ``finish_flight``."""

    items: list
    bucket: int
    inflight: object
    cache_hit: bool
    c0: float
    #: injectable clock when the launch returned — the dispatch
    #: deadline bills launch + fetch segments, not the async overlap
    #: gap between them (same rationale as the wall time in
    #: ``batch.complete_ensemble``)
    c_launched: float
    didx: Optional[int]
    #: injectable-clock seconds added by a "slow_compile" fault
    extra_s: float = 0.0


class EnsembleScheduler:
    """Bucketed-batching scenario queue (module docstring has the
    policy). ``submit`` returns an integer ticket; ``poll(ticket)``
    pumps due groups and returns ``(space, Report)`` when served,
    ``None`` while queued, and raises the lane's
    ``EnsembleConservationError`` (with ``.ticket`` attached) when that
    scenario violated — a bad scenario never poisons its batchmates'
    results (``run_ensemble(on_violation="mark")``)."""

    def __init__(self, *, impl: str = "xla", substeps: int = 1,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 max_wait_s: float = 0.0, max_batch: Optional[int] = None,
                 compute_dtype=None, check_conservation: bool = True,
                 tolerance: float = 1e-3, rtol: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic,
                 counter: Optional[ThroughputCounter] = None,
                 retry: str = "none",
                 dispatch_deadline_s: Optional[float] = None,
                 degrade_after: int = 2,
                 ticket_deadline_s: Optional[float] = None,
                 retry_budget: Optional[int] = None,
                 windows: int = 1, donate: bool = False,
                 inline_dispatch: bool = True,
                 compile_cache: Optional[str] = "auto",
                 service_id: Optional[str] = None,
                 mesh=None):
        from ..utils.compile_cache import configure_compile_cache
        from .mesh import resolve_ensemble_mesh

        if retry not in ("none", "solo"):
            raise ValueError(
                f"unknown retry policy {retry!r} (expected 'none' or "
                "'solo')")
        bl = tuple(sorted({int(b) for b in buckets}))
        if not bl or bl[0] < 1:
            raise ValueError(f"buckets must be positive ints, got {buckets}")
        if windows > 1 and impl != "xla":
            raise ValueError(
                f"windows={windows} requires impl='xla' (the active/"
                "pipeline runners carry stat lanes that do not window); "
                f"got impl={impl!r}")
        self.buckets = bl
        self.max_batch = bl[-1] if max_batch is None else int(max_batch)
        if not 1 <= self.max_batch <= bl[-1]:
            raise ValueError(
                f"max_batch={max_batch} outside [1, {bl[-1]}] (the "
                "largest bucket bounds a dispatch)")
        self.max_wait_s = float(max_wait_s)
        #: the persistent-cache dir armed under the runner cache
        #: ("auto" default → the machine default; None = disabled)
        self.compile_cache = configure_compile_cache(compile_cache)
        #: the (batch, space) device mesh every dispatch shards over
        #: (None = single device). Accepts an EnsembleMesh, a batch
        #: extent int, or a (batch, space) pair — the int/pair forms
        #: are what cross the member wire and resolve against the
        #: local (possibly member_env-pinned) device set.
        self.mesh = resolve_ensemble_mesh(mesh)
        self.executor = EnsembleExecutor(impl=impl, substeps=substeps,
                                         compute_dtype=compute_dtype,
                                         mesh=self.mesh)
        self.check_conservation = check_conservation
        self.tolerance = tolerance
        self.rtol = rtol
        self.counter = counter if counter is not None else ThroughputCounter()
        self._clock = clock
        #: stable identity of the serving member this scheduler belongs
        #: to (ISSUE 10 satellite): stamped into stats(), every served
        #: backend_report and every FailureEvent, so multi-service logs
        #: are attributable per member. None = standalone scheduler.
        self.service_id = service_id
        #: "none" (first failure surfaces at poll — the pre-ISSUE-5
        #: behavior) or "solo" (retry-with-quarantine, module docstring)
        self.retry = retry
        #: deadline per dispatch by the injectable clock (None = off)
        self.dispatch_deadline_s = dispatch_deadline_s
        #: deadline per QUEUED ticket by the injectable clock (None =
        #: off): expired tickets resolve as TicketExpired + FailureEvent
        self.ticket_deadline_s = ticket_deadline_s
        #: total solo-retry cap (None = unbounded): the amplification
        #: bound under sustained faults
        self.retry_budget = retry_budget
        #: runner calls per dispatch; >1 with donate=True carries the
        #: [B,H,W] state copy-free between windows (xla impl only)
        self.windows = max(1, int(windows))
        self.donate = bool(donate)
        #: False = the async pump thread owns all dispatching; submit
        #: never runs device work on the caller's thread
        self.inline_dispatch = bool(inline_dispatch)
        #: impl-level faults tolerated per ladder rung (DEGRADE_TO):
        #: active_fused → active → xla, pipeline → xla
        self.degrade_after = int(degrade_after)
        #: the impl the ladder degraded AWAY from (None = never engaged)
        self.degraded_from: Optional[str] = None
        #: True while the ladder is mid-fall: a rung just degraded and
        #: no dispatch has completed cleanly since — the async service
        #: pauses intake while this holds
        self.intake_gated = False
        self._impl_fault_count = 0
        #: one FailureEvent per quarantined scenario, in quarantine order
        self.quarantine_log: list = []
        #: one FailureEvent per expired ticket, in expiry order
        self.expired_log: list = []
        #: live-migration accounting (migrate_ticket): scenarios drained
        #: to / received from another scheduler
        self.migrated_out = 0
        self.migrated_in = 0
        #: THE lock: every read-modify-write of the shared state below
        #: (queues, results, pending set, logs, ladder state) holds it;
        #: device work (launch/complete) runs OUTSIDE it. RLock so the
        #: sync path's nested submit→dispatch→publish chain re-enters.
        #: Built through the lockdep factory (ISSUE 12): plain RLock
        #: when the witness is disarmed, order-recorded when armed.
        self._lock = lockdep.rlock("EnsembleScheduler._lock")
        self._queues: collections.OrderedDict[tuple, list[_Pending]] = \
            collections.OrderedDict()
        self._results: dict[int, object] = {}
        self._pending_tickets: set[int] = set()
        self._ids = itertools.count()
        #: one record per dispatch ({bucket, count, occupancy, steps,
        #: tickets, cache_hit, wall_s}) — the observable flush order.
        #: Bounded: a long-lived service must not grow a log forever
        #: (ThroughputCounter carries the aggregates); the deque keeps
        #: the most recent dispatches for debugging/tests.
        self.dispatch_log: collections.deque = collections.deque(
            maxlen=256)

    # -- submission / results ------------------------------------------------

    def submit(self, space: CellularSpace, model, steps: Optional[int] = None
               ) -> int:
        """Queue one scenario; returns its ticket. The group dispatches
        immediately once it holds ``max_batch`` scenarios (unless
        ``inline_dispatch=False`` — then the pump thread owns it)."""
        with get_tracer().span("ensemble.submit") as sm:
            steps = model.num_steps if steps is None else int(steps)
            key = structure_key(model, space) + (steps,)
            # this span's context rides the ticket: it parents every
            # dispatch span the scenario generates, and sits under the
            # caller's span where there is one (the fleet's submit span,
            # locally or re-attached from the wire's trace meta)
            trace = get_tracer().current()
            with self._lock:
                ticket = next(self._ids)
                self._queues.setdefault(key, []).append(
                    _Pending(ticket, space, model, steps, self._clock(),
                             trace))
                self._pending_tickets.add(ticket)
                full = len(self._queues[key]) >= self.max_batch
            sm["ticket"] = ticket
            get_recorder().record("submit", service_id=self.service_id,
                                  ticket=ticket, steps=steps)
        if full and self.inline_dispatch:
            self._dispatch_group(key)
        return ticket

    def allocate_ticket(self) -> int:
        """Reserve one ticket id WITHOUT queuing a scenario — the
        capacity-aware paging overlay (ISSUE 14) hands these to
        submissions it hibernates instead of enqueuing, so a client's
        ticket namespace is one sequence whether its scenario went
        resident or paged out (polling a hibernated ticket is the
        overlay's job; the scheduler itself reports it unknown)."""
        with self._lock:
            return next(self._ids)

    def queued_since(self, ticket: int) -> Optional[float]:
        """The injectable-clock time a QUEUED ticket was submitted, or
        None when it is not queued — the paging overlay reads it
        before extracting a page-out victim, so a ticket's deadline
        clock survives hibernation instead of restarting per cycle."""
        with self._lock:
            for q in self._queues.values():
                for it in q:
                    if it.ticket == ticket:
                        return it.submitted_at
            return None

    def pending_count(self) -> int:
        """Tickets submitted and not yet resolved (queued or in a
        dispatch) — the admission queue depth the async service bounds."""
        with self._lock:
            return len(self._pending_tickets)

    def due_backlog(self) -> bool:
        """True when some queued group is DUE (full, or its oldest
        submission has waited past ``max_wait_s``) — work a healthy
        pump would be making progress on RIGHT NOW. A partial bucket
        inside its max-wait window is not due: the fleet's wedge
        detector must not fence a member for legitimately waiting out
        its batching policy."""
        with self._lock:
            now = self._clock()
            for q in self._queues.values():
                if q and (len(q) >= self.max_batch
                          or (now - q[0].submitted_at) >= self.max_wait_s):
                    return True
            return False

    def queued_tickets(self) -> list[int]:
        """Tickets still in a queue (submitted, not yet claimed into a
        dispatch) — exactly the set ``migrate_ticket`` can move; the
        fleet's drain-before-retire and fencing paths iterate it."""
        with self._lock:
            return [it.ticket for q in self._queues.values() for it in q]

    def poll(self, ticket: int, pump: bool = True):
        """Result for ``ticket`` if served (due groups are pumped
        first): ``(space, Report)``; ``None`` while queued; raises the
        scenario's ``EnsembleConservationError`` on violation — or the
        dispatch's error when its whole batch failed (e.g. an
        ineligible engine), or ``TicketExpired`` when its deadline
        passed undispatched; ``KeyError`` for unknown or
        already-collected tickets. Failures surface HERE, per affected
        ticket, never out of submit()/poll() on unrelated tickets.
        ``pump=False`` (the async service) only checks — the pump
        thread owns dispatching."""
        if pump:
            self.pump()
        else:
            self.expire_due()
        with self._lock:
            if ticket in self._results:
                res = self._results.pop(ticket)
            elif ticket in self._pending_tickets:
                return None
            else:
                raise KeyError(
                    f"unknown or already-collected ticket {ticket}")
        if isinstance(res, Exception):
            raise res
        return res

    # -- deadlines -----------------------------------------------------------

    def expire_due(self) -> int:
        """Resolve every QUEUED ticket whose ``ticket_deadline_s``
        passed (injectable clock) as a ``TicketExpired`` error with a
        complete ``FailureEvent`` — called at every pump/poll, so a
        deadline miss surfaces at the same cadence a dispatch would.
        Returns the number of tickets expired."""
        if self.ticket_deadline_s is None:
            return 0
        expired: list[tuple[_Pending, float]] = []
        with self._lock:
            now = self._clock()
            for key in list(self._queues):
                q = self._queues[key]
                keep = []
                for it in q:
                    age = now - it.submitted_at
                    if age > self.ticket_deadline_s:
                        expired.append((it, age))
                    else:
                        keep.append(it)
                if keep:
                    self._queues[key] = keep
                else:
                    del self._queues[key]
            for it, age in expired:
                self._resolve_expired_locked(it, age)
        return len(expired)

    def _resolve_expired_locked(self, it: _Pending, age: float) -> None:
        from ..resilience import FailureEvent

        err = TicketExpired(
            f"ticket {it.ticket} expired after {age:.3f}s queued "
            f"(deadline {self.ticket_deadline_s}s) — never dispatched")
        ev = FailureEvent(
            step=it.steps, kind="expired",
            detail=str(err), rolled_back_to=0, attempt=1,
            wall_time_s=0.0, classification="deterministic",
            ticket=it.ticket, service_id=self.service_id)
        err.ticket = it.ticket
        err.failure_event = ev
        self.expired_log.append(ev)
        self.dispatch_log.append({
            "expired_ticket": it.ticket, "steps": it.steps,
            "queued_s": age,
        })
        self._results[it.ticket] = err
        self._pending_tickets.discard(it.ticket)
        self.counter.bump("expired")
        # record only (no dump): this runs under the scheduler lock,
        # and a flight-recorder dump may touch the filesystem
        get_recorder().record("expired", service_id=self.service_id,
                              ticket=it.ticket, queued_s=age)

    # -- flush policy --------------------------------------------------------

    def _claim_due_batch(self, force: bool = False):
        """Pop the next DUE batch (oldest head-of-queue first) under
        the lock, after expiring overdue tickets; None when nothing is
        due. Due = full group, oldest submission waited >= max_wait_s,
        or ``force``."""
        self.expire_due()
        with self._lock:
            now = self._clock()
            due = []
            for key, q in self._queues.items():
                if not q:
                    continue
                if (force or len(q) >= self.max_batch
                        or (now - q[0].submitted_at) >= self.max_wait_s):
                    due.append((q[0].submitted_at, q[0].ticket, key))
            if not due:
                return None
            _, _, key = min(due)
            return self._pop_batch_locked(key)

    def _pop_batch_locked(self, key: tuple):
        q = self._queues.get(key)
        if not q:
            return None
        k = min(len(q), self.buckets[-1])
        items, rest = q[:k], q[k:]
        if rest:
            self._queues[key] = rest
        else:
            del self._queues[key]
        bucket = next(b for b in self.buckets if b >= k)
        if self.mesh is not None:
            # pad-to-(bucket × mesh): the dispatch size must tile the
            # mesh batch extent, so round the bucket up to a multiple.
            # Occupancy/padding-waste accounting stays honest — it is
            # computed against THIS bucket, so mesh padding shows up as
            # waste instead of being hidden in a pre-rounded bucket.
            bucket = self.mesh.round_up(bucket)
        return items, bucket

    def pump(self, force: bool = False) -> int:
        """Dispatch every DUE group — full, or oldest submission waiting
        >= ``max_wait_s`` (``force`` makes everything due) — oldest
        head-of-queue first. Returns the number of dispatches."""
        n = 0
        while True:
            claimed = self._claim_due_batch(force)
            if claimed is None:
                return n
            self._dispatch_claimed(*claimed)
            n += 1

    def drain(self) -> int:
        """Force-flush until every queue is empty; returns dispatches."""
        n = 0
        while True:
            with self._lock:
                empty = not self._queues
            if empty:
                return n
            n += self.pump(force=True)

    def launch_due(self, force: bool = False) -> Optional[_Flight]:
        """Claim and LAUNCH the next due batch without completing it —
        the async loop's overlap primitive: the returned flight's
        device program runs while the caller assembles or completes
        other work; hand it to ``finish_flight``. A launch-time failure
        is fanned out to its tickets here (retry/quarantine policy) and
        None is returned."""
        claimed = self._claim_due_batch(force)
        if claimed is None:
            return None
        items, bucket = claimed
        flight, err = self._launch_batch(items, bucket)
        if err is not None:
            self._fanout_whole_error(items, bucket, err, False, 0.0)
            return None
        return flight

    def migrate_ticket(self, ticket: int,
                       target: "EnsembleScheduler") -> int:
        """Drain one QUEUED scenario off this scheduler and resubmit it
        on ``target`` — the live rebalancing primitive (ISSUE 7): the
        scenario's state crosses through the delta-stream wire format
        (``io.delta.transfer_space`` — a keyframe record whose every
        piece is CRC32-verified at materialization), so the handoff is
        bitwise and a corrupted transfer fails loudly instead of
        resuming wrong state. Neither scheduler stops the world: other
        tickets keep batching on both sides, and the target is free to
        run a different bucket ladder, impl or retry policy.

        Returns the new ticket on ``target``; the old ticket is
        forgotten here (polling it raises KeyError, the collected-
        ticket contract). A ticket already dispatched/served cannot
        migrate — collect its result instead."""
        if target is self:
            raise ValueError(
                "migrate_ticket needs a DIFFERENT target scheduler "
                "(migrating onto oneself is a no-op with extra steps)")
        space, model, steps = self.extract_ticket(ticket)
        new_ticket = target.submit(space, model, steps)
        with target._lock:
            target.migrated_in += 1
        with self._lock:
            self.dispatch_log.append({
                "migrated_ticket": ticket, "to_ticket": new_ticket,
                "steps": steps,
            })
        return new_ticket

    def extract_ticket(self, ticket: int
                       ) -> tuple[CellularSpace, object, int]:
        """Verify-then-drain one QUEUED scenario OUT of this scheduler:
        ``(space, model, steps)`` with the state already passed through
        the CRC-verified transfer (``io.delta.transfer_space``) — the
        first half of :meth:`migrate_ticket`, exposed on its own so a
        WIRE-backed migration (ISSUE 13: the source member serializes
        the scenario, the supervisor resubmits it on another process's
        scheduler) drains through the same verified path. Raises
        ``KeyError`` for unknown/served tickets and
        :class:`TicketNotMigratable` for claimed/launched ones; on any
        failure the ticket stays queued here."""
        with self._lock:
            if ticket in self._results:
                raise KeyError(
                    f"ticket {ticket} is already served — collect it with "
                    "poll() instead of migrating it")
            if ticket not in self._pending_tickets:
                raise KeyError(
                    f"unknown or already-collected ticket {ticket}")
            found = None
            for key, q in self._queues.items():
                for i, it in enumerate(q):
                    if it.ticket == ticket:
                        found = (key, i, it)
                        break
                if found:
                    break
            if found is None:
                # ISSUE 10 satellite: with an async pump running, a
                # pending-but-not-queued ticket is mid-launch (claimed
                # into a dispatch) — migrating it would double-dispatch
                # the scenario; report it as such and leave it alone
                raise TicketNotMigratable(
                    f"ticket {ticket} is inside a claimed/launched "
                    "dispatch — not migratable without risking a double "
                    "dispatch; collect its result (or re-admit it only "
                    "once its member is known dead)")
            key, i, it = found
            from ..io.delta import transfer_space

            # verify-then-drain: a transfer that fails its CRCs raises
            # HERE, with the scenario still queued locally
            # analysis: ignore[blocking-under-lock] — the CRC-verified
            # materialization must complete while the ticket is still
            # queued under this lock, or a failed transfer could both
            # lose the local copy and never deliver the remote one
            space = transfer_space(it.space)
            q.pop(i)
            if not q:
                del self._queues[key]
            self._pending_tickets.discard(ticket)
            self.migrated_out += 1
        return space, it.model, it.steps

    def flush_ticket(self, ticket: int) -> int:
        """Dispatch only the group holding ``ticket`` until that ticket
        is served; OTHER groups keep accumulating toward their own
        max-batch/max-wait flushes (one caller forcing its result must
        not degrade every other tenant's batch occupancy). Returns the
        number of dispatches."""
        n = 0
        while True:
            self.expire_due()
            with self._lock:
                if ticket not in self._pending_tickets:
                    return n
                key = next((k for k, q in self._queues.items()
                            if any(it.ticket == ticket for it in q)), None)
            if key is None:  # pragma: no cover - pending implies queued
                return n
            if not self._dispatch_group(key):
                return n
            n += 1

    def _dispatch_group(self, key: tuple) -> bool:
        with self._lock:
            claimed = self._pop_batch_locked(key)
        if claimed is None:
            return False
        self._dispatch_claimed(*claimed)
        return True

    # -- dispatch ------------------------------------------------------------

    def _dispatch_claimed(self, items: list, bucket: int) -> None:
        """One synchronous dispatch: launch + complete back-to-back —
        the same two halves the async loop drives separately."""
        flight, err = self._launch_batch(items, bucket)
        if err is not None:
            self._fanout_whole_error(items, bucket, err, False, 0.0)
            return
        self.finish_flight(flight)

    def _span_meta(self, items: list, bucket: int) -> dict:
        """Dispatch-span meta (ISSUE 15): the tickets in this batch and
        EVERY ticket's trace id — the span itself can only parent under
        one context (the first item's), so the other lanes correlate
        through ``trace_ids`` (``obs.timeline`` matches on either)."""
        return {
            "tickets": [it.ticket for it in items],
            "trace_ids": [it.trace.trace_id for it in items
                          if it.trace is not None],
            "bucket": bucket,
            "service_id": self.service_id,
        }

    def _launch_batch(self, items: list, bucket: int):
        """Assemble, pad, resolve/compile and DISPATCH ``items`` as one
        batch (no fetch): ``(_Flight, None)``, or ``(None, err)`` when
        assembly/launch failed. Runs OUTSIDE the lock — this is the
        host work the async loop overlaps with device compute."""
        k = len(items)
        template = items[0].model
        spaces = [it.space for it in items]
        models = [it.model for it in items]
        tracer = get_tracer()
        with tracer.span("ensemble.assemble", parent=items[0].trace,
                         **self._span_meta(items, bucket)):
            if bucket > k:
                pspaces, pmodels = padding_scenarios(template, spaces[0],
                                                     bucket - k)
                spaces += pspaces
                models += pmodels
        # chaos seams (resilience.inject): ticket-bound lane poisons are
        # mapped to lane indices and pushed for the launch (the capture
        # window); "batch_exc" fails this whole dispatch; "slow_compile"
        # stretches its clock duration like a hung compile
        st = inject.active()
        didx = st.bump("dispatch") if st is not None else None
        extra_s = 0.0
        pushed = False
        if st is not None:
            poisons = []
            for i, it in enumerate(items):
                f = st.ticket_fault(it.ticket)
                if f is not None:
                    poisons.append((i, f))
            if poisons:
                st.push_lane_poisons(poisons)
                pushed = True
        builds0 = self.executor.builds
        c0 = self._clock()
        try:
            if st is not None:
                bf = st.take("dispatch", didx, kinds=("batch_exc",))
                if bf is not None:
                    raise inject.InjectedFault(
                        f"injected batch fault on dispatch {didx}")
                aidx = st.bump("assemble")
                sf = st.take("assemble", aidx, kinds=("slow_compile",))
                if sf is not None:
                    extra_s = sf.seconds
            donate = self.donate and self.executor.impl == "xla"
            # "launch" covers runner resolution too: on a runner-cache
            # miss the compile happens inside — cache_hit in the span
            # meta says which it was
            with tracer.span("ensemble.launch", parent=items[0].trace,
                             **self._span_meta(items, bucket)) as sm:
                inflight = launch_ensemble(
                    template, spaces, models=models,
                    executor=self.executor,
                    steps=items[0].steps, count=k,
                    windows=(self.windows
                             if self.executor.impl == "xla" else 1),
                    donate=donate)
                sm["cache_hit"] = self.executor.builds == builds0
        # analysis: ignore[broad-except] — dispatch supervisor: any
        # whole-batch failure must fan out to the affected tickets
        # instead of stranding them or leaking into an unrelated caller
        except Exception as e:
            return None, e
        finally:
            if pushed:
                st.clear_lane_poisons()
        cache_hit = self.executor.builds == builds0
        return _Flight(items=items, bucket=bucket, inflight=inflight,
                       cache_hit=cache_hit, c0=c0,
                       c_launched=self._clock(), didx=didx,
                       extra_s=extra_s), None

    def _complete_batch(self, flight: _Flight):
        """Fetch a launched batch and enforce the dispatch deadline:
        ``(results, whole_err, cache_hit, wall)`` — ``results`` aligned
        with the flight's items (lane errors marked), or None with
        ``whole_err`` set. Serving counters are recorded here, so solo
        retries bill like any other dispatch."""
        k = len(flight.items)
        c_f0 = self._clock()
        try:
            with get_tracer().span(
                    "ensemble.fetch", parent=flight.items[0].trace,
                    **self._span_meta(flight.items, flight.bucket)):
                results = complete_ensemble(
                    flight.inflight,
                    check_conservation=self.check_conservation,
                    tolerance=self.tolerance, rtol=self.rtol,
                    on_violation="mark")
        # analysis: ignore[broad-except] — dispatch supervisor: a fetch/
        # conservation-machinery failure fans out like a launch failure
        except Exception as e:
            return None, e, flight.cache_hit, 0.0
        # the batch wall time: from any served lane's Report, else from
        # a marked violation (complete_ensemble stamps it there too, so
        # a dispatch whose every lane violated still bills its wall)
        wall = 0.0
        for res in results:
            if not isinstance(res, Exception):
                wall = res[1].wall_time_s
                break
            wall = getattr(res, "wall_time_s", 0.0) or wall
        # host-observed dispatch time: launch segment + fetch segment
        # (+ injected compile-hang seconds); the async overlap gap —
        # this batch running unobserved while its successor assembled —
        # is NOT billed, so a healthy dispatch can't blow its deadline
        # on a neighbor's slow compile. A real hang lives in the fetch
        # segment and is still caught.
        duration = ((flight.c_launched - flight.c0)
                    + (self._clock() - c_f0) + flight.extra_s)
        st = inject.active()
        if st is not None:
            hf = st.take("dispatch", flight.didx, kinds=("hang",))
            if hf is not None:
                duration += hf.seconds
        if (self.dispatch_deadline_s is not None
                and duration > self.dispatch_deadline_s):
            # a hung dispatch: its results are not trustworthy (and a
            # real hang would never have produced any) — discarded, not
            # served; scenarios are NOT billed to the counters
            return None, DispatchTimeout(
                f"dispatch overran its {self.dispatch_deadline_s}s "
                f"deadline ({duration:.3f}s by the scheduler clock)"
            ), flight.cache_hit, wall
        self.counter.record_dispatch(
            scenarios=k, bucket=flight.bucket, wall_s=wall,
            cache_hit=flight.cache_hit,
            # the outstanding span (launch start → fetched): the
            # occupancy numerator — under the async loop it covers the
            # overlap gap busy_s deliberately does not bill
            # analysis: ignore[naked-timer] — the occupancy span
            # (launch start -> fetched) closes against the launch
            # anchor batch.py recorded; it feeds the inflight_s
            # counter the spans are reconciled against
            inflight_s=time.perf_counter() - flight.inflight.t0)
        with self._lock:
            # a clean completion closes the health gate: the (possibly
            # degraded) engine is serving again
            self.intake_gated = False
            degraded = self.degraded_from
        if degraded is not None or self.service_id is not None:
            # per-row honesty: results served by a degraded engine say
            # so — a consumer must never believe pipeline/active served
            # them after the ladder swapped the engine out; and every
            # served report names the member that produced it
            # (ISSUE 10: multi-service logs must be attributable)
            extra = {}
            if degraded is not None:
                extra = {"impl": self.executor.impl,
                         "degraded_from": degraded}
            if self.service_id is not None:
                extra["service_id"] = self.service_id
            for res in results:
                if not isinstance(res, Exception):
                    rep = res[1]
                    rep.backend_report = {
                        **(rep.backend_report or {}), **extra}
        return results, None, flight.cache_hit, wall

    def _execute_batch(self, items: list, bucket: int):
        """One synchronous physical dispatch (launch + complete):
        ``(results, whole_err, cache_hit, wall)`` — the solo-retry path
        and the two-phase pump both bill through the same halves."""
        flight, err = self._launch_batch(items, bucket)
        if err is not None:
            return None, err, False, 0.0
        return self._complete_batch(flight)

    def finish_flight(self, flight: _Flight) -> None:
        """Complete a launched batch and resolve its tickets: lane
        errors go to solo retry / quarantine per policy, served lanes
        publish with their queue latency recorded, and the dispatch
        log entry reconciles with the counters."""
        items, bucket = flight.items, flight.bucket
        k = len(items)
        results, whole_err, cache_hit, wall = self._complete_batch(flight)
        if whole_err is not None:
            self._fanout_whole_error(items, bucket, whole_err, cache_hit,
                                     wall)
            return
        failed: list[int] = []
        with get_tracer().span("ensemble.publish", parent=items[0].trace,
                               **self._span_meta(items, bucket)):
            for it, res in zip(items, results):
                if isinstance(res, Exception) and self.retry == "solo":
                    if k > 1:
                        # a failed scenario in a batch: re-dispatch it
                        # solo once — its batchmates' results are never
                        # touched
                        failed.append(it.ticket)
                    else:
                        # it already ran alone: nothing left to
                        # distinguish
                        self._quarantine(it, res, attempts=1)
                    continue
                if isinstance(res, Exception):
                    res.ticket = it.ticket
                self._publish(it, res)
        # the retry budget splits the failed lanes BEFORE the log entry
        # is written, so the entry reconciles with what actually runs
        # (the solo counter only moves on this dispatch path, so the
        # predictive split is exact)
        retried: list[int] = []
        budget_starved: list[int] = []
        for t in failed:
            if (self.retry_budget is None
                    or self.counter.solo_retries + len(retried)
                    < self.retry_budget):
                retried.append(t)
            else:
                budget_starved.append(t)
        entry = {
            "bucket": bucket, "count": k, "occupancy": k / bucket,
            "steps": items[0].steps,
            "tickets": [it.ticket for it in items],
            "cache_hit": cache_hit, "wall_s": wall,
        }
        if flight.inflight.windows > 1 or self.donate:
            # the donation observable: how many of this dispatch's
            # windows verifiably reused their carry buffers (no copy)
            entry["windows"] = flight.inflight.windows
            entry["donated_windows"] = flight.inflight.donated_windows
        if retried:
            # an auditor reading the log must be able to reconcile it
            # with stats(): this dispatch was NOT clean — these lanes
            # failed and went to solo retries (logged as their own
            # entries below)
            entry["retried_solo"] = list(retried)
        if budget_starved:
            entry["retry_budget_exhausted"] = list(budget_starved)
        with self._lock:
            self.dispatch_log.append(entry)
        # retries run AFTER the batch entry so the log reads in
        # dispatch order (batch, then its solos)
        by_ticket = {it.ticket: (it, res)
                     for it, res in zip(items, results)}
        for t in retried:
            it, res = by_ticket[t]
            self._serve_solo(it, res, batch_level=False)
        for t in budget_starved:
            it, res = by_ticket[t]
            self._quarantine(it, res, attempts=1,
                             note=f"retry budget ({self.retry_budget}) "
                                  "exhausted — quarantined without a "
                                  "solo retry")

    def fail_flight(self, flight: _Flight, err: Exception) -> None:
        """Last-resort resolution when ``finish_flight`` itself raised
        OUT of the supervised path (e.g. warnings-as-errors turning a
        degrade announcement into an exception mid-fan-out): publish
        ``err`` to every still-pending ticket of the flight, so the
        zero-silently-dropped-tickets contract survives the unwind —
        a client polling one of these tickets gets the error, never an
        eternal None."""
        for it in flight.items:
            with self._lock:
                still = it.ticket in self._pending_tickets
            if still:
                self._publish(it, err)

    def _publish(self, it: _Pending, res) -> None:
        """Resolve one ticket; served results record their queue
        latency (submit → served, injectable clock)."""
        with self._lock:
            self._results[it.ticket] = res
            self._pending_tickets.discard(it.ticket)
        if not isinstance(res, Exception):
            self.counter.record_latency(self._clock() - it.submitted_at)
            get_recorder().record("served", service_id=self.service_id,
                                  ticket=it.ticket)
        else:
            get_recorder().record("failed", service_id=self.service_id,
                                  ticket=it.ticket,
                                  error=type(res).__name__)

    def _fanout_whole_error(self, items: list, bucket: int,
                            whole_err: Exception, cache_hit: bool,
                            wall: float) -> None:
        """An impl/dispatch-level fault (pipeline ineligibility, device
        fault, injected batch fault, deadline overrun): feeds the
        degradation ladder, then either the solo-retry machinery serves
        each lane or — policy "none" — every affected ticket re-raises
        this error when polled. submit()/poll() on OTHER tickets keep
        working either way."""
        k = len(items)
        self._note_impl_fault(whole_err)
        with self._lock:
            self.dispatch_log.append({
                "bucket": bucket, "count": k, "occupancy": k / bucket,
                "steps": items[0].steps,
                "tickets": [it.ticket for it in items],
                "cache_hit": cache_hit, "wall_s": wall,
                "error": f"{type(whole_err).__name__}: {whole_err}",
            })
        if self.retry == "solo":
            for it in items:
                if self._retry_budget_left():
                    self._serve_solo(it, whole_err, batch_level=True)
                else:
                    self._quarantine(
                        it, whole_err, attempts=1,
                        note=f"retry budget ({self.retry_budget}) "
                             "exhausted — quarantined without a solo "
                             "retry")
            return
        for it in items:
            self._publish(it, whole_err)

    def _retry_budget_left(self) -> bool:
        return (self.retry_budget is None
                or self.counter.solo_retries < self.retry_budget)

    def _serve_solo(self, it: _Pending, cause: Exception,
                    batch_level: bool) -> None:
        """Re-dispatch one failed scenario ALONE (once): success means
        the original failure was the batch's — the scenario recovers;
        failure means the scenario itself is at fault — quarantine.
        Solo dispatches get their own ``dispatch_log`` entries, so the
        log stays reconcilable with the ``dispatches``/``solo_retries``
        counters."""
        self.counter.bump("solo_retries")
        # a solo retry still tiles the mesh: pad-to-(bucket × mesh)
        # applies to the smallest bucket exactly like a pumped dispatch
        solo_bucket = (self.buckets[0] if self.mesh is None
                       else self.mesh.round_up(self.buckets[0]))
        results, whole_err, cache_hit, wall = self._execute_batch(
            [it], solo_bucket)
        err = whole_err
        if err is None and isinstance(results[0], Exception):
            err = results[0]
        entry = {
            "bucket": solo_bucket, "count": 1,
            "occupancy": 1 / solo_bucket, "steps": it.steps,
            "tickets": [it.ticket], "cache_hit": cache_hit,
            "wall_s": wall, "solo_retry": True,
            "outcome": "recovered" if err is None else "quarantined",
        }
        if err is not None:
            entry["error"] = f"{type(err).__name__}: {err}"
        with self._lock:
            self.dispatch_log.append(entry)
        if err is None:
            self.counter.bump("recovered_failures")
            if not batch_level:
                # a lane failure that vanishes when the scenario runs
                # alone is evidence of a BATCH-level fault — feed the
                # degradation ladder (whole-batch failures already did)
                self._note_impl_fault(cause)
            self._publish(it, results[0])
            return
        if whole_err is not None:
            self._note_impl_fault(whole_err)
        self._quarantine(it, err, attempts=2)

    def _quarantine(self, it: _Pending, err: Exception,
                    attempts: int, note: Optional[str] = None) -> None:
        """Isolate a deterministically failing scenario: its error (with
        a complete ``FailureEvent``) is what ``poll`` raises; nothing is
        retried again."""
        from ..resilience import FailureEvent

        msg = str(err)
        if isinstance(err, DispatchTimeout):
            kind = "timeout"
        elif "non-finite" in msg:
            kind = "nonfinite"
        elif "conservation" in msg:
            kind = "conservation"
        else:
            kind = "exception"
        detail = f"{type(err).__name__}: {err}"
        if note:
            detail = f"{note}; {detail}"
        ev = FailureEvent(
            step=it.steps, kind=kind,
            detail=detail,
            rolled_back_to=0, attempt=attempts, wall_time_s=0.0,
            classification="deterministic", ticket=it.ticket,
            service_id=self.service_id)
        with self._lock:
            self.quarantine_log.append(ev)
        self.counter.bump("quarantined")
        err.ticket = it.ticket
        err.failure_event = ev
        # the flight recorder dumps beside every quarantine's
        # FailureEvent (ISSUE 15): the ring holds what this service was
        # doing in the run-up, which is the first post-mortem question
        get_recorder().record("quarantined",
                              service_id=self.service_id,
                              ticket=it.ticket, fault_kind=kind)
        get_recorder().dump("quarantine", service_id=self.service_id,
                            ticket=it.ticket)
        self._publish(it, err)

    #: the degradation ladder: each impl's next-simpler engine. The
    #: fused active kernel steps DOWN to the XLA active engine first
    #: (same skip rule, no Pallas in the path — a kernel-level fault
    #: should not cost the activity win), and only then to the dense
    #: vmapped step; pipeline/active go straight to "xla".
    DEGRADE_TO = {"active_fused": "active", "active": "xla",
                  "pipeline": "xla"}

    def _note_impl_fault(self, err: Exception) -> None:
        """Count an impl/dispatch-level fault toward the degradation
        ladder; every ``degrade_after`` faults the executor degrades one
        rung (``active_fused`` → ``active`` → ``xla``, ``pipeline`` →
        ``xla``) — announced, counted, and stamped onto every
        subsequently served report, with the intake gate raised until a
        dispatch completes cleanly. ``degraded_from`` keeps the impl
        the ladder FIRST degraded away from (what the operator
        configured); the current engine is ``stats()["impl"]``."""
        self.counter.bump("impl_faults")
        with self._lock:
            self._impl_fault_count += 1
            nxt = self.DEGRADE_TO.get(self.executor.impl)
            if (nxt is None
                    or self._impl_fault_count < self.degrade_after):
                return
            old = self.executor.impl
            if self.degraded_from is None:
                self.degraded_from = old
            # each further rung needs degrade_after NEW faults
            self._impl_fault_count = 0
            self.executor = EnsembleExecutor(
                impl=nxt, substeps=self.executor.substeps,
                compute_dtype=self.executor.compute_dtype,
                mesh=self.mesh)
            # mid-fall: pause intake until a dispatch completes clean
            self.intake_gated = True
        warnings.warn(
            f"ensemble impl {old!r} degraded to {nxt!r} after "
            f"{self.degrade_after} impl-level dispatch fault(s) "
            f"(last: {type(err).__name__}: {err})", RuntimeWarning)

    # -- observability -------------------------------------------------------

    def stats(self) -> dict:
        """Serving counters (``ThroughputCounter.snapshot``) + runner
        cache accounting + queue depth — one consistent cut (both locks
        taken, never a torn read across a concurrent dispatch)."""
        with self._lock:
            out = self.counter.snapshot()
            out.update({
                "runner_builds": self.executor.builds,
                "runner_cache_hits": self.executor.cache_hits,
                "pending": len(self._pending_tickets),
                "impl": self.executor.impl,
                "substeps": self.executor.substeps,
                "buckets": list(self.buckets),
                "mesh": (None if self.mesh is None else
                         {"batch": self.mesh.batch,
                          "space": self.mesh.space,
                          "devices": self.mesh.devices}),
                "retry": self.retry,
                "retry_budget": self.retry_budget,
                "ticket_deadline_s": self.ticket_deadline_s,
                "degraded_from": self.degraded_from,
                "intake_gated": self.intake_gated,
                "migrated_out": self.migrated_out,
                "migrated_in": self.migrated_in,
                "service_id": self.service_id,
            })
            return out
