"""Fleet members as separate OS processes (ISSUE 13 tentpole, layer 2).

PR 10's ``FleetSupervisor`` isolates failure domains at the THREAD
level: a member is an in-process ``AsyncEnsembleService``, so one OS
process is still the blast radius and the scaling wall. This module
carves the member surface out behind the ``ensemble.wire`` protocol —
the paper's master-rank/worker-rank Send/Recv decomposition reborn at
the service tier:

- :class:`MemberServer` — the worker side: one
  ``AsyncEnsembleService`` behind a :class:`~.wire.FrameConn`, serving
  the RPC vocabulary (submit/poll/migrate/queued/pump/drain/stats/
  dispatch_log/heartbeat/shutdown). Scenario state and model recipes
  cross as the SAME payloads the ticket journal writes, so every byte
  is CRC-verified at both materialization points.
- :func:`main` — the spawned-process entrypoint
  (``python -m mpi_model_tpu.ensemble.member_proc``): builds its model
  from the journal recipe, its service from a JSON config, connects
  back to the supervisor's unix socket — or, in the ISSUE 20
  multi-host mode, dials a ``host:port`` TCP address and authenticates
  through the mutual HMAC handshake (secret via ``$MMTPU_WIRE_SECRET``,
  never argv) — and serves. The child owns its
  DEVICES through the environment the spawner set before ``exec``
  (``JAX_PLATFORMS`` / ``CUDA_VISIBLE_DEVICES`` / ``TPU_VISIBLE_*`` —
  jax reads them at import, which happens entirely inside the child)
  and its own persistent compile cache (``compile_cache`` in the
  member config; the default "auto" shares the machine cache so a
  respawned gen+1 member re-uses every executable gen built).
- :class:`ProcessMemberClient` — the supervisor side: duck-types the
  member surface the fleet already drives (``submit``/``poll``/
  ``pump_once``/``stop``/``abandon``/``stats``/``is_alive``/
  ``has_work_due`` plus a ``scheduler`` proxy for
  ``pending_count``/``queued_tickets``/``migrate_ticket``/counters/
  ladder state), so routing, autoscaling, drain-before-retire, fencing
  and journal recovery run UNCHANGED. Liveness rides HEARTBEATS: the
  supervisor's tick beats every member, the client caches the returned
  telemetry (one consistent member cut), and ``is_alive()`` is
  heartbeat freshness on the injectable clock — a member that misses
  its ``heartbeat_deadline_s`` is fenced, respawned as gen+1 and its
  tickets recovered exactly as PR 10 does for a dead pump thread.
- :func:`spawn_process_member` / :func:`spawn_loopback_member` — the
  two transports behind ``FleetSupervisor(member_transport=
  "process")``: a real spawned child (slow tests / the bench's real
  ``kill -9`` leg), and an in-process serve thread over a
  ``socketpair`` — the SAME codec, framing, chaos seams and client
  path with zero subprocesses, so the tier-1 chaos matrix covers the
  full wire surface (``tests/test_fleet_proc.py``).

Every RPC carries a deadline; a torn frame, CRC failure, EOF or
deadline miss raises the wire's typed errors and the fleet classifies
it as a MEMBER fault — fence, respawn, recover — never a hung
supervisor and never a failed ticket. This module's ``socket``/
``subprocess`` use is the second sanctioned boundary of the
``raw-transport`` analysis rule (``ensemble/wire.py`` is the first).
"""

from __future__ import annotations

import json
import os
import signal
import socket as _socket
import subprocess
import sys
import tempfile
import threading
import time
from typing import Callable, Optional

from ..core.cellular_space import CellularSpace
# the telemetry JSON projection is the SHARED one (ISSUE 15): the
# heartbeat stats cuts here and obs.fleet_snapshot's plane must
# project identically, so there is exactly one implementation
from ..obs import jsonable as _jsonable
from ..resilience import inject
from ..utils.tracing import TraceContext, get_tracer
from .journal import model_from_meta, model_meta, space_payload
from .scheduler import (EnsembleScheduler, TicketExpired,
                        TicketNotMigratable)
from .service import AsyncEnsembleService, ServiceOverloaded
from .wire import (SECRET_ENV, TCP_HEARTBEAT_DEADLINE_S,
                   TCP_RPC_DEADLINE_S, TRACE_META_KEY, FrameConn,
                   HandshakeError, RemoteError, WireError,
                   client_handshake, serve_handshake, tcp_dial,
                   tcp_listener)

__all__ = [
    "MemberServer",
    "ProcessMemberClient",
    "resolve_deadlines",
    "spawn_process_member",
    "spawn_loopback_member",
    "main",
]

#: member kwargs that may cross the process boundary (everything the
#: fleet forwards that is plain data; ``clock`` is dropped — a child
#: process runs wall time — and ``compute_dtype`` crosses as its name)
SPAWNABLE_KWARGS = frozenset((
    "steps", "impl", "substeps", "buckets", "max_wait_s", "max_batch",
    "compute_dtype", "check_conservation", "tolerance", "rtol", "retry",
    "dispatch_deadline_s", "degrade_after", "retry_budget", "windows",
    "donate", "max_queue", "deadline_s", "poll_interval_s",
    "compile_cache", "mesh",
))

#: how long the spawner waits for the child to import jax, build its
#: service and connect back (a cold jax import dominates this)
SPAWN_CONNECT_TIMEOUT_S = 180.0


def _space_from_payload(meta: dict, arrays: Optional[dict]
                        ) -> CellularSpace:
    import jax.numpy as jnp

    if arrays is None:
        raise WireError("scenario payload carries no state arrays")
    vals = {k: jnp.asarray(a) for k, a in arrays.items()}
    return CellularSpace(vals, meta["dim_x"], meta["dim_y"])


def _report_meta(report) -> dict:
    return {
        "comm_size": report.comm_size, "rank_id": report.rank_id,
        "steps": report.steps,
        "initial_total": _jsonable(dict(report.initial_total)),
        "final_total": _jsonable(dict(report.final_total)),
        "wall_time_s": float(report.wall_time_s),
        "backend_report": _jsonable(report.backend_report),
    }


def _report_from_meta(m: dict):
    from ..models.model import Report

    return Report(
        comm_size=m.get("comm_size", 1), rank_id=m.get("rank_id", 0),
        steps=m.get("steps", 0),
        initial_total=m.get("initial_total", {}),
        final_total=m.get("final_total", {}), last_execute=[],
        wall_time_s=m.get("wall_time_s", 0.0),
        backend_report=m.get("backend_report"))


_BACKEND_DEVICES: Optional[dict] = None


def _backend_devices() -> dict:
    """This process's visible accelerator set — the observable the
    ``member_env`` device-pinning contract is asserted against
    (ISSUE 16 satellite): a member spawned with a pinned env (e.g.
    ``CUDA_VISIBLE_DEVICES`` or the CPU rig's
    ``--xla_force_host_platform_device_count``) must report exactly
    the devices its pin allows. Computed once — a process's device set
    is fixed after backend init."""
    global _BACKEND_DEVICES
    if _BACKEND_DEVICES is None:
        import jax

        devs = jax.devices()
        _BACKEND_DEVICES = {
            "platform": devs[0].platform if devs else None,
            "device_count": len(devs),
            "devices": [str(d) for d in devs],
        }
    return _BACKEND_DEVICES


def _rss_bytes() -> Optional[int]:
    """Current resident set size of THIS process (per-member
    observability). /proc on Linux, getrusage peak as the fallback."""
    try:
        with open("/proc/self/statm") as fh:
            return int(fh.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        try:
            import resource

            return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        except (ImportError, OSError, ValueError):
            return None


#: the raw counter fields telemetry carries (the fleet's absorb/
#: progress-signature set plus ``shed`` — snapshot() derives the rest)
TELEMETRY_COUNTERS = ("dispatches", "scenarios", "lanes", "cache_hits",
                      "solo_retries", "recovered_failures", "quarantined",
                      "impl_faults", "expired", "loop_faults", "shed",
                      "busy_s", "inflight_s")


# -- the worker side ----------------------------------------------------------

class MemberServer:
    """One member service behind one wire connection: a strict
    request→reply loop on the serve thread (the caller's thread in
    tests, the child's main thread in a spawned process). The service
    pumps itself (``pump="thread"``) or is pumped over the wire
    (``pump="rpc"`` — the deterministic mode manual fleets drive).

    A ``member_kill`` chaos fault raised inside a pumped iteration
    marks the PUMP dead (the reply says so and the client re-raises it
    for the fleet) while the server keeps answering poll/stats — a dead
    pump thread is not a dead process, exactly the PR 10 semantics. A
    wire failure on the serve connection ends the loop: a member whose
    supervisor link is broken has no caller left to serve."""

    def __init__(self, service: AsyncEnsembleService, conn: FrameConn,
                 pump: str = "thread", ship_spans: bool = True):
        if pump not in ("thread", "rpc"):
            raise ValueError(f"unknown pump mode {pump!r}")
        self.service = service
        self.conn = conn
        self.pump = pump
        #: ship completed-span deltas on heartbeats (ISSUE 15). The
        #: loopback transport turns this OFF: its server shares the
        #: supervisor's process tracer, so every shipped delta would
        #: be JSON-encoded, sent over the socketpair and then
        #: discarded at ingest by the same-pid check — wasted bytes
        #: on the liveness path (the spans are already in the ring)
        self.ship_spans = bool(ship_spans)
        # single serve thread owns all state above; the flags below are
        # poked cross-thread by the loopback kill path, hence the lock
        # (a plain leaf lock: nothing is ever acquired under it)
        self._lock = threading.Lock()
        self._pump_dead = False
        self._stopping = False
        #: highest supervisor epoch seen on any request frame
        #: (ISSUE 20): once a takeover's frames arrive, the zombie
        #: supervisor's lower-epoch frames get a typed ``err`` reply —
        #: the member-side half of the journal's epoch fence
        self._epoch = 0
        #: True only when the supervisor's shutdown RPC ended serving —
        #: the entrypoint's exit-code contract reads it (a lost wire is
        #: NOT a clean shutdown)
        self.clean_shutdown = False
        #: telemetry stats cache: (state signature) -> jsonable stats,
        #: so an idle member's heartbeats skip the latency-reservoir
        #: sort + JSON re-encode (the hot liveness path must stay cheap)
        self._stats_key = None
        self._stats_cached: dict = {}
        #: span-delta cursor (ISSUE 15): each heartbeat ships only the
        #: spans recorded since the previous beat — the supervisor
        #: ingests them into its own tracer ring
        self._span_cursor = 0

    def hard_stop(self) -> None:
        """The loopback stand-in for ``SIGKILL``: close the serve
        connection out from under the loop — in-flight and future RPCs
        fail with the wire's typed errors, exactly like a peer that
        died mid-write. Nothing is drained, nothing replies. The
        member's pump thread dies too (``abandon`` — exit-NOW, no
        drain): a SIGKILLed child loses every thread, and a loopback
        "kill" that left a live in-process pump would keep dispatching
        work — and consuming armed chaos faults — after the fleet
        fenced it."""
        with self._lock:
            self._stopping = True
        self.conn.close()
        self.service.abandon()

    def serve_forever(self) -> None:
        # the conn ALWAYS closes on the way out (even on a torn/corrupt
        # request): a peer blocked on this socket must see EOF — a
        # typed WireClosed — immediately, never wait out its deadline
        # against a silently-departed server
        try:
            while True:
                try:
                    kind, meta, arrays = self.conn.recv(deadline_s=None)
                except WireError:
                    return  # supervisor gone (or torn request/hard_stop)
                try:
                    done = self._handle(kind, meta, arrays)
                except WireError:
                    return  # reply path broken: supervisor fences us
                if done:
                    return
        finally:
            self.conn.close()

    #: reply-send bound: a supervisor that stopped draining its socket
    #: must fence THIS member (WireTimeout ends the serve loop, the
    #: conn closes, the peer sees EOF), never wedge the loop forever
    #: on a full socket buffer
    REPLY_DEADLINE_S = 60.0

    def _reply(self, kind: str, meta: Optional[dict] = None,
               arrays: Optional[dict] = None) -> None:
        """Every server reply crosses here so each send carries the
        bounded deadline — the rpc-no-deadline protocol rule keeps raw
        sends from creeping back in."""
        self.conn.send(kind, meta, arrays,
                       deadline_s=self.REPLY_DEADLINE_S)

    def _handle(self, kind: str, meta: dict, arrays) -> bool:
        # epoch fence (ISSUE 20): requests stamped with a supervisor
        # epoch ratchet the member's high-water mark; a frame from an
        # OLDER epoch is a zombie supervisor's — refuse it with a typed
        # reply (the zombie must stop, the member must not double-serve)
        frame_epoch = meta.get("epoch")
        if frame_epoch is not None:
            with self._lock:
                if frame_epoch < self._epoch:
                    stale = self._epoch
                else:
                    stale = None
                    self._epoch = frame_epoch
            if stale is not None:
                self._reply("err", {
                    "error": "StaleEpochError",
                    "detail": f"frame epoch {frame_epoch} < member's "
                              f"fenced epoch {stale} (a newer "
                              "supervisor owns this member)"})
                return False
        try:
            if kind == "submit":
                return self._handle_submit(meta, arrays)
            if kind == "poll":
                return self._handle_poll(meta)
            if kind == "migrate":
                return self._handle_migrate(meta)
            if kind == "queued":
                self._reply("ok", {
                    "tickets": self.service.scheduler.queued_tickets()})
                return False
            if kind == "pump":
                return self._handle_pump(meta)
            if kind == "drain":
                try:
                    self.service.stop()
                except inject.MemberKilled:
                    # a kill fault landing inside the drain's manual
                    # pump: the pump is dead, the process (this loop)
                    # is not — same split as _handle_pump
                    with self._lock:
                        self._pump_dead = True
                self._reply("ok", {})
                return False
            if kind == "dispatch_log":
                self._reply("ok", {"entries": _jsonable(
                    list(self.service.scheduler.dispatch_log))})
                return False
            if kind == "heartbeat":
                self._reply("ok", {"telemetry": self._telemetry()})
                return False
            if kind == "shutdown":
                if meta.get("mode") == "abandon":
                    self.service.abandon()
                else:
                    self.service.stop()
                with self._lock:
                    self.clean_shutdown = True
                self._reply("ok", {})
                return True
            self._reply("err", {"error": "ValueError",
                                   "detail": f"unknown RPC {kind!r}"})
            return False
        # analysis: ignore[broad-except] — the RPC supervisor: ANY
        # handler failure must become a typed "err" reply the
        # supervisor reconstructs, never a dead serve loop (a broken
        # reply CONNECTION re-raises out of the send itself, which is
        # the one failure that legitimately ends serving)
        except Exception as e:
            self._reply("err", self._err_meta(e))
            return False

    @staticmethod
    def _err_meta(e: Exception) -> dict:
        return {"error": getattr(e, "remote_type", type(e).__name__),
                "detail": str(e)}

    def _handle_submit(self, meta: dict, arrays) -> bool:
        space = _space_from_payload(meta, arrays)
        model = model_from_meta(meta.get("model"), self.service.model)
        steps = meta.get("steps")
        # the frame's trace context (ISSUE 15): attach it around the
        # admission so this member's dispatch spans parent under the
        # FLEET-side submit span — the cross-process half of the trace
        ctx = TraceContext.from_meta(meta.get(TRACE_META_KEY))
        if meta.get("bypass"):
            # the fleet's re-admission/migration path: scheduler-level
            # submit, no admission bound (an already-admitted ticket
            # must not be shed by its rescue)
            sched = self.service.scheduler
            with get_tracer().attach(ctx):
                ticket = sched.submit(space, model, steps)
            if meta.get("migrated"):
                with sched._lock:
                    sched.migrated_in += 1
            self._reply("ok", {"ticket": ticket})
            return False
        try:
            with get_tracer().attach(ctx):
                ticket = self.service.submit(space, model=model,
                                             steps=steps)
        except ServiceOverloaded as e:
            self._reply("overloaded", {
                "detail": str(e), "queue_depth": e.queue_depth,
                "retry_after_s": e.retry_after_s})
            return False
        self._reply("ok", {"ticket": ticket})
        return False

    def _handle_poll(self, meta: dict) -> bool:
        try:
            res = self.service.poll(meta["ticket"])
        except KeyError as e:
            self._reply("err", {"error": "KeyError", "detail": str(e)})
            return False
        # analysis: ignore[broad-except] — the harvest seam crosses the
        # wire here: every per-ticket resolution error (quarantine,
        # expiry, conservation) must become a typed reply the
        # supervisor journals, never a dead serve loop
        except Exception as e:
            body = self._err_meta(e)
            if isinstance(e, TicketExpired):
                body["expired"] = True
            t = getattr(e, "ticket", None)
            if t is not None:
                body["ticket"] = t
            self._reply("err", body)
            return False
        if res is None:
            self._reply("pending", {})
            return False
        space, report = res
        s_meta, s_arrays = space_payload(space)
        s_meta["report"] = _report_meta(report)
        self._reply("ok", s_meta, s_arrays)
        return False

    def _handle_migrate(self, meta: dict) -> bool:
        sched = self.service.scheduler
        try:
            space, model, steps = sched.extract_ticket(meta["ticket"])
        except (TicketNotMigratable, KeyError) as e:
            self._reply("err", self._err_meta(e))
            return False
        recipe = model_meta(model)
        if recipe is None:  # pragma: no cover - defensive: every model
            # on a wire member arrived AS a recipe; put it back rather
            # than lose a scenario we cannot serialize
            sched.submit(space, model, steps)
            self._reply("err", {
                "error": "TicketNotMigratable",
                "detail": "scenario model has no wire recipe"})
            return False
        with sched._lock:
            sched.dispatch_log.append({
                "migrated_ticket": meta["ticket"], "to_ticket": "remote",
                "steps": steps})
        s_meta, s_arrays = space_payload(space)
        s_meta.update({"steps": steps, "model": recipe})
        self._reply("ok", s_meta, s_arrays)
        return False

    def _handle_pump(self, meta: dict) -> bool:
        if self.pump == "thread":
            self._reply("ok", {"did": False})
            return False
        with self._lock:
            dead = self._pump_dead
        if dead:
            self._reply("ok", {"did": False, "killed": True})
            return False
        try:
            did = self.service.pump_once(force=bool(meta.get("force")))
        except inject.MemberKilled:
            # the pump DIED; the process (this serve loop) lives —
            # poll/stats keep answering, the fleet fences on the
            # client's re-raise, PR 10 semantics exactly
            with self._lock:
                self._pump_dead = True
            self._reply("ok", {"did": True, "killed": True})
            return False
        # analysis: ignore[broad-except] — the manual-mode pump
        # supervisor (mirrors AsyncEnsembleService._loop across the
        # wire): a pump fault is counted member-side and survived
        except Exception:
            self.service.scheduler.counter.bump("loop_faults")
            self._reply("ok", {"did": True})
            return False
        self._reply("ok", {"did": bool(did)})
        return False

    def _telemetry(self) -> dict:
        svc = self.service
        sched = svc.scheduler
        with self._lock:
            pump_dead = self._pump_dead
        alive = (svc.is_alive() if self.pump == "thread"
                 else not pump_dead)
        c = sched.counter
        counters = {k: getattr(c, k) for k in TELEMETRY_COUNTERS}
        pending = sched.pending_count()
        gated = sched.intake_gated
        degraded = sched.degraded_from
        # the full stats cut (latency-reservoir sort + JSON encode) is
        # recomputed only when the cheap state signature moved — an
        # idle member's heartbeats, the common liveness traffic, reuse
        # the cached cut
        key = (tuple(sorted(counters.items())), pending, gated,
               degraded, alive)
        with self._lock:
            if key != self._stats_key:
                self._stats_cached = _jsonable(svc.stats())
                self._stats_key = key
            stats = self._stats_cached
            cursor = self._span_cursor
        # completed-span deltas ride the SAME telemetry cut (ISSUE 15):
        # computed OUTSIDE the stats cache — new spans do not
        # necessarily move the counter signature, and a cached cut must
        # never re-ship (duplicate) an already-shipped delta. Projected
        # through _jsonable like the stats cut: one exotic span-meta
        # value (a numpy scalar) must degrade to its repr, never kill a
        # healthy member's heartbeat reply mid-serialize.
        spans: list = []
        if self.ship_spans:
            new_cursor, spans = get_tracer().spans_since(cursor)
            spans = _jsonable(spans)
            with self._lock:
                self._span_cursor = new_cursor
        return {
            "pending": pending,
            "due": svc.has_work_due(),
            "alive": alive,
            "intake_gated": gated,
            "degraded_from": degraded,
            "impl": sched.executor.impl,
            "counters": counters,
            "rss_bytes": _rss_bytes(),
            "backend": _backend_devices(),
            "pid": os.getpid(),
            "stats": stats,
            "spans": spans,
        }


# -- the supervisor side ------------------------------------------------------

class _RemoteCounter:
    """Attribute view over the member's last-heartbeat counters, plus
    a local overlay for the few counts the fleet attributes to a
    member from ITS side (supervised pump faults in manual mode) —
    the ``ThroughputCounter`` surface the fleet's progress signature,
    absorb keys and stats aggregation actually read."""

    def __init__(self, client: "ProcessMemberClient"):
        self._client = client
        self._extra: dict = {}

    def bump(self, name: str, n: int = 1) -> None:
        with self._client._lock:
            self._extra[name] = self._extra.get(name, 0) + int(n)

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        c = self._client
        with c._lock:
            counters = c._telemetry.get("counters", {})
            extra = self._extra.get(name, 0)
        if name in counters:
            return counters[name] + extra
        if name in TELEMETRY_COUNTERS or extra:
            return extra
        raise AttributeError(name)


class _RemoteExecutor:
    def __init__(self, client: "ProcessMemberClient"):
        self._client = client

    @property
    def impl(self) -> Optional[str]:
        with self._client._lock:
            return self._client._telemetry.get("impl")


class _RemoteScheduler:
    """The scheduler surface the fleet touches, over the wire: cheap
    reads (depth, ladder state, counters) come from the cached
    heartbeat telemetry — one member-consistent cut per supervision
    tick, at most one tick stale, which is exactly the freshness the
    routing tiebreak and autoscale signals need — while the mutating
    calls (queued/migrate/submit) are real RPCs."""

    #: the class-level ladder map is plain data — shared verbatim
    DEGRADE_TO = EnsembleScheduler.DEGRADE_TO

    def __init__(self, client: "ProcessMemberClient"):
        self._client = client
        self.counter = _RemoteCounter(client)
        self.executor = _RemoteExecutor(client)

    def pending_count(self) -> int:
        with self._client._lock:
            return int(self._client._telemetry.get("pending", 0))

    @property
    def intake_gated(self) -> bool:
        with self._client._lock:
            return bool(self._client._telemetry.get("intake_gated", False))

    @property
    def degraded_from(self) -> Optional[str]:
        with self._client._lock:
            return self._client._telemetry.get("degraded_from")

    @property
    def dispatch_log(self) -> list:
        _, meta, _ = self._client._rpc("dispatch_log")
        return meta.get("entries", [])

    def queued_tickets(self) -> list:
        kind, meta, _ = self._client._rpc("queued")
        return list(meta.get("tickets", []))

    def migrate_ticket(self, ticket: int, target: "_RemoteScheduler"
                       ) -> int:
        """Wire-backed live migration: the source member drains the
        queued scenario through its CRC-verified extract, the payload
        crosses twice CRC-checked (source→supervisor→target), and the
        target resubmits it scheduler-level (an admitted ticket is
        never shed by its own rescue)."""
        kind, meta, arrays = self._client._rpc("migrate",
                                               {"ticket": ticket})
        if kind == "err":
            _raise_remote(meta)
        return target.submit_payload(
            # analysis: ignore[rpc-asymmetry] — the migrate reply meta
            # IS a space payload: dim_x/dim_y are stamped by the
            # payload codec (journal.space_payload), a vocabulary the
            # server-side literal scan cannot see
            {"dim_x": meta["dim_x"], "dim_y": meta["dim_y"],
             "steps": meta["steps"], "model": meta["model"],
             "migrated": True},
            arrays)

    def submit(self, space: CellularSpace, model, steps: int) -> int:
        """The fleet's re-admission path (bypasses the admission
        bound, like the in-proc scheduler-level submit it mirrors)."""
        meta, arrays = self._client._scenario_payload(space, model, steps)
        return self.submit_payload(meta, arrays)

    def submit_payload(self, meta: dict, arrays) -> int:
        body = dict(meta)
        body["bypass"] = True
        kind, r_meta, _ = self._client._rpc("submit", body, arrays)
        if kind == "err":
            _raise_remote(r_meta)
        return int(r_meta["ticket"])


def _raise_remote(meta: dict) -> None:
    """Reconstruct a member-side error on the supervisor side: the
    ticket-policy types the fleet dispatches on come back as
    THEMSELVES; everything else is a :class:`~.wire.RemoteError`
    whose ``remote_type`` preserves the original class name for
    journaling and the ledger."""
    et = meta.get("error", "RuntimeError")
    detail = meta.get("detail", "")
    if et == "KeyError":
        raise KeyError(detail)
    if et == "TicketExpired" or meta.get("expired"):
        e: Exception = TicketExpired(detail)
    elif et == "TicketNotMigratable":
        e = TicketNotMigratable(detail)
    else:
        e = RemoteError(et, detail)
    if "ticket" in meta:
        e.ticket = meta["ticket"]
    raise e


class ProcessMemberClient:
    """The supervisor's handle on one wire-backed member (module
    docstring). All transport use is serialized under one internal
    lock — a LEAF on purpose: nothing else is ever acquired under it,
    so it cannot participate in an inversion (it is a plain
    ``threading.RLock``, invisible to the lockdep witness, precisely
    because the static auditor cannot see through the duck-typed
    ``_Member.service`` boundary; leaf-ness is what makes that safe).
    Every RPC checks the ``proc_kill`` chaos seam (a REAL ``SIGKILL``
    on a spawned child; the loopback fake hard-stops its serve thread)
    and counts against the wire-site firing index."""

    def __init__(self, conn: FrameConn, service_id: str, *,
                 clock: Callable[[], float] = time.monotonic,
                 heartbeat_deadline_s: float = 2.0,
                 rpc_deadline_s: float = 30.0,
                 proc: Optional[subprocess.Popen] = None,
                 server: Optional[MemberServer] = None,
                 server_thread: Optional[threading.Thread] = None,
                 spawn_dir: Optional[str] = None):
        self.service_id = service_id
        self.model = None  # the member holds the template; fleet's copy routes
        self._conn = conn
        self._clock = clock
        self._hb_deadline = float(heartbeat_deadline_s)
        self._rpc_deadline = float(rpc_deadline_s)
        self._proc = proc
        self._server = server
        self._server_thread = server_thread
        self._spawn_dir = spawn_dir
        # the transport/telemetry lock (leaf; see class docstring)
        self._lock = threading.RLock()
        self._telemetry: dict = {}
        self._last_beat = clock()
        self._killed = False
        #: supervisor epoch stamped into every request frame when set
        #: (ISSUE 20): the fleet arms it from its journal epoch, so a
        #: member that has seen a takeover's frames refuses this
        #: client's if it belongs to a fenced (zombie) supervisor
        self.epoch: Optional[int] = None
        self.scheduler = _RemoteScheduler(self)
        # first beat fills the telemetry so routing/health have a cut
        # to read before the first supervision tick
        self.heartbeat()

    # -- transport -----------------------------------------------------------

    def _rpc(self, kind: str, meta: Optional[dict] = None, arrays=None,
             deadline_s: Optional[float] = None
             ) -> tuple[str, dict, Optional[dict]]:
        st = inject.active()
        if st is not None:
            f = st.member_fault(self.service_id, ("proc_kill",),
                                site="wire", count=True)
            if f is not None:
                self.kill()
        with self._lock:
            deadline = (self._rpc_deadline if deadline_s is None
                        else deadline_s)
            if self.epoch is not None:
                meta = dict(meta or {})
                meta.setdefault("epoch", self.epoch)
            self._conn.send(kind, meta, arrays, deadline_s=deadline)
            return self._conn.recv(deadline_s=deadline)

    @property
    def wire_bytes_in(self) -> int:
        return self._conn.bytes_in

    @property
    def wire_bytes_out(self) -> int:
        return self._conn.bytes_out

    # -- the member surface (duck-typed AsyncEnsembleService) ----------------

    def _scenario_payload(self, space: CellularSpace, model,
                          steps: Optional[int]) -> tuple[dict, dict]:
        meta, arrays = space_payload(space)
        # the caller's trace context crosses in the frame meta
        # (ISSUE 15): the fleet's submit span is open here, so the
        # member's dispatch spans parent under it across the wire
        ctx = get_tracer().current()
        if ctx is not None:
            meta[TRACE_META_KEY] = ctx.to_meta()
        if model is not None:
            recipe = model_meta(model)
            if recipe is None:
                raise ValueError(
                    "this scenario's model has no wire recipe "
                    "(non-scalar flow fields) — a process-transport "
                    "fleet can only serve models model_meta() can "
                    "serialize")
            meta["model"] = recipe
        if steps is not None:
            meta["steps"] = int(steps)
        return meta, arrays

    def submit(self, space: CellularSpace, *, model=None,
               steps: Optional[int] = None) -> int:
        meta, arrays = self._scenario_payload(space, model, steps)
        kind, r_meta, _ = self._rpc("submit", meta, arrays)
        if kind == "overloaded":
            raise ServiceOverloaded(
                r_meta.get("detail", "member admission shed"),
                queue_depth=r_meta.get("queue_depth", 0),
                retry_after_s=r_meta.get("retry_after_s", 0.0))
        if kind == "err":
            _raise_remote(r_meta)
        return int(r_meta["ticket"])

    def poll(self, ticket: int):
        kind, meta, arrays = self._rpc("poll", {"ticket": ticket})
        if kind == "pending":
            return None
        if kind == "err":
            _raise_remote(meta)
        space = _space_from_payload(meta, arrays)
        return space, _report_from_meta(meta.get("report", {}))

    def pump_once(self, force: bool = False) -> bool:
        kind, meta, _ = self._rpc("pump", {"force": bool(force)})
        if meta.get("killed"):
            raise inject.MemberKilled(
                f"member {self.service_id} pump died across the wire")
        return bool(meta.get("did"))

    def heartbeat(self) -> bool:
        """One liveness beat: ship the telemetry cut back and stamp
        the clock. Returns False — a MISS — on any wire failure or an
        armed ``heartbeat_loss`` (which simulates the timeout without
        waiting out real wall time). The caller (the fleet's tick)
        counts misses; ``is_alive`` compares the stamp's age against
        the heartbeat deadline."""
        st = inject.active()
        if st is not None:
            f = st.member_fault(self.service_id, ("proc_kill",),
                                site="wire", count=True)
            if f is not None:
                self.kill()
            if st.member_fault(self.service_id, ("heartbeat_loss",),
                               site="wire") is not None:
                return False
        try:
            with self._lock:
                beat_meta = ({} if self.epoch is None
                             else {"epoch": self.epoch})
                self._conn.send("heartbeat", beat_meta,
                                deadline_s=self._rpc_deadline)
                kind, meta, _ = self._conn.recv(
                    deadline_s=self._rpc_deadline)
        except WireError:
            return False
        if kind != "ok":
            return False
        telemetry = meta.get("telemetry", {})
        with self._lock:
            self._telemetry = telemetry
            self._last_beat = self._clock()
        # absorb the member's completed-span delta into the supervisor
        # tracer (ISSUE 15): ingest() keys spans by their recording pid
        # — a loopback member shares this process's tracer, so its
        # spans are skipped rather than duplicated; a real child's
        # spans merge in wall-anchored and labeled m<slot>g<gen>. A
        # member that dies between beats loses only its unshipped tail
        # (exactly like in-flight wire bytes).
        spans = telemetry.get("spans")
        if spans:
            get_tracer().ingest(spans, label=self.service_id)
        return True

    def heartbeat_age(self) -> float:
        with self._lock:
            return self._clock() - self._last_beat

    def is_alive(self) -> bool:
        """Heartbeat freshness on the injectable clock — the wire
        member's liveness IS its failure detector (there is no thread
        to probe across a process boundary): fresh beats AND the last
        telemetry's own pump-alive flag."""
        with self._lock:
            if self._killed:
                return False
            fresh = (self._clock() - self._last_beat) <= self._hb_deadline
            return fresh and bool(self._telemetry.get("alive", True))

    def has_work_due(self) -> bool:
        with self._lock:
            return bool(self._telemetry.get("due", False))

    def telemetry(self) -> dict:
        """The member's last-heartbeat telemetry cut (RPC-free copy).
        ``telemetry()["backend"]`` is what the ``member_env``
        device-pinning contract is asserted against: the child's OWN
        visible device set as shipped over the wire."""
        with self._lock:
            return dict(self._telemetry)

    def stats(self) -> dict:
        """The member's last-heartbeat stats cut plus the client-side
        wire observability (bytes in/out, heartbeat age, pid, rss) —
        deliberately RPC-free so the fleet's ``stats()`` never blocks
        on a wire under its own lock."""
        with self._lock:
            out = dict(self._telemetry.get("stats", {}))
            out.update({
                "transport": "process",
                "rss_bytes": self._telemetry.get("rss_bytes"),
                "member_pid": self._telemetry.get("pid"),
                # the child's visible device set (the member_env pin's
                # observable) rides the per-member fleet breakdown
                "backend": self._telemetry.get("backend"),
                "heartbeat_age_s": self._clock() - self._last_beat,
                "wire_bytes_in": self._conn.bytes_in,
                "wire_bytes_out": self._conn.bytes_out,
            })
            return out

    # -- lifecycle -----------------------------------------------------------

    def stop(self) -> None:
        """Drain the member (its queue resolves member-side) but KEEP
        the connection: the fleet's final tick still harvests over it.
        ``close()`` is the teardown."""
        try:
            self._rpc("drain", {}, deadline_s=600.0)
        except WireError:
            pass  # a member that died mid-stop is fenced territory

    def abandon(self) -> None:
        """Exit-NOW, the fencing path: best-effort abandon RPC, then
        the connection closes and a spawned child is SIGKILLed — an
        abandoned member must not keep serving work the fleet has
        re-admitted elsewhere."""
        try:
            self._rpc("shutdown", {"mode": "abandon"}, deadline_s=2.0)
        except WireError:
            pass
        self.kill()

    def close(self) -> None:
        """Orderly teardown after the final harvest: shutdown RPC,
        connection closed, child reaped (or killed past its grace)."""
        try:
            self._rpc("shutdown", {"mode": "drain"}, deadline_s=60.0)
        except WireError:
            pass
        with self._lock:
            self._conn.close()
        self._reap(graceful=True)

    def kill(self) -> None:
        """A REAL ``kill -9`` on a spawned child (the ``proc_kill``
        chaos seam and the fencing teardown); the loopback fake
        hard-stops its serve thread — either way the member stops
        answering mid-whatever-it-was-doing."""
        with self._lock:
            self._killed = True
            self._conn.close()
        if self._server is not None:
            self._server.hard_stop()
        self._reap(graceful=False)

    def _reap(self, graceful: bool) -> None:
        if self._proc is not None:
            try:
                if graceful:
                    self._proc.wait(timeout=30.0)
                else:
                    self._proc.kill()  # SIGKILL — the real thing
                    self._proc.wait(timeout=30.0)
            except (OSError, subprocess.TimeoutExpired):
                try:
                    self._proc.kill()
                    # reap the SIGKILLed child too — an unwaited kill
                    # leaves a zombie for the supervisor's lifetime
                    self._proc.wait(timeout=10.0)
                except (OSError, subprocess.TimeoutExpired):
                    pass
        if self._server_thread is not None:
            self._server_thread.join(timeout=30.0)
        with self._lock:
            spawn_dir, self._spawn_dir = self._spawn_dir, None
        if spawn_dir is not None:
            # a respawning fleet spawns many members over its life —
            # each spawn dir (unix socket + config) is reclaimed with
            # its member, not left to accrete in tmp
            import shutil

            shutil.rmtree(spawn_dir, ignore_errors=True)


# -- spawners -----------------------------------------------------------------

def _encode_member_kwargs(member_kwargs: dict) -> dict:
    """The JSON-able member config that crosses exec: ``clock`` is
    dropped (a child runs wall time), ``compute_dtype`` crosses as its
    name, anything outside :data:`SPAWNABLE_KWARGS` is refused loudly
    — a knob that silently failed to cross would make the child a
    different service than the fleet configured."""
    out = {}
    for k, v in member_kwargs.items():
        if k == "clock":
            continue
        if k not in SPAWNABLE_KWARGS:
            raise ValueError(
                f"member kwarg {k!r} cannot cross the process boundary "
                f"(spawnable: {sorted(SPAWNABLE_KWARGS)})")
        if k == "compute_dtype" and v is not None:
            import jax.numpy as jnp

            v = str(jnp.dtype(v))
        elif k == "buckets":
            v = [int(b) for b in v]
        elif k == "mesh" and v is not None:
            # a mesh crosses as its (batch, space) extents — the child
            # rebuilds it over ITS OWN (possibly member_env-pinned)
            # device set; concrete device handles never cross exec
            if isinstance(v, int):
                v = [int(v), 1]
            elif hasattr(v, "batch") and hasattr(v, "space"):
                v = [int(v.batch), int(v.space)]
            else:
                b, s = v
                v = [int(b), int(s)]
        out[k] = v
    json.dumps(out)  # fail at spawn, not in the child's stderr
    return out


def _decode_member_kwargs(cfg: dict) -> dict:
    out = dict(cfg)
    if out.get("compute_dtype") is not None:
        import jax.numpy as jnp

        out["compute_dtype"] = jnp.dtype(out["compute_dtype"])
    if out.get("buckets") is not None:
        out["buckets"] = tuple(out["buckets"])
    if out.get("mesh") is not None:
        out["mesh"] = tuple(out["mesh"])
    return out


def resolve_deadlines(transport: str,
                      heartbeat_deadline_s: Optional[float],
                      rpc_deadline_s: Optional[float]
                      ) -> tuple[float, float]:
    """The per-transport deadline defaults (ISSUE 20): ``None`` means
    "the transport's default" — 2s/30s on the latency-free local
    transports (unix socket, loopback, in-proc), the jitter-tolerant
    ``wire.TCP_*`` pair on tcp. An explicit float always wins."""
    if heartbeat_deadline_s is None:
        heartbeat_deadline_s = (TCP_HEARTBEAT_DEADLINE_S
                                if transport == "tcp" else 2.0)
    if rpc_deadline_s is None:
        rpc_deadline_s = (TCP_RPC_DEADLINE_S if transport == "tcp"
                          else 30.0)
    return float(heartbeat_deadline_s), float(rpc_deadline_s)


def check_chip_free(env: dict) -> None:
    """Refuse to spawn a member that would need the TPU this process
    already holds. A TPU belongs to one process at a time: once a
    parent has initialized jax on it, a child asking for it fails or
    hangs at start-up. A child pinned to ``JAX_PLATFORMS=cpu`` (the
    default) never asks."""
    if "cpu" == env.get("JAX_PLATFORMS", "").strip().lower():
        return
    if "jax" not in sys.modules:
        return
    from jax._src import xla_bridge

    if (xla_bridge.backends_are_initialized()
            and "tpu" in xla_bridge.backends()):
        raise RuntimeError(
            "this process holds the TPU, and the member's environment "
            f"(JAX_PLATFORMS={env.get('JAX_PLATFORMS', '<unset>')!r}) "
            "asks for it: a chip belongs to one process. Run process/tcp "
            "members from a parent that has not touched jax, pin them to "
            "JAX_PLATFORMS=cpu, or serve in-process "
            "(member_transport='inproc').")


def spawn_process_member(model, *, service_id: str, member_kwargs: dict,
                         clock: Callable[[], float] = time.monotonic,
                         transport: str = "unix",
                         host: str = "127.0.0.1",
                         heartbeat_deadline_s: Optional[float] = None,
                         rpc_deadline_s: Optional[float] = None,
                         member_env: Optional[dict] = None,
                         pump_mode: str = "thread",
                         python: Optional[str] = None
                         ) -> ProcessMemberClient:
    """Spawn one REAL member process and return its client handle.

    The device-pinning env contract: the child inherits this process's
    environment with ``member_env`` laid over it BEFORE exec — set
    ``JAX_PLATFORMS`` to pick the backend class and
    ``CUDA_VISIBLE_DEVICES``/``TPU_VISIBLE_DEVICES``/
    ``TPU_VISIBLE_CHIPS`` to pin devices per member (jax reads them at
    import, which happens entirely inside the child). With no override
    the child defaults to ``JAX_PLATFORMS=cpu`` — a spawned member must
    never silently fight its parent for the same accelerator. A TPU
    belongs to one process: when this process holds it and the member's
    environment asks for it, the spawn raises (``check_chip_free``)
    instead of starting a child that would hang at start-up. The
    child's persistent compile cache is ``member_kwargs[
    "compile_cache"]`` (default "auto": the shared machine cache, so a
    respawned gen+1 re-uses gen's executables).

    ``transport="tcp"`` (ISSUE 20) is the multi-host mode: the
    supervisor listens on ``host:<ephemeral>``, a fresh per-member
    shared secret crosses to the child IN ITS ENVIRONMENT
    (``wire.SECRET_ENV`` — never on the command line, where any local
    ``ps`` would read it), and the accepted connection must pass the
    mutual HMAC handshake before the first frame is parsed. Heartbeat
    and RPC deadlines default per transport (see
    :func:`resolve_deadlines`)."""
    if transport not in ("unix", "tcp"):
        raise ValueError(f"unknown member transport {transport!r} "
                         "(expected 'unix' or 'tcp')")
    heartbeat_deadline_s, rpc_deadline_s = resolve_deadlines(
        transport, heartbeat_deadline_s, rpc_deadline_s)
    recipe = model_meta(model)
    if recipe is None:
        raise ValueError(
            "process-transport members need a wire recipe for the "
            "template model (model_meta returned None — non-scalar "
            "flow fields cannot cross a process boundary)")
    cfg = {
        "service_id": service_id,
        "model": recipe,
        "member_kwargs": _encode_member_kwargs(member_kwargs),
        "pump": pump_mode,
    }
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    # dtype fidelity across the boundary: the child must read the
    # wire's f64 state AS f64 — propagate the parent's x64 flag
    # (overridable through member_env like everything else)
    try:
        import jax

        env.setdefault("JAX_ENABLE_X64",
                       "1" if jax.config.jax_enable_x64 else "0")
    except (ImportError, AttributeError):  # pragma: no cover
        pass
    env.update(member_env or {})
    check_chip_free(env)
    spawn_dir = tempfile.mkdtemp(prefix=f"mm-member-{service_id}-")
    cfg_path = os.path.join(spawn_dir, "config.json")
    with open(cfg_path, "w") as fh:
        json.dump(cfg, fh)
    secret = None
    if transport == "tcp":
        import secrets as _secrets

        secret = _secrets.token_hex(32)
        listener = tcp_listener(host, 0)
        addr = "%s:%d" % listener.getsockname()[:2]
    else:
        addr = os.path.join(spawn_dir, "sock")
        listener = _socket.socket(_socket.AF_UNIX, _socket.SOCK_STREAM)
        listener.bind(addr)
        listener.listen(1)
    try:
        if secret is not None:
            env[SECRET_ENV] = secret
        proc = subprocess.Popen(
            [python or sys.executable, "-m",
             "mpi_model_tpu.ensemble.member_proc",
             "--connect", addr, "--config", cfg_path],
            env=env)
        listener.settimeout(SPAWN_CONNECT_TIMEOUT_S)
        try:
            sock, _ = listener.accept()
        except _socket.timeout:
            proc.kill()
            raise WireError(
                f"member {service_id} did not connect within "
                f"{SPAWN_CONNECT_TIMEOUT_S}s of spawn")
        if secret is not None:
            # authenticate BEFORE any frame: a wrong-secret or wedged
            # peer is closed here and the spawn fails loudly
            try:
                serve_handshake(sock, secret, chaos_id=service_id)
            except HandshakeError:
                proc.kill()
                raise
    finally:
        listener.close()
    return ProcessMemberClient(
        FrameConn(sock, chaos_id=service_id), service_id, clock=clock,
        heartbeat_deadline_s=heartbeat_deadline_s,
        rpc_deadline_s=rpc_deadline_s, proc=proc, spawn_dir=spawn_dir)


def spawn_loopback_member(model, *, service_id: str, member_kwargs: dict,
                          clock: Callable[[], float] = time.monotonic,
                          heartbeat_deadline_s: float = 2.0,
                          rpc_deadline_s: float = 30.0,
                          member_env: Optional[dict] = None,
                          pump_mode: str = "rpc"
                          ) -> ProcessMemberClient:
    """The in-memory transport fake: a real :class:`MemberServer` on a
    thread over a real ``socketpair`` — the SAME codec, framing, chaos
    seams and client path as a spawned child, with zero subprocesses,
    so the tier-1 chaos matrix covers the full wire surface. The
    template model still crosses AS ITS RECIPE (wire honesty: a model
    the real transport could not carry must fail here too); the
    injectable ``clock`` and the in-process chaos plan are shared with
    the member service, which is exactly what a fake-clock
    deterministic matrix needs."""
    recipe = model_meta(model)
    if recipe is None:
        raise ValueError(
            "process-transport members need a wire recipe for the "
            "template model (model_meta returned None)")
    member_model = model_from_meta(recipe)
    kwargs = dict(member_kwargs)
    kwargs.setdefault("clock", clock)
    c_sock, s_sock = _socket.socketpair()
    service = AsyncEnsembleService(
        member_model, start=(pump_mode == "thread"),
        service_id=service_id, **kwargs)
    # ship_spans=False: the loopback server shares the supervisor's
    # process tracer — its spans are already in the ring, and shipping
    # them over the socketpair would only be discarded at ingest
    server = MemberServer(service, FrameConn(s_sock), pump=pump_mode,
                          ship_spans=False)
    t = threading.Thread(target=server.serve_forever, daemon=True,
                         name=f"member-serve-{service_id}")
    t.start()
    return ProcessMemberClient(
        FrameConn(c_sock, chaos_id=service_id), service_id, clock=clock,
        heartbeat_deadline_s=heartbeat_deadline_s,
        rpc_deadline_s=rpc_deadline_s, server=server, server_thread=t)


# -- the spawned-process entrypoint -------------------------------------------

def _dial_supervisor(addr: str) -> _socket.socket:
    """Connect back to the spawner: a ``host:port`` address (numeric
    port after the last colon — ISSUE 20's multi-host mode) dials TCP
    and runs the client half of the HMAC handshake with the secret the
    spawner placed in this process's environment (``wire.SECRET_ENV``);
    anything else is a unix socket path."""
    host, sep, port = addr.rpartition(":")
    if sep and host and port.isdigit():
        secret = os.environ.get(SECRET_ENV)
        if not secret:
            raise HandshakeError(
                f"tcp connect to {addr} needs the shared secret in "
                f"${SECRET_ENV} (the spawner sets it; it never rides "
                "the command line)")
        sock = tcp_dial(addr)
        client_handshake(sock, secret)
        return sock
    sock = _socket.socket(_socket.AF_UNIX, _socket.SOCK_STREAM)
    sock.connect(addr)
    return sock


def main(argv: Optional[list] = None) -> int:
    """``python -m mpi_model_tpu.ensemble.member_proc --connect <sock>
    --config <json>``: build the member service from its config and
    serve the supervisor until shutdown. ``--connect`` is a unix
    socket path or a ``host:port`` TCP address (the multi-host mode —
    the wire secret must already be in ``$MMTPU_WIRE_SECRET``). Exit
    codes: 0 = clean shutdown, 2 = bad config, 1 = wire lost (or
    refused at the handshake) before shutdown — the supervisor died,
    fenced us, or we failed its challenge."""
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m mpi_model_tpu.ensemble.member_proc")
    p.add_argument("--connect", required=True,
                   help="unix socket path or host:port TCP address "
                        "the supervisor listens on")
    p.add_argument("--config", required=True,
                   help="member config JSON path (service_id, model "
                        "recipe, member_kwargs, pump mode)")
    args = p.parse_args(argv)
    try:
        with open(args.config) as fh:
            cfg = json.load(fh)
        model = model_from_meta(cfg["model"])
        kwargs = _decode_member_kwargs(cfg.get("member_kwargs", {}))
        pump = cfg.get("pump", "thread")
        service = AsyncEnsembleService(
            model, start=(pump == "thread"),
            service_id=cfg.get("service_id"), **kwargs)
    except (OSError, ValueError, KeyError, TypeError) as e:
        print(f"member config failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 2
    try:
        sock = _dial_supervisor(args.connect)
    except (WireError, OSError) as e:
        print(f"member connect failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    server = MemberServer(service, FrameConn(sock), pump=pump)
    # ignore SIGTERM politeness: the fleet's protocol is the shutdown
    # RPC; anything harder is SIGKILL, which nothing catches anyway
    try:
        signal.signal(signal.SIGINT, signal.SIG_IGN)
    except (OSError, ValueError):  # pragma: no cover - non-main thread
        pass
    server.serve_forever()
    return 0 if server.clean_shutdown else 1


if __name__ == "__main__":
    raise SystemExit(main())
