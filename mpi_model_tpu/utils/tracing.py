"""Structured tracing — the observability layer the reference lacks.

The reference's only tracing is ``cout << __FILE__ << ": " << __LINE__``
at unhandled branches and a ``__TIMESTAMP__`` in the output filename
(SURVEY §5: "No timers anywhere — the reference never measures its own
speed"). Here tracing is structured and first-class:

- ``Tracer.span(name)`` — nested, thread-safe wall-clock spans with
  per-thread nesting (one span stack per thread, like a profiler);
- **trace context propagation** (ISSUE 15): every span carries a
  ``trace_id``/``span_id``/``parent_id``; nesting parents automatically
  through a per-thread context stack, ``span(parent=ctx)`` parents
  explicitly (a dispatch span under its ticket's submit span), and
  ``attach(ctx)`` adopts a context that crossed a PROCESS boundary (the
  fleet wire carries ``TraceContext.to_meta()`` in the submit frame, so
  member-side spans parent under the fleet-side submit span);
- ``summary()`` — per-name aggregates (count / total / mean / max /
  p50 / p99 via the shared ``metrics.LatencyReservoir`` percentile
  machinery) plus an explicit ``__tracer__`` entry carrying ``dropped``
  — a truncated trace says so in the artifact, not just on the object;
- ``export_chrome()`` — the Chrome trace-event format, loadable in
  ``chrome://tracing`` / Perfetto alongside XLA's own device traces;
  multi-process merges are labeled via ``process_name`` metadata
  records (``label_process`` — the fleet stamps members m<slot>g<gen>)
  and the export carries a top-level ``dropped`` count;
- ``export_stream()`` (ISSUE 20) — a streaming JSONL sink: every span
  appends to the file AS IT COMPLETES (bounded flush cadence), so a
  killed supervisor's trace survives up to the kill instead of dying
  with the never-written end-of-run export; ``obs.timeline`` accepts
  the ``.jsonl`` file wherever it accepts a Chrome trace;
- ``ingest()`` / ``spans_since()`` — the heartbeat shipping lane:
  a member exports its completed-span deltas as plain dicts
  (wall-clock-anchored, so merged timelines order across processes)
  and the supervisor absorbs them into its own ring;
- the profiler's trace: while a tracer is enabled, every span also
  opens a ``jax.profiler.TraceAnnotation`` named ``mm.<span name>``
  (the name only; meta stays in the ring), so any profiler capture
  (``jax.profiler.trace``, TensorBoard) holds the program's spans on
  the profiler's clock, beside the device's ops;
- a process-wide default tracer (``get_tracer``/``trace_span``) that the
  framework's own phases report into. ``Model.execute`` emits
  ``model.execute`` around ``model.totals.initial`` (the per-channel
  totals read before the run), ``executor.enqueue`` (the
  ``run_model`` call), ``executor.block`` (waiting for the device),
  ``model.totals.final`` and ``model.flows.last_execute``; the sharded
  executors emit their build-vs-run phases; the serving stack emits
  ``ensemble.submit`` per scenario and, per dispatch,
  ``ensemble.assemble``/``launch``/``fetch`` with ``fetch`` split into
  ``ensemble.fetch.block``, ``ensemble.fetch.totals`` and
  ``ensemble.fetch.lanes``, then ``ensemble.publish`` (the results'
  fan-out to their tickets), plus per-wake spans. Each host read of the
  device has a span of its own, so the innermost open span names what
  the host did while the device sat idle.

Recording one span costs two ``perf_counter`` calls, two id formats, a
list append and one annotation enter/exit (the annotation, about 1 µs,
records nothing unless a profiler captures). ``PERF.md`` gives what
that costs a long run and a served sweep on a TPU v5e, tracer on
against off and capture on against off; ``Tracer(enabled=False)``
makes it free.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import json
import os
import threading
import time
from typing import Any, Iterator, Optional

from jax.profiler import TraceAnnotation

__all__ = [
    "Span",
    "TraceContext",
    "Tracer",
    "get_tracer",
    "set_tracer",
    "trace_span",
]

#: process-unique span-id source: ids are ``<pid:x>-<n:x>`` so two
#: processes (a fleet and its spawned members) can never collide —
#: no randomness needed, and ids stay stable/debuggable
_ids = itertools.count(1)

#: the profiler annotation of span ``name`` is ``mm.<name>``
ANNOTATION_PREFIX = "mm."


def _new_id() -> str:
    return f"{os.getpid():x}-{next(_ids):x}"


@dataclasses.dataclass(frozen=True)
class TraceContext:
    """One position in a trace: the trace it belongs to and the span
    that is the current parent. Immutable; crosses thread and process
    boundaries as a two-key dict (``to_meta``/``from_meta`` — the TW1
    wire frames and the journal submit records carry exactly this)."""

    trace_id: str
    span_id: str

    def to_meta(self) -> dict:
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    @classmethod
    def from_meta(cls, meta: Optional[dict]) -> Optional["TraceContext"]:
        """None-safe decode — a frame without trace meta propagates
        nothing (spans then root locally), it never errors."""
        if not isinstance(meta, dict):
            return None
        t, s = meta.get("trace_id"), meta.get("span_id")
        if not (isinstance(t, str) and isinstance(s, str)):
            return None
        return cls(t, s)


@dataclasses.dataclass
class Span:
    """One completed span. ``start_s`` is ``perf_counter``-based and only
    meaningful relative to other spans from the same tracer;
    ``start_wall_s`` is the wall-clock anchor (``time.time`` epoch
    seconds) that lets spans from DIFFERENT processes merge into one
    ordered timeline."""

    name: str
    start_s: float
    duration_s: float
    thread: int
    depth: int
    meta: dict[str, Any] = dataclasses.field(default_factory=dict)
    #: trace-context identity (ISSUE 15); None on spans recorded by a
    #: pre-context tracer dict (ingest tolerates their absence)
    trace_id: Optional[str] = None
    span_id: Optional[str] = None
    parent_id: Optional[str] = None
    #: wall-clock anchor of start_s (epoch seconds)
    start_wall_s: Optional[float] = None
    #: recording process (spans ingested from a member keep theirs)
    pid: int = 0
    #: monotone per-tracer append index — the heartbeat delta cursor
    seq: int = 0

    def to_dict(self) -> dict:
        """The wire/export projection (plain JSON-able dict)."""
        return {
            "name": self.name, "start_s": self.start_s,
            "duration_s": self.duration_s, "thread": self.thread,
            "depth": self.depth, "meta": dict(self.meta),
            "trace_id": self.trace_id, "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start_wall_s": self.start_wall_s, "pid": self.pid,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "Span":
        return cls(
            name=d.get("name", "?"), start_s=float(d.get("start_s", 0.0)),
            duration_s=float(d.get("duration_s", 0.0)),
            thread=int(d.get("thread", 0)), depth=int(d.get("depth", 0)),
            meta=dict(d.get("meta") or {}), trace_id=d.get("trace_id"),
            span_id=d.get("span_id"), parent_id=d.get("parent_id"),
            start_wall_s=d.get("start_wall_s"),
            pid=int(d.get("pid", 0)))


class Tracer:
    """Thread-safe span recorder with per-thread nesting and trace
    contexts.

    The buffer is a ring of at most ``max_spans`` (oldest dropped first,
    ``dropped`` counts them) so the always-on default tracer stays
    bounded over arbitrarily long runs."""

    def __init__(self, enabled: bool = True, max_spans: int = 20_000):
        self.enabled = enabled
        self._spans: collections.deque[Span] = collections.deque(
            maxlen=int(max_spans))
        self.dropped = 0
        self._lock = threading.Lock()
        self._local = threading.local()
        self._pid = os.getpid()
        #: wall-clock anchor: start_wall_s = start_s + _wall_off (one
        #: pair of clock reads at construction, not two reads per span)
        self._wall_off = time.time() - time.perf_counter()
        self._seq = 0
        #: pid → human label for export_chrome's process_name metadata
        #: (the fleet labels members m<slot>g<gen> at heartbeat ingest)
        self._process_labels: dict[int, str] = {}
        #: streaming JSONL sink (ISSUE 20): (open file, path, spans
        #: written since the last flush) — see export_stream
        self._stream = None
        self._stream_path: Optional[str] = None
        self._stream_pending = 0

    # -- trace context ------------------------------------------------------

    def _ctx_stack(self) -> list:
        s = getattr(self._local, "ctx", None)
        if s is None:
            s = []
            # analysis: ignore[unguarded-shared-mutation] — threading.local
            # storage: each thread mutates only its own context stack
            self._local.ctx = s
        return s

    def current(self) -> Optional[TraceContext]:
        """The calling thread's innermost open context (a span in
        progress, or an ``attach``-ed remote parent), or None."""
        s = self._ctx_stack()
        return s[-1] if s else None

    @contextlib.contextmanager
    def attach(self, ctx: Optional[TraceContext]) -> Iterator[None]:
        """Adopt a context from elsewhere (another thread or — via the
        wire's ``trace`` meta — another process) as the calling
        thread's current parent, for the duration of the block. A None
        context is a no-op, so call sites need no branching."""
        if ctx is None:
            yield
            return
        stack = self._ctx_stack()
        stack.append(ctx)
        try:
            yield
        finally:
            stack.pop()

    # -- recording ----------------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str, parent: Optional[TraceContext] = None,
             **meta: Any) -> Iterator[dict]:
        """Record one span around the block; yields the (mutable) meta
        dict so values learned inside the block (an allocated ticket
        id) still land on the completed span. ``parent`` overrides the
        thread's current context (the cross-ticket case: a dispatch
        span parenting under ITS ticket's submit span, not under
        whatever the pump thread happens to have open)."""
        if not self.enabled:
            yield meta
            return
        depth = getattr(self._local, "depth", 0)
        # analysis: ignore[unguarded-shared-mutation] — threading.local
        # storage: each thread mutates only its own depth slot
        self._local.depth = depth + 1
        p = parent if parent is not None else self.current()
        trace_id = p.trace_id if p is not None else _new_id()
        span_id = _new_id()
        stack = self._ctx_stack()
        stack.append(TraceContext(trace_id, span_id))
        # the same block on the profiler's clock, enclosing the span
        with TraceAnnotation(ANNOTATION_PREFIX + name):
            t0 = time.perf_counter()
            try:
                yield meta
            finally:
                dt = time.perf_counter() - t0
                stack.pop()
                # analysis: ignore[unguarded-shared-mutation] — per-thread
                # (threading.local) depth slot, mutated by its thread only
                self._local.depth = depth
                s = Span(name=name, start_s=t0, duration_s=dt,
                         thread=threading.get_ident(), depth=depth,
                         meta=dict(meta), trace_id=trace_id,
                         span_id=span_id,
                         parent_id=(p.span_id if p is not None else None),
                         start_wall_s=t0 + self._wall_off, pid=self._pid)
                self._append(s)

    def instant(self, name: str, **meta: Any) -> None:
        """Record a zero-duration marker (the structured version of the
        reference's ``__FILE__:__LINE__`` couts). Parents under the
        thread's current context like a nested span would."""
        if not self.enabled:
            return
        p = self.current()
        t0 = time.perf_counter()
        s = Span(name=name, start_s=t0, duration_s=0.0,
                 thread=threading.get_ident(),
                 depth=getattr(self._local, "depth", 0), meta=dict(meta),
                 trace_id=(p.trace_id if p is not None else _new_id()),
                 span_id=_new_id(),
                 parent_id=(p.span_id if p is not None else None),
                 start_wall_s=t0 + self._wall_off, pid=self._pid)
        self._append(s)

    #: flush the streaming sink every N spans — bounded data-at-risk
    #: (a kill loses at most this many buffered spans) without paying
    #: a syscall per span on the dispatch hot path
    _STREAM_FLUSH_EVERY = 32

    def _append(self, s: Span) -> None:
        with self._lock:
            self._seq += 1
            s.seq = self._seq
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(s)
            if self._stream is not None:
                self._stream_write_locked(s)

    def _stream_write_locked(self, s: Span) -> None:
        try:
            # default=repr: a span's meta may hold anything; the sink
            # must never make recording a span raise at the call site.
            # analysis: ignore[blocking-under-lock] — a buffered
            # ~200-byte write into the libc FILE buffer (no syscall
            # except at the bounded flush below); serializing it under
            # the tracer lock is what keeps the JSONL lines whole when
            # many threads complete spans at once
            self._stream.write(
                json.dumps(s.to_dict(), default=repr) + "\n")
            self._stream_pending += 1
            if self._stream_pending >= self._STREAM_FLUSH_EVERY:
                # analysis: ignore[blocking-under-lock] — the bounded
                # flush cadence: one syscall per _STREAM_FLUSH_EVERY
                # spans, the documented data-at-risk/latency trade
                self._stream.flush()
                self._stream_pending = 0
        except (OSError, ValueError) as e:
            # a dead sink (full disk, closed fd) detaches — tracing
            # continues into the ring; the loss is loud, once
            import warnings

            try:
                self._stream.close()
            except OSError:
                pass
            self._stream = None
            self._stream_path = None
            warnings.warn(
                f"span stream sink failed and was detached: {e}",
                RuntimeWarning)

    # -- cross-process shipping ---------------------------------------------

    def spans_since(self, cursor: int) -> tuple[int, list[dict]]:
        """``(new_cursor, span dicts)`` appended after ``cursor`` — the
        heartbeat telemetry delta a member ships to its supervisor.
        Spans that aged out of the ring before being shipped are simply
        gone (the ring bounds memory; ``dropped`` counts them)."""
        out: list[Span] = []
        with self._lock:
            cur = self._seq
            for s in reversed(self._spans):
                if s.seq <= cursor:
                    break
                out.append(s)
        return cur, [s.to_dict() for s in reversed(out)]

    def ingest(self, span_dicts: list, label: Optional[str] = None
               ) -> int:
        """Absorb spans recorded by ANOTHER process (heartbeat
        telemetry / a fence's final cut) into this ring; returns how
        many were absorbed. Spans stamped with THIS process's pid are
        skipped — the loopback member transport shares the process
        tracer, and shipping its spans over the socketpair must not
        duplicate them. ``label`` names the sending process for
        ``export_chrome``'s process metadata (m<slot>g<gen>)."""
        n = 0
        pids: set = set()
        for d in span_dicts or ():
            s = Span.from_dict(d)
            if s.pid == self._pid:
                continue
            pids.add(s.pid)
            self._append(s)
            n += 1
        if label is not None and pids:
            # one label write per DISTINCT pid per call, not one lock
            # round-trip per span — this runs on every heartbeat
            with self._lock:
                for p in pids:
                    self._process_labels[p] = label
        return n

    def label_process(self, label: str, pid: Optional[int] = None) -> None:
        """Name a pid in chrome exports (``process_name`` metadata)."""
        with self._lock:
            self._process_labels[self._pid if pid is None else pid] = label

    # -- inspection ---------------------------------------------------------

    @property
    def spans(self) -> list[Span]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0

    def summary(self) -> dict[str, dict[str, float]]:
        """Per-name aggregates: count, total_s, mean_s, max_s, p50_s,
        p99_s (percentiles via the shared ``metrics.LatencyReservoir``
        discipline — the per-stage rollup the telemetry plane
        publishes). The reserved ``__tracer__`` entry carries
        ``dropped``/``recorded`` so a truncated trace is explicit in
        every artifact built from this summary."""
        from .metrics import LatencyReservoir

        out: dict[str, dict[str, float]] = {}
        durs: dict[str, list[float]] = {}
        spans = self.spans
        for s in spans:
            agg = out.setdefault(
                s.name, {"count": 0, "total_s": 0.0, "max_s": 0.0})
            agg["count"] += 1
            agg["total_s"] += s.duration_s
            agg["max_s"] = max(agg["max_s"], s.duration_s)
            durs.setdefault(s.name, []).append(s.duration_s)
        for name, agg in out.items():
            agg["mean_s"] = agg["total_s"] / agg["count"]
            d = sorted(durs[name])
            agg["p50_s"] = LatencyReservoir.percentile_of(d, 0.50)
            agg["p99_s"] = LatencyReservoir.percentile_of(d, 0.99)
        out["__tracer__"] = {"dropped": self.dropped,
                             "recorded": len(spans)}
        return out

    # -- export -------------------------------------------------------------

    def chrome_events(self) -> list[dict]:
        """Spans as Chrome trace-event ``X`` (complete) events, µs.
        Timestamps use the wall-clock anchor when present, so spans
        ingested from member processes land on one merged timeline."""
        events = []
        with self._lock:
            # ingest() mutates the label map under the lock from the
            # heartbeat thread — the copy must be under it too
            labels = dict(self._process_labels)
        pids = set()
        for s in self.spans:
            ts = (s.start_wall_s if s.start_wall_s is not None
                  else s.start_s)
            pids.add(s.pid)
            args = dict(s.meta)
            if s.trace_id is not None:
                args.update({"trace_id": s.trace_id, "span_id": s.span_id,
                             "parent_id": s.parent_id})
            events.append({
                "name": s.name,
                "ph": "X",
                "ts": ts * 1e6,
                "dur": s.duration_s * 1e6,
                "pid": s.pid or 1,
                "tid": s.thread,
                "args": args,
            })
        # process metadata records: a merged multi-process trace must
        # label members m<slot>g<gen>, not bare pids (ISSUE 15)
        for pid in sorted(pids):
            name = labels.get(pid)
            if name is None:
                name = ("fleet" if pid == self._pid else f"pid-{pid}")
            events.append({"name": "process_name", "ph": "M",
                           "pid": pid or 1, "args": {"name": name}})
        return events

    def export_stream(self, path: str) -> str:
        """Attach a streaming JSONL sink (ISSUE 20): every span
        COMPLETED from now on appends to ``path`` as one JSON line
        (the ``Span.to_dict`` projection) the moment it lands in the
        ring — unlike ``export_chrome``, which writes nothing until
        the run survives to its end. Flushes every
        ``_STREAM_FLUSH_EVERY`` spans (bounded data-at-risk, no
        syscall per span); ``close_stream()`` flushes the tail and
        detaches. Append-mode: re-attaching after a takeover continues
        the same file. A later ``export_stream`` replaces the sink."""
        f = open(path, "a")
        try:
            # the previous writer may have been KILLED mid-line (the
            # sink's whole point): appending straight after its torn
            # tail would garble the first new span — start it on a
            # fresh line instead (the reader skips the torn fragment)
            with open(path, "rb") as rf:
                rf.seek(0, os.SEEK_END)
                if rf.tell() > 0:
                    rf.seek(-1, os.SEEK_END)
                    if rf.read(1) != b"\n":
                        f.write("\n")
        except OSError:  # pragma: no cover - best effort
            pass
        with self._lock:
            old, self._stream = self._stream, f
            self._stream_path = path
            self._stream_pending = 0
        if old is not None:
            try:
                old.flush()
                old.close()
            except OSError:  # pragma: no cover - best effort
                pass
        return path

    def close_stream(self) -> Optional[str]:
        """Flush + detach the streaming sink; returns its path (None
        when no sink was attached). The ring keeps recording."""
        with self._lock:
            f, self._stream = self._stream, None
            path, self._stream_path = self._stream_path, None
            self._stream_pending = 0
        if f is None:
            return None
        try:
            f.flush()
            f.close()
        except OSError:  # pragma: no cover - best effort
            pass
        return path

    def export_chrome(self, path: str) -> str:
        """Write the trace as a ``chrome://tracing``/Perfetto JSON file.
        The document carries the ring's ``dropped`` count at top level:
        a truncated trace must say so in the artifact itself."""
        with open(path, "w") as f:
            json.dump({"traceEvents": self.chrome_events(),
                       "displayTimeUnit": "ms",
                       "dropped": self.dropped}, f)
        return path


# -- process-wide default tracer ---------------------------------------------

_default = Tracer(enabled=True)
_default_lock = threading.Lock()


def get_tracer() -> Tracer:
    return _default


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the process-wide tracer (e.g. a disabled one); returns the
    previous tracer."""
    global _default
    with _default_lock:
        prev, _default = _default, tracer
    return prev


def trace_span(name: str, **meta: Any):
    """``get_tracer().span(...)`` resolved at call time (so a tracer
    swapped in mid-process is honored)."""
    return get_tracer().span(name, **meta)
