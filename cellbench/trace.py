"""Profiler capture and the reduction from a device trace to numbers.

A traced run wraps its measured window in a ``cellbench.window`` host
annotation and its own calls in ``cellbench.<what>`` annotations
(``jax.profiler.TraceAnnotation``), so the host's spans and the
device's operations land in one XSpace on one clock. ``load`` keeps
only what the reductions read:

- per device, the operations that ran on it (name, start, end), from
  the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane (on the CPU
  backend, which only the tests use, from the XLA client threads of the
  host plane);
- the host annotations whose names start with ``cellbench.``.

Everything below ``load`` is arithmetic on plain tuples, so the tests
check it on a recorded trace file and on hand-made intervals.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile
from contextlib import contextmanager
from typing import Iterable, Optional

Interval = tuple[float, float]

#: host annotations the benchmark writes carry this prefix
PREFIX = "cellbench."
WINDOW = PREFIX + "window"

#: a device operation whose opcode holds one of these moves data between
#: chips (XLA's HLO opcodes for the collectives and their async halves)
COLLECTIVE_MARKERS = ("collective-permute", "ppermute", "all-reduce",
                      "all-gather", "reduce-scatter", "all-to-all", "send",
                      "recv")


#: host spans of the benchmark's own work inside the window (the check's
#: copy of one call), left out of the window with the device ops in them
EXCLUDED = (PREFIX + "sample",)


@dataclasses.dataclass
class Trace:
    """The reduced trace: device ops by device, host annotations, and
    the traced window, all in nanoseconds on the profiler's clock."""

    ops: dict            # device id -> [(name, start_ns, end_ns)]
    host: list           # [(name, start_ns, end_ns)]
    window: Interval     # the cellbench.window annotation

    @property
    def spans(self) -> list:
        """The window less the benchmark's own spans in it."""
        own = [(s, e) for n, s, e in self.host if n in EXCLUDED]
        return subtract([self.window], own)

    @property
    def window_s(self) -> float:
        return sum(e - s for s, e in self.spans) * 1e-9

    def device_ops(self, device) -> list:
        """``device``'s ops, clipped to the window's spans."""
        out = []
        for n, s, e in self.ops.get(device, []):
            for w0, w1 in self.spans:
                if e > w0 and s < w1:
                    out.append((n, max(s, w0), min(e, w1)))
        return out


#: an op's HLO opcode: the first lower-case word before a "(" that
#: follows the result shape ("%x = f32[8]{0} fusion(...)" -> "fusion")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def opcode(name: str) -> str:
    """The HLO opcode of a device op's event name ("" when none). The
    CPU backend names its ops without the text: ``while.4`` -> while."""
    _, eq, rhs = name.partition(" = ")
    if not eq:
        return name.lstrip("%").split(".")[0]
    m = _OPCODE.search(" " + rhs)
    return m.group(1) if m else ""


def short_name(name: str) -> str:
    """``%_pallas_step.3 = bf16[..] custom-call(..)`` ->
    ``%_pallas_step.3 custom-call``."""
    head = name.partition(" = ")[0]
    op = opcode(name)
    return f"{head} {op}" if op else head


def is_kernel(name: str) -> bool:
    """Whether a device op is a Pallas kernel (a Mosaic custom call)."""
    return 'custom_call_target="tpu_custom_call"' in name


def is_control(name: str) -> bool:
    """While loops, conditionals and calls: their events enclose the
    ops they run, so summing them would count those ops twice."""
    return opcode(name) in ("while", "conditional", "call")


def is_collective(name: str) -> bool:
    """Whether a device op moves data between chips."""
    op = opcode(name) or name.lower()
    return any(m in op for m in COLLECTIVE_MARKERS)


def union(intervals: Iterable[Interval]) -> list[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def subtract(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """The parts of the disjoint sorted cover ``a`` that ``b`` misses."""
    out = []
    b = union(b)
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def busy_ns(trace: Trace, device) -> float:
    """Time in the window during which some op ran on ``device``."""
    return covered((s, e) for _, s, e in trace.device_ops(device))


def idle_share(trace: Trace) -> float:
    """1 − busy/window, averaged over the devices the trace holds."""
    w = trace.window_s * 1e9
    devs = sorted(trace.ops)
    return sum(1.0 - busy_ns(trace, d) / w for d in devs) / len(devs)


def op_time_ns(trace: Trace, device, match) -> float:
    """Summed durations in the window of ``device``'s ops whose name
    ``match`` accepts."""
    return sum(e - s for n, s, e in trace.device_ops(device) if match(n))


def exposed_collective_share(trace: Trace, device) -> float:
    """Share of the window in which a collective runs on ``device`` and
    no other op does."""
    ops = [o for o in trace.device_ops(device) if not is_control(o[0])]
    coll = union((s, e) for n, s, e in ops if is_collective(n))
    comp = [(s, e) for n, s, e in ops if not is_collective(n)]
    w = trace.window_s * 1e9
    return sum(e - s for s, e in subtract(coll, comp)) / w


def host_label(trace: Trace, t: float) -> str:
    """The innermost benchmark annotation open on the host at ``t``."""
    best = None
    for n, s, e in trace.host:
        if n != WINDOW and s <= t < e and (best is None
                                           or e - s < best[2] - best[1]):
            best = (n, s, e)
    return best[0][len(PREFIX):] if best else "outside any span"


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The ops that took most device time in the window (summed by name
    over devices, averaged per device; loops, which enclose other ops,
    left out), and the longest idle gaps of device 0's timeline
    labelled by what the host was doing."""
    devs = sorted(trace.ops)
    by_name: dict[str, float] = {}
    for d in devs:
        for n, s, e in trace.device_ops(d):
            if is_control(n):
                continue
            k = short_name(n)
            by_name[k] = by_name.get(k, 0.0) + (e - s) * 1e-9 / len(devs)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    d0 = devs[0]
    busy = union((s, e) for _, s, e in trace.device_ops(d0))
    gaps = subtract(trace.spans, busy)
    gaps = sorted(gaps, key=lambda g: -(g[1] - g[0]))[:top]
    return {
        "device_ops": [[n, v] for n, v in ops],
        "idle_gaps": [[host_label(trace, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps],
    }


# -- capture and load --------------------------------------------------------

def _device_id(plane_name: str) -> Optional[int]:
    # "/device:TPU:3" -> 3; the TPU's per-core sub-planes are not devices
    head = "/device:TPU:"
    if not plane_name.startswith(head):
        return None
    tail = plane_name[len(head):]
    return int(tail) if tail.isdigit() else None


def from_profile(data) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`Trace`."""
    ops: dict = {}
    host: list = []
    cpu_ops: dict = {}
    for plane in data.planes:
        dev = _device_id(plane.name)
        if dev is not None:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(dev, []).extend(
                        (e.name, e.start_ns, e.end_ns) for e in line.events)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            xla_client = line.name.startswith("tf_XLAPjRtCpuClient")
            for e in line.events:
                if e.name.startswith(PREFIX):
                    host.append((e.name, e.start_ns, e.end_ns))
                elif xla_client:
                    stats = dict(e.stats)
                    if "hlo_op" in stats:
                        d = int(stats.get("device_ordinal", 0))
                        cpu_ops.setdefault(d, []).append(
                            (e.name, e.start_ns, e.end_ns))
    if not ops:
        ops = cpu_ops
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"the trace holds {len(windows)} '{WINDOW}' "
                         "annotations, not one")
    if not ops:
        raise ValueError("the trace holds no device operations")
    return Trace(ops=ops, host=host, window=windows[0])


def load(path: str) -> Trace:
    import jax

    return from_profile(jax.profiler.ProfileData.from_file(path))


@contextmanager
def capture(workdir: Optional[str] = None):
    """Trace the ``with`` body; yields a list that holds the path of the
    written ``.xplane.pb`` once the body has left. Host Python calls are
    not traced (``python_tracer_level=0``): only annotations and device
    ops, so tracing costs the host little."""
    import jax

    d = tempfile.mkdtemp(prefix="cellbench-trace-", dir=workdir)
    out: list = []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        out.append(files[0] if files else None)
        out.append(d)


def discard(captured: list) -> None:
    """Remove a capture's directory."""
    if len(captured) == 2:
        shutil.rmtree(captured[1], ignore_errors=True)
