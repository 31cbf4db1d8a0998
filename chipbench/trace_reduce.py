"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy/idle time,
the device operations that took most time, and the longest idle gaps with
what the host was doing in them.

The trace is read with ``jax.profiler.ProfileData``.  On a TPU each chip is
a plane ``/device:TPU:<i>``; its line ``XLA Ops`` holds one event per
operation run and ``XLA Modules`` one per program run.  Host threads are
lines of the plane ``/host:CPU``, where ``jax.profiler.TraceAnnotation``
spans appear by name.  Busy time is the union of the operation intervals
inside the window (overlapping operations count once); idle is the rest.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Callable, Iterable, Optional, Sequence

Interval = tuple[float, float]
NS = 1e-9

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def merge(intervals: Iterable[Interval]) -> list[Interval]:
    """Sorted, disjoint union of closed intervals (touching ones joined)."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float,
         hi: float) -> list[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def busy(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    return sum(b - a for a, b in merge(clip(intervals, lo, hi)))


def gaps(intervals: Iterable[Interval], lo: float,
         hi: float) -> list[Interval]:
    """The parts of ``[lo, hi]`` that no interval covers, in order."""
    out, t = [], lo
    for a, b in merge(clip(intervals, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class Span:
    """A host span on the trace's clock (ns): what labels an idle gap."""

    start: float
    end: float
    name: str


def label_at(t: float, spans: Sequence[Span], default: str) -> str:
    """Name of the innermost (shortest) span that holds instant ``t``."""
    best: Optional[Span] = None
    for s in spans:
        if s.start <= t <= s.end and (
                best is None or s.end - s.start < best.end - best.start):
            best = s
    return best.name if best is not None else default


@dataclasses.dataclass
class Summary:
    """What one traced window shows; times in seconds."""

    window_s: float
    busy_s: float               # mean over the device planes
    n_devices: int
    top_ops: list               # [[name, seconds]], most time first
    idle_gaps: list             # [[label, seconds]], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _events(plane, line_pred: Callable[[str], bool]):
    for line in plane.lines:
        if line_pred(line.name):
            yield from line.events


_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_kind(hlo: str) -> str:
    """``opcode`` (``opcode:target`` for a custom call) of an operation
    event, whose name on a TPU is the HLO instruction's text; any other
    name is kept as it is."""
    _, eq, rest = hlo.partition(" = ")
    m = _OPCODE.search(rest) if eq else None
    if m is None:
        return hlo
    t = _TARGET.search(rest)
    return f"{m.group(1)}:{t.group(1)}" if t else m.group(1)


def _op_names(plane) -> list[tuple[float, float, str]]:
    """(start, end, "module/opcode") of every operation on a device
    plane (the module's id suffix dropped, so programs of one name add
    up)."""
    mods = sorted((e.start_ns, e.end_ns, e.name.split("(")[0])
                  for e in _events(plane, lambda n: n == MODULES_LINE))
    starts = [m[0] for m in mods]
    out = []
    for e in _events(plane, lambda n: n == OPS_LINE):
        i = bisect.bisect_right(starts, e.start_ns) - 1
        mod = mods[i][2] if i >= 0 and e.start_ns <= mods[i][1] else ""
        kind = op_kind(e.name)
        out.append((e.start_ns, e.end_ns, f"{mod}/{kind}" if mod else kind))
    return out


def self_times(ops: Iterable[tuple[float, float, str]]) -> dict:
    """Total self time per name: an operation's time less the time of the
    operations nested in it (a loop's body runs inside the loop's event)."""
    out: dict = {}
    stack: list = []                      # [end, name, self time]

    def close(e):
        out[e[1]] = out.get(e[1], 0.0) + e[2]

    for a, b, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= a:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(b, stack[-1][0]) - a
        stack.append([b, name, b - a])
    for e in stack:
        close(e)
    return out


def cpu_op_names(plane) -> list[tuple[float, float, str]]:
    """(start, end, name) of the operations XLA's CPU backend ran: the
    timed events of its ``tf_XLA*`` thread lines (a CPU trace has no
    device plane; tests read these instead)."""
    return [(e.start_ns, e.end_ns, e.name)
            for e in _events(plane, lambda n: n.startswith("tf_XLA"))
            if e.duration_ns > 0]


def host_annotations(pd, prefix: str) -> list[Span]:
    """Host events whose name starts with ``prefix`` (TraceAnnotations)."""
    return [Span(e.start_ns, e.end_ns, e.name)
            for p in pd.planes if p.name.startswith(HOST_PLANE)
            for e in _events(p, lambda n: True)
            if e.name.startswith(prefix)]


def summarize(pd, window: Interval, spans: Sequence[Span] = (), *,
              plane_prefix: str = DEVICE_PLANE,
              ops_of: Callable = _op_names, top: int = 10) -> Summary:
    """Busy/idle, top operations (by self time) and labelled gaps inside
    ``window`` (ns).

    ``spans`` label the gaps (innermost span at each gap's midpoint); a
    gap outside every span is labelled ``"outside spans"``.  Gaps with the
    same label are summed, so each entry says how long the chip sat idle
    while the host was in that span.
    """
    lo, hi = window
    planes = [p for p in pd.planes if p.name.startswith(plane_prefix)]
    if not planes or hi <= lo:
        raise ValueError(f"no plane {plane_prefix!r} or an empty window")
    total_busy, per_op, per_gap = 0.0, {}, {}
    for p in planes:
        ops = [(max(a, lo), min(b, hi), name) for a, b, name in ops_of(p)
               if b > lo and a < hi]
        ivs = [(a, b) for a, b, _ in ops]
        total_busy += busy(ivs, lo, hi)
        for name, t in self_times(ops).items():
            per_op[name] = per_op.get(name, 0.0) + t
        for a, b in gaps(ivs, lo, hi):
            key = label_at((a + b) / 2, spans, "outside spans")
            per_gap[key] = per_gap.get(key, 0.0) + (b - a)

    def ranked(d):
        items = sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v * NS / len(planes)] for k, v in items]

    return Summary(window_s=(hi - lo) * NS,
                   busy_s=total_busy * NS / len(planes),
                   n_devices=len(planes), top_ops=ranked(per_op),
                   idle_gaps=ranked(per_gap))


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` a ``jax.profiler`` session wrote under
    ``log_dir``."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} xplane files in {log_dir}")
    return found[0]
