"""Reduce a profiler trace to device busy time, per-step device time and
idle gaps named by what the host was doing.

The harness wraps each phase of its loop in a ``jax.profiler.TraceAnnotation``
(``HOST_SPANS``). The trace puts a chip's clock about a millisecond off the
host's, so device times are shifted onto the host clock first: each program
run starts when the host finished handing it to the chip (the host's
``DoEnqueueProgram`` and the device's ``XLA Modules`` event share a
``run_id``), and the median of those differences is the shift. Busy time is
the union of the intervals in which an operation ran on a device; the
traced window runs from the first host span's start to the last one's end.
Nothing here reads program names, so a renamed kernel does not change what
is counted.
"""
from __future__ import annotations

from bisect import bisect_right
from collections import defaultdict
from dataclasses import dataclass, field

HOST_SPANS = ("submit", "stream_step", "wait")
STEP_SPAN = "stream_step"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"  # the host event that hands a program to a chip


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # averaged over the devices used
    step_busy_s: list[float] = field(default_factory=list)  # per step span
    idle_by_span: dict[str, float] = field(default_factory=dict)
    top_ops: list[tuple[str, float]] = field(default_factory=list)
    devices: int = 0


def merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Covered:
    """Busy time before any instant, over a merged interval list, by
    bisection: ``between(lo, hi)`` is the busy part of ``[lo, hi)``."""

    def __init__(self, merged):
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.before = [0.0]
        for s, e in merged:
            self.before.append(self.before[-1] + e - s)

    def at(self, t: float) -> float:
        i = bisect_right(self.starts, t)  # intervals starting at or before t
        if i == 0:
            return 0.0
        return self.before[i - 1] + min(t, self.ends[i - 1]) - self.starts[i - 1]

    def between(self, lo: float, hi: float) -> float:
        return self.at(hi) - self.at(lo)


def gaps(merged, lo, hi):
    """The parts of ``[lo, hi)`` that ``merged`` leaves uncovered."""
    out, t = [], lo
    for s, e in merged:
        if e <= t:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def run_id(ev):
    """The ``run_id`` stat of a profiler event, or None."""
    for key, value in ev.stats:
        if key == "run_id":
            return value
    return None


def self_times(events):
    """Each event's duration less the part its nested events cover: a
    ``while`` op's events enclose its body's on the same line."""
    out = defaultdict(float)
    stack = []  # (end, name) of the enclosing events
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        out[name] += e - s
        if stack:
            out[stack[-1][1]] -= e - s
        stack.append((e, name))
    return out


def read_events(profile):
    """(host spans, per-device op intervals on the host clock, op self-times
    by name) from a ``jax.profiler.ProfileData``. Times are in seconds."""
    spans, enqueued, planes = [], {}, []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, starts = [], {}
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    if line.name == OPS_LINE:
                        ops.append((s, s + ev.duration_ns * 1e-9,
                                    op_name(ev.name)))
                    elif line.name == MODULES_LINE:
                        run = run_id(ev)
                        if run is not None:
                            starts[run] = s
            if ops:
                planes.append((ops, starts))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    if ev.name in HOST_SPANS:
                        spans.append((s, e, ev.name))
                    elif ev.name == ENQUEUE:
                        run = run_id(ev)
                        if run is not None:
                            enqueued[run] = e
    spans.sort()
    devices, by_name = [], defaultdict(float)
    for ops, starts in planes:
        shifts = sorted(enqueued[r] - t for r, t in starts.items()
                        if r in enqueued)
        shift = shifts[len(shifts) // 2] if shifts else 0.0
        devices.append(merge([(s + shift, e + shift) for s, e, _ in ops]))
        for name, t in self_times(ops).items():
            by_name[name] += t
    return spans, devices, by_name


def summarize(profile, top: int = 10) -> TraceSummary | None:
    """The traced window's numbers, or None where the trace holds no host
    span or no device operation."""
    spans, devices, by_name = read_events(profile)
    if not spans or not devices:
        return None
    lo = spans[0][0]
    hi = max(e for _, e, _ in spans)
    window = hi - lo
    covered = [Covered(m) for m in devices]
    busy = sum(c.between(lo, hi) for c in covered) / len(devices)
    steps = [(s, e) for s, e, n in spans if n == STEP_SPAN]
    step_busy = [sum(c.between(s, e) for c in covered) / len(devices)
                 for s, e in steps]
    idle = defaultdict(float)
    ends = [e for _, e, _ in spans]
    for m in devices:
        for gs, ge in gaps(m, lo, hi):
            # split the gap over the host spans it overlaps (they do not
            # nest); what no span covers is the harness's own bookkeeping
            covered = 0.0
            i = bisect_right(ends, gs)
            while i < len(spans) and spans[i][0] < ge:
                part = min(ge, spans[i][1]) - max(gs, spans[i][0])
                if part > 0:
                    idle[spans[i][2]] += part / len(devices)
                    covered += part
                i += 1
            idle["harness"] += (ge - gs - covered) / len(devices)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    ops = [(n, d / len(devices)) for n, d in ops]
    return TraceSummary(window, busy, step_busy,
                        dict(sorted(idle.items(), key=lambda kv: -kv[1])),
                        ops, len(devices))


def load(path) -> TraceSummary | None:
    from jax.profiler import ProfileData
    return summarize(ProfileData.from_file(str(path)))
