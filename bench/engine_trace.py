"""Put a profiler trace's device programs and idle gaps on the serving
engine's phases.

``ServingEngine.stream_step`` wraps each phase of its step in a span named
``engine.<phase>`` (``src/repro/runtime/spans.py``). Beside what
``bench/trace.py`` reads from the same trace, this reads:

* ``idle_by_engine_span``: each device gap, on the same shifted device
  intervals and in the same traced window as ``trace.summarize``, split
  over the engine spans it overlaps, by span name;
* ``engine_device_s`` and ``engine_spans``: each program run's device time
  (the busy part of its run, the union of its operations as in
  ``trace.summarize``) goes to the engine span that holds the host call
  that launched it, and
  the spans of each name are counted. The runtime hands a program to the
  chip on a thread of its own about half a millisecond after the Python
  call, while Python has moved on, so the enqueue cannot place a program.
  The Python thread records a launch event (``LAUNCH``) inside the calling
  span. A device runs its programs in launch order, so the i-th launch is
  the i-th program run on each device. Where the counts disagree, both
  mappings are left empty;
* ``longest_s`` and ``longest_idle_s``: the longest span of each name,
  where a stall sits, and the part of it in which the device was idle.
"""
from __future__ import annotations

from bisect import bisect_right
from collections import defaultdict
from dataclasses import dataclass, field

from bench import trace

ENGINE_PREFIX = "engine."
LAUNCH = "PJRT_LoadedExecutable_Execute linkage"  # Python thread, per program
OUTSIDE = "outside"  # launches that no engine span holds


@dataclass
class EngineTrace:
    idle_by_engine_span: dict[str, float] = field(default_factory=dict)
    engine_device_s: dict[str, float] = field(default_factory=dict)
    engine_spans: dict[str, int] = field(default_factory=dict)
    longest_s: dict[str, float] = field(default_factory=dict)
    longest_idle_s: dict[str, float] = field(default_factory=dict)
    launches: int = 0
    runs: int = 0  # program runs on the first device used

    def device_ms(self, name: str) -> float | None:
        """Device milliseconds per ``name`` span, or None where no span of
        that name ran or the launches were not paired with runs."""
        count = self.engine_spans.get(name, 0)
        if not count or not self.engine_device_s:
            return None
        return 1e3 * self.engine_device_s.get(name, 0.0) / count


def read_engine_events(profile):
    """(engine spans sorted by start, launch times sorted, per device the
    program runs' (start, busy seconds) sorted by start). Times in
    seconds."""
    spans, launches, devices = [], [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            runs, ops = [], []
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    e = s + ev.duration_ns * 1e-9
                    if line.name == trace.OPS_LINE:
                        ops.append((s, e))
                    elif (line.name == trace.MODULES_LINE
                          and trace.run_id(ev) is not None):
                        runs.append((s, e))
            if runs:
                busy = trace.Covered(trace.merge(ops))
                devices.append(sorted((s, busy.between(s, e))
                                      for s, e in runs))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    if ev.name.startswith(ENGINE_PREFIX):
                        spans.append((s, s + ev.duration_ns * 1e-9, ev.name))
                    elif ev.name == LAUNCH:
                        launches.append(s)
    spans.sort()
    launches.sort()
    return spans, launches, devices


def holder(spans, ends, t: float) -> str:
    """The name of the span among ``spans`` (sorted, not overlapping, with
    ``ends`` their end times) that holds instant ``t``."""
    i = bisect_right(ends, t)
    if i < len(spans) and spans[i][0] <= t:
        return spans[i][2]
    return OUTSIDE


def summarize(profile) -> EngineTrace | None:
    """The engine-span reduction of a trace, or None where the trace holds
    no harness span or no device operation (as ``trace.summarize``)."""
    host, devices, _ = trace.read_events(profile)
    if not host or not devices:
        return None
    lo = host[0][0]
    hi = max(e for _, e, _ in host)
    spans, launches, runs = read_engine_events(profile)
    ends = [e for _, e, _ in spans]
    out = EngineTrace(launches=len(launches),
                      runs=len(runs[0]) if runs else 0)
    counts, longest = defaultdict(int), {}
    for s, e, name in spans:
        counts[name] += 1
        if name not in longest or e - s > longest[name][1] - longest[name][0]:
            longest[name] = (s, e)
    covered = [trace.Covered(m) for m in devices]
    out.engine_spans = dict(counts)
    out.longest_s = {n: e - s for n, (s, e) in longest.items()}
    out.longest_idle_s = {
        n: e - s - sum(c.between(s, e) for c in covered) / len(covered)
        for n, (s, e) in longest.items()}
    idle = defaultdict(float)
    for m in devices:
        for gs, ge in trace.gaps(m, lo, hi):
            i = bisect_right(ends, gs)
            while i < len(spans) and spans[i][0] < ge:
                part = min(ge, spans[i][1]) - max(gs, spans[i][0])
                if part > 0:
                    idle[spans[i][2]] += part / len(devices)
                i += 1
    out.idle_by_engine_span = dict(sorted(idle.items(), key=lambda kv: -kv[1]))
    if runs and all(len(r) == len(launches) for r in runs):
        device = defaultdict(float)
        owners = [holder(spans, ends, t) for t in launches]
        for r in runs:
            for owner, (_, d) in zip(owners, r):
                device[owner] += d / len(runs)
        out.engine_device_s = dict(device)
    return out


def load(path) -> EngineTrace | None:
    from jax.profiler import ProfileData
    return summarize(ProfileData.from_file(str(path)))


def idle_gaps(t: EngineTrace) -> list[list]:
    """The engine spans' idle parts as ``breakdown.idle_gaps`` entries: the
    ``stream_step`` entry's idle, split by the phase that held the host."""
    return [[f"{trace.STEP_SPAN}/{n}", s]
            for n, s in t.idle_by_engine_span.items()]
