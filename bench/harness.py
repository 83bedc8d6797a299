"""One run of one cell: set-up, the measured window, the check, the result.

Everything that belongs to a configuration, a traffic mix, a cell or a
metric is read from its own file under ``bench/``, found by the names in
``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the registered arch, its overrides, the
  published sizes (checked against the arch), precision and provenance;
* ``bench/traffic/<mix>.json``: the arrival process and the length
  distributions;
* ``bench/cells/<cell>.json``: slots, ``max_len``, the open loop's rate, the
  check's sample size and its limits;
* ``bench/metrics/<metric>.py``: one reader per metric;
* ``bench/work/<family>.py`` and ``bench/reference/<family>.py``: the work a
  step needs, and the plain float32 reference.

The window drives ``ServingEngine``'s session API from one thread: before
each ``stream_step`` it submits every request that is due; with nothing to
do it sleeps until the next arrival. After the window it steps on, with no
new arrivals, until every request due in the window has its first token.
The engine's step returns only once the device has produced the step's
tokens, so host clock readings after it are true times.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import tempfile
import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path


from bench import check, generator, trace as trace_mod, weights

ROOT = Path(__file__).resolve().parents[1]
TRACE_SECONDS = 4.0  # the traced part of a --trace 1 window: its last seconds
DRAIN_S = 60.0  # the longest the run goes on after the window for first tokens


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    for every program however small, so that a cell's second run compiles
    nothing."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(ROOT / ".bench_cache" / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    conf: dict  # configs/<config>.json
    mix: dict  # traffic/<mix>.json
    params: dict  # cells/<cell>.json
    chips: int = 1
    end_to_end: list = field(default_factory=list)  # metric entries
    per_layer: list = field(default_factory=list)


def reported(entries: list, cell: str, e2e_names=None) -> list:
    """The metric entries a cell reports: those that list it, or, with no
    list, those whose end-to-end metric the cell reports."""
    out = []
    for m in entries:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif e2e_names is None or m.get("moves") in e2e_names:
            out.append(m)
    return out


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = reported(spec["end_to_end"], name)
    return Cell(
        name=name,
        conf=read_json(root / conf_entry["file"]),
        mix=read_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        params=read_json(root / "bench" / "cells" / f"{name}.json"),
        chips=w["chips"],
        end_to_end=e2e,
        per_layer=reported(spec["per_layer"], name,
                           {m["name"] for m in e2e}))


def build_config(conf: dict):
    """The registered arch with the file's overrides, checked field by field
    against the file's published sizes."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(conf["arch"]), **conf["overrides"])
    for fld, key in conf["arch_fields"].items():
        have = getattr(cfg, fld)
        if have != conf["model"][key]:
            raise SystemExit(f"bench: {conf['arch']}.{fld} is {have}, the "
                             f"configuration file says {key}={conf['model'][key]}")
    return cfg


def family(conf: dict, kind: str):
    """``bench/<kind>/<family>.py`` for this configuration."""
    return importlib.import_module(f"bench.{kind}.{conf['family']}")


def make_engine(cfg, params, cell: Cell):
    """The system under test, as it serves."""
    from repro.runtime.serving import ServingEngine
    return ServingEngine(cfg, params, slots=cell.params["slots"],
                         max_len=cell.params["max_len"], name=cell.name)


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------


@dataclass
class Record:
    """One request as the client saw it. Times are ``perf_counter`` seconds."""

    job: generator.Job
    req: object  # repro.runtime.serving.Request
    due: float
    admit_step: int = -1
    admit_t: float = -1.0
    tokens_t: list[float] = field(default_factory=list)
    finish_t: float = -1.0


@dataclass
class Step:
    """One engine step, as bench/work/ needs it."""

    active: int  # slots serving a request in this step
    rows: int  # sum over those slots of the tokens already in their cache
    start: float = 0.0  # perf_counter at the call of stream_step
    end: float = 0.0  # and at its return


@dataclass
class Timeline:
    t0: float
    t1: float
    records: list[Record]
    steps: list[Step]
    rejected: int
    stats: dict  # EngineStats counters over the window
    compiles: int  # programs compiled inside the window (should be 0)
    traced_steps: tuple[int, int] = (0, 0)  # [first, last) step indices
    t_drained: float | None = None  # end of the drain after the window
    closed_steps: int | None = None  # steps inside the window

    @property
    def t_last(self) -> float:
        """The last clock reading: the drain's end, or the window's."""
        return self.t1 if self.t_drained is None else self.t_drained

    def in_window(self, times: list[float]) -> list[float]:
        """The readings of ``times`` that fall inside the window."""
        return [t for t in times if t <= self.t1]


class CompileCounter:
    """Counts backend compiles (a persistent-cache hit counts too) from JAX's
    own monitoring events, while ``on``."""

    def __init__(self) -> None:
        import jax
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, secs, **_):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def warm_up(engine, slots: int) -> None:
    """Serve one wave of two-token requests: the step, the masked slot
    reset, the argmax pull, and an admission while other slots decode.
    These are the only shapes the window uses."""
    from repro.runtime.serving import Request
    reqs = [Request(rid=-1 - i, prompt=[0, 1], max_new_tokens=2)
            for i in range(slots + 1)]
    for r in reqs:
        engine.submit(r)
    while engine.stream_step() is not None:
        pass
    if not all(r.done for r in reqs):
        raise RuntimeError("warm-up requests did not finish")


def serve_window(engine, traffic: generator.Traffic, seconds: float, *,
                 compiles: CompileCounter | None = None,
                 trace_dir: str | None = None) -> Timeline:
    """Drive the open engine session for ``seconds`` of wall time, then on,
    with no new arrivals, until every request due in the window has its
    first token (at most ``DRAIN_S`` more), so that its time to the first
    token is its own and not cut by the window's close."""
    import jax
    from repro.runtime.serving import Request

    annotate = jax.profiler.TraceAnnotation
    now = time.perf_counter
    records: list[Record] = []
    queued: list[Record] = []
    active: list[Record] = []
    steps: list[Step] = []
    rejected = 0
    pending = deque(traffic.jobs)

    def step() -> None:
        nonlocal queued, active
        k = len(steps)
        ts = now()
        with annotate("stream_step"):
            finished = engine.stream_step()
        te = now()
        admitted = [r for r in queued if r.req.status != "queued"]
        if admitted:
            queued = [r for r in queued if r.req.status == "queued"]
            for r in admitted:
                r.admit_step, r.admit_t = k, ts
            active.extend(admitted)
        rows = 0
        for r in active:
            rows += k - r.admit_step
            if len(r.req.output) > len(r.tokens_t):
                r.tokens_t.append(te)
        steps.append(Step(len(active), rows, ts, te))
        if finished:
            done = {id(q) for q in finished}
            for r in active:
                if id(r.req) in done:
                    r.finish_t = te
            active = [r for r in active if id(r.req) not in done]

    def submit_due(t: float) -> None:
        nonlocal rejected
        while pending and t0 + pending[0].due_s <= t:
            job = pending.popleft()
            req = Request(rid=job.index, prompt=list(job.prompt),
                          max_new_tokens=job.output_tokens)
            rec = Record(job, req, t0 + job.due_s)
            records.append(rec)
            if engine.submit(req):
                queued.append(rec)
            else:
                rejected += 1

    stats0 = dataclasses.asdict(engine.stats)
    if compiles is not None:
        compiles.count, compiles.on = 0, True
    t0 = now()
    end = t0 + seconds
    trace_from = end - TRACE_SECONDS if trace_dir else None
    traced = [0, 0]
    tracing = False
    while True:
        t = now()
        if t >= end:
            break
        if trace_from is not None and not tracing and t >= trace_from:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing, traced[0] = True, len(steps)
        with annotate("submit"):
            submit_due(t)
        if not engine.stream_busy():
            nxt = t0 + pending[0].due_s if pending else end
            with annotate("wait"):
                time.sleep(max(0.0, min(nxt, end) - now()))
            continue
        step()
    t1 = now()
    if compiles is not None:
        compiles.on = False
    if tracing:
        traced[1] = len(steps)
        jax.profiler.stop_trace()
    stats1 = dataclasses.asdict(engine.stats)
    stats = {k: stats1[k] - stats0[k] for k in stats1
             if isinstance(stats1[k], (int, float))}
    closed = len(steps)
    submit_due(end)  # due in the window, after its last submission
    while (any(not r.tokens_t and r.req.status in ("queued", "active")
               for r in records)
           and engine.stream_busy() and now() < t1 + DRAIN_S):
        step()
    return Timeline(t0, t1, records, steps, rejected, stats,
                    compiles.count if compiles else 0, tuple(traced),
                    now(), closed)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


@dataclass
class Run:
    """What the metric readers read."""

    cell: Cell
    setup_s: float
    timeline: Timeline
    work: object  # bench/work/<family>.py
    peak: dict  # bench/peaks.json entry of this device
    trace: trace_mod.TraceSummary | None = None


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def read_metrics(entries: list, run: Run) -> dict:
    out = {}
    for m in entries:
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def host_stalls(tl: Timeline) -> dict:
    """The longest ``stream_step`` of the window, and the longest time
    between one step's return and the next step's call (with the harness's
    submissions and any sleep for arrivals in it)."""
    steps = tl.steps[:tl.closed_steps]
    if not steps:
        return {}
    return {"longest_step_s": max(s.end - s.start for s in steps),
            "longest_between_steps_s": max(
                (b.start - a.end for a, b in zip(steps, steps[1:])),
                default=0.0)}


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_start: float, peak: dict) -> dict:
    """One run, from set-up to the result line's object. ``t_start`` is the
    process's start on the ``perf_counter`` clock; ``peak`` is the device's
    entry of ``bench/peaks.json``."""
    import jax

    marks = [("start", t_start), ("devices", time.perf_counter())]
    conf, model = cell.conf, cell.conf["model"]
    cfg = build_config(conf)
    ref = family(conf, "reference")
    layout = ref.layout(model)
    params = weights.make_params(layout, seed)
    jax.block_until_ready(params)
    marks.append(("weights", time.perf_counter()))
    check.same_layout(cfg, params)
    traffic = generator.make_traffic(cell.mix, cell.params, seed, seconds,
                                     model["vocab_size"])
    engine = make_engine(cfg, params, cell)
    engine.stream_open()
    marks.append(("engine", time.perf_counter()))
    warm_up(engine, cell.params["slots"])
    compiles = CompileCounter()
    marks.append(("warm_up", time.perf_counter()))
    setup_s = marks[-1][1] - t_start

    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        timeline = serve_window(engine, traffic, seconds, compiles=compiles,
                                trace_dir=tdir if traced else None)
        device = device_info(jax, cell.chips)
        summary = None
        if traced:
            files = sorted(Path(tdir).rglob("*.xplane.pb"))
            summary = trace_mod.load(files[-1]) if files else None
    engine.stream_close()
    del engine, params
    gc.collect()

    failed = timeline.rejected + sum(
        1 for r in timeline.records
        if r.req.done and r.req.finish_reason != "max_new_tokens")
    checks = check.compare(cell, timeline, seed, ref, layout)
    run = Run(cell, setup_s, timeline, family(conf, "work"), peak, summary)
    if traced:
        metrics = read_metrics(cell.per_layer, run)
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
    else:
        metrics = read_metrics(cell.end_to_end, run)
    checks["failed_requests"] = {"value": failed, "limit": 0}
    correct = check.verdict(checks)
    result = {
        "correct": correct,
        "attempted": len(timeline.records),
        "failed": failed,
        "metrics": metrics,
        "device": device,
        "window": {"seconds": timeline.t1 - timeline.t0,
                   "steps": timeline.closed_steps,
                   "drain_s": timeline.t_last - timeline.t1,
                   "compiles": timeline.compiles,
                   **host_stalls(timeline)},
        "setup": {b: t - a_t for (_, a_t), (b, t) in zip(marks, marks[1:])},
    }
    if traced and summary is not None:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.top_ops],
            "idle_gaps": [[n, s] for n, s in
                          list(summary.idle_by_span.items())[:10]],
        }
    result["checks"] = checks
    return result
