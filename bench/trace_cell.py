"""Run one cell with the profiler on, as ``bench/run.py --trace 1`` does, and
put the traced steps' device programs and idle gaps on the serving engine's
phases (``bench/engine_trace.py``).

    python3 bench/trace_cell.py --workload <cell> --seed <n> [--seed <m> ...]
        --seconds <s> [--keep <dir>]

From the root of a checkout, on a TPU; it exits non-zero elsewhere. For
each seed one line of JSON: ``correct`` and the checks, as ``bench/run.py``
decides them; the cell's per-layer metrics and the readers of the engine's
own spans and counters (``ENGINE_METRICS``); the harness's idle gaps, then
the engine spans' parts of the ``stream_step`` gap
(``stream_step/engine.<phase>``); device milliseconds per engine span
beside the traced steps' busy time; host milliseconds a step of each phase
over the window, and the collector's pauses over the run; the longest
step; the mean and median step of the traced part of the window against
the part before it; and the pause between the window's end and the
drain's first step. ``--keep`` copies each trace file there.

It reuses the harness's pieces and stands beside ``bench/run.py`` only
until ``harness.run_cell`` reads ``bench/engine_trace.py``'s reduction
itself; then this script and the reduction's separate pass go.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
PHASES = ("admit", "reset", "feed", "decode", "pull", "emit")
# readers in bench/metrics/ of the engine's spans, counters and stamps
ENGINE_METRICS = [{"name": "step_host_ms", "unit": "ms"},
                  {"name": "reset_device_ms", "unit": "ms"},
                  {"name": "decode_device_ms", "unit": "ms"},
                  {"name": "prefill_p95_ms", "unit": "ms"},
                  {"name": "kv_live_share", "unit": "%"}]


def step_ms(steps) -> dict:
    times = [1e3 * (s.end - s.start) for s in steps]
    return {"mean": statistics.fmean(times),
            "median": statistics.median(times)} if times else {}


def traced_run(cell, seed: int, seconds: float, peak: dict,
               compiles, keep: Path | None) -> dict:
    from bench import check, engine_trace, generator, harness, trace, weights
    from repro.runtime import spans as engine_spans

    conf, model = cell.conf, cell.conf["model"]
    cfg = harness.build_config(conf)
    ref = harness.family(conf, "reference")
    layout = ref.layout(model)
    params = weights.make_params(layout, seed)
    traffic = generator.make_traffic(cell.mix, cell.params, seed, seconds,
                                     model["vocab_size"])
    engine = harness.make_engine(cfg, params, cell)
    engine.stream_open()
    harness.warm_up(engine, cell.params["slots"])
    gc0 = engine_spans.GC.pause_s
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        tl = harness.serve_window(engine, traffic, seconds, compiles=compiles,
                                  trace_dir=tdir)
        files = sorted(Path(tdir).rglob("*.xplane.pb"))
        summary = trace.load(files[-1]) if files else None
        spans = engine_trace.load(files[-1]) if files else None
        if keep is not None and files:
            keep.mkdir(parents=True, exist_ok=True)
            shutil.copy(files[-1], keep / f"{cell.name}-{seed}.xplane.pb")
    gc_s = engine_spans.GC.pause_s - gc0
    engine.stream_close()
    del engine, params
    gc.collect()

    checks = check.compare(cell, tl, seed, ref, layout)
    run = harness.Run(cell, 0.0, tl, harness.family(conf, "work"), peak,
                      summary)
    run.engine_trace = spans
    first, last = tl.traced_steps
    window = tl.steps[:tl.closed_steps]
    s = tl.stats
    out = {"seed": seed, "correct": check.verdict(checks), "checks": checks,
           "window_steps": tl.closed_steps,
           "traced_steps": last - first,
           "metrics": {k: v["value"] for k, v in harness.read_metrics(
               cell.per_layer + ENGINE_METRICS, run).items()},
           "host_ms_per_step": {p: 1e3 * s.get(f"{p}_s", 0.0) / s["steps"]
                                for p in PHASES} if s.get("steps") else {},
           "gc_s": gc_s,
           "step_ms_untraced": step_ms(window[:first]),
           "step_ms_traced": step_ms(window[first:last]),
           **harness.host_stalls(tl),
           # the window's end to the drain's first step: the profiler's
           # stop, which delays every first token still to come
           "stop_to_drain_s": (tl.steps[tl.closed_steps].start - tl.t1
                               if len(tl.steps) > tl.closed_steps else None)}
    if summary is not None:
        out["busy_s"], out["window_s"] = summary.busy_s, summary.window_s
        out["step_busy_s"] = sum(summary.step_busy_s)
        out["idle_gaps"] = [[n, v] for n, v in summary.idle_by_span.items()]
    if spans is not None:
        out["idle_gaps"] = out.get("idle_gaps", []) \
            + engine_trace.idle_gaps(spans)
        out["launches"], out["runs"] = spans.launches, spans.runs
        out["engine_spans"] = spans.engine_spans
        out["engine_device_ms"] = {n: spans.device_ms(n)
                                   for n in spans.engine_spans}
        out["engine_device_s"] = spans.engine_device_s
        out["longest_span_ms"] = {n: 1e3 * v
                                  for n, v in spans.longest_s.items()}
        out["longest_span_idle_ms"] = {
            n: 1e3 * v for n, v in spans.longest_idle_s.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", type=Path)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.load_cell(args.workload)
    harness.use_compile_cache()
    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"trace_cell: needs a TPU; JAX found platform "
              f"{device.platform!r}", file=sys.stderr)
        return 1
    kind = device.device_kind
    peak = harness.read_json(ROOT / "bench" / "peaks.json").get(kind, {})
    compiles = harness.CompileCounter()
    print(json.dumps({"setup_s": time.perf_counter() - T_START,
                      "device": kind}), flush=True)
    for seed in args.seed:
        print(json.dumps(traced_run(cell, seed, args.seconds, peak, compiles,
                                    args.keep)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
