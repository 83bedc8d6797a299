"""Record ``bench/testdata/engine_probe.xplane.pb``: a short profiler trace
of a one-layer reduced RWKV-6 serving engine with its phase spans, on the
chip.

    python tools/record_engine_probe.py [--out chiprun_out/engine_probe.xplane.pb]

From the root of the repo, on a TPU; it exits non-zero elsewhere. The loop
mimics the benchmark harness: a ``submit`` span that submits the step's arrivals, a
``stream_step`` span around the engine's step and a ``wait`` span that
sleeps 2 ms, with the harness's profiler options, for three rounds. Four
slots and prompts of three tokens make the traced steps mix admitting steps
(the eager masked reset over every state leaf) with plain ones. Before
tracing, it times the six spans of a step with the profiler off and prints
the microseconds a step costs. The trace is written without its
``/host:metadata`` plane (the HLO of every program, which neither
``bench/trace.py`` nor ``bench/engine_trace.py`` reads; two thirds of the
file). The last line printed is the trace's reductions as JSON.
"""
import argparse
import dataclasses
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

PHASES = ("admit", "reset", "feed", "decode", "pull", "emit")


def span_cost_us(n: int = 20000) -> float:
    """Microseconds the six spans of one step cost with the profiler off."""
    from repro.runtime.serving import EngineStats
    from repro.runtime.spans import span

    stats = EngineStats()
    t0 = time.perf_counter()
    for k in range(n):
        for p in PHASES:
            with span(stats, p, k):
                pass
    return 1e6 * (time.perf_counter() - t0) / n


def drop_metadata_plane(data: bytes) -> bytes:
    """An ``.xplane.pb`` without its ``/host:metadata`` plane, through the
    XSpace protobuf module that ships with the installed TensorFlow (loaded
    from its file, without importing TensorFlow)."""
    tf = importlib.util.find_spec("tensorflow")
    path = Path(tf.origin).parent / "tsl/profiler/protobuf/xplane_pb2.py"
    spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    xplane = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(xplane)
    space = xplane.XSpace.FromString(data)
    keep = [p for p in space.planes if p.name != "/host:metadata"]
    del space.planes[:]
    space.planes.extend(keep)
    return space.SerializeToString()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", type=Path,
                    default=ROOT / "chiprun_out" / "engine_probe.xplane.pb")
    args = ap.parse_args()

    import jax
    if jax.devices()[0].platform != "tpu":
        print(f"record_engine_probe: needs a TPU; JAX found platform "
              f"{jax.devices()[0].platform!r}", file=sys.stderr)
        return 1
    from repro import models as M
    from repro.configs import get_config, reduced
    from repro.runtime import Request, ServingEngine

    from bench import engine_trace, trace

    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "span_us_per_step": span_cost_us()}), flush=True)
    cfg = dataclasses.replace(reduced(get_config("rwkv6-1.6b")), num_layers=1)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, slots=4, max_len=32)
    engine.stream_open()
    rid = 0

    def request():
        nonlocal rid
        rid += 1
        return Request(rid=rid, prompt=[1, 2, 3], max_new_tokens=2)

    for _ in range(5):  # warm up: admitting and plain steps
        engine.submit(request())
    while engine.stream_step() is not None:
        pass
    annotate = jax.profiler.TraceAnnotation
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        for k in range(3):
            with annotate("submit"):
                if k % 2 == 0:
                    for _ in range(2):
                        engine.submit(request())
            with annotate("stream_step"):
                engine.stream_step()
            with annotate("wait"):
                time.sleep(0.002)
        jax.profiler.stop_trace()
        path = sorted(Path(d).rglob("*.xplane.pb"))[-1]
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_bytes(drop_metadata_plane(path.read_bytes()))
    engine.stream_close()
    t, e = trace.load(args.out), engine_trace.load(args.out)
    print(json.dumps({
        "bytes": args.out.stat().st_size, "window_s": t.window_s,
        "busy_s": t.busy_s, "step_busy_s": t.step_busy_s,
        "idle_by_span": t.idle_by_span, "launches": e.launches,
        "runs": e.runs, "engine_spans": e.engine_spans,
        "engine_device_s": e.engine_device_s,
        "idle_by_engine_span": e.idle_by_engine_span}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
