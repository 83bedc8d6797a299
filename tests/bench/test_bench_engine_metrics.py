"""The readers of the engine's own spans, counters and stamps, on hand-made
stats, timelines and engine-span reductions: ``step_host_ms``,
``reset_device_ms``, ``decode_device_ms``, ``prefill_p95_ms`` and
``kv_live_share``. Each reads nothing where the program under test has no
such span or counter, or the run has no engine-span reduction."""
import importlib

import pytest

from bench import harness
from bench.engine_trace import EngineTrace
from bench.generator import Job


class _Req:
    def __init__(self, admit_t=None, first_token_t=None):
        self.admit_t = admit_t
        self.first_token_t = first_token_t


def _rec(req):
    return harness.Record(Job(0, [1, 2], 2), req, 0.0)


def _run(records=(), stats=None, t1=10.0, drained=None, max_len=1024,
         family="dense"):
    cell = harness.Cell("x", {"model": {}, "family": family}, {},
                        {"max_len": max_len})
    tl = harness.Timeline(0.0, t1, list(records), [], 0, stats or {}, 0,
                          (0, 0), drained)
    return harness.Run(cell, 1.0, tl, None, {}, None)


def metric(name, run):
    return importlib.import_module(f"bench.metrics.{name}").read(run)


PHASES = {"admit_s": 0.1, "reset_s": 0.2, "feed_s": 0.3, "decode_s": 0.4,
          "pull_s": 5.0, "emit_s": 0.5}


def test_step_host_ms_leaves_out_the_wait_for_the_device():
    run = _run(stats={"steps": 100, **PHASES})
    # (0.1 + 0.2 + 0.3 + 0.4 + 0.5) s over 100 steps; pull is the wait
    assert metric("step_host_ms", run) == pytest.approx(15.0)


@pytest.mark.parametrize("stats", [
    {"steps": 0, **PHASES},  # no step in the window
    {"steps": 100, "slot_steps": 400},  # a program without the spans
])
def test_step_host_ms_reads_nothing_without_steps_or_spans(stats):
    assert metric("step_host_ms", _run(stats=stats)) is None


def test_prefill_p95_from_the_engine_stamps():
    recs = [_rec(_Req(1.0, 1.0 + 0.01 * i)) for i in range(1, 20)]
    recs.append(_rec(_Req()))  # never admitted: not a prefill sample
    # 19 samples 10..190 ms: nearest rank 19 is the largest
    assert metric("prefill_p95_ms", _run(recs)) == pytest.approx(190.0)
    # admitted, with no first token when the drain ended at 14 s: 21
    # samples, and nearest rank 20 is one of these two
    recs += [_rec(_Req(9.0)), _rec(_Req(9.0))]
    assert metric("prefill_p95_ms", _run(recs, drained=14.0)) \
        == pytest.approx(5000.0)


def test_prefill_p95_reads_nothing_without_an_admission_or_stamps():
    assert metric("prefill_p95_ms", _run([_rec(_Req())])) is None
    assert metric("prefill_p95_ms", _run([])) is None

    class Unstamped:  # the request of a program without the stamps
        pass

    assert metric("prefill_p95_ms", _run([_rec(Unstamped())])) is None


def test_kv_live_share_is_live_rows_over_reserved():
    run = _run(stats={"slot_steps": 400, "live_row_steps": 20480},
               max_len=1024)
    assert metric("kv_live_share", run) == pytest.approx(5.0)
    assert metric("kv_live_share", _run(stats={"slot_steps": 400})) is None
    assert metric("kv_live_share",
                  _run(stats={"slot_steps": 0, "live_row_steps": 0})) is None
    # a recurrent configuration has no KV cache
    run = _run(stats={"slot_steps": 400, "live_row_steps": 20480},
               family="rwkv6")
    assert metric("kv_live_share", run) is None


def test_device_ms_per_engine_span():
    t = EngineTrace(engine_device_s={"engine.reset": 0.03,
                                     "engine.decode": 0.9},
                    engine_spans={"engine.reset": 4, "engine.decode": 10,
                                  "engine.admit": 10})
    assert t.device_ms("engine.reset") == pytest.approx(7.5)
    assert t.device_ms("engine.decode") == pytest.approx(90.0)
    assert t.device_ms("engine.admit") == 0.0  # spans that launch nothing
    assert t.device_ms("engine.pull") is None  # no such span ran
    # launches and runs that did not pair leave the mapping empty
    unpaired = EngineTrace(engine_spans={"engine.decode": 10})
    assert unpaired.device_ms("engine.decode") is None


def test_device_ms_readers_read_the_runs_engine_trace():
    run = _run()
    for name in ("reset_device_ms", "decode_device_ms"):
        assert metric(name, run) is None  # no engine-span reduction
    run.engine_trace = EngineTrace(
        engine_device_s={"engine.reset": 0.03, "engine.decode": 0.9},
        engine_spans={"engine.reset": 4, "engine.decode": 10})
    assert metric("reset_device_ms", run) == pytest.approx(7.5)
    assert metric("decode_device_ms", run) == pytest.approx(90.0)
    run.engine_trace = EngineTrace(engine_spans={"engine.decode": 10},
                                   launches=900, runs=899)
    assert metric("decode_device_ms", run) is None  # counts disagreed


def test_a_traced_rwkv6_chat_run_reports_the_engine_metrics_it_lists():
    """``bench/run.py --trace 1``'s own path, with ``rwkv6-chat``'s per-layer
    entries: the program-side engine readers report; the CPU trace holds no
    TPU plane, so the device readers stay silent."""
    import time

    from test_bench_run import PEAK, tiny_cell

    cell = tiny_cell("rwkv6")
    cell.per_layer = harness.load_cell("rwkv6-chat").per_layer
    res = harness.run_cell(cell, 2**33 + 11, 1.5, True, time.perf_counter(),
                           PEAK)
    assert res["correct"]
    assert {"step_host_ms", "prefill_p95_ms"} <= set(res["metrics"])
    assert 0.0 < res["metrics"]["step_host_ms"]["value"]
    assert 0.0 < res["metrics"]["prefill_p95_ms"]["value"]
