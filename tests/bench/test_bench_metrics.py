"""Metric arithmetic on hand-made request timelines."""
import importlib

import pytest

from bench import harness, readout
from bench.generator import Job
from bench.trace import TraceSummary


class _Req:
    def __init__(self, done=False):
        self.done = done
        self.status = "done" if done else "active"
        self.finish_reason = "max_new_tokens" if done else None
        self.output = []


def _rec(due, admit=None, tokens=(), done=False):
    r = harness.Record(Job(0, [1, 2], len(tokens)), _Req(done), due)
    if admit is not None:
        r.admit_step, r.admit_t = 0, admit
    r.tokens_t = list(tokens)
    return r


def _run(records, t0=0.0, t1=10.0, stats=None, steps=(), traced=(0, 0),
         trace=None, drained=None):
    cell = harness.Cell("x", {"model": {}}, {}, {})
    tl = harness.Timeline(t0, t1, records, list(steps), 0, stats or {}, 0,
                          traced, drained)
    return harness.Run(cell, 12.5, tl, None, {}, trace)


def metric(name, run):
    return importlib.import_module(f"bench.metrics.{name}").read(run)


def test_p95_is_nearest_rank():
    assert readout.p95(range(1, 101)) == 95
    assert readout.p95([3.0]) == 3.0
    assert readout.p95(range(1, 21)) == 19
    assert readout.p95([]) is None


def test_ttft_counts_a_stalled_request_with_its_wait():
    recs = [_rec(0.0, 0.1, [1.0, 1.1]) for _ in range(19)]
    recs.append(_rec(2.0))  # never admitted, no token by t1 = 10
    run = _run(recs)
    # 20 samples: nearest rank 19 is the largest of the served ones...
    assert metric("ttft_p95_ms", run) == pytest.approx(1000.0)
    # ...and the stall is the tail as soon as it is beyond the 95th rank
    recs.append(_rec(1.0))
    assert metric("ttft_p95_ms", _run(recs)) == pytest.approx(8000.0)


def test_ttft_and_queue_wait_take_the_drain_after_the_window():
    recs = [_rec(0.0, 0.1, [1.0, 1.1]) for _ in range(10)]
    recs.append(_rec(9.0, 9.5, [13.0]))  # its first token after t1 = 10
    # 11 samples: nearest rank 11 is the largest
    run = _run(recs, drained=13.0)
    assert metric("ttft_p95_ms", run) == pytest.approx(4000.0)
    recs.append(_rec(9.8))  # still queued when the drain gave up at 15
    run = _run(recs, drained=15.0)
    assert metric("ttft_p95_ms", run) == pytest.approx(5200.0)
    assert metric("queue_wait_p95_ms", run) == pytest.approx(5200.0)
    # the drain's tokens are not the window's
    late = [_rec(0.0, 0.0, [9.0, 9.5, 10.5, 12.0])]
    run = _run(late, drained=12.0)
    assert metric("itl_p95_ms", run) == pytest.approx(500.0)
    assert metric("output_tokens_per_s", run) == pytest.approx(0.2)


def test_itl_takes_every_gap_of_every_request():
    recs = [_rec(0.0, 0.0, [1.0, 1.02, 1.04, 1.10]),
            _rec(0.0, 0.0, [2.0, 2.03])]
    # gaps 20, 20, 60, 30 ms: nearest-rank p95 of 4 is the largest
    assert metric("itl_p95_ms", _run(recs)) == pytest.approx(60.0)
    assert metric("itl_p95_ms", _run([_rec(0.0, 0.0, [1.0])])) is None


def test_output_tokens_per_s_over_the_whole_window():
    recs = [_rec(0.0, 0.0, [1.0, 2.0, 3.0]), _rec(1.0, 1.0, [4.0])]
    assert metric("output_tokens_per_s", _run(recs, 0.0, 8.0)) == 0.5


def test_queue_wait_counts_the_unadmitted_to_the_window_end():
    recs = [_rec(1.0, 1.5, [2.0]) for _ in range(19)] + [_rec(4.0)]
    assert metric("queue_wait_p95_ms", _run(recs)) == pytest.approx(500.0)
    recs += [_rec(5.0)]
    assert metric("queue_wait_p95_ms", _run(recs)) == pytest.approx(5000.0)


def test_occupancy_and_setup():
    run = _run([], stats={"slot_steps": 400, "active_slot_steps": 300})
    assert metric("slot_occupancy", run) == 75.0
    assert metric("setup_s", run) == 12.5
    assert metric("slot_occupancy", _run([], stats={"slot_steps": 0})) is None


def test_device_metrics_need_a_trace():
    run = _run([])
    for name in ("step_device_ms", "decode_step_roofline", "mfu",
                 "device_idle_share"):
        assert metric(name, run) is None


def test_roofline_and_mfu_from_work_and_busy_time():
    class Work:
        @staticmethod
        def step_flops(m, active, rows):
            return 1e9 * active

        @staticmethod
        def step_bytes(m, active, rows):
            return 1e9 + rows

    steps = [harness.Step(2, 0), harness.Step(4, 0)]
    trace = TraceSummary(window_s=0.04, busy_s=0.03,
                         step_busy_s=[0.012, 0.018])
    run = _run([], steps=steps, traced=(0, 2), trace=trace)
    run.work = Work
    run.peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    # each step is bound by bytes: 1e9 / 1e11 = 10 ms; busy 30 ms
    assert metric("decode_step_roofline", run) == pytest.approx(100 * 0.02 / 0.03)
    assert metric("mfu", run) == pytest.approx(100 * 6e9 / (0.04 * 1e12))
    assert metric("step_device_ms", run) == pytest.approx(15.0)
    assert metric("device_idle_share", run) == pytest.approx(25.0)
    # a trace whose step spans do not match the harness's steps reads nothing
    run.timeline.traced_steps = (0, 1)
    assert metric("decode_step_roofline", run) is None


def test_host_stalls_take_the_window_steps_only():
    steps = [harness.Step(1, 0, 0.0, 0.1), harness.Step(1, 0, 0.3, 0.4),
             harness.Step(1, 0, 0.45, 1.2), harness.Step(1, 0, 5.0, 9.0)]
    run = _run([], steps=steps)
    run.timeline.closed_steps = 3  # the last step is the drain's
    stalls = harness.host_stalls(run.timeline)
    assert stalls["longest_step_s"] == pytest.approx(0.75)
    assert stalls["longest_between_steps_s"] == pytest.approx(0.2)
