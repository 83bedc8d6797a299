"""The engine-span reduction (``bench/engine_trace.py``) on a hand-made
trace and on the probe traces recorded on a TPU v5e.

``engine_probe.xplane.pb`` (``tools/record_engine_probe.py``): a one-layer
reduced RWKV-6 engine with four slots, three rounds of a ``submit`` span, a
``stream_step`` span holding the engine's phase spans, and a ``wait`` span
(2 ms sleep); the first and third steps admit and run the eager masked
reset, one small program per state leaf."""
from types import SimpleNamespace as NS

import pytest
from jax.profiler import ProfileData

from bench import engine_trace, trace
from bench.harness import ROOT

PROBE = ROOT / "bench" / "testdata" / "probe.xplane.pb"
ENGINE_PROBE = ROOT / "bench" / "testdata" / "engine_probe.xplane.pb"
MS = 1_000_000  # ns


def _ev(name, start_ms, end_ms, **stats):
    return NS(name=name, start_ns=start_ms * MS,
              duration_ns=(end_ms - start_ms) * MS, stats=list(stats.items()))


def _profile(launch_ms):
    """One ``stream_step`` span over 0-10 ms holding the six phases; four
    programs run on the device, the first two launched in the reset."""
    phases = [("admit", 0.5, 1), ("reset", 1, 3), ("feed", 3, 3.5),
              ("decode", 3.5, 4), ("pull", 4, 9), ("emit", 9, 9.8)]
    runs = [(1.5, 1.8), (2.3, 2.5), (4.0, 8.0), (8.1, 8.3)]
    python = [_ev("stream_step", 0, 10)]
    python += [_ev(f"engine.{n}", s, e) for n, s, e in phases]
    python += [_ev(engine_trace.LAUNCH, t, t + 0.001) for t in launch_ms]
    # the enqueues end where the runs start: no clock shift
    queue = [_ev(trace.ENQUEUE, s - 0.05, s, run_id=i)
             for i, (s, _) in enumerate(runs)]
    device = [NS(name=trace.MODULES_LINE,
                 events=[_ev("jit_decode_step", s, e, run_id=i)
                         for i, (s, e) in enumerate(runs)]),
              NS(name=trace.OPS_LINE,
                 events=[_ev(f"%fusion.{i} = f32[] fusion()", s, e)
                         for i, (s, e) in enumerate(runs)])]
    return NS(planes=[NS(name="/host:CPU",
                         lines=[NS(name="python", events=python),
                                NS(name="queue", events=queue)]),
                      NS(name="/device:TPU:0", lines=device)])


def test_programs_go_to_the_span_that_launched_them():
    t = engine_trace.summarize(_profile([1.2, 2.0, 3.7, 4.1]))
    assert t.launches == t.runs == 4
    assert t.engine_device_s == pytest.approx(
        {"engine.reset": 0.5e-3, "engine.decode": 4e-3, "engine.pull": 0.2e-3})
    assert t.device_ms("engine.reset") == pytest.approx(0.5)
    assert t.engine_spans == {f"engine.{n}": 1 for n in
                              ("admit", "reset", "feed", "decode", "pull",
                               "emit")}
    assert t.longest_s["engine.pull"] == pytest.approx(5e-3)
    # the device idled 8-8.1 and 8.3-9 ms of the pull
    assert t.longest_idle_s["engine.pull"] == pytest.approx(0.8e-3)


def test_idle_gaps_split_over_the_phases():
    t = engine_trace.summarize(_profile([1.2, 2.0, 3.7, 4.1]))
    want = {f"engine.{k}": v * 1e-3 for k, v in
            {"admit": 0.5, "reset": 1.5, "feed": 0.5, "decode": 0.5,
             "pull": 0.8, "emit": 0.8}.items()}
    assert t.idle_by_engine_span == pytest.approx(want)
    # the phases hold all of the stream_step idle but its first half
    # millisecond and its last 0.2 ms
    whole = trace.summarize(_profile([1.2, 2.0, 3.7, 4.1]))
    assert whole.idle_by_span["stream_step"] == pytest.approx(5.3e-3)
    assert sum(want.values()) == pytest.approx(5.3e-3 - 0.7e-3)
    gaps = engine_trace.idle_gaps(t)
    assert gaps[0] == ["stream_step/engine.reset", pytest.approx(1.5e-3)]


def test_counts_that_disagree_leave_the_device_mapping_empty():
    t = engine_trace.summarize(_profile([1.2, 3.7, 4.1]))  # one launch lost
    assert (t.launches, t.runs) == (3, 4)
    assert t.engine_device_s == {}
    assert t.device_ms("engine.decode") is None
    assert t.idle_by_engine_span  # the idle split needs no pairing


def test_a_trace_without_device_work_reads_nothing():
    p = _profile([])
    p.planes = p.planes[:1]
    assert engine_trace.summarize(p) is None


def test_the_old_probe_pairs_its_five_launches_outside_any_engine_span():
    t = engine_trace.summarize(ProfileData.from_file(str(PROBE)))
    assert t.launches == t.runs == 5
    assert t.engine_spans == {} and t.idle_by_engine_span == {}
    assert set(t.engine_device_s) == {engine_trace.OUTSIDE}
    assert t.engine_device_s[engine_trace.OUTSIDE] == pytest.approx(
        5 * 0.436e-3, rel=0.01)


@pytest.fixture(scope="module")
def engine_probe():
    return ProfileData.from_file(str(ENGINE_PROBE))


def test_engine_probe_is_small():
    assert ENGINE_PROBE.stat().st_size < 200_000


def test_engine_probe_launches_pair_with_runs(engine_probe):
    t = engine_trace.summarize(engine_probe)
    # the eager reset launches one small program per state leaf: more
    # launches than steps, each paired with one program run
    assert t.launches == t.runs > 3 * t.engine_spans["engine.decode"]
    assert t.engine_spans == {"engine.admit": 3, "engine.reset": 2,
                              "engine.feed": 3, "engine.decode": 3,
                              "engine.pull": 3, "engine.emit": 3}
    assert set(t.engine_device_s) == {"engine.reset", "engine.decode",
                                      "engine.pull"}


def test_engine_probe_device_time_sums_to_the_busy_time(engine_probe):
    t = engine_trace.summarize(engine_probe)
    whole = trace.summarize(engine_probe)
    assert sum(t.engine_device_s.values()) == pytest.approx(whole.busy_s,
                                                            rel=0.02)
    assert sum(whole.step_busy_s) == pytest.approx(whole.busy_s, rel=1e-6)


def test_engine_probe_idle_split_fits_the_stream_step_idle(engine_probe):
    t = engine_trace.summarize(engine_probe)
    whole = trace.summarize(engine_probe)
    split = sum(t.idle_by_engine_span.values())
    assert split <= whole.idle_by_span["stream_step"] * (1 + 1e-9)
    assert split >= 0.9 * whole.idle_by_span["stream_step"]
    # the sleeps in the wait spans are no engine phase's
    assert whole.idle_by_span["wait"] > 3 * 0.002


def test_pinned_numbers_of_the_engine_probe(engine_probe):
    t = engine_trace.summarize(engine_probe)
    assert t.launches == t.runs == 38
    assert t.engine_device_s["engine.reset"] == pytest.approx(17.263e-6,
                                                              abs=1e-9)
    assert t.engine_device_s["engine.decode"] == pytest.approx(25.473e-6,
                                                               abs=1e-9)
    assert t.idle_by_engine_span["engine.reset"] == pytest.approx(
        11.502085e-3, abs=1e-8)
