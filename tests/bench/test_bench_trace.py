"""The trace reduction, on interval arithmetic and on a small trace recorded
on a TPU v5e (``bench/testdata/probe.xplane.pb``): five rounds of a
``submit`` span (2 ms sleep), a ``stream_step`` span running one jitted
program of ~0.44 ms on the chip and pulling its result, and a ``wait`` span
(20 ms sleep)."""
import pytest
from jax.profiler import ProfileData

from bench import trace
from bench.harness import ROOT

PROBE = ROOT / "bench" / "testdata" / "probe.xplane.pb"


def test_interval_arithmetic():
    m = trace.merge([(0, 1), (0.5, 2), (3, 4), (3.5, 3.7)])
    assert m == [(0, 2), (3, 4)]
    c = trace.Covered(m)
    assert c.between(0, 4) == 3 and c.between(1.5, 3.5) == 1.0
    assert c.between(2, 3) == 0 and c.between(-5, -1) == 0
    assert trace.gaps(m, 0, 5) == [(2, 3), (4, 5)]
    assert trace.gaps(m, 0.5, 1.5) == []


def test_self_times_take_nested_ops_out_of_their_parent():
    t = trace.self_times([(0, 10, "while"), (1, 3, "fusion"), (4, 5, "copy"),
                          (12, 13, "fusion")])
    assert t == {"while": 7, "fusion": 3, "copy": 1}
    assert trace.op_name("%fusion.12 = f32[2]{0} fusion(%a)") == "fusion.12"


@pytest.fixture(scope="module")
def probe():
    return ProfileData.from_file(str(PROBE))


def test_probe_trace_reduces_to_its_known_shape(probe):
    t = trace.summarize(probe)
    assert t.devices == 1 and len(t.step_busy_s) == 5
    # each step's busy time is that step's one program run on the device
    modules = [ev.duration_ns * 1e-9 for pl in probe.planes
               if pl.name == "/device:TPU:0" for line in pl.lines
               if line.name == "XLA Modules" for ev in line.events]
    assert len(modules) == 5
    for busy, run in zip(t.step_busy_s, modules):
        assert busy == pytest.approx(run, rel=0.02)
    assert t.busy_s == pytest.approx(sum(t.step_busy_s), rel=1e-6)
    assert t.busy_s + sum(t.idle_by_span.values()) == pytest.approx(t.window_s)
    # the sleeps are idle, named by the span the host was in
    assert t.idle_by_span["wait"] == pytest.approx(5 * 0.0208, rel=0.02)
    assert 5 * 0.002 < t.idle_by_span["submit"] < 5 * 0.003
    assert t.idle_by_span["stream_step"] < 5 * 0.002
    assert t.top_ops[0][0] == "fusion.13"


def test_pinned_numbers_of_the_probe_trace(probe):
    t = trace.summarize(probe)
    assert t.window_s == pytest.approx(0.124979, abs=2e-6)
    assert t.busy_s == pytest.approx(0.0021799, abs=2e-7)
    assert sum(t.step_busy_s) / 5 == pytest.approx(0.00043598, abs=2e-7)
    assert 100 * (1 - t.busy_s / t.window_s) == pytest.approx(98.2558, abs=1e-3)
