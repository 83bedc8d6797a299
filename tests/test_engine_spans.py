"""Spans, counters and request stamps inside ``ServingEngine.stream_step``
(``runtime/spans.py``): each phase of the step adds its host seconds to
``EngineStats.<phase>_s`` and shows in a profiler trace as ``engine.<phase>``;
``live_row_steps`` counts at the same boundaries; every request carries
admit and first-token stamps; one process-wide hook names collector
pauses while any session is open."""
import gc
import tempfile
import time
from pathlib import Path

import jax
import pytest

from repro import models as M
from repro.configs import get_config, reduced
from repro.runtime import Request, ServingEngine
from repro.runtime.serving import CLOCK_FIELDS, EngineStats
from repro.runtime.spans import GC, GcPauses, span

PHASES = ("admit", "reset", "feed", "decode", "pull", "emit")


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced(get_config("llama3.2-3b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _requests(n=5):
    return [Request(rid=i, prompt=[1 + (i + j) % 7 for j in range(2 + i % 3)],
                    max_new_tokens=1 + i % 3) for i in range(n)]


def _serve(cfg, params, reqs, slots=2):
    """Step an open session to the end; per step (wall seconds of the call,
    stats before, stats after)."""
    eng = ServingEngine(cfg, params, slots=slots, max_len=24)
    eng.stream_open()
    for r in reqs:
        eng.submit(r)
    steps = []
    while True:
        before = eng.stats.snapshot()
        t0 = time.perf_counter()
        out = eng.stream_step()
        dt = time.perf_counter() - t0
        if out is None:
            break
        steps.append((dt, before, eng.stats.snapshot()))
    eng.stream_close()
    return eng, steps


def test_phase_seconds_grow_and_fit_inside_the_calls(small_model):
    eng, steps = _serve(*small_model, _requests())
    for phase in PHASES:
        assert getattr(eng.stats, f"{phase}_s") > 0.0, phase
    spans = sum(getattr(eng.stats, f"{p}_s") for p in PHASES)
    assert spans <= sum(dt for dt, _, _ in steps)
    for dt, before, after in steps:
        grew = sum(getattr(after, f"{p}_s") - getattr(before, f"{p}_s")
                   for p in PHASES)
        assert 0.0 < grew <= dt
    assert set(CLOCK_FIELDS) == {f"{p}_s" for p in PHASES}


def test_reset_only_on_admitting_steps(small_model):
    _, steps = _serve(*small_model, _requests())
    admitting = 0
    for _, before, after in steps:
        admitted = after.admissions > before.admissions
        admitting += admitted
        assert (after.reset_s > before.reset_s) == admitted
    assert admitting >= 3  # 5 requests, 2 slots


def test_live_rows_match_a_hand_count(small_model):
    reqs = _requests()
    eng, _ = _serve(*small_model, reqs)
    # a request of L prompt and n output tokens has its first L - 1 tokens
    # chunk-fed at admission, is stepped at cursors L - 1 .. L + n - 2, and
    # the step at cursor c sees c + 1 rows
    want = sum(sum(range(len(r.prompt), len(r.prompt) + len(r.output)))
               for r in reqs)
    assert all(r.finish_reason == "max_new_tokens" for r in reqs)
    assert eng.stats.live_row_steps == want
    assert eng.stats.live_row_steps <= eng.stats.slot_steps * eng.max_len


def test_request_stamps_are_ordered(small_model):
    reqs = _requests()
    _serve(*small_model, reqs)
    for r in reqs:
        assert r.admit_t <= r.first_token_t
    # two slots: the later requests were admitted after the first tokens
    # of the earlier ones
    assert reqs[-1].admit_t > reqs[0].first_token_t


def test_span_adds_its_time_even_when_the_phase_raises():
    stats = EngineStats()
    with pytest.raises(ValueError):
        with span(stats, "feed", 0):
            time.sleep(0.002)
            raise ValueError("phase failed")
    assert stats.feed_s >= 0.002


def test_one_hook_counts_each_pause_once():
    hook = GcPauses()
    hook.acquire()
    hook.acquire()  # a second open session shares the hook
    assert gc.callbacks.count(hook) == 1
    gc.collect()
    paused = hook.pause_s
    assert paused > 0.0
    hook.release()
    assert hook in gc.callbacks
    hook.release()
    assert hook not in gc.callbacks
    gc.collect()
    assert hook.pause_s == paused


def test_open_sessions_hold_the_process_hook(small_model):
    engines = [ServingEngine(*small_model, slots=2, max_len=24)
               for _ in range(2)]
    users = GC.users
    for eng in engines:
        eng.stream_open()
    assert GC.users == users + 2 and gc.callbacks.count(GC) == 1
    before = GC.pause_s
    gc.collect()
    assert GC.pause_s > before
    for eng in engines:
        eng.stream_close()
        eng.stream_close()  # a closed session releases nothing more
    assert GC.users == users


def test_profiler_trace_names_the_six_phases_in_order(small_model):
    from jax.profiler import ProfileData

    cfg, params = small_model
    eng = ServingEngine(cfg, params, slots=2, max_len=24)
    eng.stream_open()
    first, second = _requests(2)
    eng.submit(first)
    eng.stream_step()  # compiles the step, the reset and the argmax
    eng.submit(second)  # admitted, and reset, in the traced step
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation("stream_step"):
            eng.stream_step()
        jax.profiler.stop_trace()
        profile = ProfileData.from_file(
            str(sorted(Path(d).rglob("*.xplane.pb"))[-1]))
    eng.stream_close()
    events = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
               {k: v for k, v in ev.stats})
              for plane in profile.planes for line in plane.lines
              for ev in line.events
              if ev.name == "stream_step" or ev.name.startswith("engine.")]
    events.sort()
    outer = [e for e in events if e[2] == "stream_step"]
    inner = [e for e in events if e[2].startswith("engine.")]
    assert len(outer) == 1
    assert [e[2] for e in inner] == [f"engine.{p}" for p in PHASES]
    lo, hi = outer[0][:2]
    for (s, e, _, stats), nxt in zip(inner, inner[1:] + [None]):
        assert lo <= s <= e <= hi
        assert stats["step"] == 1
        if nxt is not None:
            assert e <= nxt[0]  # in order, none nested in another
