"""Traffic-adaptive placement controller (runtime/placement.py).

Controller-logic tests drive a fake engine with synthetic EngineStats
windows (no model needed); one end-to-end test serves real requests through
a reduced model and checks the adaptive Watt·s ledger beats the static one.
"""
import jax
import pytest

from repro.configs import get_config, reduced
from repro.core.ga import GAConfig
from repro import models as M
from repro.runtime import (
    PlacementController, Request, ServingEngine, static_placements,
)
from repro.runtime.placement import occupancy_bucket
from repro.runtime.serving import EngineStats

MESH0 = {"data": 16, "model": 16}
MESH1 = {"pod": 2, "data": 16, "model": 16}
GA = GAConfig(population=8, generations=6, seed=0)


class FakeEngine:
    """Just enough engine surface for the controller: stats + placements +
    the reconfigure contract."""

    def __init__(self):
        self.stats = EngineStats()
        self.placements = {}
        self.energy_correction = {}
        self.on_step_end = None

    def reconfigure(self, placements):
        if self.placements:  # mirrors ServingEngine: first apply isn't a RE-
            self.stats.reconfigurations += 1
        self.placements = dict(placements)


def make_controller(tmp_path, engine=None, **kw):
    eng = engine or FakeEngine()
    kw.setdefault("ga_config", GA)
    return eng, PlacementController(
        eng, "llama3.2-3b", [MESH0, MESH1],
        cache_path=str(tmp_path / "cache.jsonl"), **kw)


def _traffic(engine, *, prefill=0, decode=0, slot_steps=0, active=0):
    s = engine.stats
    s.prefill_tokens += prefill
    s.decode_tokens += decode
    s.slot_steps += slot_steps
    s.active_slot_steps += active


def test_observe_consumes_window(tmp_path):
    eng, ctrl = make_controller(tmp_path)
    _traffic(eng, prefill=90, decode=10, slot_steps=100, active=50)
    mix = ctrl.observe()
    assert mix.tokens == 100
    assert mix.weight("prefill") == pytest.approx(0.9)
    assert mix.occupancy == pytest.approx(0.5)
    # window consumed: a second observe with no new traffic is empty
    assert ctrl.observe().tokens == 0


def test_occupancy_buckets_are_quarters():
    assert occupancy_bucket(0.0) == 0.25
    assert occupancy_bucket(0.3) == 0.5
    assert occupancy_bucket(0.74) == 0.75
    assert occupancy_bucket(0.76) == 1.0
    assert occupancy_bucket(1.0) == 1.0


def test_low_occupancy_scales_observed_cell_batch(tmp_path):
    eng, ctrl = make_controller(tmp_path)
    shape = ctrl.shape_for("decode", 0.25)
    assert shape.global_batch == ctrl.catalog["decode"].global_batch // 4
    assert "occ25" in shape.name
    assert ctrl.shape_for("decode", 1.0) == ctrl.catalog["decode"]


def test_controller_reacts_to_traffic_mix_shift(tmp_path):
    eng, ctrl = make_controller(tmp_path)

    # window 1: decode-heavy traffic -> a decode placement is adopted,
    # prefill traffic is below the planning threshold
    _traffic(eng, prefill=2, decode=398, slot_steps=400, active=400)
    report = ctrl.update()
    assert set(report.placements) == {"decode"}
    assert eng.placements["decode"].source == "adaptive"
    assert eng.stats.reconfigurations == 0  # first apply is configuration

    # window 2: the mix shifts prefill-heavy -> a prefill placement appears;
    # the decode placement from window 1 is retained (merge semantics)
    _traffic(eng, prefill=500, decode=5, slot_steps=520, active=500)
    report2 = ctrl.update()
    assert set(report2.placements) == {"prefill"}
    assert set(eng.placements) == {"decode", "prefill"}
    assert eng.placements["prefill"].source == "adaptive"
    assert eng.stats.reconfigurations == 1


def test_adaptive_placements_never_worse_than_static_baseline(tmp_path):
    eng, ctrl = make_controller(tmp_path)
    _traffic(eng, prefill=200, decode=200, slot_steps=400, active=400)
    report = ctrl.update()
    static = static_placements("llama3.2-3b", MESH0)
    for kind, placement in report.placements.items():
        # default requirement narrows to >= baseline Watt·s efficiency
        assert placement.energy_per_token_ws \
            <= static[kind].energy_per_token_ws * (1 + 1e-9)
        assert placement.clock <= 1.0
        assert placement.kind == kind


def test_low_occupancy_never_adopts_worse_than_live_placement(tmp_path):
    """An occupancy-scaled cell's own baseline can be LESS efficient per
    token than the live placement (fixed parameter traffic over fewer
    tokens); the default requirement must also cap against the live rate,
    so the controller keeps the current placement rather than regress."""
    eng, ctrl = make_controller(tmp_path)
    static = static_placements("llama3.2-3b", MESH0)
    eng.reconfigure(static)
    # decode-heavy window at ~25% occupancy
    _traffic(eng, prefill=2, decode=398, slot_steps=1600, active=400)
    ctrl.update()
    assert eng.placements["decode"].energy_per_token_ws \
        <= static["decode"].energy_per_token_ws * (1 + 1e-9)


def test_joint_choice_includes_destination_and_clock(tmp_path):
    eng, ctrl = make_controller(tmp_path)
    _traffic(eng, prefill=400, decode=20, slot_steps=420, active=420)
    report = ctrl.update()
    p = report.placements["prefill"]
    assert p.destination in ("data16xmodel16", "data16xmodel16xpod2")
    assert p.clock in (1.0, 0.85, 0.7)
    assert p.cell  # fleet cell key recorded
    sel = report.selections["prefill"]
    assert sel.chosen == p.destination
    # the cost model makes energy mesh-invariant while the 2-pod mesh halves
    # time, so the single-pod mesh's frontier is wholly dominated and must
    # drop out BEFORE staged verification (no verify cost charged for it)
    assert sel.order == ["data16xmodel16xpod2"]
    assert "data16xmodel16" not in sel.verified


def test_no_traffic_no_reconfiguration(tmp_path):
    eng, ctrl = make_controller(tmp_path)
    report = ctrl.update()
    assert report.placements == {} and report.fleet is None
    assert eng.stats.reconfigurations == 0


def test_repeat_plan_hits_persistent_cache(tmp_path):
    eng, ctrl = make_controller(tmp_path)
    _traffic(eng, prefill=200, decode=200, slot_steps=400, active=400)
    r1 = ctrl.update()
    assert r1.new_measurements > 0
    # same traffic again, fresh controller + fresh cache over the same file
    eng2, ctrl2 = make_controller(tmp_path)
    _traffic(eng2, prefill=200, decode=200, slot_steps=400, active=400)
    r2 = ctrl2.update()
    assert r2.new_measurements == 0
    assert {k: (p.destination, p.clock) for k, p in r2.placements.items()} \
        == {k: (p.destination, p.clock) for k, p in r1.placements.items()}


def test_step_window_controller_updates_on_interval_steps(tmp_path):
    """The controller observes on a step-count window through the engine's
    on_step_end hook."""
    eng, ctrl = make_controller(tmp_path, interval_steps=4)
    ctrl.attach()
    assert eng.on_step_end == ctrl._on_step_end
    _traffic(eng, prefill=2, decode=398, slot_steps=400, active=400)
    for _ in range(3):
        ctrl._on_step_end(eng)
    assert not ctrl.history  # window still open
    ctrl._on_step_end(eng)  # 4th step closes it
    assert len(ctrl.history) == 1
    assert eng.placements["decode"].source == "adaptive"


def test_slo_budget_joins_narrowing_requirement(tmp_path):
    """Multi-requirement §3.3: the tightest per-step time budget implied by
    request SLOs joins energy in the UserRequirement used for narrowing."""
    eng, ctrl = make_controller(tmp_path)
    eng.slo_time_per_step_s = lambda: 1e3  # generous: never binds
    _traffic(eng, prefill=200, decode=200, slot_steps=400, active=400)
    report = ctrl.update()
    assert report.mix.slo_time_per_step_s == 1e3
    assert report.placements
    for p in report.placements.values():
        assert p.time_per_token_s <= 1e3

    eng2, ctrl2 = make_controller(tmp_path)
    eng2.slo_time_per_step_s = lambda: 1e-12  # impossible per-step budget
    _traffic(eng2, prefill=200, decode=200, slot_steps=400, active=400)
    report2 = ctrl2.update()
    assert report2.mix.slo_time_per_step_s == 1e-12
    # nothing satisfies time AND energy jointly -> keep the current
    # placement rather than adopt one that blows the SLO
    assert report2.placements == {}


# ---------------------------------------------------------------------------
# Metered drift hook (telemetry feedback)
# ---------------------------------------------------------------------------


def test_note_metered_calibrates_ledger_without_resweep(tmp_path):
    eng, ctrl = make_controller(tmp_path, interval_steps=100,
                                drift_threshold=0.2)
    eng.reconfigure(static_placements("llama3.2-3b", MESH0))
    modeled = eng.placements["decode"].energy_per_token_ws
    # 10% drift: below threshold -> ledger corrected, no re-sweep scheduled
    assert ctrl.note_metered("decode", modeled * 1.1) is False
    assert eng.energy_correction["decode"] == pytest.approx(1.1)
    assert ctrl.drift["decode"] == pytest.approx(0.1)
    before = len(ctrl.history)
    ctrl._on_step_end(eng)  # far from the 100-step interval
    assert len(ctrl.history) == before


def test_note_metered_drift_triggers_off_interval_resweep(tmp_path):
    eng, ctrl = make_controller(tmp_path, interval_steps=100,
                                drift_threshold=0.2)
    eng.reconfigure(static_placements("llama3.2-3b", MESH0))
    modeled = eng.placements["decode"].energy_per_token_ws
    # 50% drift: the model the placement was chosen by is falsified
    assert ctrl.note_metered("decode", modeled * 1.5) is True
    _traffic(eng, prefill=2, decode=398, slot_steps=400, active=400)
    ctrl._on_step_end(eng)  # step 1 of 100 — but the drift forces a re-plan
    assert len(ctrl.history) == 1
    assert eng.placements["decode"].source == "adaptive"
    # the pending flag is one-shot
    ctrl._on_step_end(eng)
    assert len(ctrl.history) == 1


def test_note_metered_ignores_unplaced_kind(tmp_path):
    eng, ctrl = make_controller(tmp_path)
    assert ctrl.note_metered("decode", 5.0) is False
    assert "decode" not in eng.energy_correction


def test_note_metered_rejects_zero_metered_rate(tmp_path):
    """metered == 0 is a failed measurement, not a free placement: it must
    not zero out the ledger or trigger a re-sweep."""
    eng, ctrl = make_controller(tmp_path)
    eng.reconfigure(static_placements("llama3.2-3b", MESH0))
    assert ctrl.note_metered("decode", 0.0) is False
    assert "decode" not in eng.energy_correction
    assert "decode" not in ctrl.drift


def test_replan_resets_stale_energy_correction(tmp_path):
    """A re-sweep installs a new placement; the correction ratio measured
    against the OLD placement must not keep scaling the new one."""
    eng, ctrl = make_controller(tmp_path, interval_steps=100,
                                drift_threshold=0.2)
    eng.reconfigure(static_placements("llama3.2-3b", MESH0))
    modeled = eng.placements["decode"].energy_per_token_ws
    assert ctrl.note_metered("decode", modeled * 1.5) is True
    assert eng.energy_correction["decode"] == pytest.approx(1.5)
    _traffic(eng, prefill=2, decode=398, slot_steps=400, active=400)
    ctrl._on_step_end(eng)  # drift-forced re-plan replaces the placement
    assert eng.placements["decode"].source == "adaptive"
    assert "decode" not in eng.energy_correction
    assert "decode" not in ctrl.drift


def test_energy_correction_scales_serving_ledger():
    from repro.runtime.serving import Placement

    class Probe(ServingEngine):
        def __init__(self):  # skip model setup; only the ledger is probed
            self.placements = {}
            self.energy_correction = {}

    eng = Probe()
    eng.placements["decode"] = Placement(
        kind="decode", cell="c", destination="d", decisions=None, clock=1.0,
        energy_per_token_ws=2.0)
    assert eng._token_energy("decode") == pytest.approx(2.0)
    eng.energy_correction["decode"] = 1.25
    assert eng._token_energy("decode") == pytest.approx(2.5)
    assert eng._token_energy("prefill") == 0.0


# ---------------------------------------------------------------------------
# End-to-end: live serving loop
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced(get_config("llama3.2-3b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_end_to_end_adaptive_serving_beats_static(small_model, tmp_path):
    cfg, params = small_model

    def run_engine(adaptive):
        eng = ServingEngine(cfg, params, slots=4, max_len=48)
        eng.reconfigure(static_placements("llama3.2-3b", MESH0))
        ctrl = None
        if adaptive:
            # each request holds its slot 4 steps (its prompt chunk-fed at
            # admission, then 4 output tokens): re-plan after every batch
            ctrl = PlacementController(
                eng, "llama3.2-3b", [MESH0, MESH1],
                cache_path=str(tmp_path / "e2e.jsonl"),
                ga_config=GAConfig(population=10, generations=8, seed=0),
                interval_steps=4).attach()
        for i in range(12):
            eng.submit(Request(rid=i, prompt=[1 + (i + j) % 11
                                              for j in range(12)],
                               max_new_tokens=4))
        done = eng.run()
        assert len(done) == 12
        return eng, ctrl

    static_eng, _ = run_engine(False)
    adaptive_eng, ctrl = run_engine(True)
    # identical traffic, identical token counts, lower modeled Watt·s
    assert adaptive_eng.stats.total_tokens == static_eng.stats.total_tokens
    assert adaptive_eng.stats.energy_ws < static_eng.stats.energy_ws
    assert adaptive_eng.stats.reconfigurations > 1
    assert any(p.source == "adaptive"
               for p in adaptive_eng.placements.values())
    assert ctrl.history  # the loop actually planned from observations
