"""Fleet router invariants (runtime/router.py).

Policy determinism, ledger aggregation (fleet == Σ engines), drain/
rebalance never double-bills, mixed-fleet outputs are token-identical to
each engine running alone, and the shared sweep re-plans through the
persisted cache with zero new measurements.
"""
import jax
import pytest

from repro.configs import DESTINATIONS, get_config, mixed_fleet, reduced
from repro.core.fitness import Measurement
from repro.core.ga import GAConfig
from repro.core.pareto import (
    ParetoPoint, dominated_destinations, frontier_by_destination,
)
from repro import models as M
from repro.runtime import FleetRouter, Request, ServingEngine
from repro.runtime.serving import CLOCK_FIELDS

GA = GAConfig(population=8, generations=6, seed=0)
MIXED = ("pod2_v5e", "mxu_dense", "hbm_lp")


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced(get_config("llama3.2-3b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def make_router(cfg, params, tmp_path, *, dests=MIXED, **kw):
    kw.setdefault("policy", "energy")
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("ga_config", GA)
    return FleetRouter(cfg, params, [DESTINATIONS[n] for n in dests],
                       arch="llama3.2-3b",
                       cache_path=str(tmp_path / "cache.jsonl"), **kw)


def prefill_heavy(rid, slo=None):
    return Request(rid=rid, prompt=[1 + (rid + j) % 17 for j in range(20)],
                   max_new_tokens=2, slo_s=slo)


def decode_heavy(rid, slo=None):
    return Request(rid=rid, prompt=[1 + rid % 7, 3], max_new_tokens=10,
                   slo_s=slo)


def mixed_requests(n=8, base=0):
    return [prefill_heavy(base + i) if i % 2 == 0 else decode_heavy(base + i)
            for i in range(n)]


# ---------------------------------------------------------------------------
# Routing policies
# ---------------------------------------------------------------------------


def test_round_robin_cycles_engines_in_catalog_order(small_model, tmp_path):
    cfg, params = small_model
    router = make_router(cfg, params, tmp_path, policy="round_robin")
    for r in mixed_requests(6):
        router.submit(r)
    assert [router.assignments[i] for i in range(6)] == list(MIXED) * 2


def test_energy_policy_splits_by_request_shape(small_model, tmp_path):
    """Marginal modeled Watt·s routes prefill-heavy requests to the
    compute-optimized destination and decode-heavy ones to the low-power
    memory part — the mixed-environment point of the catalog."""
    cfg, params = small_model
    router = make_router(cfg, params, tmp_path)
    assert router.route(prefill_heavy(0)) == "mxu_dense"
    assert router.route(decode_heavy(1)) == "hbm_lp"
    # and the policy decision matches the marginal-rate arithmetic
    req = decode_heavy(2)
    costs = {b.name: router.marginal_energy_ws(b.engine, req)
             for b in router.bindings}
    assert min(costs, key=costs.get) == "hbm_lp"


def test_policies_are_deterministic(small_model, tmp_path):
    cfg, params = small_model
    for policy in ("energy", "latency", "round_robin"):
        a = make_router(cfg, params, tmp_path / f"a_{policy}", policy=policy)
        b = make_router(cfg, params, tmp_path / f"b_{policy}", policy=policy)
        for r1, r2 in zip(mixed_requests(8), mixed_requests(8)):
            a.submit(r1)
            b.submit(r2)
        assert a.assignments == b.assignments


def test_slo_constrains_routing_to_feasible_engines(small_model, tmp_path):
    """A tight completion SLO drops slow destinations from the candidate
    set: the router pays energy for latency rather than blow the SLO."""
    cfg, params = small_model
    router = make_router(cfg, params, tmp_path)
    # loose SLO: the cheap (slow) destination is feasible and wins on energy
    assert router.route(decode_heavy(0, slo=1e-2)) == "hbm_lp"
    # tight SLO: only the fast slice models inside the budget
    tight = decode_heavy(1, slo=2e-4)
    assert router.route(tight) == "pod2_v5e"
    router.submit(tight)
    assert router.engines["pod2_v5e"].queue  # actually admitted there


def test_unknown_policy_and_empty_fleet_rejected(small_model, tmp_path):
    cfg, params = small_model
    with pytest.raises(ValueError):
        make_router(cfg, params, tmp_path, policy="nope")
    with pytest.raises(ValueError):
        make_router(cfg, params, tmp_path, dests=())


def test_homogeneous_fleet_gets_unique_engine_names(small_model, tmp_path):
    cfg, params = small_model
    router = make_router(cfg, params, tmp_path,
                         dests=("pod2_v5e",) * 3, policy="round_robin")
    assert [b.name for b in router.bindings] \
        == ["pod2_v5e:0", "pod2_v5e:1", "pod2_v5e:2"]
    # the shared sweep still plans the destination once
    assert [d.name for d in router.destinations] == ["pod2_v5e"]


# ---------------------------------------------------------------------------
# Fleet ledger
# ---------------------------------------------------------------------------


def test_fleet_ledger_equals_sum_of_engine_ledgers(small_model, tmp_path):
    cfg, params = small_model
    router = make_router(cfg, params, tmp_path)
    reqs = mixed_requests(8)
    for r in reqs:
        router.submit(r)
    done = router.run()
    assert len(done) == len(reqs)
    fleet = router.fleet_stats()
    per_engine = router.per_engine_stats().values()
    for f in ("steps", "admissions", "prefill_tokens", "decode_tokens",
              "completed", "slot_steps", "active_slot_steps", "energy_ws",
              "slo_at_risk", "rejected", "reconfigurations"):
        assert getattr(fleet, f) == sum(getattr(s, f) for s in per_engine), f
    # and the PR-4 attribution invariant survives aggregation
    assert fleet.prefill_tokens == sum(len(r.prompt) for r in reqs)
    assert fleet.energy_ws > 0


def test_per_request_attribution_stamped(small_model, tmp_path):
    cfg, params = small_model
    router = make_router(cfg, params, tmp_path)
    for r in mixed_requests(4):
        router.submit(r)
    done = router.run()
    for r in done:
        assert r.served_by == router.assignments[r.rid]
        assert r.destination == r.served_by  # catalog names, not mesh labels


# ---------------------------------------------------------------------------
# Drain / rebalance
# ---------------------------------------------------------------------------


def test_drained_requests_never_double_billed(small_model, tmp_path):
    """Queued (never admitted) requests migrate; each is admitted exactly
    once, and fleet token/admission counts match a no-migration serve."""
    cfg, params = small_model
    router = make_router(cfg, params, tmp_path, policy="round_robin")
    reqs = mixed_requests(9)
    for r in reqs:
        router.submit(r)
    # drain everything queued on the fast slice before anything runs
    moved = router.rebalance(dominated=["pod2_v5e"])
    assert moved == {"pod2_v5e": 3}
    assert not router.engines["pod2_v5e"].queue
    done = router.run()
    assert len(done) == len(reqs)
    fleet = router.fleet_stats()
    assert fleet.admissions == len(reqs)  # exactly once each
    assert fleet.completed == len(reqs)
    assert fleet.prefill_tokens == sum(len(r.prompt) for r in reqs)
    # attribution followed the migration
    for r in done:
        assert r.served_by != "pod2_v5e"
        assert router.assignments[r.rid] == r.served_by


def test_rebalance_refuses_to_drain_whole_fleet(small_model, tmp_path):
    cfg, params = small_model
    router = make_router(cfg, params, tmp_path, policy="round_robin")
    for r in mixed_requests(3):
        router.submit(r)
    assert router.rebalance(dominated=list(MIXED)) == {}
    assert sum(len(e.queue) for e in router.engines.values()) == 3


def test_identical_silicon_twins_share_frontier_fate(small_model, tmp_path):
    """Two distinct-named destinations on identical mesh + power share one
    cell label by design; dominance must treat them as one cell — neither
    may be falsely reported dominated (and drained) over the other."""
    cfg, params = small_model
    pod2 = DESTINATIONS["pod2_v5e"]
    twin = type(pod2)(name="pod2_twin", mesh=pod2.mesh, power=pod2.power,
                      verify_cost_s=pod2.verify_cost_s)
    router = FleetRouter(cfg, params, [pod2, twin, DESTINATIONS["hbm_lp"]],
                         arch="llama3.2-3b", policy="round_robin", slots=2,
                         max_len=32, ga_config=GA,
                         cache_path=str(tmp_path / "cache.jsonl"))
    for r in mixed_requests(8):
        router.submit(r)
    router.run()
    report = router.plan()
    assert "pod2_v5e" not in report.dominated
    assert "pod2_twin" not in report.dominated


def test_plan_flags_dominated_destination_for_drain(small_model, tmp_path):
    """pod_v5e (same silicon as pod2_v5e, twice the step time) must fall
    off every kind's fleet frontier; rebalance then moves its queue."""
    cfg, params = small_model
    router = make_router(cfg, params, tmp_path,
                         dests=("pod_v5e",) + MIXED, policy="round_robin")
    for r in mixed_requests(8):
        router.submit(r)
    router.run()
    report = router.plan()
    assert report.dominated == ["pod_v5e"]
    for r in mixed_requests(8, base=100):
        router.submit(r)
    queued = len(router.engines["pod_v5e"].queue)
    assert queued > 0
    moved = router.rebalance()  # uses the last plan's verdict
    assert moved == {"pod_v5e": queued}
    assert not router.engines["pod_v5e"].queue


# ---------------------------------------------------------------------------
# Exactness: routing changes placement, never tokens
# ---------------------------------------------------------------------------


def test_mixed_fleet_outputs_identical_to_engines_alone(small_model,
                                                        tmp_path):
    cfg, params = small_model
    router = make_router(cfg, params, tmp_path)
    for r in mixed_requests(8):
        router.submit(r)
    fleet_done = {r.rid: list(r.output) for r in router.run()}

    solo_done = {}
    for name, engine in router.engines.items():
        solo = ServingEngine(cfg, params, slots=2, max_len=32)
        for r in mixed_requests(8):  # fresh copies; same rids
            if router.assignments[r.rid] == name:
                solo.submit(r)
        solo_done.update({r.rid: list(r.output) for r in solo.run()})
    assert solo_done == fleet_done


# ---------------------------------------------------------------------------
# One shared sweep through the persisted cache
# ---------------------------------------------------------------------------


def test_shared_sweep_narrows_every_engine(small_model, tmp_path):
    cfg, params = small_model
    router = make_router(cfg, params, tmp_path)
    for r in mixed_requests(8):
        router.submit(r)
    router.run()
    report = router.plan()
    assert report.new_measurements > 0
    assert set(report.placements) == set(MIXED)  # one sweep, N engines
    for name, by_kind in report.placements.items():
        for kind, p in by_kind.items():
            assert p.source == "adaptive"
            assert p.destination == name
            assert p.kind == kind
    # staged §3.3 preferences cover the observed kinds
    assert set(report.preferred) == {"prefill", "decode"}


def test_repeat_replan_hits_persistent_cache(small_model, tmp_path):
    """The acceptance-criteria cache assertion: an identical traffic window
    re-planned by a FRESH router over the same cache file performs zero new
    measurements — N engines share one sweep's history across processes."""
    cfg, params = small_model

    def serve_and_plan():
        router = make_router(cfg, params, tmp_path)
        for r in mixed_requests(8):
            router.submit(r)
        router.run()
        return router.plan()

    first = serve_and_plan()
    assert first.new_measurements > 0
    again = serve_and_plan()
    assert again.new_measurements == 0
    assert {e: {k: (p.destination, p.clock) for k, p in by_kind.items()}
            for e, by_kind in again.placements.items()} \
        == {e: {k: (p.destination, p.clock) for k, p in by_kind.items()}
            for e, by_kind in first.placements.items()}


def test_adaptive_placements_no_worse_than_static(small_model, tmp_path):
    cfg, params = small_model
    router = make_router(cfg, params, tmp_path)
    static_rates = {b.name: {k: p.energy_per_token_ws
                             for k, p in b.engine.placements.items()}
                    for b in router.bindings}
    for r in mixed_requests(8):
        router.submit(r)
    router.run()
    report = router.plan()
    for name, by_kind in report.placements.items():
        for kind, p in by_kind.items():
            assert p.energy_per_token_ws \
                <= static_rates[name][kind] * (1 + 1e-9)


# ---------------------------------------------------------------------------
# Pareto destination queries (core/pareto.py)
# ---------------------------------------------------------------------------


def _pt(cell, t, e):
    return ParetoPoint(genome=(0,), cell=cell,
                       measurement=Measurement(time_s=t, energy_ws=e))


def test_frontier_by_destination_groups_and_preserves_order():
    pts = [_pt("a", 1, 4), _pt("b", 2, 3), _pt("a", 3, 2)]
    dest = {"a": "gpu", "b": "fpga"}.__getitem__
    grouped = frontier_by_destination(pts, lambda p: dest(p.cell))
    assert [p.time_s for p in grouped["gpu"]] == [1, 3]
    assert [p.time_s for p in grouped["fpga"]] == [2]


def test_dominated_destinations_keeps_candidate_order():
    frontier = [_pt("a", 1, 4), _pt("b", 2, 3)]
    dest = {"a": "gpu", "b": "fpga"}.__getitem__
    out = dominated_destinations(["cpu", "gpu", "edge", "fpga"], frontier,
                                 lambda p: dest(p.cell))
    assert out == ["cpu", "edge"]
    assert dominated_destinations([], frontier, lambda p: dest(p.cell)) == []


# ---------------------------------------------------------------------------
# Autoscaling regression: the clockless path reproduces PR 5 exactly
# ---------------------------------------------------------------------------


def test_always_on_pins_pre_autoscaling_outputs(small_model, tmp_path):
    """Golden regression for the energy-proportional change: serving the
    standard mixed scenario WITHOUT a clock must reproduce the pre-
    autoscaling ledger token for token — integer counts pinned to the
    values the pre-power-state router produced, power plumbing fully inert
    (zero idle Watt·s, zero transitions, every engine awake)."""
    cfg, params = small_model
    router = make_router(cfg, params, tmp_path)
    reqs = mixed_requests(8)
    for r in reqs:
        assert router.submit(r)
    done = router.run()
    s = router.fleet_stats()
    # steps: 24 since dense prompts are chunk-fed at admission (64 when
    # every prompt token took a step)
    assert (s.completed, s.prefill_tokens, s.decode_tokens, s.steps,
            s.admissions) == (8, 88, 40, 24, 8)
    assert [router.assignments[i] for i in range(8)] == \
        ["mxu_dense", "hbm_lp"] * 4
    assert len(done) == 8
    assert s.idle_ws == 0.0 and s.idle_s == 0.0
    assert s.wakes == 0 and s.sleeps == 0
    assert all(st == "awake" for st in router.power_states().values())


def test_autoscale_flag_changes_nothing_without_a_clock(small_model,
                                                        tmp_path):
    """autoscale=True but no `now` anywhere: token-identical outputs and a
    field-identical ledger vs the default router — the PR 5 benchmarks
    (which never pass a clock) cannot move."""
    cfg, params = small_model
    legacy = make_router(cfg, params, tmp_path, dests=MIXED)
    scaled = make_router(cfg, params, tmp_path, dests=MIXED,
                         autoscale=True, min_awake=2, headroom=3.0,
                         sleep_after_s=0.5)
    outs = {}
    for router in (legacy, scaled):
        for r in mixed_requests(8):
            router.submit(r)
        done = router.run()
        router.plan()  # clockless plan: no scaling, no power_states verdict
        outs[router is scaled] = {r.rid: list(r.output) for r in done}
        assert router.history[-1].power_states == {}
        assert router.history[-1].demand_tps is None
    assert outs[False] == outs[True]
    a, b = legacy.fleet_stats(), scaled.fleet_stats()
    for f in type(a).__dataclass_fields__:
        if f not in CLOCK_FIELDS:
            assert getattr(a, f) == getattr(b, f), f
    assert a.idle_ws == 0.0 and a.wakes == 0 and a.sleeps == 0


def test_plan_with_clock_scales_the_fleet(small_model, tmp_path):
    """plan(now=...) is the autoscaling entry point: once an observation
    window exists, the pass records a demand rate and spins the fleet to
    the provisioned awake set — including scale-DOWN on an all-idle window
    (the early-out must not skip it)."""
    cfg, params = small_model
    router = make_router(cfg, params, tmp_path, autoscale=True,
                         min_awake=1, sleep_after_s=0.0,
                         ga_config=GA)
    router.observe(now=0.0)  # open the observation window
    for r in mixed_requests(6):
        router.submit(r, now=0.0)
    router.run()
    report = router.plan(now=1.0)
    assert report.mix.window_s == pytest.approx(1.0)
    assert report.demand_tps == pytest.approx(report.mix.tokens / 1.0)
    assert report.power_states  # the pass took a scaling decision
    # a silent window: no kinds observed, yet the fleet still spins down
    report2 = router.plan(now=100.0)
    assert report2.fleet is None  # early-out: nothing to sweep
    assert report2.demand_tps == pytest.approx(0.0)
    assert sorted(report2.power_states.values()).count("asleep") == 2
