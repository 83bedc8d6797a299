"""Traffic benchmark: energy-proportional autoscaling under diurnal load.

The paper's Watt·s claims are steady-state, single-workload numbers; a
fleet's bill is dominated by what it burns when traffic is NOT at peak.
This bench replays one seed-deterministic diurnal workload
(``src/repro/workload/``: open-loop Poisson arrivals under a
trough-to-peak sinusoid, heavy-tailed lengths, an interactive tenant with
a completion SLO next to a batch tenant) against the mixed fleet twice:

* **always-on** — every destination awake for the whole horizon, paying
  its full idle floor (``p_idle`` x chips) every second. Routing behavior
  is exactly PR 5's (the regression test pins it token-identical).
* **autoscaled** — ``FleetRouter`` power states driven by the observed
  arrival rate (``scale_to`` every control tick + mid-run ``plan(now)``
  passes): engines the demand doesn't justify drop to the DVFS floor and
  then deep-sleep; wake latency is charged against SLOs.

Reported metric is **Watt·s per 1k tokens on the FULL bill**
(serving energy + static idle energy). The acceptance gate (CLI exit
code): the autoscaled fleet is *strictly cheaper* than always-on AND holds
the SLOs at least as well (no additional violations).

Determinism is part of the contract: the same seed re-simulated from a
fresh router over the same persisted eval cache must reproduce the
identical request trace (SHA-256 digest), an identical ledger field for
field, and perform **zero** new measurements on its re-plans.

``python benchmarks/traffic_bench.py --json BENCH_traffic.json`` writes
the unified artifact (``benchmarks/artifact.py`` schema) that CI uploads.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))

from benchmarks.artifact import artifact, cache_stats_json, write_artifact  # noqa: E402

ARCH = "llama3.2-3b"
SLOTS = 2
MAX_LEN = 32
CACHE_PATH = "results/traffic_bench_cache.jsonl"
MIXED = ("pod2_v5e", "mxu_dense", "hbm_lp")

# Simulated timescale: the reduced model's modeled step times are tens of
# microseconds, so a "day" is 60 ms and rates are thousands of requests
# per simulated second — the shapes (trough/peak ratio, SLO-to-latency
# ratio, wake-to-step ratio) are what carry over to real deployments.
AUTOSCALE_EVERY_S = 0.002
PLAN_TIMES = (0.02, 0.04)


def _spec():
    from repro.workload import TenantSpec, WorkloadSpec

    return WorkloadSpec(
        seed=7, duration_s=0.06, rate_rps=3000.0, max_len=MAX_LEN,
        arrival="poisson", diurnal_period_s=0.06, diurnal_trough=0.15,
        diurnal_peak=2.0,
        tenants=(
            TenantSpec("chat", weight=3.0, prompt_median=6, prompt_max=14,
                       new_tokens_median=4, new_tokens_max=8, slo_s=0.05),
            TenantSpec("batch", weight=1.0, prompt_median=10, prompt_max=20,
                       new_tokens_median=6, new_tokens_max=10),
        ))


def _simulate(cfg, params, *, autoscale: bool,
              cache_path: str = CACHE_PATH) -> dict:
    """One full run: fresh router + fresh trace from the shared spec."""
    from repro.configs import DESTINATIONS
    from repro.core.ga import GAConfig
    from repro.runtime import FleetRouter
    from repro.workload import generate, simulate, trace_digest

    spec = _spec()
    trace = generate(spec)
    router = FleetRouter(
        cfg, params, [DESTINATIONS[n] for n in MIXED], arch=ARCH,
        policy="energy", slots=SLOTS, max_len=MAX_LEN,
        cache_path=cache_path,
        ga_config=GAConfig(population=10, generations=8, seed=0),
        autoscale=autoscale, min_awake=1, headroom=1.2,
        sleep_after_s=2 * AUTOSCALE_EVERY_S)
    t0 = time.perf_counter()
    rep = simulate(router, trace, horizon_s=spec.duration_s,
                   autoscale_every_s=AUTOSCALE_EVERY_S,
                   plan_times=PLAN_TIMES)
    wall = time.perf_counter() - t0
    return {
        "autoscale": autoscale,
        "trace_digest": trace_digest(trace),
        "requests": rep.submitted,
        "completed": rep.completed,
        "rejected": rep.rejected,
        "tokens": rep.tokens,
        "steps": rep.steps,
        "energy_ws": rep.energy_ws,
        "idle_ws": rep.idle_ws,
        "total_ws": rep.total_ws,
        "ws_per_1k": rep.ws_per_1k_tokens,
        "slo_total": rep.slo_total,
        "slo_violations": rep.slo_violations,
        "wakes": rep.fleet.wakes,
        "sleeps": rep.fleet.sleeps,
        "power_transitions": len(rep.power_log),
        "duration_s": rep.duration_s,
        "new_measurements": sum(r.new_measurements for r in router.history),
        "plans": len(router.history),
        "cache": cache_stats_json(router.eval_engine.cache.stats()),
        "wall_s": wall,
    }


def run(json_path=None) -> list[tuple]:
    import jax

    from repro import models as M
    from repro.configs import get_config, reduced

    cfg = reduced(get_config(ARCH))
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    always_on = _simulate(cfg, params, autoscale=False)
    autoscaled = _simulate(cfg, params, autoscale=True)
    # the determinism contract: fresh router, same seed, same cache file —
    # identical trace + ledger, zero new measurements on the re-plans
    again = _simulate(cfg, params, autoscale=True)

    win = (autoscaled["ws_per_1k"] < always_on["ws_per_1k"]
           and autoscaled["slo_violations"] <= always_on["slo_violations"])
    deterministic = (
        again["trace_digest"] == autoscaled["trace_digest"]
        and all(again[k] == autoscaled[k] for k in (
            "requests", "completed", "tokens", "steps", "energy_ws",
            "idle_ws", "slo_violations", "wakes", "sleeps"))
        and again["new_measurements"] == 0)

    saved = always_on["ws_per_1k"] - autoscaled["ws_per_1k"]
    rows = [
        ("traffic_always_on", always_on["wall_s"] * 1e6,
         f"ws/1k={always_on['ws_per_1k']:.1f} "
         f"(serve={always_on['energy_ws']:.1f}Ws "
         f"idle={always_on['idle_ws']:.1f}Ws) "
         f"viol={always_on['slo_violations']}/{always_on['slo_total']} "
         f"completed={always_on['completed']}/{always_on['requests']}"),
        ("traffic_autoscaled", autoscaled["wall_s"] * 1e6,
         f"ws/1k={autoscaled['ws_per_1k']:.1f} "
         f"(serve={autoscaled['energy_ws']:.1f}Ws "
         f"idle={autoscaled['idle_ws']:.1f}Ws) "
         f"viol={autoscaled['slo_violations']}/{autoscaled['slo_total']} "
         f"wakes={autoscaled['wakes']} sleeps={autoscaled['sleeps']}"),
        ("traffic_autoscale_win", float(win),
         f"autoscaled saves {saved:.1f} Ws/1k "
         f"({saved / always_on['ws_per_1k'] * 100:.0f}%) at "
         f"{autoscaled['slo_violations']}<= {always_on['slo_violations']} "
         f"SLO violations"),
        ("traffic_determinism", float(deterministic),
         f"digest_match={again['trace_digest'] == autoscaled['trace_digest']} "
         f"ledger_match={again['energy_ws'] == autoscaled['energy_ws']} "
         f"resim_new_measurements={again['new_measurements']}"),
    ]

    if json_path:
        write_artifact(json_path, artifact(
            "traffic_bench",
            scenarios={"always_on": always_on, "autoscaled": autoscaled,
                       "autoscaled_resim": again},
            metrics={
                "arch": ARCH,
                "destinations": list(MIXED),
                "trace_digest": autoscaled["trace_digest"],
                "autoscale_win": win,
                "deterministic": deterministic,
                "ws_per_1k_always_on": always_on["ws_per_1k"],
                "ws_per_1k_autoscaled": autoscaled["ws_per_1k"],
                "ws_per_1k_saved": saved,
                "slo_violations_always_on": always_on["slo_violations"],
                "slo_violations_autoscaled": autoscaled["slo_violations"],
            },
            cache=again["cache"]))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="write the machine-readable record here "
                         "(e.g. BENCH_traffic.json)")
    args = ap.parse_args()
    rows = run(json_path=args.json)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")
    by_name = {name: us for name, us, _ in rows}
    if by_name["traffic_autoscale_win"] < 1.0:
        print("FAIL: autoscaled fleet is not strictly cheaper (Watt·s/1k) "
              "at no additional SLO violations", file=sys.stderr)
        sys.exit(1)
    if by_name["traffic_determinism"] < 1.0:
        print("FAIL: re-simulated run did not reproduce the trace/ledger "
              "(or re-planned with new measurements)", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
