"""Serving driver: batched requests through the slot-stream engine.

``--adaptive`` attaches the traffic-adaptive placement controller
(runtime/placement.py): the engine starts on the static paper-faithful
placement and re-plans from the observed traffic mix on a step-count window,
through the disk-persisted measurement cache under ``results/``.

``--fleet`` serves through the :class:`~repro.runtime.router.FleetRouter`
instead: one engine per mixed-environment catalog destination
(``configs/destinations.py``), requests routed by ``--policy``
(energy | latency | round_robin), with one shared sweep re-planning every
engine mid-run when ``--adaptive`` is also set. Every served request
reports which engine/destination billed it.

``--provision-budget-w W`` (with ``--fleet``) runs the capacity planner
first: instead of standing up the whole catalog, the fleet is the
destination multiset ``repro.provision`` recommends under a W-watt
nameplate budget for a small default forecast — the serve CLI's door into
"which destinations should exist at all".
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax

from repro.configs import get_config, mixed_fleet, reduced as reduce_cfg
from repro import models as M
from repro.core.ga import GAConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime import FleetRouter, PlacementController, Request, \
    ServingEngine, static_placements
from repro.runtime.placement import DEFAULT_MESH_OPTIONS

DEFAULT_MESH = DEFAULT_MESH_OPTIONS[0]


def _requests(num_requests: int, max_new_tokens: int) -> list[Request]:
    return [Request(rid=i, prompt=[1 + i % 7, 2, 3 + i % 5],
                    max_new_tokens=max_new_tokens)
            for i in range(num_requests)]


def serve(arch: str = "llama3.2-3b", *, use_reduced: bool = True,
          num_requests: int = 8, slots: int = 4, max_new_tokens: int = 8,
          max_len: int = 64, adaptive: bool = False,
          cache_path: Optional[str] = "results/eval_cache.jsonl",
          interval_steps: int = 16) -> dict:
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduce_cfg(cfg)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, slots=slots, max_len=max_len)
    # modeled production-cell energy rates (full config, not the reduced one
    # actually decoding locally): the Watt·s ledger the search minimizes
    engine.reconfigure(static_placements(arch, DEFAULT_MESH))
    controller = None
    if adaptive:
        controller = PlacementController(
            engine, arch, DEFAULT_MESH_OPTIONS, cache_path=cache_path,
            ga_config=GAConfig(population=10, generations=8),
            interval_steps=interval_steps).attach()
    for r in _requests(num_requests, max_new_tokens):
        engine.submit(r)
    t0 = time.time()
    done = engine.run()
    wall = time.time() - t0
    toks = engine.stats.decode_tokens
    total = engine.stats.total_tokens
    return {
        "completed": len(done),
        "rejected": engine.stats.rejected,
        "decode_tokens": toks,
        "wall_s": wall,
        "tokens_per_s": toks / max(wall, 1e-9),
        "steps": engine.stats.steps,
        "occupancy": engine.stats.occupancy,
        "energy_ws": engine.stats.energy_ws,
        "ws_per_1k_tokens": engine.stats.energy_ws / max(total, 1) * 1e3,
        "reconfigurations": engine.stats.reconfigurations,
        "placements": {k: (p.destination, p.clock, p.source)
                       for k, p in engine.placements.items()},
        "new_measurements": (sum(r.new_measurements
                                 for r in controller.history)
                             if controller else 0),
        "outputs": {r.rid: r.output for r in done},
        "served_by": {r.rid: (r.served_by, r.destination) for r in done},
    }


def _provision_counts(arch: str, budget_w: float,
                      cache_path: Optional[str]) -> dict[str, int]:
    """Run the capacity planner: the destination multiset to build under a
    ``budget_w``-watt nameplate budget for a small default diurnal
    forecast (the provisioning bench's workload shape)."""
    from repro.configs import DESTINATIONS
    from repro.provision import Budget, destination_economics, plan_fleet
    from repro.runtime.placement import DEFAULT_CATALOG
    from repro.workload import TenantSpec, WorkloadSpec
    from repro.workload.forecast import WorkloadForecast

    spec = WorkloadSpec(
        seed=7, duration_s=0.06, rate_rps=15000.0, max_len=32,
        arrival="poisson", diurnal_period_s=0.06, diurnal_trough=0.15,
        diurnal_peak=2.0,
        tenants=(
            TenantSpec("chat", weight=3.0, prompt_median=6, prompt_max=14,
                       new_tokens_median=4, new_tokens_max=8, slo_s=0.05),
            TenantSpec("batch", weight=1.0, prompt_median=10, prompt_max=20,
                       new_tokens_median=6, new_tokens_max=10),
        ))
    econ = destination_economics(
        arch, list(DESTINATIONS.values()), shapes=DEFAULT_CATALOG,
        slots=2, cache_path=cache_path,
        ga_config=GAConfig(population=10, generations=8, seed=0))
    result = plan_fleet(econ.economics, Budget.create(budget_w),
                        WorkloadForecast.from_spec(spec))
    if result.best is None:
        raise SystemExit(f"--provision-budget-w {budget_w}: no destination "
                         "type is buildable under that budget")
    return result.counts


def serve_fleet(arch: str = "llama3.2-3b", *, use_reduced: bool = True,
                num_requests: int = 8, slots: int = 2,
                max_new_tokens: int = 8, max_len: int = 64,
                policy: str = "energy", adaptive: bool = False,
                cache_path: Optional[str] = "results/eval_cache.jsonl",
                provision_budget_w: Optional[float] = None) -> dict:
    """Serve across the mixed-destination fleet (one engine per catalog
    destination). With ``adaptive``, one shared sweep re-plans every engine
    between two serving phases. With ``provision_budget_w``, the fleet is
    not the whole catalog but the multiset the capacity planner recommends
    under that nameplate watt budget."""
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduce_cfg(cfg)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    kwargs = dict(arch=arch, policy=policy, slots=slots, max_len=max_len,
                  cache_path=cache_path,
                  ga_config=GAConfig(population=10, generations=8))
    if provision_budget_w is not None:
        counts = _provision_counts(arch, provision_budget_w, cache_path)
        router = FleetRouter.provisioned(cfg, params, counts, **kwargs)
    else:
        router = FleetRouter(cfg, params, mixed_fleet(), **kwargs)
    reqs = _requests(num_requests, max_new_tokens)
    half = len(reqs) // 2 if adaptive else len(reqs)
    t0 = time.time()
    for r in reqs[:half]:
        router.submit(r)
    done = router.run()
    if adaptive:
        router.plan()
        for r in reqs[half:]:
            router.submit(r)
        done += router.run()
    wall = time.time() - t0
    s = router.fleet_stats()
    return {
        "completed": len(done),
        "rejected": s.rejected,
        "decode_tokens": s.decode_tokens,
        "wall_s": wall,
        "tokens_per_s": s.decode_tokens / max(wall, 1e-9),
        "steps": s.steps,
        "occupancy": s.occupancy,
        "energy_ws": s.energy_ws,
        "ws_per_1k_tokens": s.energy_ws / max(s.total_tokens, 1) * 1e3,
        "reconfigurations": s.reconfigurations,
        "slo_at_risk": s.slo_at_risk,
        "engines": {b.name: b.dest.description for b in router.bindings},
        "new_measurements": sum(r.new_measurements for r in router.history),
        "outputs": {r.rid: r.output for r in done},
        "served_by": {r.rid: (r.served_by, r.destination) for r in done},
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--adaptive", action="store_true",
                    help="traffic-adaptive placement (observe/sweep/narrow/"
                         "reconfigure on a step-count window)")
    ap.add_argument("--fleet", action="store_true",
                    help="serve across the mixed-destination fleet "
                         "(FleetRouter, one engine per catalog destination)")
    ap.add_argument("--policy", default="energy",
                    choices=("energy", "latency", "round_robin"),
                    help="fleet routing policy (with --fleet)")
    ap.add_argument("--provision-budget-w", type=float, default=None,
                    help="with --fleet: run the capacity planner and serve "
                         "on the destination multiset it recommends under "
                         "this nameplate watt budget, instead of the whole "
                         "catalog")
    args = ap.parse_args()
    enable_compile_cache()
    if args.provision_budget_w is not None and not args.fleet:
        ap.error("--provision-budget-w requires --fleet")
    if args.fleet:
        out = serve_fleet(args.arch, use_reduced=not args.full,
                          num_requests=args.requests, slots=args.slots,
                          max_new_tokens=args.max_new_tokens,
                          policy=args.policy, adaptive=args.adaptive,
                          provision_budget_w=args.provision_budget_w)
    else:
        out = serve(args.arch, use_reduced=not args.full,
                    num_requests=args.requests, slots=args.slots,
                    max_new_tokens=args.max_new_tokens,
                    adaptive=args.adaptive)
    print(f"served {out['completed']} requests, {out['decode_tokens']} tokens "
          f"in {out['wall_s']:.2f}s ({out['tokens_per_s']:.1f} tok/s, "
          f"{out['steps']} steps, occupancy {out['occupancy']:.2f})")
    print(f"modeled energy: {out['energy_ws']:.0f} Ws "
          f"({out['ws_per_1k_tokens']:.0f} Ws/1k tokens), "
          f"{out['reconfigurations']} reconfigurations")
    for rid, (engine, destination) in sorted(out["served_by"].items()):
        print(f"  rid={rid} engine={engine} destination={destination}")


if __name__ == "__main__":
    main()
