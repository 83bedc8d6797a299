"""Serving benchmark: static vs traffic-adaptive placement (Watt·s / 1k tok).

Drives the slot-stream :class:`ServingEngine` under three traffic
scenarios — prefill-heavy, decode-heavy, mixed-burst — twice each:

* **static**   — the paper-faithful default placement (``Decisions()`` at
  nominal clock on the default mesh) for the whole run.
* **adaptive** — the :class:`PlacementController` loop: observe the traffic
  mix, sweep the observed cells with ``search_fleet`` through the
  disk-persisted measurement cache, narrow via the kind-level fleet
  frontier + staged destination selection, reconfigure.

A fourth **ragged-length** scenario (wildly mixed prompt/generation
lengths) runs static and under the step-windowed adaptive controller.

Reported metric is modeled Watt·s per 1k processed tokens (the paper's Fig.5
quantity, normalized to traffic); the adaptive loop must not lose to static
(its requirement narrows to placements at least as good as the static
baseline). A final pass re-plans every scenario against a *fresh*
``PersistentEvalCache`` over the same results file and asserts-by-report
that zero new measurements were needed (ROADMAP item 3: sweeps are
incremental across processes).

``python benchmarks/serving_bench.py --json BENCH_serving.json`` writes the
machine-readable trajectory record CI uploads as an artifact
(``benchmarks/run.py --bench-out`` writes the same record from the full
harness).
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
from repro.runtime.placement import DEFAULT_MESH_OPTIONS as MESH_OPTIONS  # noqa: E402

ARCH = "llama3.2-3b"
SLOTS = 4
MAX_LEN = 48
CACHE_PATH = "results/serving_bench_cache.jsonl"


def _requests(scenario: str):
    """Deterministic request mixes. Prompt tokens stay in the reduced vocab."""
    from repro.runtime import Request

    reqs = []
    if scenario == "prefill_heavy":  # long prompts, short generations
        for i in range(12):
            reqs.append(Request(rid=i, prompt=[1 + (i + j) % 17
                                               for j in range(24)],
                                max_new_tokens=2))
    elif scenario == "decode_heavy":  # short prompts, long generations
        for i in range(12):
            reqs.append(Request(rid=i, prompt=[1 + i % 7, 3],
                                max_new_tokens=12))
    elif scenario == "mixed_burst":  # alternating slot-sized bursts
        rid = 0
        for burst in range(3):
            long_burst = burst % 2 == 0
            for _ in range(SLOTS):
                if long_burst:
                    reqs.append(Request(rid=rid,
                                        prompt=[1 + (rid + j) % 17
                                                for j in range(20)],
                                        max_new_tokens=3))
                else:
                    reqs.append(Request(rid=rid, prompt=[2 + rid % 5, 4],
                                        max_new_tokens=10))
                rid += 1
    elif scenario == "ragged":  # wildly mixed lengths
        for i in range(16):
            plen = 2 + (i * 7) % 23
            reqs.append(Request(rid=i,
                                prompt=[1 + (i + j) % 17
                                        for j in range(plen)],
                                max_new_tokens=1 + (i * 5) % 12))
    else:
        raise ValueError(f"unknown scenario {scenario!r}")
    return reqs


def _serve(cfg, params, scenario: str, *, adaptive: bool,
           cache_path: str = CACHE_PATH):
    from repro.core.ga import GAConfig
    from repro.runtime import (
        PlacementController, ServingEngine, static_placements,
    )

    engine = ServingEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN)
    engine.reconfigure(static_placements(ARCH, MESH_OPTIONS[0]))
    controller = None
    if adaptive:
        controller = PlacementController(
            engine, ARCH, MESH_OPTIONS, cache_path=cache_path,
            ga_config=GAConfig(population=10, generations=8, seed=0),
            interval_steps=12).attach()
    for r in _requests(scenario):
        engine.submit(r)
    t0 = time.perf_counter()
    done = engine.run()
    wall = time.perf_counter() - t0
    s = engine.stats
    return {
        "cache": (cache_stats_json(controller.eval_engine.cache.stats())
                  if controller else cache_stats_json(None)),
        "completed": len(done),
        "tokens": s.total_tokens,
        "energy_ws": s.energy_ws,
        "ws_per_1k": s.energy_ws / max(s.total_tokens, 1) * 1e3,
        "steps": s.steps,
        "reconfigurations": s.reconfigurations,
        "occupancy": s.occupancy,
        "length_capped": s.length_capped,
        "new_measurements": (sum(r.new_measurements for r in controller.history)
                             if controller else 0),
        "placements": {k: {"destination": p.destination, "clock": p.clock,
                           "source": p.source,
                           "ws_per_token": p.energy_per_token_ws}
                       for k, p in engine.placements.items()},
        "wall_s": wall,
    }


def run(json_path=None) -> list[tuple]:
    import jax

    from repro import models as M
    from repro.configs import get_config, reduced

    cfg = reduced(get_config(ARCH))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    scenarios = ("prefill_heavy", "decode_heavy", "mixed_burst")

    rows: list[tuple] = []
    scenario_records: dict = {}
    wins = 0
    for sc in scenarios:
        static = _serve(cfg, params, sc, adaptive=False)
        adaptive = _serve(cfg, params, sc, adaptive=True)
        saving = 1.0 - adaptive["ws_per_1k"] / max(static["ws_per_1k"], 1e-12)
        wins += adaptive["ws_per_1k"] < static["ws_per_1k"]
        scenario_records[sc] = {"static": static, "adaptive": adaptive,
                                "ws_per_1k_saving": saving}
        rows.append((
            f"serving_{sc}", adaptive["wall_s"] * 1e6,
            f"static={static['ws_per_1k']:.1f}Ws/1k "
            f"adaptive={adaptive['ws_per_1k']:.1f}Ws/1k "
            f"saving={saving:.1%} reconfigs={adaptive['reconfigurations']} "
            f"occ={adaptive['occupancy']:.2f} "
            f"new_meas={adaptive['new_measurements']}"))
    rows.append(("serving_adaptive_wins", float(wins),
                 f"adaptive beats static on {wins}/{len(scenarios)} scenarios"
                 f" (Watt·s per 1k tokens)"))

    # ragged-length scenario: static, then under the step-windowed
    # adaptive controller
    static = _serve(cfg, params, "ragged", adaptive=False)
    adaptive = _serve(cfg, params, "ragged", adaptive=True)
    scenario_records["ragged"] = {"static": static, "adaptive": adaptive}
    rows.append(("serving_ragged_adaptive_stream", adaptive["wall_s"] * 1e6,
                 f"static={static['ws_per_1k']:.1f}Ws/1k "
                 f"adaptive={adaptive['ws_per_1k']:.1f}Ws/1k "
                 f"occ={adaptive['occupancy']:.2f} "
                 f"reconfigs={adaptive['reconfigurations']}"))

    # persisted cache: every scenario re-planned from a FRESH cache over the
    # same results file must need zero new measurements (cross-process
    # incrementality, ROADMAP item 3)
    resweep_meas = 0
    t0 = time.perf_counter()
    for sc in scenarios + ("ragged",):
        again = _serve(cfg, params, sc, adaptive=True)
        resweep_meas += again["new_measurements"]
    rows.append(("serving_cache_resweep", (time.perf_counter() - t0) * 1e6,
                 f"new_measurements={resweep_meas} across "
                 f"{len(scenarios) + 1} re-served scenarios "
                 f"(persistent cache)"))

    if json_path:
        # aggregate eval-cache traffic over every adaptive serve in the run
        totals = cache_stats_json(None)
        for run_rec in (rec["adaptive"] for rec in scenario_records.values()):
            for k in ("lookups", "hits", "cross_cell_hits", "inserts"):
                totals[k] += run_rec["cache"][k]
        totals["hit_rate"] = (totals["hits"] / totals["lookups"]
                              if totals["lookups"] else 0.0)
        write_artifact(json_path, artifact(
            "serving_bench",
            scenarios=scenario_records,
            metrics={
                "arch": ARCH,
                "mesh_options": [dict(m) for m in MESH_OPTIONS],
                "adaptive_wins": wins,
                "scenario_count": len(scenarios),
                "resweep_new_measurements": resweep_meas,
            },
            cache=totals))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="write the machine-readable record here "
                         "(e.g. BENCH_serving.json)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for name, us, derived in run(json_path=args.json):
        print(f"{name},{us:.2f},{derived}")


if __name__ == "__main__":
    main()
