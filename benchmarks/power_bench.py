"""Power-telemetry benchmark: the paper's Fig.5 Watt·s comparison through
the *meter* path, plus model calibration against metered traces.

Sections:

  power_counter_sources   — live counter availability on this machine (RAPL /
                            nvidia-smi); absent counters degrade gracefully
                            to the synthesized ModeledSampler path
  power_fig5_*            — CPU-only vs offloaded Watt·s measured by
                            trace integration (≈4131 → ≈2071 W·s on the
                            calibrated Himeno path), with the trapezoid
                            integral's error vs the closed-form model
  power_calibration_paper — least-squares refit of (p_cpu, p_accel) from
                            metered runs; must recover the 27 / 82 anchors
  power_calibration_tpu   — TPU component-power refit from metered LM traces
                            synthesized under a perturbed "true machine"
                            model; modeled-vs-metered error before vs after
                            calibration
  power_fleet_metered     — a search_fleet sweep mixing analytic and
                            meter-backed cells through one shared EvalEngine
                            cache; the re-sweep is all cache hits

``--json BENCH_power.json`` writes the unified benchmark artifact
(benchmarks/artifact.py) CI uploads weekly.
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
MESH = {"data": 16, "model": 16}


def _fig5_metered(record: dict) -> list[tuple]:
    """CPU-only vs offloaded Watt·s through trace integration."""
    from repro.apps.himeno_app import LOOP_UNITS, UNIT_NAMES
    from repro.core.ga import GAConfig
    from repro.core.offload_search import search_himeno
    from repro.core.verifier import (
        HimenoCalibratedBackend, PAPER_GPU_TIME_S,
    )
    from repro.telemetry import MeteredBackend, ModeledSampler, trapezoid_ws

    rows: list[tuple] = []
    be = MeteredBackend(HimenoCalibratedBackend(), hz=20.0)
    cpu = be.measure_bits([0] * 13)
    paper_bits = [1 if u in LOOP_UNITS else 0 for u in UNIT_NAMES]
    off = be.measure_bits(paper_bits)

    # The paper's own Fig.5 anchor: during the offloaded run s-tui +
    # nvidia-smi read 109 W for the *whole* 19 s (the host keeps
    # orchestrating while the device runs), so the anchor timeline is
    # device-active end to end — metering it must reproduce ≈2071 W·s
    # against the CPU-only ≈4131 W·s.
    anchor = trapezoid_ws(ModeledSampler.from_paper_run(
        PAPER_GPU_TIME_S, PAPER_GPU_TIME_S, be.power, hz=1000.0).trace())

    t0 = time.perf_counter()
    ga = search_himeno(be, GAConfig(population=12, generations=12, seed=1))
    ga_wall = time.perf_counter() - t0
    best = ga.best.measurement

    errs = [abs(m.detail["metered"]["model_error"]) for m in (cpu, off, best)]
    rows.append(("power_fig5_metered_cpu_only", cpu.time_s,
                 f"{cpu.energy_ws:.0f}Ws metered "
                 f"(model_err={cpu.detail['metered']['model_error']:.2%})"))
    rows.append(("power_fig5_metered_anchor_offload", PAPER_GPU_TIME_S,
                 f"{anchor:.0f}Ws metered (paper anchor 2071) "
                 f"ratio={anchor / cpu.energy_ws:.3f}"))
    rows.append(("power_fig5_metered_offload", off.time_s,
                 f"{off.energy_ws:.0f}Ws metered "
                 f"ratio={off.energy_ws / cpu.energy_ws:.3f} "
                 f"(model_err={off.detail['metered']['model_error']:.2%})"))
    rows.append(("power_fig5_metered_ga_best", best.time_s,
                 f"{best.energy_ws:.0f}Ws metered "
                 f"ratio={best.energy_ws / cpu.energy_ws:.3f} "
                 f"evals={ga.evaluations} wall={ga_wall:.1f}s"))
    rows.append(("power_modeled_sampler_max_err", max(errs),
                 f"max |metered-modeled|/modeled = {max(errs):.3%} "
                 f"(must be < 2%)"))
    record["fig5"] = {
        "cpu_only_ws": cpu.energy_ws,
        "anchor_offload_ws": anchor,
        "offload_ws": off.energy_ws,
        "ga_best_ws": best.energy_ws,
        "ratio_anchor_vs_cpu": anchor / cpu.energy_ws,
        "ratio_offload_vs_cpu": off.energy_ws / cpu.energy_ws,
        "ratio_ga_vs_cpu": best.energy_ws / cpu.energy_ws,
        "max_model_error": max(errs),
        "ga_evaluations": ga.evaluations,
    }
    return rows


def _calibration_paper(record: dict) -> list[tuple]:
    """Refit the paper's 27 W / +82 W from metered runs."""
    from repro.core.verifier import HimenoCalibratedBackend
    from repro.telemetry import MeteredBackend, PaperSample, fit_paper_model

    be = MeteredBackend(HimenoCalibratedBackend(), hz=20.0)
    patterns = [
        [0] * 13, [1] * 13,
        [1 if i >= 8 else 0 for i in range(13)],   # hot loops
        [1 if i % 2 else 0 for i in range(13)],
        [1 if i < 8 else 0 for i in range(13)],    # init-only offload
    ]
    samples = [PaperSample.from_measurement(be.measure_bits(b))
               for b in patterns]
    fit = fit_paper_model(samples)
    err_cpu = abs(fit.p_cpu - 27.0) / 27.0
    err_acc = abs(fit.p_accel_extra - 82.0) / 82.0
    record["calibration_paper"] = {
        "fit_p_cpu": fit.p_cpu, "fit_p_accel_extra": fit.p_accel_extra,
        "rel_err_p_cpu": err_cpu, "rel_err_p_accel": err_acc,
        "runs": len(samples),
    }
    return [("power_calibration_paper", float(len(samples)),
             f"fit p_cpu={fit.p_cpu:.2f}W (err {err_cpu:.2%}) "
             f"p_accel={fit.p_accel_extra:.2f}W (err {err_acc:.2%}) "
             f"from {len(samples)} metered runs")]


def _calibration_tpu(record: dict) -> list[tuple]:
    """Refit TPU component powers from metered LM traces synthesized under a
    perturbed 'true machine' model; the modeled-vs-metered error report
    before vs after feeding the calibrated model back into the search."""
    from repro.configs import SHAPES, get_config
    from repro.core.lm_cost_model import Decisions
    from repro.core.power import TpuPowerModel
    from repro.telemetry import TpuSample, error_report, fit_tpu_model
    from repro.telemetry.backends import metered_lm_backend

    cfg = get_config(ARCH)
    nominal = TpuPowerModel()
    true = TpuPowerModel(p_idle=66.0, p_mxu=99.0, p_hbm=42.0, p_ici=13.0)
    decisions = [
        Decisions(), Decisions(clock=0.85), Decisions(clock=0.7),
        Decisions(overlap=False), Decisions(attn_impl="xla"),
        Decisions(matmul_precision="f32_accum"),
        Decisions(overlap=False, clock=0.7),
    ]
    shapes = (SHAPES["prefill_32k"], SHAPES["decode_32k"])

    samples: list[TpuSample] = []
    pairs = []  # (cell, modeled under nominal, metered under true)
    for shape in shapes:
        measure = metered_lm_backend(cfg, shape, MESH, power=nominal,
                                     true_power=true)
        for dec in decisions:
            m = measure(dec)
            if not m.feasible:
                continue
            samples.append(TpuSample.from_measurement(m, clock=dec.clock))
            pairs.append((f"{ARCH}/{shape.name}/clk{dec.clock}"
                          f"{'' if dec.overlap else '/seq'}",
                          m.detail["metered"]["modeled_ws"], m.energy_ws))
    fit = fit_tpu_model(samples)
    before = error_report(pairs)

    # calibrated model fed back into the search path: re-model each cell
    # with the fitted coefficients and compare against the same metered Ws
    after_pairs = []
    for (cell, _, metered), s in zip(pairs, samples):
        # clock³ folds into p_mxu inside the fit; apply it per sample
        remodeled = s.chips * (
            fit.p_idle * s.t_step
            + fit.p_mxu * s.clock ** 3 * min(s.t_compute, s.t_step)
            + fit.p_hbm * min(s.t_memory, s.t_step)
            + fit.p_ici * min(s.t_collective, s.t_step))
        after_pairs.append((cell, remodeled, metered))
    after = error_report(after_pairs)

    record["calibration_tpu"] = {
        "true": {"p_idle": true.p_idle, "p_mxu": true.p_mxu,
                 "p_hbm": true.p_hbm, "p_ici": true.p_ici},
        "fit": {"p_idle": fit.p_idle, "p_mxu": fit.p_mxu,
                "p_hbm": fit.p_hbm, "p_ici": fit.p_ici},
        "error_before": before.to_json(),
        "error_after": after.to_json(),
    }
    return [
        ("power_calibration_tpu_fit", float(len(samples)),
         f"fit idle={fit.p_idle:.1f} mxu={fit.p_mxu:.1f} "
         f"hbm={fit.p_hbm:.1f} ici={fit.p_ici:.1f} "
         f"(true 66/99/42/13) from {len(samples)} metered cells"),
        ("power_calibration_tpu_error", before.max_abs_rel_error,
         f"modeled-vs-metered max err: nominal={before.max_abs_rel_error:.2%}"
         f" -> calibrated={after.max_abs_rel_error:.2%}"),
    ]


def _fleet_metered(record: dict) -> list[tuple]:
    """Mixed model-/meter-backed fleet sweep through one shared engine."""
    import repro.telemetry  # noqa: F401  (registers the "metered" backend)
    from repro.core.evaluator import EvalEngine, VectorizedExecutor
    from repro.core.ga import GAConfig
    from repro.core.offload_search import CellSpec, search_fleet
    from repro.telemetry import report_from_metered

    fleet = [
        CellSpec.create(ARCH, "prefill_32k", MESH),
        CellSpec.create(ARCH, "prefill_32k", MESH, backend="metered"),
        CellSpec.create(ARCH, "decode_32k", MESH, backend="metered"),
    ]
    ga = GAConfig(population=8, generations=6, seed=0)
    engine = EvalEngine(executor=VectorizedExecutor())
    t0 = time.perf_counter()
    sweep = search_fleet(fleet, ga_config=ga, engine=engine, cell_workers=1)
    wall = time.perf_counter() - t0
    resweep = search_fleet(fleet, ga_config=ga, engine=engine, cell_workers=1)

    metered_cells = [(cr.cell, cr.search.ga.best.measurement)
                     for cr in sweep.cells if cr.spec.backend == "metered"]
    err = report_from_metered(metered_cells)
    rows = [
        ("power_fleet_metered", wall * 1e6,
         f"cells={len(sweep.cells)} (2 metered) evals={sweep.evaluations} "
         f"hit_rate={sweep.cache_hit_rate:.3f} "
         f"metered_model_err={err.max_abs_rel_error:.3%}"),
        ("power_fleet_metered_resweep", float(resweep.evaluations),
         f"resweep new_evals={resweep.evaluations} "
         f"hit_rate={resweep.cache_hit_rate:.3f} (shared EvalEngine cache)"),
    ]
    record["fleet_metered"] = {
        "cells": len(sweep.cells),
        "metered_cells": len(metered_cells),
        "evaluations": sweep.evaluations,
        "hit_rate": sweep.cache_hit_rate,
        "resweep_evaluations": resweep.evaluations,
        "resweep_hit_rate": resweep.cache_hit_rate,
        "metered_model_error": err.to_json(),
    }
    record["_cache_stats"] = engine.cache.stats()
    return rows


def run(json_path=None) -> list[tuple]:
    from repro.telemetry import CounterSampler

    rows: list[tuple] = []
    scenarios: dict = {}

    cs = CounterSampler()
    rows.append(("power_counter_sources", float(len(cs.domains())),
                 f"available={cs.available} domains={list(cs.domains())} "
                 f"(fallback=modeled when absent)"))

    rows += _fig5_metered(scenarios)
    rows += _calibration_paper(scenarios)
    rows += _calibration_tpu(scenarios)
    rows += _fleet_metered(scenarios)

    cache_stats = scenarios.pop("_cache_stats", None)
    if json_path:
        fig5 = scenarios.get("fig5", {})
        write_artifact(json_path, artifact(
            "power_bench",
            scenarios=scenarios,
            metrics={
                "counter_sampler_available": cs.available,
                "counter_domains": list(cs.domains()),
                "fig5_cpu_only_ws": fig5.get("cpu_only_ws"),
                "fig5_offload_ws": fig5.get("anchor_offload_ws"),
                "fig5_ratio": fig5.get("ratio_anchor_vs_cpu"),
                "max_model_error": fig5.get("max_model_error"),
            },
            cache=cache_stats_json(cache_stats)))
    return rows


MODEL_ERROR_BAND = 0.02  # acceptance: trace integrals within 2% of closed form


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="write the machine-readable record here "
                         "(e.g. BENCH_power.json)")
    args = ap.parse_args()
    rows = run(json_path=args.json)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")
    # standalone runs (incl. the weekly CI job) enforce the acceptance band,
    # so an integration regression fails the workflow, not just a row
    worst = max((us for name, us, _ in rows
                 if name == "power_modeled_sampler_max_err"), default=0.0)
    if worst >= MODEL_ERROR_BAND:
        print(f"FAIL: modeled-sampler integration error {worst:.3%} "
              f">= {MODEL_ERROR_BAND:.0%} band", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
