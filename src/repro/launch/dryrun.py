import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

__doc__ = """Multi-pod dry-run: lower + compile every (architecture × shape)
cell on the production meshes, extract memory/cost/collective artifacts, and
write one JSON record per cell.

Cost extraction uses the delta method (EXPERIMENTS.md §Dry-run): XLA's
cost_analysis counts a while-loop body ONCE, so the scanned-layers artifact
under-reports FLOPs. We therefore lower three structural probes with
accum=1 and unrolled inner chunk loops (mode="probe"):

    dense-ish:  total = raw(L=0) + L·(raw(L=1) − raw(L=0))
    hybrid:     groups g∈{0,1}, ng_eff = num_layers / attn_every
    enc-dec:    (e,l)∈{(0,0),(1,0),(0,1)} two-delta form

while the FULL-depth scanned artifact provides memory_analysis (exact),
the collective schedule, and the compile-success proof.
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Optional

import jax

from repro.configs import SHAPES, cell_supported, get_config, list_configs
from repro.configs.base import ArchConfig, ShapeSpec
from repro.core.hlo_analysis import collective_stats
from repro.core.lm_cost_model import Decisions
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import chips, make_production_mesh, mesh_shape_dict
from repro.launch.steps import build_cell_program
from repro.parallel.layouts import rules_for
from repro.parallel.sharding import use_mesh


def _with_depth(cfg: ArchConfig, n: int, keep_accum: bool = False) -> ArchConfig:
    ch: dict = {} if keep_accum else {"accum": 1}
    if cfg.family == "hybrid":
        ch["num_layers"] = n * (cfg.attn_every or 1)
        ch["attn_every"] = cfg.attn_every
    else:
        ch["num_layers"] = n
    return dataclasses.replace(cfg, **ch)


def _with_enc_depth(cfg: ArchConfig, e: int, l: int,
                    keep_accum: bool = False) -> ArchConfig:
    ch = {"encoder_layers": e, "num_layers": l}
    if not keep_accum:
        ch["accum"] = 1
    return dataclasses.replace(cfg, **ch)


def _cost(cfg, shape, mesh, dec, *, mode: str, overrides=None) -> dict:
    rules = rules_for(cfg, shape, mesh, overrides=overrides)
    prog = build_cell_program(cfg, shape, mesh, rules, dec, mode=mode)
    with use_mesh(mesh, rules):
        lowered = prog.lower()
        compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    coll = collective_stats(compiled.as_text(), default_group=chips(mesh))
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "collective_bytes": coll.wire_bytes,
        "collective_by_kind": coll.by_kind,
        "collective_count": coll.count,
    }


def _sub(a: dict, b: dict) -> dict:
    return {k: (a[k] - b[k]) if isinstance(a[k], float) else a[k]
            for k in ("flops", "bytes", "collective_bytes")}


def _delta_total(cfg: ArchConfig, shape: ShapeSpec, mesh, dec, *,
                 mode: str, overrides=None, keep_accum: bool = False
                 ) -> tuple[dict, dict]:
    """raw(0) + depth·(raw(1) − raw(0)) per family structure."""
    keys = ("flops", "bytes", "collective_bytes")
    if cfg.is_encdec:
        r00 = _cost(_with_enc_depth(cfg, 0, 0, keep_accum), shape, mesh, dec,
                    mode=mode, overrides=overrides)
        r10 = _cost(_with_enc_depth(cfg, 1, 0, keep_accum), shape, mesh, dec,
                    mode=mode, overrides=overrides)
        r01 = _cost(_with_enc_depth(cfg, 0, 1, keep_accum), shape, mesh, dec,
                    mode=mode, overrides=overrides)
        total = {k: r00[k] + cfg.encoder_layers * (r10[k] - r00[k])
                 + cfg.num_layers * (r01[k] - r00[k]) for k in keys}
        return total, {"e0l0": r00, "e1l0": r10, "e0l1": r01}
    r0 = _cost(_with_depth(cfg, 0, keep_accum), shape, mesh, dec, mode=mode,
               overrides=overrides)
    r1 = _cost(_with_depth(cfg, 1, keep_accum), shape, mesh, dec, mode=mode,
               overrides=overrides)
    if cfg.family == "hybrid":
        depth = cfg.num_layers / (cfg.attn_every or cfg.num_layers)
    else:
        depth = cfg.num_layers
    total = {k: r0[k] + depth * (r1[k] - r0[k]) for k in keys}
    return total, {"l0": r0, "l1": r1}


def probe_costs(cfg: ArchConfig, shape: ShapeSpec, mesh, dec,
                overrides: Optional[dict] = None) -> dict:
    """Delta-method per-device totals (flops / hbm bytes / collective wire).

    flops/bytes: mode="probe" (unrolled chunk loops = exact trip-count
    accounting, flash-style block skipping) WITHOUT seq-SP — causal slicing
    of a seq-sharded tensor would insert all-gathers/copies the scanned
    artifact doesn't execute, corrupting the byte counts.

    collectives: mode="exec" with the REAL layout (scan bodies appear once;
    the delta gives per-layer wire bytes). Activation-proportional wire is
    batch-linear (already a full-step total at any accum); weight-
    proportional wire (FSDP gathers, grad reduce-scatters) repeats per
    microbatch. Probing at accum∈{1,2} separates them — note the accum-2
    scan body is counted ONCE by the HLO parse, so:
        coll(1) = W + Act          (no scan at accum=1)
        coll(2) = W + Act/2        (one body, half-size microbatch)
        ⇒ Act = 2·(coll(1) − coll(2)),  W = 2·coll(2) − coll(1)
        step total = cfg.accum·W + Act.
    """
    accum = cfg.accum if shape.kind == "train" else 1
    comp_over = dict(overrides or {})
    comp_over["seq"] = None
    comp_total, comp_probes = _delta_total(
        cfg, shape, mesh, dec, mode="probe", overrides=comp_over)
    coll_total, coll_probes = _delta_total(
        cfg, shape, mesh, dec, mode="exec", overrides=overrides)
    coll_step = coll_total["collective_bytes"]
    if shape.kind == "train" and accum > 1 and shape.global_batch % 2 == 0:
        cfg_a2 = dataclasses.replace(cfg, accum=2)
        coll2_t, _ = _delta_total(cfg_a2, shape, mesh, dec, mode="exec",
                                  overrides=overrides, keep_accum=True)
        coll1 = coll_step
        coll2 = coll2_t["collective_bytes"]
        act_part = max(2 * (coll1 - coll2), 0.0)
        w_part = max(2 * coll2 - coll1, 0.0)
        coll_step = accum * w_part + act_part
    total = {
        "flops": comp_total["flops"],
        "bytes": comp_total["bytes"],
        "collective_bytes": coll_step,
    }
    if accum > 1:
        # accum re-streams weights once per extra microbatch (probes ran
        # accum=1); fwd + bwd re-reads
        w_bytes = cfg.param_count() * 2.0 / chips(mesh)
        total["bytes"] += (accum - 1) * 2 * w_bytes
    return {"total_per_device": total,
            "probes": {"compute": comp_probes, "collective": coll_probes},
            "accum": accum}


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             dec: Optional[Decisions] = None, skip_probes: bool = False,
             overrides: Optional[dict] = None,
             cfg_changes: Optional[dict] = None) -> dict:
    cfg = get_config(arch)
    if cfg_changes:
        cfg = dataclasses.replace(cfg, **cfg_changes)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    record: dict = {
        "arch": arch, "shape": shape_name,
        "mesh": mesh_shape_dict(mesh), "chips": chips(mesh),
        "decisions": dataclasses.asdict(dec) if dec else None,
        "overrides": overrides, "cfg_changes": cfg_changes,
    }
    ok, reason = cell_supported(cfg, shape)
    if not ok:
        record["status"] = "skipped"
        record["reason"] = reason
        return record

    rules = rules_for(cfg, shape, mesh, overrides=overrides)
    t0 = time.time()
    prog = build_cell_program(cfg, shape, mesh, rules, dec, mode="exec")
    with use_mesh(mesh, rules):
        lowered = prog.lower()
        record["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t1, 2)

    ma = compiled.memory_analysis()
    print(ma)  # proves it fits
    ca = compiled.cost_analysis() or {}
    print({k: ca.get(k) for k in ("flops", "bytes accessed")})
    record["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_per_device": int(ma.argument_size_in_bytes
                               + ma.temp_size_in_bytes
                               + ma.output_size_in_bytes
                               - ma.alias_size_in_bytes),
    }
    record["artifact_cost_analysis"] = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
    }
    coll = collective_stats(compiled.as_text(), default_group=chips(mesh))
    record["artifact_collectives"] = {
        "wire_bytes_per_device": coll.wire_bytes,
        "by_kind": coll.by_kind, "count": coll.count,
    }
    if not skip_probes:
        t2 = time.time()
        record["probe"] = probe_costs(cfg, shape, mesh, dec,
                                      overrides=overrides)
        record["probe_s"] = round(time.time() - t2, 2)
    record["status"] = "ok"
    record["description"] = prog.description
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-probes", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    archs = list_configs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    pods = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                tag = f"{arch}__{shape}__{'pod2' if mp else 'pod1'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[skip existing] {tag}")
                    continue
                print(f"=== {tag} ===", flush=True)
                try:
                    rec = run_cell(arch, shape, multi_pod=mp,
                                   skip_probes=args.skip_probes)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                           "status": "error", "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()}
                    failures += 1
                    print(rec["error"], flush=True)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                print(f"[{rec['status']}] {tag}", flush=True)
    print(f"done; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
