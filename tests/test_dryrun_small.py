"""Small-mesh dry-run integration: lower + compile cell programs on an 8-dev
host mesh (subprocess so the 8-device XLA flag never leaks into this
process), plus HLO collective parsing units."""
import json
import subprocess
import sys
import textwrap

import pytest

from repro.core.hlo_analysis import collective_stats, remat_stats

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, dataclasses
    import jax
    from repro.configs import get_config, reduced, SHAPES
    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import make_mesh_compat
    from repro.launch.steps import build_cell_program
    from repro.parallel.layouts import rules_for
    from repro.parallel.sharding import use_mesh

    mesh = make_mesh_compat((4, 2), ("data", "model"))
    out = {}
    cells = [
        ("llama3.2-3b", ShapeSpec("t", "train", 32, 8)),
        ("mixtral-8x7b", ShapeSpec("p", "prefill", 64, 4)),
        ("rwkv6-1.6b", ShapeSpec("d", "decode", 64, 4)),
        ("zamba2-7b", ShapeSpec("d", "decode", 64, 4)),
        ("seamless-m4t-medium", ShapeSpec("t", "train", 32, 8)),
    ]
    for arch, shape in cells:
        cfg = dataclasses.replace(reduced(get_config(arch)), accum=2
                                  if shape.kind == "train" else 1)
        rules = rules_for(cfg, shape, mesh)
        prog = build_cell_program(cfg, shape, mesh, rules)
        with use_mesh(mesh, rules):
            compiled = prog.lower().compile()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        out[f"{arch}/{shape.kind}"] = {
            "flops": float(ca.get("flops", 0)),
            "temp": int(ma.temp_size_in_bytes),
            "collectives": compiled.as_text().count("all-reduce"),
        }
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def small_mesh_results():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"}, timeout=540)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_cells_compile_on_8dev_mesh(small_mesh_results):
    assert len(small_mesh_results) == 5
    for cell, rec in small_mesh_results.items():
        assert rec["flops"] > 0, cell


@pytest.mark.slow
def test_sharded_programs_communicate(small_mesh_results):
    train_cells = [c for c in small_mesh_results if "/train" in c]
    assert any(small_mesh_results[c]["collectives"] > 0 for c in train_cells)


# ---------------------------------------------------------------------------
# HLO analysis units
# ---------------------------------------------------------------------------


def test_collective_stats_parses_kinds():
    hlo = """
  %ar = f32[128,256] all-reduce(%x), replica_groups=[16,16]<=[256], to_apply=%add
  %ag = bf16[64,512] all-gather(%y), replica_groups={{0,1,2,3}}, dimensions={1}
  %cp = f32[32] collective-permute(%z), source_target_pairs={{0,1}}
    """
    stats = collective_stats(hlo, default_group=8)
    assert stats.count == 3
    ar = 2 * 128 * 256 * 4 * 15 / 16
    ag = 64 * 512 * 2 * 3 / 4
    cp = 32 * 4
    assert stats.by_kind["all-reduce"] == pytest.approx(ar)
    assert stats.by_kind["all-gather"] == pytest.approx(ag)
    assert stats.by_kind["collective-permute"] == pytest.approx(cp)


def test_collective_stats_ignores_noncollectives():
    assert collective_stats("%d = f32[8,8] dot(%a, %b)").count == 0


def test_remat_stats_counts_duplicate_dots():
    hlo = """
  %dot.1 = f32[128,64] dot(%a, %b)
  %dot.2 = f32[128,64] dot(%a, %b)
  %dot.3 = f32[32,16] dot(%c, %d)
    """
    st = remat_stats(hlo)
    assert st["dot_signatures"] == 2
    assert st["duplicated_signatures"] == 1
    assert st["max_duplication"] == 2


_WHILE_HLO = """
%body.7 (p.1: (s32[], f32[256])) -> (s32[], f32[256]) {
  %p.1 = (s32[], f32[256]) parameter(0)
  %ar.1 = f32[256] all-reduce(%gte.1), replica_groups={{0,1,2,3}}, to_apply=%add
  ROOT %tuple.1 = (s32[], f32[256]) tuple(%next, %ar.1)
}

%cond.9 (p.2: (s32[], f32[256])) -> pred[] {
  %p.2 = (s32[], f32[256]) parameter(0)
  %iv = s32[] get-tuple-element(%p.2), index=0
  %limit = s32[] constant(12)
  ROOT %lt = pred[] compare(%iv, %limit), direction=LT
}

ENTRY %main.20 (arg0: f32[256]) -> f32[256] {
  %ag.0 = f32[512] all-gather(%arg0), replica_groups={{0,1}}, dimensions={0}
  %w = (s32[], f32[256]) while(%init), condition=%cond.9, body=%body.7
  ROOT %out = f32[256] get-tuple-element(%w), index=1
}
"""


def test_collective_stats_multiplies_while_trip_counts():
    stats = collective_stats(_WHILE_HLO)
    # all-reduce inside the 12-trip loop: 12 × 2·S·(n-1)/n
    ar = 12 * 2 * 256 * 4 * 3 / 4
    # all-gather in the entry computation counts once
    ag = 512 * 4 * 1 / 2
    assert stats.by_kind["all-reduce"] == pytest.approx(ar)
    assert stats.by_kind["all-gather"] == pytest.approx(ag)
    assert stats.count == 13


def test_collective_stats_underivable_trip_counts_once():
    # dynamic loop bound: the condition compares against another tuple
    # element, not a constant — the body's collective must count once.
    hlo = _WHILE_HLO.replace("%limit = s32[] constant(12)",
                             "%limit = s32[] get-tuple-element(%p.2), index=1")
    stats = collective_stats(hlo)
    assert stats.by_kind["all-reduce"] == pytest.approx(2 * 256 * 4 * 3 / 4)
    assert stats.count == 2


def test_collective_stats_nested_while_trips_multiply():
    hlo = """
%inner_body.1 (p: (s32[], f32[64])) -> (s32[], f32[64]) {
  %ar.i = f32[64] all-reduce(%g), replica_groups={{0,1}}, to_apply=%add
}

%inner_cond.1 (p: (s32[], f32[64])) -> pred[] {
  %k.i = s32[] constant(3)
  ROOT %lt.i = pred[] compare(%iv.i, %k.i), direction=LT
}

%outer_body.1 (p: (s32[], f32[64])) -> (s32[], f32[64]) {
  %w.i = (s32[], f32[64]) while(%t), condition=%inner_cond.1, body=%inner_body.1
}

%outer_cond.1 (p: (s32[], f32[64])) -> pred[] {
  %k.o = s32[] constant(5)
  ROOT %lt.o = pred[] compare(%iv.o, %k.o), direction=LT
}

ENTRY %main.1 (a: f32[64]) -> f32[64] {
  %w.o = (s32[], f32[64]) while(%t0), condition=%outer_cond.1, body=%outer_body.1
}
"""
    stats = collective_stats(hlo)
    assert stats.by_kind["all-reduce"] == pytest.approx(15 * 64 * 4)
    assert stats.count == 15


def test_collective_stats_iota_replica_groups_forms():
    # iota form [g,n]<=[devices]: group size is the second number
    hlo = "%ar = f32[64] all-reduce(%x), replica_groups=[2,8]<=[16], to_apply=%a"
    stats = collective_stats(hlo)
    assert stats.by_kind["all-reduce"] == pytest.approx(2 * 64 * 4 * 7 / 8)
    # degenerate iota groups of one device move no bytes
    hlo1 = "%ar = f32[64] all-reduce(%x), replica_groups=[16,1]<=[16], to_apply=%a"
    assert collective_stats(hlo1).count == 0
    # iota form with a transposed device order still parses group size
    hlo2 = ("%ag = bf16[32,32] all-gather(%y), "
            "replica_groups=[4,4]<=[2,8]T(1,0), dimensions={0}")
    stats2 = collective_stats(hlo2)
    assert stats2.by_kind["all-gather"] == pytest.approx(32 * 32 * 2 * 3 / 4)


def test_shape_bytes_unknown_dtype_warns_not_raises():
    from repro.core import hlo_analysis

    hlo_analysis._warned_dtypes.discard("f8e8m0fnu")
    hlo = ("%ar = f8e8m0fnu[128] all-reduce(%x), replica_groups={{0,1}}, "
           "to_apply=%a")
    with pytest.warns(UserWarning, match="unknown dtype 'f8e8m0fnu'"):
        stats = collective_stats(hlo)
    # bit-width fallback: f8... -> 1 byte/element
    assert stats.by_kind["all-reduce"] == pytest.approx(2 * 128 * 1 * 1 / 2)
