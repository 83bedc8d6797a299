"""Readings that the check's limit is set from; not part of a benchmark run.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, in one process: a window at the cell's own load through the
timed path, exactly as ``run.py`` drives it; then ``check.compare`` twice
over the same sample of served requests, with the cell's own limits:

* program: the engine's tokens against the float32 reference, what a run
  compares;
* control: the int8 reference (int8 weights and activations, the precision
  step below the configuration's bfloat16) put in the program's place, its
  first choice at each of the same positions.

Each comes with ``correct`` as a run decides it (``check.verdict``): the
program's must come out true and the control's false. One JSON line per
seed goes to standard output.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cell, seed: int, seconds: float) -> dict:
    """The program's and the control's checks, and verdicts, of one seed's
    window."""
    from bench import check, generator, harness, weights

    conf, model = cell.conf, cell.conf["model"]
    cfg = harness.build_config(conf)
    ref = harness.family(conf, "reference")
    layout = ref.layout(model)
    params = weights.make_params(layout, seed)
    check.same_layout(cfg, params)
    traffic = generator.make_traffic(cell.mix, cell.params, seed, seconds,
                                     model["vocab_size"])
    engine = harness.make_engine(cfg, params, cell)
    engine.stream_open()
    harness.warm_up(engine, cell.params["slots"])
    timeline = harness.serve_window(engine, traffic, seconds)
    engine.stream_close()
    del engine, params
    gc.collect()

    out = {"seed": seed}
    for side, control in (("program", False), ("control", True)):
        checks = check.compare(cell, timeline, seed, ref, layout,
                               control=control)
        out[side] = {"correct": check.verdict(checks), "checks": checks}
    out["served"] = sum(1 for r in timeline.records if r.req.output)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    harness.use_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print(f"control: needs a TPU; JAX found "
              f"{jax.devices()[0].platform!r}", file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = readings(cell, seed, args.seconds)
        out["wall_s"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
