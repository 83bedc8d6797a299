"""Find an open-loop cell's knee once; not part of a benchmark run.

    python3 bench/sweep.py --workload <cell> --rates 2,3,4 --seconds <s>

In one process, with the cell's weights and engine made once, one window
per rate through the timed path, with the traffic of ``bench/generator.py``
at that rate. For each rate one JSON line: the metrics read by
``bench/metrics/`` (the same readers a run uses), the requests due and the
backlog (due, not yet admitted) at the window's close. The knee is the
highest rate whose backlog does not grow; the cell's fixed rate
(``rate_rps`` in ``bench/cells/<cell>.json``) is about four fifths of it.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
METRICS = [{"name": n, "unit": u} for n, u in (
    ("ttft_p95_ms", "ms"), ("queue_wait_p95_ms", "ms"), ("itl_p95_ms", "ms"),
    ("output_tokens_per_s", "tokens/s"), ("slot_occupancy", "%"))]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import check, generator, harness, weights
    harness.use_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2

    cell = harness.load_cell(args.workload)
    cfg = harness.build_config(cell.conf)
    layout = harness.family(cell.conf, "reference").layout(cell.conf["model"])
    params = weights.make_params(layout, args.seed)
    check.same_layout(cfg, params)
    engine = harness.make_engine(cfg, params, cell)
    for rate in (float(r) for r in args.rates.split(",")):
        engine.stream_open()
        harness.warm_up(engine, cell.params["slots"])
        traffic = generator.make_traffic(
            cell.mix, {**cell.params, "rate_rps": rate}, args.seed,
            args.seconds, cell.conf["model"]["vocab_size"])
        tl = harness.serve_window(engine, traffic, args.seconds)
        engine.stream_close()
        run = harness.Run(cell, 0.0, tl, None, {})
        metrics = harness.read_metrics(METRICS, run)
        print(json.dumps({
            "rate_rps": rate, "due": len(tl.records),
            "backlog_at_close": sum(not 0 <= r.admit_t <= tl.t1
                                    for r in tl.records),
            "drain_s": tl.t_last - tl.t1,
            **{k: v["value"] for k, v in metrics.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
