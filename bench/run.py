"""Run one benchmark cell once, on the chip this process is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell's configuration, traffic and metrics
are found by name through ``BENCHMARK.json``. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and with ``--trace 1`` ``breakdown``), ending with
``checks``, each number compared beside its limit; the same numbers are
the last lines of standard error. Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.

JAX's persistent compilation cache lives in ``.bench_cache/jax`` in the
checkout, so only a cell's first run there compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def fail(msg: str, code: int = 2):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import repro.runtime.serving  # noqa: F401  the system under test
    except ImportError as e:
        fail(f"the system under test is not in this checkout ({e})")
    from bench import harness

    cell = harness.load_cell(args.workload)
    harness.use_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"needs a TPU; JAX found platform {devices[0].platform!r}")
    if len(devices) < cell.chips:
        fail(f"{args.workload} needs {cell.chips} chips; JAX found "
             f"{len(devices)}")
    peaks = harness.read_json(ROOT / "bench" / "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks:
        fail(f"no peak for device kind {kind!r} in bench/peaks.json")

    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START, peaks[kind])
    for name, c in result["checks"].items():
        bound = (f"limit {c['limit']}" if "limit" in c
                 else f"at least {c['min']}")
        print(f"check {name} {c['value']} {bound}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
