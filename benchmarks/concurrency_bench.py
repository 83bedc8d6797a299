"""Concurrency benchmark: the lockstep fleet executor's two promises.

``FleetRouter.run(concurrent=True)`` (``repro.runtime.executor``) steps
every engine of a mixed 3-destination fleet from its own worker thread,
one lockstep tick at a time. This benchmark pins the two claims the
race-lint certified executor makes:

* **identity** — a fresh fleet served concurrently produces exactly the
  sequential drain's tokens, finish reasons and per-engine + fleet
  ledgers (compared via one sha256 digest over the canonical JSON);
* **speedup** — with a per-step device dwell (the accelerator round-trip
  the CPU-only host cannot exhibit on its own, emulated by a
  GIL-releasing sleep in ``FleetExecutor._step_engine``), the concurrent
  step phase must beat the sequential baseline by ≥ 1.5× on the
  3-engine fleet. The baseline is the *same* ``FleetExecutor`` with
  ``max_workers=1`` — identical code path, identical dwell, no
  thread-pool overlap — so the ratio isolates the overlap itself.

Timing excludes jit compilation: a warmup batch is served before either
timed run. ``python benchmarks/concurrency_bench.py --json
BENCH_concurrency.json`` writes the unified artifact
(``benchmarks/artifact.py`` schema) that CI uploads; the CLI exits 1 if
the digest mismatches or the speedup falls below 1.5×.
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))

from benchmarks.artifact import artifact, write_artifact  # noqa: E402

ARCH = "llama3.2-3b"
MIXED = ("pod2_v5e", "mxu_dense", "hbm_lp")
SLOTS = 2
MAX_LEN = 32
DWELL_S = 0.005  # emulated device round-trip per stream step
MIN_SPEEDUP = 1.5


def _router(cfg, params):
    from repro.configs import DESTINATIONS
    from repro.runtime import FleetRouter

    return FleetRouter(cfg, params, [DESTINATIONS[n] for n in MIXED],
                       arch=ARCH, policy="round_robin", slots=SLOTS,
                       max_len=MAX_LEN, cache_path=None)


def _requests(n, base_rid=0):
    """Decode-heavy batch: the step phase dominates, which is exactly the
    phase the executor overlaps."""
    from repro.runtime import Request

    return [Request(rid=base_rid + i, prompt=[1 + i % 7, 3 + i % 5],
                    max_new_tokens=12) for i in range(n)]


def _digest(done, router) -> str:
    state = {
        "outputs": [(r.rid, list(r.output), r.finish_reason, r.served_by)
                    for r in done],
        "engines": {n: dataclasses.asdict(s)
                    for n, s in router.per_engine_stats().items()},
        "fleet": dataclasses.asdict(router.fleet_stats()),
    }
    return hashlib.sha256(
        json.dumps(state, sort_keys=True).encode("utf-8")).hexdigest()


def run(json_path=None) -> list[tuple]:
    import jax

    from repro import models as M
    from repro.configs import get_config, reduced

    cfg = reduced(get_config(ARCH))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rows: list[tuple] = []

    # identity: fresh fleets, sequential drain vs lockstep concurrent
    t0 = time.perf_counter()
    seq, conc = _router(cfg, params), _router(cfg, params)
    for r in _requests(9):
        seq.submit(r)
    for r in _requests(9):
        conc.submit(r)
    seq_digest = _digest(seq.run(), seq)
    conc_digest = _digest(conc.run(concurrent=True), conc)
    identical = seq_digest == conc_digest
    rows.append(("concurrency_identity", (time.perf_counter() - t0) * 1e6,
                 f"digest_match={identical} sha256={conc_digest[:16]}"))

    # speedup: warmed router, same dwell, max_workers=1 vs full pool
    bench = _router(cfg, params)
    for r in _requests(len(MIXED), base_rid=100):  # warmup: jit compiles
        bench.submit(r)
    bench.run(concurrent=True)

    for r in _requests(9, base_rid=200):
        bench.submit(r)
    t0 = time.perf_counter()
    done_1w = bench.run(concurrent=True, max_workers=1, dwell_s=DWELL_S)
    seq_wall = time.perf_counter() - t0

    for r in _requests(9, base_rid=300):
        bench.submit(r)
    t0 = time.perf_counter()
    done_nw = bench.run(concurrent=True, dwell_s=DWELL_S)
    conc_wall = time.perf_counter() - t0

    speedup = seq_wall / max(conc_wall, 1e-9)
    tokens_match = ([list(r.output) for r in done_1w]
                    == [list(r.output) for r in done_nw])
    rows.append(("concurrency_step_seq", seq_wall * 1e6,
                 f"max_workers=1 dwell={DWELL_S * 1e3:.1f}ms "
                 f"reqs={len(done_1w)}"))
    rows.append(("concurrency_step_conc", conc_wall * 1e6,
                 f"max_workers={len(MIXED)} dwell={DWELL_S * 1e3:.1f}ms "
                 f"reqs={len(done_nw)}"))
    rows.append(("concurrency_speedup", speedup,
                 f"{speedup:.2f}x over {len(MIXED)}-engine fleet "
                 f"(gate >= {MIN_SPEEDUP}x) tokens_match={tokens_match}"))

    if json_path:
        write_artifact(json_path, artifact(
            "concurrency_bench",
            scenarios={
                "identity": {
                    "seq_digest": seq_digest,
                    "conc_digest": conc_digest,
                    "digest_match": identical,
                    "requests": 9,
                },
                "step_timing": {
                    "seq_wall_s": seq_wall,
                    "conc_wall_s": conc_wall,
                    "speedup": speedup,
                    "dwell_s": DWELL_S,
                    "tokens_match": tokens_match,
                },
            },
            metrics={
                "arch": ARCH,
                "destinations": list(MIXED),
                "engines": len(MIXED),
                "ledger_digest_match": identical,
                "speedup": speedup,
                "min_speedup": MIN_SPEEDUP,
                "dwell_s": DWELL_S,
            }))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="write the machine-readable record here "
                         "(e.g. BENCH_concurrency.json)")
    args = ap.parse_args()
    rows = run(json_path=args.json)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")
    by_name = {name: (us, derived) for name, us, derived in rows}
    if "digest_match=True" not in by_name["concurrency_identity"][1]:
        print("FAIL: concurrent ledger digest != sequential",
              file=sys.stderr)
        sys.exit(1)
    if by_name["concurrency_speedup"][0] < MIN_SPEEDUP:
        print(f"FAIL: step-phase speedup "
              f"{by_name['concurrency_speedup'][0]:.2f}x < {MIN_SPEEDUP}x",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
