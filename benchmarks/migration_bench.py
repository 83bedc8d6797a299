"""Migration benchmark: mid-flight load-shedding vs queue-drain-only.

The saturation-spike scenario the PR 10 rebalance escalation exists for:
an energy-greedy fleet routes a burst straight at its cheapest-per-token
destination (``hbm_lp`` — also the slowest), saturating it while two
faster ``mxu_dense`` engines sit idle. Both arms replay the identical
trace on the virtual-clock driver (``workload/driver.py``) with the same
rebalance cadence:

* **drain** — ``rebalance_live=False``: the PR 5 queue-drain moves the
  *queued* backlog to the fast engines, but the requests already admitted
  into the slow engine's slots stay pinned there until they finish;
* **live** — ``rebalance_live=True``: the same drain, escalated with
  mid-flight migration (``runtime/migration.py``) of the admitted slots
  onto the fast engines at the rebalance tick.

Gates (CI fails otherwise):

* the live arm strictly reduces deadline violations — the pinned slots
  are exactly the traffic queue-drain cannot save;
* the live arm's **full bill** (serving energy + idle floors + the
  migration transfer cost) per 1k tokens is no worse than the drain
  arm's — migrations must pay for themselves on the paper's headline
  metric, transfer cost included;
* resimulating the live arm reproduces the identical report field for
  field (migration is deterministic on the virtual clock).

``python benchmarks/migration_bench.py --json BENCH_migration.json``
writes the unified artifact (``benchmarks/artifact.py`` schema).
"""
from __future__ import annotations

import argparse
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))

from benchmarks.artifact import artifact, write_artifact  # noqa: E402

ARCH = "llama3.2-3b"
FLEET = ("hbm_lp", "mxu_dense", "mxu_dense")  # 1 slow-cheap + 2 fast
SLOTS = 2
MAX_LEN = 32
SPIKE = 10  # burst arrivals at t=0, all routed to the cheap engine
MAX_NEW = 20
REBALANCE_EVERY_S = 5e-4
SATURATION_FACTOR = 3.0  # queue > 3 x slots flags the spike source
DEADLINE_S = 2.6e-3  # between the live-arm tail (~2.41ms) and the
# drain-arm pinned slots (~2.75ms): only the slots queue-drain cannot
# move miss it


def _router(cfg, params):
    from repro.configs import DESTINATIONS
    from repro.runtime import FleetRouter

    return FleetRouter(cfg, params, [DESTINATIONS[n] for n in FLEET],
                       arch=ARCH, policy="energy", slots=SLOTS,
                       max_len=MAX_LEN, cache_path=None,
                       saturation_factor=SATURATION_FACTOR)


def _trace():
    from repro.runtime import Request
    from repro.workload.generator import TimedRequest

    return [TimedRequest(at_s=0.0, tenant="spike",
                         request=Request(rid=i, prompt=[1 + i % 7, 3],
                                         max_new_tokens=MAX_NEW))
            for i in range(SPIKE)]


def _arm(cfg, params, live):
    from repro.workload.driver import simulate

    router = _router(cfg, params)
    trace = _trace()
    report = simulate(router, trace,
                      rebalance_every_s=REBALANCE_EVERY_S,
                      rebalance_live=live)
    violations = sum(1 for tr in trace
                     if report.finish_s.get(tr.rid, float("inf")) - tr.at_s
                     > DEADLINE_S)
    return report, violations


def _report_json(report, violations):
    return {
        "duration_s": report.duration_s,
        "completed": report.completed,
        "tokens": report.tokens,
        "energy_ws": report.energy_ws,
        "idle_ws": report.idle_ws,
        "migration_ws": report.migration_ws,
        "migrations": report.migrations,
        "total_ws": report.total_ws,
        "ws_per_1k_tokens": report.ws_per_1k_tokens,
        "deadline_violations": violations,
    }


def run(json_path=None) -> list[tuple]:
    import jax

    from repro import models as M
    from repro.configs import get_config, reduced

    cfg = reduced(get_config(ARCH))
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    drain, v_drain = _arm(cfg, params, live=False)
    live, v_live = _arm(cfg, params, live=True)
    live2, v_live2 = _arm(cfg, params, live=True)  # deterministic resim

    deterministic = (_report_json(live, v_live)
                     == _report_json(live2, v_live2))
    fewer_violations = v_live < v_drain
    no_worse_bill = live.ws_per_1k_tokens <= drain.ws_per_1k_tokens

    rows = [
        ("migration_drain_violations", float(v_drain),
         f"queue-drain only: {v_drain}/{SPIKE} miss the "
         f"{DEADLINE_S * 1e3:.1f}ms deadline, migrations=0"),
        ("migration_live_violations", float(v_live),
         f"live shedding: {v_live}/{SPIKE} miss, "
         f"migrations={live.migrations} "
         f"transfer_ws={live.migration_ws:.3f}"),
        ("migration_drain_ws_per_1k", drain.ws_per_1k_tokens,
         f"full bill, tokens={drain.tokens}"),
        ("migration_live_ws_per_1k", live.ws_per_1k_tokens,
         f"full bill incl transfer cost, tokens={live.tokens}"),
        ("migration_gates", 1.0 if (fewer_violations and no_worse_bill
                                    and deterministic) else 0.0,
         f"fewer_violations={fewer_violations} "
         f"no_worse_bill={no_worse_bill} deterministic={deterministic}"),
    ]

    if json_path:
        write_artifact(json_path, artifact(
            "migration_bench",
            scenarios={
                "drain": _report_json(drain, v_drain),
                "live": _report_json(live, v_live),
            },
            metrics={
                "arch": ARCH,
                "fleet": list(FLEET),
                "spike_requests": SPIKE,
                "deadline_s": DEADLINE_S,
                "rebalance_every_s": REBALANCE_EVERY_S,
                "violations_drain": v_drain,
                "violations_live": v_live,
                "ws_per_1k_drain": drain.ws_per_1k_tokens,
                "ws_per_1k_live": live.ws_per_1k_tokens,
                "migrations_live": live.migrations,
                "fewer_violations": fewer_violations,
                "no_worse_bill": no_worse_bill,
                "deterministic": deterministic,
            }))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="write the machine-readable record here "
                         "(e.g. BENCH_migration.json)")
    args = ap.parse_args()
    rows = run(json_path=args.json)
    print("name,us_per_call,derived")
    for name, val, derived in rows:
        print(f"{name},{val:.4f},{derived}")
    gates = next(derived for name, _, derived in rows
                 if name == "migration_gates")
    if "False" in gates:
        print(f"FAIL: migration gates not met: {gates}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
