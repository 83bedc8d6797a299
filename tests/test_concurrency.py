"""Concurrency-soundness pass: race/deadlock lint + lockstep fleet executor.

Three layers, mirroring analysis/concurrency.py + runtime/executor.py:

1. Lint unit tests on synthetic racy/deadlocky classes — every finding kind
   (shared-write, mixed-guard, lock-cycle, lock-blocking, global-write) on a
   fixture built to trip it, clean/exempt fixtures staying clean, and the
   whole-repo scan staying at ZERO findings (the certification the
   concurrent executor rides on; tools/race_lint.py gates the same in CI).
2. Regression pins for the real defects the lint found and this PR fixed:
   CacheStore.append is one atomic O_APPEND os.write (no flush under the
   store lock), compaction aborts instead of dropping a raced append, and
   EvalCache.put fires its persistence hook OUTSIDE the cache lock.
3. Executor certification: FleetRouter.run(concurrent=True) is token- and
   ledger-identical to the sequential drain across dense/ssm/hybrid
   families, and a seed-deterministic interleaving fuzzer permutes thread
   switch points across submit/plan/scale_to/step/migrate operations
   asserting the fleet==Σengines ledger invariant (and, with mid-flight
   migrations in play, exactly-once token billing) under every schedule.
"""
import dataclasses
import random
import threading

import jax
import pytest

from repro.analysis.concurrency import (
    DEFAULT_ENTRY_POINTS, lint_runtime, lint_scan, scan_source,
)
from repro.configs import DESTINATIONS, get_config, reduced
from repro.core.evaluator import EvalCache, EvalEngine, VectorizedExecutor
from repro.core.fitness import Measurement
from repro.core.ga import GAConfig
from repro import models as M
from repro.runtime import FleetExecutor, FleetRouter, Request
from repro.runtime.serving import CLOCK_FIELDS

MIXED = ("pod2_v5e", "mxu_dense", "hbm_lp")
FAMILIES = {"dense": "llama3.2-3b", "ssm": "rwkv6-1.6b", "hybrid": "zamba2-7b"}


def lint_src(src):
    return lint_scan(scan_source(src, module="fix"))


def fids(report):
    return [f.fid for f in report.findings]


def rules(report):
    return {f.rule for f in report.findings}


# ---------------------------------------------------------------------------
# 1. Lint rules on synthetic fixtures
# ---------------------------------------------------------------------------


RACY = """
import threading

class Racy:
    def __init__(self):
        self._count = 0
        self._thread = None

    def start(self):
        self._thread = threading.Thread(target=self._worker)
        self._thread.start()

    def _worker(self):
        self._count += 1

    def total(self):
        return self._count
"""


def test_unguarded_shared_write_is_flagged():
    rep = lint_src(RACY)
    assert fids(rep) == ["shared-write:fix.Racy._count"]
    assert rep.findings[0].severity == "error"
    # the shared-state map attributes the write to the thread body
    (attr,) = [s for s in rep.shared if s.qualname.endswith("_count")]
    assert attr.discipline == "unguarded"
    assert attr.writers == ["fix.Racy._worker"]


def test_single_writer_marker_suppresses_shared_write():
    marked = RACY.replace(
        "class Racy:",
        'class Racy:\n    "Thread-safety: single-writer."')
    rep = lint_src(marked)
    assert fids(rep) == []
    (attr,) = [s for s in rep.shared if s.qualname.endswith("_count")]
    assert attr.discipline == "single-writer"


def test_lock_guarded_class_is_clean():
    rep = lint_src("""
import threading

class Clean:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []
        self._thread = None

    def start(self):
        self._thread = threading.Thread(target=self._worker)
        self._thread.start()

    def _worker(self):
        with self._lock:
            self._items.append(1)

    def snapshot(self):
        with self._lock:
            return list(self._items)
""")
    assert fids(rep) == []
    (attr,) = [s for s in rep.shared if s.qualname.endswith("_items")]
    assert attr.discipline == "lock"
    assert attr.lock == "fix.Clean._lock"


def test_pre_start_and_post_join_writes_are_exempt():
    """Construction-publication and join-termination order the accesses:
    a correct fork/join helper lints clean without any lock."""
    rep = lint_src("""
import threading

class ForkJoin:
    def __init__(self):
        self._out = []
        self._thread = None

    def run(self):
        self._out = []
        self._thread = threading.Thread(target=self._worker)
        self._thread.start()
        self._thread.join()
        return list(self._out)

    def _worker(self):
        self._out.append(1)
""")
    assert fids(rep) == []


def test_mixed_guard_is_flagged():
    rep = lint_src("""
import threading

class MixedGuard:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []

    def add(self, x):
        with self._lock:
            self._items.append(x)

    def drop(self):
        self._items.pop()
""")
    assert fids(rep) == ["mixed-guard:fix.MixedGuard._items"]


def test_immutable_attr_read_mixed_states_is_not_mixed_guard():
    """An attribute only ever written in __init__ is published by
    construction; reading it both under and outside the lock is fine."""
    rep = lint_src("""
import threading

class Immutable:
    def __init__(self, path):
        self._lock = threading.Lock()
        self.path = path
        self._n = 0

    def bump(self):
        with self._lock:
            self._n += 1
            return self.path

    def where(self):
        return self.path
""")
    assert fids(rep) == []


def test_lock_cycle_across_methods_is_flagged():
    rep = lint_src("""
import threading

class Deadlock:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def ab(self):
        with self._a:
            with self._b:
                pass

    def ba(self):
        with self._b:
            with self._a:
                pass
""")
    assert rules(rep) == {"lock-cycle"}
    (f,) = rep.findings
    assert "fix.Deadlock._a" in f.site and "fix.Deadlock._b" in f.site


def test_non_reentrant_reacquire_is_a_self_cycle():
    rep = lint_src("""
import threading

class Reacquire:
    def __init__(self):
        self._lock = threading.Lock()

    def outer(self):
        with self._lock:
            self.inner()

    def inner(self):
        with self._lock:
            pass
""")
    assert rules(rep) == {"lock-cycle"}
    assert "non-reentrant" in rep.findings[0].message


def test_blocking_call_under_lock_is_flagged():
    rep = lint_src("""
import threading
import time

class Blocking:
    def __init__(self):
        self._lock = threading.Lock()

    def poke(self):
        with self._lock:
            time.sleep(0.1)
""")
    assert fids(rep) == ["lock-blocking:fix.Blocking.poke/sleep"]
    assert rep.findings[0].severity == "warn"


def test_transitive_blocking_through_a_callee_is_flagged():
    rep = lint_src("""
import threading
import time

class Indirect:
    def __init__(self):
        self._lock = threading.Lock()

    def _io(self):
        time.sleep(0.1)

    def poke(self):
        with self._lock:
            self._io()
""")
    assert "lock-blocking:fix.Indirect.poke/_io" in fids(rep)


def test_unguarded_module_global_write_is_flagged():
    rep = lint_src("""
import threading

_REGISTRY = {}

class Registrar:
    def start(self):
        threading.Thread(target=self._worker).start()

    def _worker(self):
        _REGISTRY["x"] = 1
""")
    assert fids(rep) == ["global-write:fix._REGISTRY"]


def test_thread_local_global_is_exempt():
    rep = lint_src("""
import threading

class _Ctx(threading.local):
    def __init__(self):
        self.depth = 0

_CTX = _Ctx()

class User:
    def start(self):
        threading.Thread(target=self._worker).start()

    def _worker(self):
        _CTX.depth = 1
""")
    assert fids(rep) == []


def test_finding_ids_are_stable_and_baseline_compatible():
    """Same fid scheme as offload_lint: <rule>:<site>, deterministic
    across scans — what the baseline/NEW/FIXED machinery keys on."""
    a, b = lint_src(RACY), lint_src(RACY)
    assert fids(a) == fids(b)
    f = a.findings[0]
    assert f.fid == f"{f.rule}:{f.site}"
    assert set(f.to_json()) >= {"rule", "severity", "site", "message"}


def test_fixture_coverage_spans_at_least_three_finding_kinds():
    """The acceptance floor: the synthetic fixtures above exercise >=3
    distinct finding kinds (we cover five)."""
    seen = set()
    for src in (RACY,
                "import threading\n\nclass M:\n"
                "    def __init__(self):\n"
                "        self._lock = threading.Lock()\n"
                "        self._x = []\n"
                "    def a(self):\n"
                "        with self._lock:\n"
                "            self._x.append(1)\n"
                "    def b(self):\n"
                "        self._x.pop()\n",
                "import threading\n\nclass D:\n"
                "    def __init__(self):\n"
                "        self._a = threading.Lock()\n"
                "        self._b = threading.Lock()\n"
                "    def ab(self):\n"
                "        with self._a:\n"
                "            with self._b:\n"
                "                pass\n"
                "    def ba(self):\n"
                "        with self._b:\n"
                "            with self._a:\n"
                "                pass\n",
                "import threading, time\n\nclass B:\n"
                "    def __init__(self):\n"
                "        self._lock = threading.Lock()\n"
                "    def poke(self):\n"
                "        with self._lock:\n"
                "            time.sleep(0.1)\n"):
        seen |= rules(lint_src(src))
    assert len(seen) >= 3


def test_repo_runtime_lints_clean():
    """THE certification: zero findings over src/repro with the shipped
    single-writer contracts and lock disciplines in place. Remove the
    ServingEngine marker or re-introduce flush-under-lock in CacheStore
    and this test (and the CI race-lint gate) fails."""
    rep = lint_runtime()
    assert rep.findings == [], [f.fid for f in rep.findings]
    # the executor's entry point is part of the scanned thread roots
    assert "repro.runtime.executor.FleetExecutor._step_engine" in rep.entries
    # and the engine's discipline is the documented single-writer contract
    disc = rep.disciplines["repro.runtime.serving.ServingEngine"]
    assert "single-writer" in disc


def test_entry_points_cover_the_issue_surfaces():
    names = [e for e, _ in DEFAULT_ENTRY_POINTS]
    assert "TraceRecorder._loop" in names
    assert "ThreadedExecutor.run" in names
    assert "FleetExecutor._step_engine" in names


# ---------------------------------------------------------------------------
# 2. Regression pins for the fixed findings
# ---------------------------------------------------------------------------


def test_eval_cache_insert_hook_runs_outside_the_lock():
    """The lint's lock-blocking finding on EvalCache.put: the persistence
    hook (disk I/O) must not run under the hot cache lock."""
    held = []

    class Probe(EvalCache):
        def _on_insert(self, key, cell, m):
            got = self._lock.acquire(blocking=False)
            if got:
                self._lock.release()
            held.append(not got)

    cache = Probe()
    cache.put("k", "cell", Measurement(time_s=1.0, energy_ws=2.0))
    assert held == [False]  # hook observed the lock released
    # and the hook still fires exactly once per key
    cache.put("k", "cell", Measurement(time_s=1.0, energy_ws=2.0))
    assert held == [False]


# ---------------------------------------------------------------------------
# 3. Lockstep concurrent fleet executor
# ---------------------------------------------------------------------------


def build_model(family):
    cfg = reduced(get_config(FAMILIES[family]))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def build_router(cfg, params, **kw):
    kw.setdefault("policy", "round_robin")
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("cache_path", None)
    return FleetRouter(cfg, params, [DESTINATIONS[n] for n in MIXED],
                       arch="llama3.2-3b", **kw)


def make_requests(n=8):
    out = []
    for i in range(n):
        if i % 2 == 0:
            out.append(Request(rid=i, prompt=[1 + (i + j) % 17
                                              for j in range(10)],
                               max_new_tokens=2))
        else:
            out.append(Request(rid=i, prompt=[1 + i % 7, 3],
                               max_new_tokens=6))
    return out


def outputs(done):
    return [(r.rid, tuple(r.output), r.finish_reason, r.served_by)
            for r in done]


def ledger(stats):
    """An EngineStats as a dict, less the fields read off the wall clock."""
    return {k: v for k, v in dataclasses.asdict(stats).items()
            if k not in CLOCK_FIELDS}


def ledgers(router):
    return {n: ledger(s) for n, s in router.per_engine_stats().items()}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_concurrent_run_token_and_ledger_identical(family):
    """FleetRouter.run(concurrent=True) vs the sequential drain: same
    tokens, same finish reasons, same per-engine and fleet ledgers —
    across attention, recurrent and hybrid decode states."""
    cfg, params = build_model(family)
    seq, conc = build_router(cfg, params), build_router(cfg, params)
    for r in make_requests():
        seq.submit(r)
    for r in make_requests():
        conc.submit(r)
    done_seq = seq.run()
    done_conc = conc.run(concurrent=True)
    assert outputs(done_conc) == outputs(done_seq)
    assert ledgers(conc) == ledgers(seq)
    assert ledger(conc.fleet_stats()) == ledger(seq.fleet_stats())


def test_single_worker_executor_matches_wide_pool():
    """max_workers=1 degenerates to the sequential schedule through the
    same code path — the bench's like-for-like baseline is honest."""
    cfg, params = build_model("dense")
    a, b = build_router(cfg, params), build_router(cfg, params)
    for r in make_requests(6):
        a.submit(r)
    for r in make_requests(6):
        b.submit(r)
    done_a = a.run(concurrent=True, max_workers=1)
    done_b = b.run(concurrent=True, max_workers=len(MIXED))
    assert outputs(done_a) == outputs(done_b)
    assert ledgers(a) == ledgers(b)


def test_device_dwell_never_touches_the_ledger():
    """dwell_s is wall-clock pacing only: the modeled ledger and the
    decoded tokens are byte-identical with and without it."""
    cfg, params = build_model("dense")
    a, b = build_router(cfg, params), build_router(cfg, params)
    for r in make_requests(4):
        a.submit(r)
    for r in make_requests(4):
        b.submit(r)
    done_a = a.run(concurrent=True)
    done_b = b.run(concurrent=True, dwell_s=0.001)
    assert outputs(done_a) == outputs(done_b)
    assert ledgers(a) == ledgers(b)


def test_executor_counts_lockstep_ticks():
    cfg, params = build_model("dense")
    router = build_router(cfg, params)
    for r in make_requests(4):
        router.submit(r)
    ex = FleetExecutor(router.bindings)
    done = ex.run()
    assert done and ex.ticks > 0
    # every engine stepped within the tick budget: ticks >= the busiest
    # engine's step count (each tick advances an engine at most one step)
    assert ex.ticks >= max(s.steps for s in
                           router.per_engine_stats().values())


def test_executor_rejects_empty_fleet_and_negative_dwell():
    with pytest.raises(ValueError):
        FleetExecutor([])
    cfg, params = build_model("dense")
    router = build_router(cfg, params)
    with pytest.raises(ValueError):
        FleetExecutor(router.bindings, dwell_s=-1.0)


# ---------------------------------------------------------------------------
# Interleaving fuzzer: permuted thread switch points, one invariant
# ---------------------------------------------------------------------------


def run_interleaved(scripts, seed):
    """Run one op list per thread, serializing whole ops into a single
    seed-deterministic global order: a cooperative scheduler picks which
    thread's NEXT op runs at every switch point (real threads, one op in
    flight at a time — the switch points are what the seed permutes).
    Returns the schedule as a list of thread indices."""
    turn = [threading.Event() for _ in scripts]
    ack = threading.Event()

    def worker(i):
        for op in scripts[i]:
            turn[i].wait()
            turn[i].clear()
            try:
                op()
            finally:
                ack.set()

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(scripts))]
    for t in threads:
        t.start()
    rng = random.Random(seed)
    remaining = {i: len(s) for i, s in enumerate(scripts) if s}
    order = []
    while remaining:
        i = rng.choice(sorted(remaining))
        order.append(i)
        ack.clear()
        turn[i].set()
        ack.wait()
        remaining[i] -= 1
        if not remaining[i]:
            del remaining[i]
    for t in threads:
        t.join()
    return order


@pytest.fixture(scope="module")
def fuzz_world():
    cfg = reduced(get_config("llama3.2-3b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    # one shared eval engine: the first schedule's plan() pays the GA, every
    # other schedule re-plans from cache (zero new measurements)
    shared = EvalEngine(executor=VectorizedExecutor(), cache=EvalCache())
    return cfg, params, shared


def run_schedule(fuzz_world, seed):
    """One fuzzed schedule: three threads interleaving submit / step /
    plan+scale_to ops over a fresh fleet, then a full concurrent drain.
    Returns (schedule, finished outputs, per-engine ledgers, fleet ledger).
    """
    cfg, params, shared = fuzz_world
    router = build_router(cfg, params, policy="energy", eval_engine=shared,
                          ga_config=GAConfig(population=6, generations=3,
                                             seed=0))
    for b in router.bindings:
        b.engine.stream_open()
    reqs = make_requests(6)
    finished = []
    clock = iter(float(i) for i in range(1, 100))

    def step_all():
        for b in router.bindings:
            out = b.engine.stream_step()
            if out:
                finished.extend(out)

    def try_migrate():
        """Deterministic mid-flight move: the first occupied slot in
        binding order hops to the first other engine with a free slot;
        refusals (no free slot anywhere, target not awake) are tolerated —
        they are deterministic too, so the schedule stays seed-stable."""
        from repro.runtime import migration
        for src_b in router.bindings:
            s = src_b.engine._stream
            if s is None:
                continue
            occ = [i for i, r in enumerate(s["slot_req"])
                   if r is not None]
            if not occ:
                continue
            for dst_b in router.bindings:
                if dst_b.name == src_b.name:
                    continue
                if not migration.free_slots(dst_b.engine):
                    continue
                try:
                    router.migrate_slot(src_b.name, occ[0], dst_b.name)
                    return
                except migration.MigrationError:
                    continue
            return

    scripts = [
        [lambda r=r: router.submit(r) for r in reqs],
        [step_all] * 5,
        [lambda: router.plan(),
         lambda: router.scale_to(1e9, now=next(clock)),
         lambda: router.plan()],
        [try_migrate] * 4,
    ]
    order = run_interleaved(scripts, seed)
    # drain: step until every queue and slot is empty, then close sessions
    for _ in range(200):
        if not any(b.engine.stream_busy() for b in router.bindings):
            break
        step_all()
    for b in router.bindings:
        b.engine.stream_close()
    return order, outputs(finished), ledgers(router), \
        ledger(router.fleet_stats())


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_fuzzer_fleet_ledger_invariant_under_every_schedule(fuzz_world,
                                                            seed):
    """Whatever the interleaving, the fleet ledger stays the exact
    field-wise sum of the engine ledgers and every submitted request is
    accounted for exactly once."""
    order, outs, per_engine, fleet = run_schedule(fuzz_world, seed)
    for field_name in fleet:
        total = sum(e[field_name] for e in per_engine.values())
        assert fleet[field_name] == pytest.approx(total), field_name
    assert len(outs) == 6  # all submitted requests finished exactly once
    assert len({rid for rid, *_ in outs}) == 6
    assert fleet["completed"] == 6
    # mid-flight moves never double-bill: admissions count requests (not
    # hops), every out-migration landed somewhere, and the token ledger
    # is exactly the traffic served
    assert fleet["admissions"] == 6
    assert fleet["migrations_in"] == fleet["migrations_out"]


def test_fuzzer_same_seed_same_schedule_same_ledger(fuzz_world):
    """Seed-determinism: same seed => same switch-point schedule => same
    outputs and byte-identical ledgers; a different seed permutes the
    schedule."""
    a = run_schedule(fuzz_world, seed=7)
    b = run_schedule(fuzz_world, seed=7)
    assert a == b
    c = run_schedule(fuzz_world, seed=8)
    assert c[0] != a[0]  # the schedule actually moved
