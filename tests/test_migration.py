"""Mid-flight migration of admitted requests (``runtime/migration.py``).

The headline is the differential serving-equivalence harness: every
scenario runs twice — once with forced migrations at adversarial points
(right after admission, mid-decode, one-token-before-eos) and once without
— asserting byte-identical output tokens and finish reasons across all
five architecture families (dense KV, recurrent SSM, hybrid, MoE with a
sliding-window ring, encoder-decoder with cross-attention state).

Around it: snapshot→reshape→restore roundtrip identity and the
no-token-billed-twice fleet-ledger conservation as property tests
(``_hypothesis_compat``), the sleep→migrate→drain power-guard regression,
the deterministic geometry refusals (sliding-window ring mismatch, target
cache too short, digest tamper, no free slot — all transactional: the
source is untouched), transfer-cost billing, cap-carry semantics, and the
router's live rebalance escalation.
"""
import random

import jax
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.checkpoint.checkpointer import resize_axis
from repro.configs import DESTINATIONS, get_config, reduced
from repro import models as M
from repro.models import transformer as T
from repro.runtime import (
    FleetRouter, MigrationError, Request, ServingEngine, migrate,
)
from repro.runtime import migration
from repro.runtime.serving import EngineStats

FAMILIES = {
    "dense": "llama3.2-3b",
    "ssm": "rwkv6-1.6b",
    "hybrid": "zamba2-7b",
    "moe": "mixtral-8x7b",
    "encdec": "seamless-m4t-medium",
    "moe_hybrid": "granite-4.0-h-small",
}
MIXED = ("pod2_v5e", "mxu_dense", "hbm_lp")

_MODELS: dict = {}
_GOLDEN: dict = {}


def _model(arch):
    if arch not in _MODELS:
        cfg = reduced(get_config(arch))
        _MODELS[arch] = (cfg, M.init_params(cfg, jax.random.PRNGKey(0)))
    return _MODELS[arch]


def _requests(eos=None, rid0_prompt=(2, 5, 9)):
    """Fixed request set; rid 1 length-caps (its budget exceeds max_len=32),
    so every differential run also exercises the cap-carry path."""
    return [
        Request(rid=0, prompt=list(rid0_prompt), max_new_tokens=6,
                eos_id=eos),
        Request(rid=1, prompt=[3, 7], max_new_tokens=40, eos_id=eos),
        Request(rid=2, prompt=[4, 1, 6, 8], max_new_tokens=5, eos_id=eos),
        Request(rid=3, prompt=[5, 2], max_new_tokens=4, eos_id=eos),
    ]


def _record(rs):
    return {r.rid: (tuple(r.output), r.finish_reason) for r in rs}


def _golden(arch, eos=None, rid0_prompt=(2, 5, 9)):
    """Never-migrated baseline: one engine serves the whole set."""
    key = (arch, eos, tuple(rid0_prompt))
    if key not in _GOLDEN:
        cfg, params = _model(arch)
        eng = ServingEngine(cfg, params, slots=2, max_len=32)
        rs = _requests(eos, rid0_prompt)
        for r in rs:
            eng.submit(r)
        eng.run()
        _GOLDEN[key] = _record(rs)
    return _GOLDEN[key]


def _migrated_run(arch, eos, trigger, rid0_prompt=(2, 5, 9),
                  dst_max_len=48):
    """The same request set, but slot 0's occupant (rid 0) is force-migrated
    to a second engine with a roomier cache the moment ``trigger`` fires."""
    cfg, params = _model(arch)
    src = ServingEngine(cfg, params, slots=2, max_len=32, name="src")
    dst = ServingEngine(cfg, params, slots=2, max_len=dst_max_len,
                        name="dst")
    rs = _requests(eos, rid0_prompt)
    for r in rs:
        src.submit(r)
    src.stream_open()
    dst.stream_open()
    migrated = False
    for _ in range(400):
        if (not migrated and src._stream["slot_req"][0] is rs[0]
                and trigger(rs)):
            migrate(src, dst, 0)
            migrated = True
        f = src.stream_step()
        g = dst.stream_step()
        if f is None and g is None:
            break
    src.stream_close()
    dst.stream_close()
    assert migrated, "the forced migration never fired"
    return _record(rs), src, dst


_EOS_POINTS: dict = {}
_RID0_PROMPTS = ((2, 5, 9), (1, 4, 8), (3, 6, 2), (7, 2, 11), (9, 3, 5))


def _eos_point(arch):
    """A (rid0 prompt, position, token) to force eos on: the first probe
    prompt whose natural output has a late token not seen earlier, so the
    eos-forced run stops exactly one step after the migration point."""
    if arch in _EOS_POINTS:
        return _EOS_POINTS[arch]
    cfg, params = _model(arch)
    for prompt in _RID0_PROMPTS:
        eng = ServingEngine(cfg, params, slots=2, max_len=32)
        probe = Request(rid=0, prompt=list(prompt), max_new_tokens=6)
        eng.submit(probe)
        eng.run()
        nat = list(probe.output)
        for i in range(1, len(nat)):
            if nat[i] not in nat[:i]:
                _EOS_POINTS[arch] = (prompt, i, nat[i])
                return _EOS_POINTS[arch]
    pytest.skip("no probe prompt yields a unique late token to force eos")


# ---------------------------------------------------------------------------
# Differential golden harness: migrated == never-migrated, all families
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("point", ["admission", "mid_decode", "before_eos"])
def test_migrated_traffic_token_identical(family, point):
    """Serving equivalence at adversarial migration points: output tokens
    AND finish reasons (incl. rid 1's length_cap, proving the carried cap
    fires on the roomier destination exactly where the baseline's did)."""
    arch = FAMILIES[family]
    rid0_prompt = (2, 5, 9)
    if point == "before_eos":
        rid0_prompt, i, eos = _eos_point(arch)
        trigger = (lambda rs, i=i: len(rs[0].output) == i)
    elif point == "admission":
        eos = None
        trigger = (lambda rs: True)  # fires the step after slot 0 fills
    else:
        eos = None
        trigger = (lambda rs: len(rs[0].output) >= 2)
    golden = _golden(arch, eos, rid0_prompt)
    got, src, dst = _migrated_run(arch, eos, trigger, rid0_prompt)
    assert got == golden
    if eos is None:  # with a forced eos rid 1 may stop before the cap
        assert golden[1][1] == "length_cap"  # the cap-carry witness
    assert src.stats.migrations_out == 1
    assert dst.stats.migrations_in == 1
    # no token billed twice: the two engines' combined token count is
    # exactly the traffic's (prompt tokens once, generated tokens once)
    prompts = sum(len(r.prompt) for r in _requests(eos, rid0_prompt))
    assert src.stats.total_tokens + dst.stats.total_tokens \
        == prompts + sum(len(out) - 1 for out, _ in golden.values())
    # the move billed as a transfer-cost line on the target, nowhere else
    assert dst.stats.migration_ws > 0.0
    assert src.stats.migration_ws == 0.0


# ---------------------------------------------------------------------------
# Property: snapshot -> reshape -> restore roundtrip identity
# ---------------------------------------------------------------------------


@given(st.sampled_from(["llama3.2-3b", "rwkv6-1.6b"]),
       st.sampled_from([24, 32, 48]),
       st.integers(0, 4))
@settings(max_examples=8, deadline=None)
def test_snapshot_restore_roundtrip_identity(arch, dst_len, steps):
    """For random (family, destination geometry, decode progress): restoring
    a snapshot and re-snapshotting it returns the identical request state —
    metadata exactly, state leaves exactly over the commonly-addressable
    cache rows (padding beyond the source length is zeros by construction)."""
    cfg, params = _model(arch)
    src = ServingEngine(cfg, params, slots=2, max_len=32, name="src")
    dst = ServingEngine(cfg, params, slots=2, max_len=dst_len, name="dst")
    # 6 new tokens: a chunk-fed dense request holds its slot for 6 steps
    rs = [Request(rid=i, prompt=[2 + i, 5, 9], max_new_tokens=6)
          for i in range(2)]
    for r in rs:
        src.submit(r)
    src.stream_open()
    dst.stream_open()
    for _ in range(steps + 1):  # >=1 step so slot 0 is occupied
        src.stream_step()
    snap = src.snapshot_slot(0)
    slot = dst.restore_slot(snap)
    resnap = dst.snapshot_slot(slot)
    assert resnap.request is snap.request
    assert resnap.cursor == snap.cursor
    assert resnap.pos == snap.pos
    assert resnap.cap == snap.cap == 32  # the admitting engine's max_len
    cache_keys = T.decode_state_cache_keys(cfg)
    for key in snap.leaves:
        a = jax.tree.leaves(snap.leaves[key])
        b = jax.tree.leaves(resnap.leaves[key])
        for la, lb in zip(a, b):
            if key in cache_keys:
                n = min(la.shape[1], lb.shape[1])
                la, lb = la[:, :n], lb[:, :n]
            np.testing.assert_array_equal(np.asarray(la, np.float32),
                                          np.asarray(lb, np.float32))
    src.stream_close()
    dst.stream_close()


# ---------------------------------------------------------------------------
# Property: fleet ledger conservation — no token billed twice
# ---------------------------------------------------------------------------


def _try_random_migration(router, rng):
    """One seeded migration attempt between random fleet members; refusals
    are deterministic and tolerated. Returns 1 on a completed move."""
    occupied = []
    for b in router.bindings:
        s = b.engine._stream
        if s is None:
            continue
        occupied.extend((b, i) for i, r in enumerate(s["slot_req"])
                        if r is not None)
    if not occupied:
        return 0
    src_b, slot = occupied[rng.randrange(len(occupied))]
    targets = [b for b in router.bindings
               if b is not src_b and migration.free_slots(b.engine)]
    if not targets:
        return 0
    dst_b = targets[rng.randrange(len(targets))]
    try:
        router.migrate_slot(src_b.name, slot, dst_b.name)
    except MigrationError:
        return 0
    return 1


@given(st.integers(0, 7))
@settings(max_examples=6, deadline=None)
def test_fleet_ledger_conserved_under_arbitrary_migrations(seed):
    """Whatever sequence of migrations a seed produces, the fleet ledger is
    the exact field-wise sum of the engine ledgers, every request is
    admitted once and completed once, and the fleet-wide token counts equal
    the traffic's — i.e. no token is billed twice across any move chain."""
    cfg, params = _model("llama3.2-3b")
    router = FleetRouter(cfg, params, [DESTINATIONS[n] for n in MIXED],
                         arch="llama3.2-3b", policy="round_robin",
                         slots=2, max_len=32, cache_path=None)
    rs = [Request(rid=i, prompt=[2 + i % 5, 7], max_new_tokens=3 + i % 4)
          for i in range(6)]
    for r in rs:
        router.submit(r)
    for b in router.bindings:
        b.engine.stream_open()
    rng = random.Random(seed)
    moves = 0
    for _ in range(200):
        if not any(b.engine.stream_busy() for b in router.bindings):
            break
        for b in router.bindings:
            b.engine.stream_step()
        if rng.random() < 0.6:
            moves += _try_random_migration(router, rng)
    for b in router.bindings:
        b.engine.stream_close()
    fleet = router.fleet_stats()
    per = router.per_engine_stats()
    for fname in EngineStats.__dataclass_fields__:
        total = sum(getattr(s, fname) for s in per.values())
        assert getattr(fleet, fname) == pytest.approx(total), fname
    assert all(r.done for r in rs)
    assert fleet.completed == len(rs)
    assert fleet.admissions == len(rs)  # a move is not a re-admission
    assert fleet.prefill_tokens == sum(len(r.prompt) for r in rs)
    assert fleet.decode_tokens == sum(len(r.output) - 1 for r in rs)
    assert fleet.migrations_in == fleet.migrations_out == moves
    if moves:
        assert fleet.migration_ws > 0.0
        # every completed move is reflected in the routing table
        for r in rs:
            assert router.assignments[r.rid] == r.served_by


# ---------------------------------------------------------------------------
# Power guard: the sleep -> migrate -> drain regression
# ---------------------------------------------------------------------------


def test_sleep_migrate_drain_wake_charges_then_refuses_deterministically():
    """A migration into a non-awake engine must wake-charge or refuse
    deterministically: no clock -> refusal with nothing consumed; with a
    clock -> the wake is initiated (charged once) and the restore still
    refuses until the latency elapses; afterwards the move lands and the
    drain is token-identical to the never-migrated baseline."""
    golden = _golden("llama3.2-3b")
    cfg, params = _model("llama3.2-3b")
    src = ServingEngine(cfg, params, slots=2, max_len=32, name="src")
    dst = ServingEngine(cfg, params, slots=2, max_len=32, name="dst")
    dst.set_power(idle_watts=10.0, wake_s=2.0)
    rs = _requests()
    for r in rs:
        src.submit(r)
    src.stream_open()
    dst.stream_open()
    dst.sleep()
    for _ in range(4):
        src.stream_step()

    # 1. no clock: refuse outright, both engines untouched
    with pytest.raises(MigrationError):
        migrate(src, dst, 0)
    assert src._stream["slot_req"][0] is rs[0]
    assert dst.power_state == "asleep"
    assert dst.stats.wakes == 0 and dst.stats.migrations_in == 0

    # 2. clocked: wake-charge fires, restore still refuses until awake
    with pytest.raises(MigrationError):
        migrate(src, dst, 0, now=10.0)
    assert dst.power_state == "waking" and dst.stats.wakes == 1
    assert src._stream["slot_req"][0] is rs[0]  # snapshot unconsumed
    with pytest.raises(MigrationError):
        migrate(src, dst, 0, now=11.0)  # latency not yet elapsed
    assert dst.stats.wakes == 1  # the retry does not re-charge the wake

    # 3. after the wake latency: the move lands, the drain is equivalent
    migrate(src, dst, 0, now=12.0)
    assert dst.power_state == "awake"
    assert dst.stats.migrations_in == 1 and src.stats.migrations_out == 1
    for _ in range(400):
        f = src.stream_step()
        g = dst.stream_step()
        if f is None and g is None:
            break
    src.stream_close()
    dst.stream_close()
    assert _record(rs) == golden


# ---------------------------------------------------------------------------
# Deterministic refusals — all transactional (source left intact)
# ---------------------------------------------------------------------------


def _src_with_work(arch="llama3.2-3b", max_len=32):
    cfg, params = _model(arch)
    src = ServingEngine(cfg, params, slots=2, max_len=max_len, name="src")
    rs = _requests()
    for r in rs:
        src.submit(r)
    src.stream_open()
    for _ in range(3):
        src.stream_step()
    return src, rs


def test_migrate_to_self_refused():
    src, rs = _src_with_work()
    with pytest.raises(MigrationError):
        migrate(src, src, 0)
    assert src._stream["slot_req"][0] is rs[0]


def test_snapshot_of_free_or_out_of_range_slot_refused():
    cfg, params = _model("llama3.2-3b")
    eng = ServingEngine(cfg, params, slots=2, max_len=32)
    eng.stream_open()
    with pytest.raises(MigrationError):
        eng.snapshot_slot(0)  # open session, nothing admitted
    with pytest.raises(MigrationError):
        eng.snapshot_slot(5)  # out of range
    eng.stream_close()
    with pytest.raises(MigrationError):
        eng.snapshot_slot(0)  # no session at all


def test_restore_without_free_slot_refused_and_source_drains_identical():
    golden = _golden("llama3.2-3b")
    src, rs = _src_with_work()
    cfg, params = _model("llama3.2-3b")
    dst = ServingEngine(cfg, params, slots=1, max_len=32, name="dst")
    blocker = Request(rid=99, prompt=[6, 6], max_new_tokens=30)
    dst.submit(blocker)
    dst.stream_open()
    dst.stream_step()  # the only slot fills
    with pytest.raises(MigrationError):
        migrate(src, dst, 0)
    dst.stream_close()
    # transactional: the refused source serves on, tokens unchanged
    while src.stream_step() is not None:
        pass
    src.stream_close()
    assert _record(rs) == golden


def test_target_cache_too_short_refused():
    src, rs = _src_with_work()
    cfg, params = _model("llama3.2-3b")
    dst = ServingEngine(cfg, params, slots=2, max_len=8, name="dst")
    dst.stream_open()
    # rid 0 can still address min(cap=32, 3+6)=9 rows > the 8 offered
    with pytest.raises(MigrationError, match="cannot hold"):
        migrate(src, dst, 0)
    assert src._stream["slot_req"][0] is rs[0]


def test_sliding_window_ring_length_mismatch_refused():
    """MoE's sliding-window KV ring: ring phase is a function of ring
    length, so differing ring lengths refuse instead of rephasing."""
    cfg, params = _model("mixtral-8x7b")
    assert cfg.sliding_window  # reduced() keeps a 32-token window
    src = ServingEngine(cfg, params, slots=2, max_len=16, name="src")
    dst = ServingEngine(cfg, params, slots=2, max_len=24, name="dst")
    r = Request(rid=0, prompt=[2, 5], max_new_tokens=3)
    src.submit(r)
    src.stream_open()
    dst.stream_open()
    src.stream_step()
    with pytest.raises(MigrationError, match="sliding-window"):
        migrate(src, dst, 0)
    # equal ring lengths migrate fine (the moe differential test covers
    # the equal-ring 32-vs-48 geometry end to end)
    assert src._stream["slot_req"][0] is r


def test_tampered_snapshot_digest_refused():
    src, _ = _src_with_work()
    cfg, params = _model("llama3.2-3b")
    dst = ServingEngine(cfg, params, slots=2, max_len=32, name="dst")
    dst.stream_open()
    snap = src.snapshot_slot(0)
    path = next(iter(snap.manifest))
    snap.manifest[path] = dict(snap.manifest[path], dtype="tampered")
    with pytest.raises(MigrationError, match="digest"):
        dst.restore_slot(snap)
    assert dst.stats.migrations_in == 0


# ---------------------------------------------------------------------------
# Billing and cap semantics
# ---------------------------------------------------------------------------


def test_transfer_cost_bills_by_bytes_on_the_target():
    src, _ = _src_with_work()
    cfg, params = _model("llama3.2-3b")
    dst = ServingEngine(cfg, params, slots=2, max_len=32, name="dst")
    dst.stream_open()
    snap = src.snapshot_slot(0)
    assert snap.nbytes > 0
    dst.restore_slot(snap, transfer_ws_per_mib=2.0)
    migration.detach_slot(src, 0)
    expect = snap.nbytes / (1 << 20) * 2.0
    assert dst.stats.migration_ws == pytest.approx(expect)
    assert src.stats.migration_ws == 0.0
    # the transfer line joins the full bill but never the serving energy
    assert dst.stats.total_ws == pytest.approx(
        dst.stats.energy_ws + dst.stats.idle_ws + dst.stats.migration_ws)


def test_cap_carries_through_to_a_roomier_destination():
    """A request admitted under max_len=16 keeps capping at 16 after moving
    to a 48-row engine: serving equivalence for the length_cap reason."""
    cfg, params = _model("llama3.2-3b")
    base = ServingEngine(cfg, params, slots=1, max_len=16)
    b = Request(rid=0, prompt=[2, 5], max_new_tokens=64)
    base.submit(b)
    base.run()
    assert b.finish_reason == "length_cap"

    src = ServingEngine(cfg, params, slots=1, max_len=16, name="src")
    dst = ServingEngine(cfg, params, slots=1, max_len=48, name="dst")
    r = Request(rid=0, prompt=[2, 5], max_new_tokens=64)
    src.submit(r)
    src.stream_open()
    dst.stream_open()
    for _ in range(5):
        src.stream_step()
    migrate(src, dst, 0)
    for _ in range(200):
        f = src.stream_step()
        g = dst.stream_step()
        if f is None and g is None:
            break
    src.stream_close()
    dst.stream_close()
    assert (tuple(r.output), r.finish_reason) \
        == (tuple(b.output), b.finish_reason)


def test_resize_axis_roundtrip_edges():
    """The checkpoint-module leaf reshaper migration leans on: identity,
    zero-padding growth, and prefix-preserving truncation."""
    arr = np.arange(24, dtype=np.float32).reshape(2, 4, 3)
    assert resize_axis(arr, 1, 4) is arr
    grown = resize_axis(arr, 1, 6)
    assert grown.shape == (2, 6, 3)
    np.testing.assert_array_equal(grown[:, :4], arr)
    np.testing.assert_array_equal(grown[:, 4:], 0.0)
    np.testing.assert_array_equal(resize_axis(grown, 1, 4), arr)


# ---------------------------------------------------------------------------
# Router escalation: live load-shedding off a saturated engine
# ---------------------------------------------------------------------------


def _shed_router(cfg, params, **kw):
    kw.setdefault("policy", "round_robin")
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("cache_path", None)
    kw.setdefault("saturation_factor", 0.5)
    return FleetRouter(cfg, params, [DESTINATIONS[n] for n in MIXED],
                       arch="llama3.2-3b", **kw)


def test_rebalance_live_sheds_admitted_slots_off_saturated_engine():
    cfg, params = _model("llama3.2-3b")
    router = _shed_router(cfg, params)
    hot = router.bindings[0]
    rs = [Request(rid=i, prompt=[2 + i % 5, 7], max_new_tokens=4)
          for i in range(8)]
    for r in rs:
        hot.engine.submit(r)  # pile everything onto one engine
    for b in router.bindings:
        b.engine.stream_open()
    hot.engine.stream_step()  # admits 2; 6 stay queued > 0.5 * 2 slots
    assert router.saturated() == [hot.name]
    moved = router.rebalance(live=True)
    # both queued requests AND both admitted slots left the hot engine
    assert moved[hot.name] == 8
    assert hot.engine.stats.migrations_out == 2
    assert sum(b.engine.stats.migrations_in
               for b in router.bindings) == 2
    for _ in range(200):
        if not any(b.engine.stream_busy() for b in router.bindings):
            break
        for b in router.bindings:
            b.engine.stream_step()
    for b in router.bindings:
        b.engine.stream_close()
    fleet = router.fleet_stats()
    assert all(r.done for r in rs)
    assert fleet.completed == len(rs)
    assert fleet.decode_tokens == sum(len(r.output) - 1 for r in rs)


def test_rebalance_without_live_keeps_admitted_slots_pinned():
    cfg, params = _model("llama3.2-3b")
    router = _shed_router(cfg, params)
    hot = router.bindings[0]
    rs = [Request(rid=i, prompt=[2 + i % 5, 7], max_new_tokens=4)
          for i in range(8)]
    for r in rs:
        hot.engine.submit(r)
    for b in router.bindings:
        b.engine.stream_open()
    hot.engine.stream_step()
    moved = router.rebalance(live=False, include_saturated=True)
    assert moved[hot.name] == 6  # the queue moved, the 2 slots stayed
    assert hot.engine.stats.migrations_out == 0
    assert hot.engine._stream["slot_req"][0] is rs[0]
    for b in router.bindings:
        b.engine.stream_close()


def test_concurrent_run_with_rebalance_hook_completes_and_conserves():
    """``FleetRouter.run(concurrent=True, rebalance_every=k)``: migrations
    happen on the coordinator thread at tick barriers and the drained fleet
    still accounts for every token exactly once."""
    cfg, params = _model("llama3.2-3b")
    router = _shed_router(cfg, params)
    hot = router.bindings[0]
    rs = [Request(rid=i, prompt=[2 + i % 5, 7], max_new_tokens=4)
          for i in range(10)]
    for r in rs:
        hot.engine.submit(r)
    done = router.run(concurrent=True, rebalance_every=2)
    fleet = router.fleet_stats()
    assert len(done) == len(rs) and all(r.done for r in rs)
    assert fleet.completed == len(rs)
    assert fleet.admissions == len(rs)
    assert fleet.prefill_tokens == sum(len(r.prompt) for r in rs)
    assert fleet.decode_tokens == sum(len(r.output) - 1 for r in rs)
    assert fleet.migrations_in == fleet.migrations_out
    assert fleet.migrations_in > 0  # the hook genuinely shed live slots
