"""Mid-flight migration of admitted requests across destinations.

The fleet could only act on *queued* requests: once admitted, a request was
pinned to its slot until finish, even when its destination was
fleet-dominated or saturating — which capped what ``FleetRouter.rebalance``
could save under a traffic spike. This module unpins it:

* :func:`snapshot_slot` pulls ONE slot's share of a live engine's decode
  state to host numpy — per-slot KV rows, recurrent RWKV/Mamba/hybrid
  leaves, the per-slot position, the request, its cursor and its effective
  length cap — into a :class:`SlotSnapshot`. The pull is **mesh-agnostic**
  (``np.asarray`` gathers a sharded array), so the snapshot crosses
  destinations with different meshes/layouts, the way the checkpoint module
  restores a checkpoint onto a rescaled mesh.
* :func:`restore_slot` reshapes the snapshot onto the target's geometry —
  cache-length-bearing leaves (``models/transformer.decode_state_cache_keys``)
  are padded/truncated with the checkpointer's :func:`~repro.checkpoint.
  checkpointer.resize_axis` when ``max_len`` disagrees; truncation is safe
  because the per-row causal mask makes rows at index >= pos unreachable —
  and masked-writes it into a free slot via
  ``models/transformer.restore_decode_slot`` (the restore-side dual of
  ``reset_decode_slots``): the target's other slots keep decoding.
* :func:`migrate` is the transactional move (snapshot → restore → detach,
  in an order that leaves the source untouched when the target refuses).

Billing contract (no token billed twice): tokens decoded before the move
billed under the slot's epoch on the source; tokens after the move bill
under the **target's** placement epoch captured at restore. The move itself
bills as a separate transfer-cost ledger line
(``EngineStats.migration_ws`` = snapshot bytes x ``transfer_ws_per_mib``,
charged to the receiving engine). ``admissions`` is NOT re-counted — the
fleet ledger sees one admission per request regardless of how often it
moves; ``migrations_in``/``migrations_out`` record the events.

Serving equivalence: the snapshot carries the slot's **cap** (``max_len``
of the admitting engine, chained through re-migration), so a request moved
to a roomier destination still length-caps exactly where its
never-migrated baseline would. ``tests/test_migration.py`` pins the
stronger property: output tokens and finish reasons are byte-identical to
the never-migrated baseline across all five model families, with
migrations forced at step 0, mid-decode and one-token-before-eos.

Deterministic refusals (:class:`MigrationError`), never silent corruption:
a sliding-window ring whose length differs between engines (ring phase is
length-dependent), a target cache too short for the rows the request can
still address, a non-awake target without a clock to wake-charge it, or a
wake whose latency has not elapsed. The caller retries after the wake.

Thread-safety: single-writer, inherited from ``ServingEngine``'s contract —
migration mutates both engines, so the caller must own both. The lockstep
``FleetExecutor`` runs migrations on the coordinator thread at tick
barriers (its ``on_tick`` hook), where no worker holds any engine; the race
lint (``analysis/concurrency.py``) certifies that schedule.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import numpy as np

from repro.checkpoint.checkpointer import _digest, resize_axis, tree_paths
from repro.models import transformer as T
from repro.runtime.serving import Request, ServingEngine

# Default transfer-cost rate: Watt·s charged per MiB of snapshot moved
# between destinations (interconnect + host staging). Deliberately modeled,
# like every other rate on the ledger; benchmarks may override it.
DEFAULT_TRANSFER_WS_PER_MIB = 0.5


class MigrationError(RuntimeError):
    """A migration that cannot proceed — deterministic refusal, raised
    before either engine's state is modified."""


@dataclass
class SlotSnapshot:
    """Host-side, mesh-agnostic image of one live slot.

    ``leaves`` mirrors the decode-state structure minus ``pos`` (numpy,
    batch axis dropped); ``manifest``/``digest`` follow the checkpoint
    manifest convention (flat escaped leaf paths -> shape/dtype, sha256
    digest) so integrity is checked at restore; ``cap`` is the effective
    length cap of the ADMITTING engine, preserved across re-migration.
    """

    request: Request
    cursor: int
    pos: int
    cap: int
    source: str  # engine name the snapshot was taken from
    source_max_len: int
    leaves: dict = field(repr=False)
    manifest: dict = field(repr=False)
    digest: str = ""
    nbytes: int = 0


def _leaf_manifest(leaves: dict) -> tuple[dict, int]:
    manifest: dict[str, Any] = {}
    nbytes = 0
    for path, arr in tree_paths(leaves):
        manifest[path] = {"shape": list(arr.shape), "dtype": str(arr.dtype)}
        nbytes += arr.nbytes
    return manifest, nbytes


def _session(engine: ServingEngine) -> dict:
    if engine._stream is None:
        raise MigrationError(
            f"engine {engine.name!r} has no open session to migrate through")
    return engine._stream


def free_slots(engine: ServingEngine) -> list[int]:
    """Slot indices of the open session a snapshot could restore into
    ([] when no session is open)."""
    if engine._stream is None:
        return []
    return [i for i, r in enumerate(engine._stream["slot_req"]) if r is None]


def _cache_len(tree: Any, axis: int) -> int:
    return jax.tree.leaves(tree)[0].shape[axis]


def snapshot_slot(engine: ServingEngine, slot: int) -> SlotSnapshot:
    """Pure host-side snapshot of occupied ``slot`` in ``engine``'s open
    session. Read-only on the engine: pair with :func:`detach_slot` (or use
    :func:`migrate`) to actually move the request."""
    s = _session(engine)
    if engine.power_state != "awake":
        # unreachable through the state machine (sleep/floor require
        # idleness), but state surgery deserves a belt
        raise MigrationError(
            f"source {engine.name!r} is {engine.power_state}; only an "
            f"awake engine's decode state is coherent to snapshot")
    reqs, cursors, caps = s["slot_req"], s["cursors"], s["cap"]
    if slot < 0 or slot >= len(reqs) or reqs[slot] is None:
        raise MigrationError(
            f"slot {slot} of {engine.name!r} holds no admitted request")
    leaves, pos = T.extract_decode_slot(engine.cfg, s["state"], slot)
    manifest, nbytes = _leaf_manifest(leaves)
    return SlotSnapshot(
        request=reqs[slot], cursor=cursors[slot], pos=pos, cap=caps[slot],
        source=engine.name, source_max_len=engine.max_len,
        leaves=leaves, manifest=manifest, digest=_digest(manifest),
        nbytes=nbytes)


def detach_slot(engine: ServingEngine, slot: int) -> Request:
    """Release ``slot`` on the source after its snapshot restored elsewhere:
    the slot frees (it re-admits from the queue next step), the
    request leaves ``engine.active`` and ``migrations_out`` ticks. No token
    is un-billed — everything decoded here was genuinely served here."""
    s = _session(engine)
    req = s["slot_req"][slot]
    if req is None:
        raise MigrationError(f"slot {slot} of {engine.name!r} is free")
    s["slot_req"][slot] = None
    engine.active.remove(req)
    engine.stats.migrations_out += 1
    return req


def _check_geometry(engine: ServingEngine, snap: SlotSnapshot,
                    state: dict) -> None:
    """Deterministic refusals, all raised before any state is written."""
    cfg = engine.cfg
    req = snap.request
    if _digest(snap.manifest) != snap.digest:
        raise MigrationError("snapshot manifest digest mismatch")
    cache_keys = T.decode_state_cache_keys(cfg)
    axis = T.decode_state_cache_axis(cfg)  # (layers, batch, len, ...)
    for key in cache_keys:
        if key not in snap.leaves:
            raise MigrationError(
                f"snapshot is missing state key {key!r} — source and "
                f"target disagree on the model family")
        src_len = _cache_len(snap.leaves[key], axis - 1)  # batch dropped
        dst_len = _cache_len(state[key], axis)
        if cfg.sliding_window and src_len != dst_len:
            # a ring buffer's occupancy layout is a function of its length
            # (slot = pos % length): resizing would scramble the ring
            raise MigrationError(
                f"sliding-window ring length differs ({src_len} vs "
                f"{dst_len}); refusing to rephase the ring")
        # rows the request can still address: its carried cap bounds every
        # future position, and prompt+max_new_tokens bounds the request's
        # own footprint — whichever is tighter
        needed = min(snap.cap, len(req.prompt) + req.max_new_tokens)
        if dst_len < needed:
            raise MigrationError(
                f"target cache ({dst_len} rows) cannot hold the "
                f"{needed} rows request {req.rid} can still address")


def restore_slot(engine: ServingEngine, snap: SlotSnapshot, *,
                 now: Optional[float] = None,
                 transfer_ws_per_mib: float = DEFAULT_TRANSFER_WS_PER_MIB
                 ) -> int:
    """Reshape ``snap`` onto ``engine``'s geometry and masked-write it into
    a free slot of the open session; returns the slot index.

    Power guard (the sleep→migrate→drain path): a non-awake target without
    a clock refuses outright; with a clock the wake is initiated first
    (wake-charged — ``stats.wakes`` ticks and the driver bills the waking
    interval's full static draw), and the restore still refuses until the
    wake latency has elapsed, so the caller retries on a later tick.
    Either way the refusal is deterministic and the snapshot unconsumed.

    Post-migration tokens bill under the TARGET's placement epoch captured
    here; the transfer itself bills ``nbytes x transfer_ws_per_mib`` to the
    target's ``migration_ws`` ledger line.
    """
    if engine.power_state != "awake":
        if now is None:
            raise MigrationError(
                f"target {engine.name!r} is {engine.power_state} and no "
                f"clock was given to wake-charge it")
        engine.wake(now)
        if not engine.check_awake(now):
            raise MigrationError(
                f"target {engine.name!r} is waking until "
                f"t={engine._awake_at:.3f}; retry after the wake latency")
    s = _session(engine)
    free = free_slots(engine)
    if not free:
        raise MigrationError(f"target {engine.name!r} has no free slot")
    slot = free[0]
    _check_geometry(engine, snap, s["state"])

    leaves = dict(snap.leaves)
    axis = T.decode_state_cache_axis(engine.cfg)
    for key in T.decode_state_cache_keys(engine.cfg):
        dst_len = _cache_len(s["state"][key], axis)
        leaves[key] = jax.tree.map(
            lambda v: resize_axis(np.asarray(v), axis - 1, dst_len),
            leaves[key])
    s["state"] = T.restore_decode_slot(engine.cfg, s["state"], slot,
                                       leaves, snap.pos)
    req = snap.request
    s["slot_req"][slot] = req
    s["cursors"][slot] = snap.cursor
    s["epoch"][slot] = dict(engine.placements)
    s["cap"][slot] = snap.cap
    req.served_by = engine.name
    billed = engine.placements.get("decode") or engine.placements.get(
        "prefill")
    req.destination = billed.destination if billed else None
    engine.active.append(req)
    engine.stats.migrations_in += 1
    engine.stats.migration_ws += snap.nbytes / (1 << 20) * transfer_ws_per_mib
    return slot


def migrate(source: ServingEngine, target: ServingEngine, slot: int, *,
            now: Optional[float] = None,
            transfer_ws_per_mib: float = DEFAULT_TRANSFER_WS_PER_MIB) -> int:
    """The transactional move: snapshot ``slot`` off ``source``, restore it
    into ``target``, and only then detach the source slot — a refusal at
    restore leaves the source exactly as it was. Returns the target slot."""
    if source is target:
        raise MigrationError("source and target are the same engine")
    snap = snapshot_slot(source, slot)
    dst = restore_slot(target, snap, now=now,
                       transfer_ws_per_mib=transfer_ws_per_mib)
    detach_slot(source, slot)
    return dst
