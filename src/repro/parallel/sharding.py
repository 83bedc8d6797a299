"""Logical-axis sharding engine.

Model code annotates parameters and activations with *logical* axis names
("batch", "heads", "ffn", ...). A ``ShardingRules`` table maps logical names
to mesh axes. The offload genome mutates this table (sharding-axis genes), so
the paper's GA can search sharding layouts without touching model code.

When no mesh is active (CPU smoke tests), all annotations are no-ops.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field, replace
from typing import Any, Optional, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, tuple[str, ...]]

# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------

# Default logical->mesh mapping for the production mesh ("data", "model") or
# ("pod", "data", "model"). "batch"-like axes compose pod+data; "model" axis
# carries TP/SP. Entries may be overridden per-arch and per-genome.
DEFAULT_RULES: dict[str, Axis] = {
    # activations
    "batch": ("pod", "data"),
    "seq": None,              # residual-stream seq; "model" = Megatron-SP
    "seq_inner": None,        # seq INSIDE blocks (TP on heads/ffn wins there)
    "embed": None,
    "act_heads": "model",
    "act_kv_heads": None,
    "act_ffn": "model",
    "act_vocab": "model",
    "kv_seq": "model",        # decode: KV cache sequence-sharded (flash-decode)
    "kv_batch": ("pod", "data"),  # cache batch dim (decoupled from act batch)
    "act_experts": None,
    "expert_cap": None,
    # parameters  (fsdp = ZeRO-3 axis, tensor = TP axis); the pod axis joins
    # FSDP so optimizer state keeps shrinking as pods are added
    "fsdp": ("pod", "data"),
    "heads": "model",
    "kv_heads": None,         # kv heads usually < model axis; replicate
    "ffn": "model",
    "vocab": "model",
    "experts": None,          # 8 experts vs 16-wide axis: expert-TP instead (DESIGN.md)
    "expert_ffn": "model",
    "ssm_heads": "model",
    "ssm_inner": "model",
    "rwkv_heads": "model",
    "layers": None,
    "stage": None,            # pipeline axis when PP enabled ("pod")
    "unsharded": None,
}


@dataclass(frozen=True)
class ShardingRules:
    mapping: dict[str, Axis] = field(default_factory=lambda: dict(DEFAULT_RULES))
    # light=True keeps only *essential* activation constraints (residual
    # stream, loss region, caches) and lets GSPMD propagate the rest from
    # parameter shardings — each dropped constraint removes an AG/RS pair
    # (fwd + transposed bwd) per layer. A §Perf hillclimb knob.
    light: bool = False

    def with_overrides(self, **overrides: Axis) -> "ShardingRules":
        m = dict(self.mapping)
        light = bool(overrides.pop("light", self.light))
        m.update(overrides)
        return ShardingRules(m, light)

    def axis(self, logical: Optional[str]) -> Axis:
        if logical is None:
            return None
        if logical not in self.mapping:
            raise KeyError(f"unknown logical axis {logical!r}")
        return self.mapping[logical]

    def spec(self, logical_axes: tuple[Optional[str], ...]) -> P:
        return P(*(self.axis(a) for a in logical_axes))


# ---------------------------------------------------------------------------
# Active-context plumbing (mesh + rules), threading-safe for pytest-xdist.
# ---------------------------------------------------------------------------


class _Ctx(threading.local):
    def __init__(self) -> None:
        self.mesh: Optional[Mesh] = None
        self.rules: Optional[ShardingRules] = None


_CTX = _Ctx()


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], rules: Optional[ShardingRules] = None):
    """Activate (mesh, rules) for model-internal sharding annotations."""
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, rules
    try:
        if mesh is not None:
            with jax.set_mesh(mesh):
                yield
        else:
            yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def current_rules() -> Optional[ShardingRules]:
    return _CTX.rules


def _mesh_axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _prune_spec_for(shape: tuple[int, ...], spec: P, mesh: Mesh) -> P:
    """Drop mesh axes whose size does not divide the dim (replicate instead)
    and axes already claimed by an earlier dim (first use wins).

    This keeps one rules table valid across archs (e.g. 24 heads on a 16-wide
    model axis falls back to replication rather than erroring)."""
    sizes = _mesh_axis_sizes(mesh)
    used: set[str] = set()
    out = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        axes = tuple(a for a in axes if a in sizes and a not in used)
        total = 1
        kept: list[str] = []
        for a in axes:
            if dim % (total * sizes[a]) == 0:
                kept.append(a)
                total *= sizes[a]
        used.update(kept)
        out.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    return P(*out)


def shard_act(x: jax.Array, logical_axes: tuple[Optional[str], ...],
              essential: bool = False) -> jax.Array:
    """Annotate an activation with logical axes (no-op without a mesh)."""
    mesh, rules = _CTX.mesh, _CTX.rules
    if mesh is None or rules is None:
        return x
    if rules.light and not essential:
        return x
    spec = _prune_spec_for(x.shape, rules.spec(logical_axes), mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(
    mesh: Mesh, rules: ShardingRules, logical_axes: tuple[Optional[str], ...],
    shape: Optional[tuple[int, ...]] = None,
) -> NamedSharding:
    spec = rules.spec(logical_axes)
    if shape is not None:
        spec = _prune_spec_for(shape, spec, mesh)
    return NamedSharding(mesh, spec)


# ---------------------------------------------------------------------------
# Parameter definitions -> init / sharding specs  (single source of truth)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PDef:
    """Declarative parameter: shape + logical axes + initializer."""

    shape: tuple[int, ...]
    axes: tuple[Optional[str], ...]
    init: str = "normal"  # normal | zeros | ones | scaled
    scale: float = 0.02
    dtype: Any = None  # None => model dtype; norms default float32

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def stack_defs(defs: Any, num: int) -> Any:
    """Add a leading stacked-layers axis to every PDef in a tree."""

    def _stack(d: PDef) -> PDef:
        return PDef((num,) + d.shape, ("layers",) + d.axes, d.init, d.scale, d.dtype)

    return jax.tree.map(_stack, defs, is_leaf=lambda x: isinstance(x, PDef))


def init_from_defs(key: jax.Array, defs: Any, dtype: Any) -> Any:
    """Materialize parameters from defs (traceable; eval_shape-safe)."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=lambda x: isinstance(x, PDef))
    keys = jax.random.split(key, len(leaves))

    def _one(k, d: PDef):
        dt = d.dtype or dtype
        if d.init == "zeros":
            return jax.numpy.zeros(d.shape, dt)
        if d.init == "ones":
            return jax.numpy.ones(d.shape, dt)
        fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
        std = d.scale if d.init == "normal" else 1.0 / (fan_in ** 0.5)
        return (jax.random.normal(k, d.shape, jax.numpy.float32) * std).astype(dt)

    return jax.tree.unflatten(treedef, [_one(k, d) for k, d in zip(keys, leaves)])


def specs_from_defs(defs: Any, rules: ShardingRules, mesh: Optional[Mesh] = None) -> Any:
    def _one(d: PDef):
        spec = rules.spec(d.axes)
        if mesh is not None:
            spec = _prune_spec_for(d.shape, spec, mesh)
        return spec

    return jax.tree.map(_one, defs, is_leaf=lambda x: isinstance(x, PDef))


def shardings_from_defs(defs: Any, rules: ShardingRules, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda d: NamedSharding(mesh, _prune_spec_for(d.shape, rules.spec(d.axes), mesh)),
        defs,
        is_leaf=lambda x: isinstance(x, PDef),
    )
