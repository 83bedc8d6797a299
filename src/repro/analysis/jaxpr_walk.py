"""Traverse traced jaxprs and derive per-region static cost estimates.

This is the jaxpr analogue of the paper's Clang loop parse: instead of
scanning C `for` statements for offloadable regions, we walk the
``ClosedJaxpr`` of a traced program — recursing into ``jit`` / ``scan`` /
``while`` / ``cond`` / ``remat`` sub-jaxprs — classify every equation
(matmul / elementwise / scatter / collective / callback / kernel), and
accumulate FLOPs, an HBM-byte proxy, and trip counts per region. The
result cross-checks `arithmetic_intensity.UnitCost` (config-derived
estimates) against what the *real* traced program contains, and feeds the
lint rules in :mod:`repro.analysis.offload_lint`.

Conventions (documented so the consistency test can state tolerances):

* FLOPs: ``dot_general`` counts ``2 * batch * M * N * K``; float
  elementwise ops count one FLOP per output element; reductions count one
  per input element; integer/bool ops count zero.
* Bytes: each equation charges ``sum(input aval bytes) + sum(output aval
  bytes)`` — an **unfused upper bound** (XLA fuses elementwise chains, so
  real HBM traffic is lower). Arithmetic intensity derived from these is
  therefore a lower bound.
* Trip counts: ``scan`` multiplies its body by ``params["length"]``;
  ``while`` bodies are counted once and recorded in
  ``RegionReport.dynamic_loops`` (statically unbounded); ``cond`` charges
  the most expensive branch.
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.extend import core as jcore

# ---------------------------------------------------------------------------
# Equation classification
# ---------------------------------------------------------------------------

MATMUL = "matmul"
ELEMENTWISE = "elementwise"
SCATTER = "scatter"
COLLECTIVE = "collective"
CALLBACK = "callback"
CONTROL = "control"
KERNEL = "kernel"
OTHER = "other"

KINDS = (MATMUL, ELEMENTWISE, SCATTER, COLLECTIVE, CALLBACK, CONTROL, KERNEL, OTHER)

_MATMUL_PRIMS = {"dot_general", "conv_general_dilated"}
_COLLECTIVE_PRIMS = {
    "psum", "pmax", "pmin", "all_gather", "all_to_all", "ppermute",
    "reduce_scatter", "psum_scatter", "pgather", "axis_index",
}
_CALLBACK_PRIMS = {
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "host_callback_call",
}
_CONTROL_PRIMS = {
    "jit", "closed_call", "core_call", "scan", "while", "cond",
    "remat2", "remat", "checkpoint", "custom_jvp_call", "custom_vjp_call",
    "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr", "custom_lin",
    "named_call", "shard_map",
}
_KERNEL_PRIMS = {"pallas_call"}
# Gather/scatter-family data movement (the decode KV write path lives here).
_SCATTER_PRIMS = {
    "gather", "dynamic_slice", "dynamic_update_slice", "sort", "argsort",
}
# Pure layout/metadata ops: no FLOPs, and XLA usually folds them into
# consumers, but we still charge bytes (conservative upper bound).
_LAYOUT_PRIMS = {
    "reshape", "broadcast_in_dim", "transpose", "squeeze", "slice",
    "concatenate", "convert_element_type", "bitcast_convert_type", "copy",
    "rev", "pad", "iota", "stop_gradient", "select_n",
}
# One-FLOP-per-element float ops that should count even though they are not
# arithmetic in the add/mul sense.
_REDUCE_PRIMS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "argmax", "argmin", "cumsum", "cumlogsumexp", "cummax",
    "cummin", "cumprod",
}


def classify_primitive(name: str) -> str:
    """Map a primitive name to one of the coarse KINDS buckets."""
    if name in _MATMUL_PRIMS:
        return MATMUL
    if name in _KERNEL_PRIMS:
        return KERNEL
    if name in _CALLBACK_PRIMS or name.endswith("_callback"):
        return CALLBACK
    if name in _COLLECTIVE_PRIMS:
        return COLLECTIVE
    if name in _CONTROL_PRIMS:
        return CONTROL
    if name in _SCATTER_PRIMS or "scatter" in name:
        return SCATTER
    if name in _LAYOUT_PRIMS or name in _REDUCE_PRIMS:
        return ELEMENTWISE
    # Default bucket: unary/binary math (add, mul, exp, tanh, integer_pow...)
    return ELEMENTWISE


def _aval_bytes(aval: Any) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        itemsize = 4
    n = 1
    for d in shape:
        n *= int(d)
    return n * itemsize


def _aval_size(aval: Any) -> int:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _is_float(aval: Any) -> bool:
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return False
    return np.issubdtype(np.dtype(dtype), np.floating)


def _dot_general_flops(eqn: Any) -> float:
    """2 * batch * M * N * K from dimension_numbers and operand shapes."""
    (lhs_c, rhs_c), (lhs_b, _rhs_b) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval
    rhs = eqn.invars[1].aval
    batch = 1
    for d in lhs_b:
        batch *= int(lhs.shape[d])
    contract = 1
    for d in lhs_c:
        contract *= int(lhs.shape[d])
    m = 1
    for i, d in enumerate(lhs.shape):
        if i not in lhs_c and i not in lhs_b:
            m *= int(d)
    n = 1
    rhs_b = set(_rhs_b)
    rhs_c = set(rhs_c)
    for i, d in enumerate(rhs.shape):
        if i not in rhs_c and i not in rhs_b:
            n *= int(d)
    return 2.0 * batch * m * n * contract


def _conv_flops(eqn: Any) -> float:
    """Rough conv cost: 2 * out_elems * (kernel elems per output channel)."""
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    kernel_elems = 1
    for d in rhs.shape:
        kernel_elems *= int(d)
    # Divide out the output-feature dimension so each output element pays
    # for one kernel stencil, not all of them.
    dnums = eqn.params.get("dimension_numbers")
    out_feat = int(rhs.shape[dnums.rhs_spec[0]]) if dnums is not None else 1
    return 2.0 * _aval_size(out) * kernel_elems / max(out_feat, 1)


def _eqn_flops(eqn: Any) -> float:
    name = eqn.primitive.name
    if name == "dot_general":
        return _dot_general_flops(eqn)
    if name == "conv_general_dilated":
        return _conv_flops(eqn)
    if name in _LAYOUT_PRIMS:
        return 0.0
    if name in _REDUCE_PRIMS:
        return float(sum(_aval_size(v.aval) for v in eqn.invars
                         if hasattr(v, "aval")))
    # Elementwise float math: one FLOP per output element.
    out_flops = 0.0
    for v in eqn.outvars:
        if _is_float(v.aval):
            out_flops += _aval_size(v.aval)
    return out_flops


def _eqn_bytes(eqn: Any) -> int:
    total = 0
    for v in eqn.invars:
        if hasattr(v, "aval"):
            total += _aval_bytes(v.aval)
    for v in eqn.outvars:
        total += _aval_bytes(v.aval)
    return total


# ---------------------------------------------------------------------------
# Region reports
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EqnStats:
    """Accumulated cost for one classification bucket."""

    count: float = 0.0
    flops: float = 0.0
    bytes: float = 0.0

    def add(self, flops: float, nbytes: float, mult: float) -> None:
        self.count += mult
        self.flops += flops * mult
        self.bytes += nbytes * mult


@dataclasses.dataclass
class RegionReport:
    """Static cost summary of one jaxpr region (and its sub-regions).

    ``flops`` / ``hbm_bytes`` are totals with trip counts applied;
    ``regions`` maps sub-region paths (e.g. ``"scan[x24]"``) to their own
    reports so callers can inspect loop bodies; ``callbacks`` and
    ``dynamic_loops`` record hazard sites for the lint layer.
    """

    path: str = ""
    trip_count: float = 1.0
    flops: float = 0.0
    hbm_bytes: float = 0.0
    eqn_count: float = 0.0
    by_kind: Dict[str, EqnStats] = dataclasses.field(
        default_factory=lambda: {k: EqnStats() for k in KINDS})
    primitive_counts: Counter = dataclasses.field(default_factory=Counter)
    callbacks: List[str] = dataclasses.field(default_factory=list)
    dynamic_loops: List[str] = dataclasses.field(default_factory=list)
    conversions: List[Tuple[str, str, str, int]] = dataclasses.field(
        default_factory=list)  # (path, from_dtype, to_dtype, out_bytes)
    regions: Dict[str, "RegionReport"] = dataclasses.field(default_factory=dict)

    @property
    def intensity(self) -> float:
        """FLOPs per HBM byte (lower bound — bytes are an unfused bound)."""
        return self.flops / self.hbm_bytes if self.hbm_bytes else 0.0

    def merge_child(self, child: "RegionReport", mult: float) -> None:
        self.flops += child.flops * mult
        self.hbm_bytes += child.hbm_bytes * mult
        self.eqn_count += child.eqn_count * mult
        for kind, stats in child.by_kind.items():
            mine = self.by_kind[kind]
            mine.count += stats.count * mult
            mine.flops += stats.flops * mult
            mine.bytes += stats.bytes * mult
        for name, n in child.primitive_counts.items():
            self.primitive_counts[name] += n
        self.callbacks.extend(child.callbacks)
        self.dynamic_loops.extend(child.dynamic_loops)
        self.conversions.extend(child.conversions)

    def summary(self) -> Dict[str, Any]:
        return {
            "path": self.path or "<root>",
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "intensity": self.intensity,
            "eqn_count": self.eqn_count,
            "by_kind": {k: dataclasses.asdict(v)
                        for k, v in self.by_kind.items() if v.count},
            "callbacks": list(self.callbacks),
            "dynamic_loops": list(self.dynamic_loops),
            "regions": {p: {"flops": r.flops, "hbm_bytes": r.hbm_bytes,
                            "trip_count": r.trip_count,
                            "intensity": r.intensity}
                        for p, r in self.regions.items()},
        }


def _as_closed(obj: Any) -> Optional[jcore.ClosedJaxpr]:
    if isinstance(obj, jcore.ClosedJaxpr):
        return obj
    if isinstance(obj, jcore.Jaxpr):
        return jcore.ClosedJaxpr(obj, [])
    return None


def _sub_jaxprs(eqn: Any) -> List[Tuple[str, jcore.ClosedJaxpr, float]]:
    """Yield (tag, closed_jaxpr, trip_count) for every sub-jaxpr of ``eqn``.

    ``scan`` multiplies by its static length; ``while`` bodies get trip
    count 1 (recorded separately as dynamic); ``cond`` is handled by the
    caller (max over branches); everything else recurses with trip 1.
    """
    name = eqn.primitive.name
    out: List[Tuple[str, jcore.ClosedJaxpr, float]] = []
    if name == "scan":
        closed = _as_closed(eqn.params["jaxpr"])
        if closed is not None:
            out.append(("scan[x%d]" % int(eqn.params["length"]), closed,
                        float(eqn.params["length"])))
        return out
    if name == "while":
        for key in ("cond_jaxpr", "body_jaxpr"):
            closed = _as_closed(eqn.params.get(key))
            if closed is not None:
                out.append(("while.%s" % key.split("_")[0], closed, 1.0))
        return out
    # Generic: recurse into any jaxpr-valued param (jit, remat2, custom_*).
    for key, val in sorted(eqn.params.items()):
        closed = _as_closed(val)
        if closed is not None:
            out.append(("%s.%s" % (name, key) if key != "jaxpr" else name,
                        closed, 1.0))
    return out


def walk_closed(closed: jcore.ClosedJaxpr, *, path: str = "",
                _depth: int = 0) -> RegionReport:
    """Walk one ClosedJaxpr recursively and return its RegionReport."""
    if _depth > 64:  # pathological nesting guard
        return RegionReport(path=path)
    report = RegionReport(path=path)
    for i, eqn in enumerate(closed.jaxpr.eqns):
        name = eqn.primitive.name
        kind = classify_primitive(name)
        report.primitive_counts[name] += 1
        here = "%s/%s:%d" % (path, name, i) if path else "%s:%d" % (name, i)

        if kind == CALLBACK:
            report.callbacks.append(here)
        if name == "while":
            report.dynamic_loops.append(here)
        if name == "convert_element_type":
            src = eqn.invars[0].aval if hasattr(eqn.invars[0], "aval") else None
            dst = eqn.outvars[0].aval
            if src is not None:
                report.conversions.append(
                    (here, str(np.dtype(src.dtype)), str(np.dtype(dst.dtype)),
                     _aval_bytes(dst)))

        if name == "cond":
            branches = [b for b in (
                _as_closed(b) for b in eqn.params.get("branches", ()))
                if b is not None]
            reports = [walk_closed(b, path=here + "/branch%d" % j,
                                   _depth=_depth + 1)
                       for j, b in enumerate(branches)]
            if reports:
                worst = max(reports, key=lambda r: (r.flops, r.hbm_bytes))
                worst.trip_count = 1.0
                report.regions[here] = worst
                report.merge_child(worst, 1.0)
            report.by_kind[CONTROL].add(0.0, 0.0, 1.0)
            report.eqn_count += 1
            continue

        subs = _sub_jaxprs(eqn)
        if subs:
            for tag, sub, trips in subs:
                sub_path = "%s/%s" % (here, tag) if tag != name else here
                child = walk_closed(sub, path=sub_path, _depth=_depth + 1)
                child.trip_count = trips
                report.regions[sub_path] = child
                report.merge_child(child, trips)
            report.by_kind[kind if kind != OTHER else CONTROL].add(0.0, 0.0, 1.0)
            report.eqn_count += 1
            continue

        flops = _eqn_flops(eqn)
        nbytes = _eqn_bytes(eqn)
        report.by_kind[kind].add(flops, nbytes, 1.0)
        report.flops += flops
        report.hbm_bytes += nbytes
        report.eqn_count += 1
    return report


def trace_and_walk(fn: Callable[..., Any], *args: Any,
                   **kwargs: Any) -> RegionReport:
    """``jax.make_jaxpr`` the callable on the given args and walk it.

    Args may be concrete arrays or ``jax.ShapeDtypeStruct`` pytrees —
    ``make_jaxpr`` traces abstractly either way, so no FLOP is executed.
    """
    closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    return walk_closed(closed)
