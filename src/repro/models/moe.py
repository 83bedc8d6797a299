"""Mixture-of-Experts: top-k router + capacity-based dispatch, and the
dropless grouped layer over the experts one chip holds (``held_experts``).

Dispatch is *row-local* (per batch row): each sequence dispatches its own
tokens into (E, C) expert slots via an argsort over that row only, so the
token axis stays batch-sharded — no global resort across data shards.

Expert weights use expert-TP: every expert's FFN dim is sharded over the
"model" axis and stored FSDP over "data" (with 8 experts on a 16-wide mesh
axis, expert-dim sharding is impossible; see DESIGN.md §5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.parallel.sharding import PDef, shard_act


def moe_defs(cfg: ArchConfig) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    return {
        "router": PDef((d, e), ("fsdp", "act_experts"), dtype=jnp.float32),
        "w_gate": PDef((e, d, f), ("experts", "fsdp", "expert_ffn")),
        "w_up": PDef((e, d, f), ("experts", "fsdp", "expert_ffn")),
        "w_down": PDef((e, f, d), ("experts", "expert_ffn", "fsdp")),
    }


def capacity(cfg: ArchConfig, tokens_per_row: int) -> int:
    c = int(cfg.experts_per_token * tokens_per_row * cfg.capacity_factor
            / cfg.num_experts)
    return max(c, cfg.experts_per_token)


def route(cfg: ArchConfig, p: dict, x: jax.Array):
    """x: (B, S, D) -> (weights (B,S,k), expert_ids (B,S,k), aux_loss)."""
    logits = (x.astype(jnp.float32) @ p["router"])  # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, cfg.experts_per_token)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss
    e = cfg.num_experts
    density = jnp.mean(jax.nn.one_hot(ids[..., 0], e), axis=(0, 1))
    density_proxy = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(density * density_proxy)
    return weights.astype(x.dtype), ids, aux


def moe_apply(cfg: ArchConfig, p: dict, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,D), aux_loss)."""
    b, s, d = x.shape
    k, e = cfg.experts_per_token, cfg.num_experts
    cap = capacity(cfg, s)

    weights, ids, aux = route(cfg, p, x)

    # ---- row-local dispatch index build ------------------------------------
    flat_ids = ids.reshape(b, s * k)  # (B, N) expert id per (token, choice)
    flat_w = weights.reshape(b, s * k)
    # slot of each (token,choice) within its expert = #earlier entries w/ same id
    oh = jax.nn.one_hot(flat_ids, e, dtype=jnp.int32)  # (B, N, E)
    csum = jnp.cumsum(oh, axis=1)  # inclusive prefix count per expert
    slot = jnp.take_along_axis(csum, flat_ids[..., None], axis=-1)[..., 0] - 1
    keep = slot < cap

    # destination in flattened (E*C) space; dropped tokens go to a trash slot
    dest = jnp.where(keep, flat_ids * cap + slot, e * cap)
    token_idx = jnp.arange(s * k)[None, :] // k  # source token per choice

    # gather source tokens into (E*C) slots
    src_for_slot = jnp.full((b, e * cap + 1), s, jnp.int32)  # s = pad token
    src_for_slot = src_for_slot.at[jnp.arange(b)[:, None], dest].set(
        jnp.where(keep, token_idx, s))
    src_for_slot = src_for_slot[:, :-1]  # drop trash slot
    x_pad = jnp.concatenate([x, jnp.zeros((b, 1, d), x.dtype)], axis=1)
    dispatched = jnp.take_along_axis(
        x_pad, src_for_slot[..., None], axis=1)  # (B, E*C, D)
    dispatched = dispatched.reshape(b, e, cap, d)
    dispatched = shard_act(dispatched, ("batch", "act_experts", "expert_cap", "embed"))

    # ---- expert FFN (expert-TP over "expert_ffn") ---------------------------
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", dispatched, p["w_gate"]))
    h = h * jnp.einsum("becd,edf->becf", dispatched, p["w_up"])
    h = shard_act(h, ("batch", "act_experts", "expert_cap", "act_ffn"))
    out_slots = jnp.einsum("becf,efd->becd", h, p["w_down"])  # (B,E,C,D)
    out_slots = out_slots.reshape(b, e * cap, d)

    # ---- combine: weighted scatter-add back to tokens -----------------------
    flat_dest = jnp.where(keep, dest, e * cap)  # (B, N)
    slot_out = jnp.concatenate(
        [out_slots, jnp.zeros((b, 1, d), out_slots.dtype)], axis=1)
    per_choice = jnp.take_along_axis(
        slot_out, flat_dest[..., None], axis=1)  # (B, N, D)
    per_choice = per_choice * flat_w[..., None].astype(per_choice.dtype)
    combined = per_choice.reshape(b, s, k, d).sum(axis=2)
    return shard_act(combined, ("batch", "seq", "embed")), aux


# ---------------------------------------------------------------------------
# Held experts: dropless, grouped (moe_hybrid)
# ---------------------------------------------------------------------------


def held_moe_defs(cfg: ArchConfig) -> dict:
    """The router over all ``num_experts``, and the weights of the
    ``held_experts`` this chip holds: ``w_in`` gives [gate | up], ``w_out``
    the down projection."""
    d, f, e, eh = cfg.d_model, cfg.d_ff, cfg.num_experts, cfg.held_experts
    return {
        "router": PDef((d, e), ("fsdp", "act_experts"), dtype=jnp.float32),
        "w_in": PDef((eh, d, 2 * f), ("experts", "fsdp", "expert_ffn")),
        "w_out": PDef((eh, f, d), ("experts", "expert_ffn", "fsdp")),
    }


def shared_expert_defs(cfg: ArchConfig) -> dict:
    d, f = cfg.d_model, cfg.shared_expert_ff
    return {"w_in": PDef((d, 2 * f), ("fsdp", "ffn")),
            "w_out": PDef((f, d), ("ffn", "fsdp"))}


def _glu(h: jax.Array, f: int) -> jax.Array:
    """[a | b] -> silu(a) * b."""
    return jax.nn.silu(h[..., :f]) * h[..., f:]


def shared_expert(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    """The expert every token uses: x (..., D) -> (..., D)."""
    return _glu(x @ p["w_in"], cfg.shared_expert_ff) @ p["w_out"]


def held_experts(cfg: ArchConfig, p: dict, x: jax.Array, layer=0,
                 first: int = 0) -> tuple[jax.Array, jax.Array]:
    """Layer ``layer``'s part of the routed experts' output that this chip
    holds, for every token.

    ``p`` holds every layer's expert layer, stacked: ``router`` (layers, D,
    num_experts), ``w_in`` (layers, held, D, 2F), ``w_out`` (layers, held,
    F, D). x: (..., D). The router scores all ``num_experts`` and each
    token keeps its top ``experts_per_token``, weighted by a softmax over
    the chosen logits (``route``). The chip holds experts ``first`` to
    ``first + held_experts - 1`` (its block in expert parallelism) and adds
    only their part; the other experts' part is their chips'. Dropless:
    every choice that lands on a held expert is computed. The choices are
    sorted by expert, so that each held expert's rows are contiguous, and
    one grouped matmul (``lax.ragged_dot``) per projection runs over them;
    the choices of experts held elsewhere sort last and fall outside every
    group. The grouped matmul takes the experts of all layers as its groups
    and this layer's rows in this layer's groups (the others are empty): so
    it reads the layer's weights in place, where a slice of the stack
    would be copied for it.

    Returns ``(y (..., D), hits (...))``: ``hits`` counts each token's
    choices that landed on a held expert.
    """
    lead, d = x.shape[:-1], x.shape[-1]
    k, eh, f = cfg.experts_per_token, cfg.held_experts, cfg.d_ff
    layers = p["w_in"].shape[0]
    xt = x.reshape(-1, d)
    router = jax.lax.dynamic_index_in_dim(p["router"], layer, 0, False)
    weights, ids, _ = route(cfg, {"router": router}, xt[None])
    local = ids[0] - first  # (n, k)
    held = (local >= 0) & (local < eh)
    group = jnp.where(held, local, eh).reshape(-1)
    order = jnp.argsort(group)  # stable: a token's choices keep their order
    sizes = jax.lax.dynamic_update_slice(
        jnp.zeros((layers * eh,), jnp.int32),
        jnp.sum(jax.nn.one_hot(group, eh, dtype=jnp.int32), axis=0),
        (layer * eh,))
    rows = xt[order // k]  # (n * k, D), grouped by held expert
    h = jax.lax.ragged_dot(rows, p["w_in"].reshape((layers * eh,)
                                                   + p["w_in"].shape[2:]),
                           sizes)
    y = jax.lax.ragged_dot(_glu(h, f), p["w_out"].reshape(
        (layers * eh,) + p["w_out"].shape[2:]), sizes)
    y = y[jnp.argsort(order)].reshape(-1, k, d)  # back to (token, choice)
    gate = jnp.where(held, weights[0], 0).astype(jnp.float32)
    out = jnp.einsum("nk,nkd->nd", gate, y.astype(jnp.float32))
    return (out.astype(x.dtype).reshape(lead + (d,)),
            jnp.sum(held, axis=-1, dtype=jnp.int32).reshape(lead))
