"""Model assembly: decoder-only / MoE / hybrid(Mamba2+shared-attn) /
moe_hybrid (Mamba2 and attention layers, each followed by held experts) /
RWKV / encoder-decoder / VLM — one scan-over-layers LM with per-family
blocks.

Public surface:
    model_defs(cfg)                  -> PDef tree (single source of truth)
    init_params(cfg, key)            -> params pytree (eval_shape-safe)
    forward_loss(cfg, params, batch) -> (loss, metrics)         [train]
    forward(cfg, params, batch)      -> logits                  [prefill]
    init_decode_state(cfg, batch, cache_len) -> state
    decode_step(cfg, params, state, tokens)  -> (logits, state) [serve]
    prefill_chunk(cfg, params, state, slot, tokens, start, n_valid)
                                             -> state           [serve]
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.parallel.sharding import (
    PDef, current_mesh, current_rules, init_from_defs, shard_act,
    shardings_from_defs, specs_from_defs, stack_defs,
)

# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------


def _dense_layer_defs(cfg: ArchConfig) -> dict:
    d = cfg.d_model
    defs = {
        "ln1": L.rms_norm_defs(d),
        "attn": attn.attention_defs(cfg),
        "ln2": L.rms_norm_defs(d),
    }
    if cfg.num_experts:
        defs["moe"] = moe_mod.moe_defs(cfg)
    else:
        defs["mlp"] = L.mlp_defs(cfg)
    return defs


def _rwkv_layer_defs(cfg: ArchConfig) -> dict:
    d = cfg.d_model
    return {
        "ln1": L.rms_norm_defs(d),
        "tm": rwkv_mod.rwkv_defs(cfg),
        "ln2": L.rms_norm_defs(d),
    }


def _mamba_layer_defs(cfg: ArchConfig) -> dict:
    return {"ln": L.rms_norm_defs(cfg.d_model), "mamba": ssm_mod.mamba_defs(cfg)}


def _encoder_layer_defs(cfg: ArchConfig) -> dict:
    return {
        "ln1": L.rms_norm_defs(cfg.d_model),
        "attn": attn.attention_defs(cfg),
        "ln2": L.rms_norm_defs(cfg.d_model),
        "mlp": L.mlp_defs(cfg),
    }


def _decoder_xattn_layer_defs(cfg: ArchConfig) -> dict:
    defs = _encoder_layer_defs(cfg)
    defs["ln_x"] = L.rms_norm_defs(cfg.d_model)
    defs["xattn"] = attn.attention_defs(cfg, cross=True)
    return defs


def _moe_hybrid_layer_defs(cfg: ArchConfig) -> dict:
    """What every moe_hybrid layer has besides its mixer: the norm before
    the mixer, the norm before the experts, the held experts and the shared
    expert."""
    return {
        "ln_in": L.rms_norm_defs(cfg.d_model),
        "ln_post": L.rms_norm_defs(cfg.d_model),
        "moe": moe_mod.held_moe_defs(cfg),
        "shared": moe_mod.shared_expert_defs(cfg),
    }


def hybrid_groups(cfg: ArchConfig) -> tuple[int, int]:
    """(num_groups, tail) — zamba: shared attn block heads each group."""
    g = cfg.attn_every or cfg.num_layers
    return cfg.num_layers // g, cfg.num_layers % g


def model_defs(cfg: ArchConfig) -> dict:
    defs: dict[str, Any] = {"embedding": L.embedding_defs(cfg)}
    defs["final_norm"] = L.rms_norm_defs(cfg.d_model)

    if cfg.family == "ssm":
        defs["layers"] = stack_defs(_rwkv_layer_defs(cfg), cfg.num_layers)
    elif cfg.family == "hybrid":
        ng, tail = hybrid_groups(cfg)
        per_group = stack_defs(_mamba_layer_defs(cfg), cfg.attn_every)
        defs["groups"] = stack_defs(per_group, ng)
        if tail:
            defs["tail"] = stack_defs(_mamba_layer_defs(cfg), tail)
        defs["shared_attn"] = {
            "ln": L.rms_norm_defs(cfg.d_model),
            "attn": attn.attention_defs(cfg),
        }
    elif cfg.family == "moe_hybrid":
        kinds = cfg.layer_types
        defs["mamba"] = stack_defs(ssm_mod.mamba_defs(cfg), kinds.count("mamba"))
        defs["attn"] = stack_defs(attn.attention_defs(cfg),
                                  kinds.count("attention"))
        defs["layers"] = stack_defs(_moe_hybrid_layer_defs(cfg), cfg.num_layers)
    elif cfg.is_encdec:
        defs["encoder"] = stack_defs(_encoder_layer_defs(cfg), cfg.encoder_layers)
        defs["enc_norm"] = L.rms_norm_defs(cfg.d_model)
        defs["layers"] = stack_defs(_decoder_xattn_layer_defs(cfg), cfg.num_layers)
    else:  # dense / moe / vlm
        defs["layers"] = stack_defs(_dense_layer_defs(cfg), cfg.num_layers)

    if cfg.frontend == "vision":
        defs["frontend"] = {
            "proj": PDef((cfg.d_model, cfg.d_model), ("fsdp", "embed")),
            "ln": L.rms_norm_defs(cfg.d_model),
        }
    elif cfg.frontend == "audio":
        defs["frontend"] = {
            "proj": PDef((cfg.d_model, cfg.d_model), ("fsdp", "embed")),
        }
    return defs


def init_params(cfg: ArchConfig, key: jax.Array):
    return init_from_defs(key, model_defs(cfg), jnp.dtype(cfg.dtype))


def param_specs(cfg: ArchConfig, rules, mesh=None):
    return specs_from_defs(model_defs(cfg), rules, mesh)


# ---------------------------------------------------------------------------
# Blocks (single layer)
# ---------------------------------------------------------------------------


def _residual(x: jax.Array) -> jax.Array:
    """Pin the residual stream at block boundaries — this is what the remat
    stack saves, so its sharding (batch × seq-SP) bounds train memory."""
    return shard_act(x, ("batch", "seq", "embed"), essential=True)


def _dense_block(cfg: ArchConfig, p: dict, x: jax.Array, *, mode: str):
    h = attn.attention(cfg, p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps),
                       causal=True, window=cfg.sliding_window, mode=mode)
    x = _residual(x + h)
    xn = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.num_experts:
        h2, aux = moe_mod.moe_apply(cfg, p["moe"], xn)
    else:
        h2, aux = L.mlp_apply(cfg, p["mlp"], xn), jnp.zeros((), jnp.float32)
    return _residual(x + h2), aux


def _rwkv_block(cfg: ArchConfig, p: dict, x: jax.Array, *, mode: str):
    x = _residual(x + rwkv_mod.rwkv_time_mix(
        cfg, p["tm"], L.rms_norm(x, p["ln1"], cfg.norm_eps), mode=mode))
    x = _residual(x + rwkv_mod.rwkv_channel_mix(
        cfg, p["tm"], L.rms_norm(x, p["ln2"], cfg.norm_eps)))
    return x, jnp.zeros((), jnp.float32)


def _hybrid_group_block(cfg: ArchConfig, p_group: dict, shared: dict,
                        x: jax.Array, *, mode: str):
    h = attn.attention(cfg, shared["attn"],
                       L.rms_norm(x, shared["ln"], cfg.norm_eps),
                       causal=True, mode=mode)
    x = _residual(x + h)
    for i in range(cfg.attn_every):
        p_i = jax.tree.map(lambda v: v[i], p_group)
        x = _residual(x + ssm_mod.mamba_apply(
            cfg, p_i["mamba"], L.rms_norm(x, p_i["ln"], cfg.norm_eps),
            mode=mode))
    return x, jnp.zeros((), jnp.float32)


def _mamba_block(cfg: ArchConfig, p: dict, x: jax.Array, *, mode: str):
    return _residual(x + ssm_mod.mamba_apply(
        cfg, p["mamba"], L.rms_norm(x, p["ln"], cfg.norm_eps), mode=mode))


def _moe_hybrid_layers(cfg: ArchConfig, p):
    """The layers of period ``p`` (an index, traced in a scan), in order:
    ``(kind, layer, i)`` with ``kind`` "M" or "A" and ``i`` the layer's
    index in its mixer's stack."""
    pattern = cfg.layer_pattern
    seen = {"M": 0, "A": 0}
    for j, kind in enumerate(pattern):
        yield (kind, p * len(pattern) + j,
               p * pattern.count(kind) + seen[kind])
        seen[kind] += 1


def _moe_hybrid_experts(cfg: ArchConfig, params: dict, x: jax.Array, l):
    """Layer ``l``'s expert layer: the held experts' part plus the shared
    expert. The held experts' weights stay in their stack over all layers,
    which the grouped matmul reads in place (``moe.held_experts``).
    Returns ``(y, hits)`` as ``moe.held_experts``."""
    with jax.named_scope("moe"):
        y, hits = moe_mod.held_experts(cfg, params["layers"]["moe"], x,
                                       layer=l)
        shared = _layer(params["layers"]["shared"], l)
        return y + moe_mod.shared_expert(cfg, shared, x), hits


def _moe_hybrid_period(cfg: ArchConfig, params: dict, x: jax.Array, p, *,
                       mode: str) -> jax.Array:
    """Period ``p`` of moe_hybrid layers over full sequences; each layer's
    weights are read from their stacks at its index."""
    rm, eps = cfg.residual_multiplier, cfg.norm_eps
    norms = params["layers"]
    for kind, l, i in _moe_hybrid_layers(cfg, p):
        h = L.rms_norm(x, _layer(norms["ln_in"], l), eps)
        if kind == "M":
            with jax.named_scope("mamba"):
                y = ssm_mod.mamba_apply(cfg, _layer(params["mamba"], i), h,
                                        mode=mode)
        else:
            with jax.named_scope("attn"):
                y = attn.attention(cfg, _layer(params["attn"], i), h,
                                   causal=True, rope=cfg.rope, mode=mode)
        x = _residual(x + rm * y)
        h = L.rms_norm(x, _layer(norms["ln_post"], l), eps)
        y, _ = _moe_hybrid_experts(cfg, params, h, l)
        x = _residual(x + rm * y)
    return x


def _encoder_block(cfg: ArchConfig, p: dict, x: jax.Array, *, mode: str):
    x = _residual(x + attn.attention(
        cfg, p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps),
        causal=False, mode=mode))
    return _residual(
        x + L.mlp_apply(cfg, p["mlp"], L.rms_norm(x, p["ln2"], cfg.norm_eps)))


def _decoder_xattn_block(cfg: ArchConfig, p: dict, x: jax.Array,
                         memory: jax.Array, *, mode: str):
    x = _residual(x + attn.attention(
        cfg, p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps),
        causal=True, mode=mode))
    x = _residual(x + attn.attention(
        cfg, p["xattn"], L.rms_norm(x, p["ln_x"], cfg.norm_eps),
        kv_x=memory, causal=False, rope=False, mode=mode))
    x = _residual(
        x + L.mlp_apply(cfg, p["mlp"], L.rms_norm(x, p["ln2"], cfg.norm_eps)))
    return x, jnp.zeros((), jnp.float32)


def _constrain_layer_params(p_l, defs: dict):
    """Pin one scanned layer slice to its parameter sharding INSIDE the scan
    body. The transpose of with_sharding_constraint constrains the grad
    cotangent too, so backward reduce-scatters each layer's weight grads
    per iteration instead of carrying a data-unsharded stacked grad buffer
    through the whole backward scan (12 GiB/device for grok otherwise)."""
    rules, mesh = current_rules(), current_mesh()
    if rules is None or mesh is None:
        return p_l
    sh = shardings_from_defs(defs, rules, mesh)
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(x, s), p_l, sh)


def _maybe_remat(fn, remat: str):
    if remat == "full":
        return jax.checkpoint(fn)
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


# ---------------------------------------------------------------------------
# Input embedding (incl. modality frontends)
# ---------------------------------------------------------------------------


def _embed_inputs(cfg: ArchConfig, params: dict, batch: dict) -> jax.Array:
    x = L.embed_tokens(cfg, params["embedding"], batch["tokens"])
    if cfg.frontend == "vision" and "patches" in batch:
        fp = params["frontend"]
        patches = batch["patches"].astype(x.dtype) @ fp["proj"]
        patches = L.rms_norm(patches, fp["ln"], cfg.norm_eps)
        x = jnp.concatenate([patches, x], axis=1)
        x = shard_act(x, ("batch", "seq", "embed"))
    return x


def _encode(cfg: ArchConfig, params: dict, batch: dict, *, mode: str,
            remat: str = "none") -> jax.Array:
    """Audio/enc-dec: run the encoder over stub frame embeddings."""
    frames = batch["frames"].astype(jnp.dtype(cfg.dtype))
    x = frames @ params["frontend"]["proj"]
    x = shard_act(x, ("batch", "seq", "embed"))
    edefs = _encoder_layer_defs(cfg)
    block = _maybe_remat(
        lambda p_l, x: _encoder_block(cfg, p_l, x, mode=mode), remat)

    def body(carry, p_l):
        return block(_constrain_layer_params(p_l, edefs), carry), None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return L.rms_norm(x, params["enc_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def forward(cfg: ArchConfig, params: dict, batch: dict, *, mode: str = "exec",
            remat: Optional[str] = None) -> tuple[jax.Array, jax.Array]:
    """Returns (logits, moe_aux_loss)."""
    remat = cfg.remat if remat is None else remat
    x = _embed_inputs(cfg, params, batch)

    if cfg.family == "ssm":
        ldefs = _rwkv_layer_defs(cfg)
        block = _maybe_remat(
            lambda p_l, x: _rwkv_block(cfg, p_l, x, mode=mode), remat)

        def body(carry, p_l):
            x, aux = carry
            x, a = block(_constrain_layer_params(p_l, ldefs), x)
            return (x, aux + a), None

        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["layers"])
    elif cfg.family == "hybrid":
        gdefs = stack_defs(_mamba_layer_defs(cfg), cfg.attn_every)
        block = _maybe_remat(
            lambda p_g, shared, x: _hybrid_group_block(cfg, p_g, shared, x,
                                                       mode=mode), remat)

        def body(carry, p_g):
            x, aux = carry
            x, a = block(_constrain_layer_params(p_g, gdefs),
                         params["shared_attn"], x)
            return (x, aux + a), None

        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["groups"])
        if "tail" in params:
            tdefs = _mamba_layer_defs(cfg)
            tail_block = _maybe_remat(
                lambda p_l, x: _mamba_block(cfg, p_l, x, mode=mode), remat)

            def tbody(carry, p_l):
                return tail_block(_constrain_layer_params(p_l, tdefs),
                                  carry), None

            x, _ = jax.lax.scan(tbody, x, params["tail"])
    elif cfg.family == "moe_hybrid":
        block = _maybe_remat(
            lambda p, x: _moe_hybrid_period(cfg, params, x, p, mode=mode),
            remat)

        def body(carry, p):
            return block(p, carry), None

        periods = cfg.num_layers // len(cfg.layer_pattern)
        x, _ = jax.lax.scan(body, x, jnp.arange(periods))
        aux = jnp.zeros((), jnp.float32)
    elif cfg.is_encdec:
        memory = _encode(cfg, params, batch, mode=mode, remat=remat)
        ldefs = _decoder_xattn_layer_defs(cfg)
        block = _maybe_remat(
            lambda p_l, mem, x: _decoder_xattn_block(cfg, p_l, x, mem, mode=mode),
            remat)

        def body(carry, p_l):
            x, aux = carry
            x, a = block(_constrain_layer_params(p_l, ldefs), memory, x)
            return (x, aux + a), None

        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["layers"])
    else:
        ldefs = _dense_layer_defs(cfg)
        block = _maybe_remat(
            lambda p_l, x: _dense_block(cfg, p_l, x, mode=mode), remat)

        def body(carry, p_l):
            x, aux = carry
            x, a = block(_constrain_layer_params(p_l, ldefs), x)
            return (x, aux + a), None

        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["layers"])

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = L.lm_logits(cfg, params["embedding"], x)
    return logits, aux


def forward_loss(cfg: ArchConfig, params: dict, batch: dict, *,
                 mode: str = "exec", remat: Optional[str] = None,
                 aux_weight: float = 0.01):
    logits, aux = forward(cfg, params, batch, mode=mode, remat=remat)
    mask = batch.get("loss_mask")
    loss = L.cross_entropy_loss(logits, batch["labels"], mask)
    total = loss + aux_weight * aux
    return total, {"ce_loss": loss, "moe_aux": aux}


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def _stack(tree, n: int):
    """``tree``'s leaves repeated over a new leading axis of ``n`` layers."""
    return jax.tree.map(lambda v: jnp.broadcast_to(v[None], (n,) + v.shape),
                        tree)


def init_decode_state(cfg: ArchConfig, batch: int, cache_len: int) -> dict:
    """Zeroed decode state for ``batch`` slots. Self-attention caches are
    stacked ``(layers, batch, cache_len, kv_heads, head_dim)`` bfloat16
    leaves (``cache_len`` capped at the sliding window, a ring). Each
    ``decode_step`` writes one row a slot into them in place: a one-hot
    select in ``decode_attention``, and the layer loop's write of the slice
    back at its index (``_scan_layers_in_place``); with the state donated,
    as ``ServingEngine`` does, no copy of the cache is made.

    moe_hybrid keeps two stacks side by side, ``mamba`` (conv and SSM state
    of each Mamba2 layer) and ``attn`` (the KV cache of each attention
    layer), both carried in place by the layer loop, and ``expert_hits``
    ``(layers, batch)``: the last step's choices of each slot that landed on
    a held expert, per layer (the engine's ``expert_assignments``). Its KV
    cache is head-major, ``(layers, batch, kv_heads, cache_len,
    head_dim)``: with 128-wide heads the compiler keeps the
    ``(batch, len, kv_heads, head_dim)`` order row-major, and the attention
    dots then copy each layer's slice into head-major order and back."""
    # "pos" is a (batch,) vector: every slot carries its OWN position stream
    # so a serving slot can be reset (reset_decode_slots) and re-admitted
    # mid-stream without aliasing cache positions across requests. Uniform
    # values reproduce the legacy single-stream behavior exactly.
    state: dict[str, Any] = {"pos": jnp.zeros((batch,), jnp.int32)}
    if cfg.family == "ssm":
        state["rwkv"] = _stack(rwkv_mod.init_rwkv_state(cfg, batch),
                               cfg.num_layers)
    elif cfg.family == "hybrid":
        ng, tail = hybrid_groups(cfg)
        m = ssm_mod.init_ssm_state(cfg, batch)
        state["mamba"] = _stack(m, ng * cfg.attn_every)
        if tail:
            state["mamba_tail"] = _stack(m, tail)
        state["attn"] = _stack(attn.init_kv_cache(cfg, batch, cache_len), ng)
    elif cfg.family == "moe_hybrid":
        kinds = cfg.layer_types
        state["mamba"] = _stack(ssm_mod.init_ssm_state(cfg, batch),
                                kinds.count("mamba"))
        kv = attn.init_kv_cache(cfg, batch, cache_len)
        state["attn"] = _stack(jax.tree.map(lambda c: jnp.swapaxes(c, 1, 2),
                                            kv), kinds.count("attention"))
        state["expert_hits"] = jnp.zeros((cfg.num_layers, batch), jnp.int32)
    elif cfg.is_encdec:
        state["self"] = _stack(attn.init_kv_cache(cfg, batch, cache_len),
                               cfg.num_layers)
        hd = cfg.resolved_head_dim
        state["cross_k"] = jnp.zeros(
            (cfg.num_layers, batch, cache_len, cfg.num_kv_heads, hd), jnp.bfloat16)
        state["cross_v"] = jnp.zeros_like(state["cross_k"])
    else:
        state["kv"] = _stack(attn.init_kv_cache(cfg, batch, cache_len,
                                                window=cfg.sliding_window),
                             cfg.num_layers)
    return state


def decode_state_logical_axes(cfg: ArchConfig, state: dict) -> dict:
    """Logical sharding axes mirroring init_decode_state's structure."""
    kv_axes = ("layers",) + attn.cache_logical_axes()["k"]
    out: dict[str, Any] = {"pos": (None,)}  # (batch,) vector, replicated
    if cfg.family == "ssm":
        out["rwkv"] = {
            "wkv": ("layers", "batch", "rwkv_heads", None, None),
            "tm_x": ("layers", "batch", "embed"),
            "cm_x": ("layers", "batch", "embed"),
        }
    elif cfg.family == "hybrid":
        m_axes = {"ssm": ("layers", "batch", "ssm_heads", None, None),
                  "conv": ("layers", "batch", None, "ssm_inner")}
        out["mamba"] = m_axes
        if "mamba_tail" in state:
            out["mamba_tail"] = m_axes
        out["attn"] = {"k": kv_axes, "v": kv_axes}
    elif cfg.family == "moe_hybrid":
        out["mamba"] = {"ssm": ("layers", "batch", "ssm_heads", None, None),
                        "conv": ("layers", "batch", None, "ssm_inner")}
        head_major = ("layers", "kv_batch", "act_kv_heads", "kv_seq", None)
        out["attn"] = {"k": head_major, "v": head_major}
        out["expert_hits"] = ("layers", "batch")
    elif cfg.is_encdec:
        out["self"] = {"k": kv_axes, "v": kv_axes}
        out["cross_k"] = kv_axes
        out["cross_v"] = kv_axes
    else:
        out["kv"] = {"k": kv_axes, "v": kv_axes}
    return out


def reset_decode_slots(cfg: ArchConfig, state: dict, reset_mask) -> dict:
    """Masked per-slot reset: slots where ``reset_mask`` is True restart
    their position stream at 0 with fresh recurrent state, WITHOUT touching
    the other slots — the admission primitive of slot-stream continuous
    batching (a freed slot takes a new request while its neighbors keep
    decoding).

    KV caches are deliberately NOT cleared: ``decode_attention``'s per-row
    causal mask only exposes cache rows a slot has written since its last
    reset (``idx <= pos``), so the previous occupant's entries are
    unreachable and each row is overwritten before it becomes visible —
    including the sliding-window ring buffer, whose "fully wrapped" clause
    only unlocks after the new stream has itself written the whole ring.
    Recurrent families (RWKV / Mamba / hybrid) carry history densely in
    their state, so those leaves ARE re-initialized under the mask; the
    per-request encoder memory of enc-dec models is cleared for the same
    reason. moe_hybrid is the exception: its ``decode_step`` reads the Mamba
    state of a slot at position 0 as fresh, inside the state update, so the
    reset is the position alone. Rewriting the stack here would take two
    eager copies of it (the fresh stack and the selected one), which do not
    fit beside the model on one chip.
    """
    reset = jnp.asarray(reset_mask, bool)
    batch = reset.shape[0]

    def sel(old, fresh):
        # batch axis is axis 1 on every stacked state leaf
        m = reset.reshape((1, batch) + (1,) * (old.ndim - 2))
        return jnp.where(m, fresh.astype(old.dtype), old)

    new_state = dict(state)
    new_state["pos"] = jnp.where(reset, 0, state["pos"])
    if cfg.family == "ssm":
        fresh = _stack(rwkv_mod.init_rwkv_state(cfg, batch), cfg.num_layers)
        new_state["rwkv"] = jax.tree.map(sel, state["rwkv"], fresh)
    elif cfg.family == "hybrid":
        ng, tail = hybrid_groups(cfg)
        m0 = ssm_mod.init_ssm_state(cfg, batch)
        fresh = _stack(m0, ng * cfg.attn_every)
        new_state["mamba"] = jax.tree.map(sel, state["mamba"], fresh)
        if "mamba_tail" in state:
            fresh_t = _stack(m0, tail)
            new_state["mamba_tail"] = jax.tree.map(sel, state["mamba_tail"],
                                                   fresh_t)
    elif cfg.is_encdec:
        new_state["cross_k"] = sel(state["cross_k"],
                                   jnp.zeros_like(state["cross_k"]))
        new_state["cross_v"] = sel(state["cross_v"],
                                   jnp.zeros_like(state["cross_v"]))
    return new_state


def decode_state_cache_axis(cfg: ArchConfig) -> int:
    """Axis of the cache length in the stacked leaves that
    :func:`decode_state_cache_keys` names: 2, or 3 in moe_hybrid's
    head-major KV cache (see :func:`init_decode_state`)."""
    return 3 if cfg.family == "moe_hybrid" else 2


def decode_state_cache_keys(cfg: ArchConfig) -> tuple[str, ...]:
    """State keys whose leaves carry the **cache length** axis (``cache_len``
    at init; axis 2 of the stacked ``(layers, batch, len, ...)`` leaf, axis 1
    after :func:`extract_decode_slot` drops the batch axis; one further in
    moe_hybrid, :func:`decode_state_cache_axis`). These are the
    leaves mid-flight migration must pad/truncate when source and target
    engines disagree on ``max_len``; recurrent leaves (RWKV/Mamba) are
    length-free and move unchanged."""
    if cfg.family == "ssm":
        return ()
    if cfg.family in ("hybrid", "moe_hybrid"):
        return ("attn",)
    if cfg.is_encdec:
        return ("self", "cross_k", "cross_v")
    return ("kv",)


def extract_decode_slot(cfg: ArchConfig, state: dict, slot: int
                        ) -> tuple[dict, int]:
    """Host-side copy of ONE slot's decode state: ``(leaves, pos)``.

    Every stacked state leaf carries batch at axis 1 (the layout
    :func:`reset_decode_slots` relies on), so one slot's share is the
    ``[:, slot]`` slice of each non-``pos`` leaf, pulled to host numpy —
    mesh-agnostic by construction (``np.asarray`` gathers a sharded array),
    which is what lets a :class:`~repro.runtime.migration.SlotSnapshot`
    cross destinations with different meshes/layouts."""
    leaves = {
        key: jax.tree.map(lambda v: np.asarray(v[:, slot]), val)
        for key, val in state.items() if key != "pos"
    }
    pos = int(np.asarray(state["pos"])[slot])
    return leaves, pos


def restore_decode_slot(cfg: ArchConfig, state: dict, slot: int,
                        leaves: dict, pos: int) -> dict:
    """Masked single-slot **write** — the restore-side dual of
    :func:`reset_decode_slots`: overwrite slot ``slot``'s share of every
    state leaf with ``leaves`` (an :func:`extract_decode_slot` payload,
    already resized to this state's cache length) and pin its position
    stream at ``pos``, WITHOUT touching the other slots. The neighbors keep
    decoding through a migration exactly as they keep decoding through an
    admission reset."""
    batch = state["pos"].shape[0]
    new_state = dict(state)
    new_state["pos"] = jnp.broadcast_to(
        jnp.asarray(state["pos"], jnp.int32), (batch,)).at[slot].set(pos)
    for key, val in state.items():
        if key == "pos":
            continue
        new_state[key] = jax.tree.map(
            lambda cur, leaf: cur.at[:, slot].set(
                jnp.asarray(leaf).astype(cur.dtype)),
            val, leaves[key])
    return new_state


def _layer(stack, i):
    """Slice ``i`` of every leaf of a stacked state tree."""
    return jax.tree.map(
        lambda c: jax.lax.dynamic_index_in_dim(c, i, 0, keepdims=False), stack)


def _put_layer(stack, new, i):
    """``stack`` with slice ``i`` of every leaf replaced by ``new``'s."""
    return jax.tree.map(
        lambda c, n: jax.lax.dynamic_update_index_in_dim(c, n, i, 0),
        stack, new)


def _scan_in_place(body, x, xs, state, length=None):
    """``lax.scan`` over stacked layers, or periods of layers, with the
    stacked decode state in the carry, not in ``xs``/``ys``.

    ``body(x, xs_l, state, l) -> (x, state, ys_l)`` reads the slices of
    step ``l`` that it needs with :func:`_layer` and writes them back with
    :func:`_put_layer`. The stacks therefore never pass through a fresh
    output stack, and under donation the input buffers are the output
    buffers: no whole-state copy. ``length`` is the number of steps where
    ``xs`` is None. Returns ``(x, state, ys)``.
    """
    def step(carry, xs_l):
        x, state, l = carry
        x, state, ys = body(x, xs_l, state, l)
        return (x, state, l + 1), ys

    (x, state, _), ys = jax.lax.scan(step, (x, state, jnp.int32(0)), xs,
                                     length=length)
    return x, state, ys


def _scan_layers_in_place(body, x, xs, cache):
    """:func:`_scan_in_place` with one slice of the stacked self-attention
    cache a layer: ``body(x, xs_l, cache_l) -> (x, cache_l, ys_l)`` sees
    layer ``l``'s slice of every cache leaf, and the slice it returns is
    written back at index ``l``. Returns ``(x, cache, ys)``."""
    def general(x, xs_l, cache, l):
        x, cache_l, ys = body(x, xs_l, _layer(cache, l))
        return x, _put_layer(cache, cache_l, l), ys

    return _scan_in_place(general, x, xs, cache)


def _moe_hybrid_decode(cfg: ArchConfig, params: dict, state: dict,
                       x: jax.Array, pos: jax.Array):
    """moe_hybrid's layers for one token a slot: one ``lax.scan`` over the
    periods of the layer pattern, each period's layers unrolled, with the
    Mamba and KV stacks carried in place and each layer's weights read from
    their stacks at its index. A slot at position 0 starts its Mamba state
    afresh here (``reset_decode_slots`` resets the position alone).
    Returns ``(x, {"mamba", "attn"}, hits (periods, per period, B))``."""
    rm, eps = cfg.residual_multiplier, cfg.norm_eps
    norms = params["layers"]
    fresh = (pos == 0)[:, None, None]

    def period(x, _, st, p):
        hits = []
        for kind, l, i in _moe_hybrid_layers(cfg, p):
            h = L.rms_norm(x, _layer(norms["ln_in"], l), eps)
            if kind == "M":
                with jax.named_scope("mamba"):
                    m_i = _layer(st["mamba"], i)
                    m_i = {"conv": jnp.where(fresh, 0, m_i["conv"]),
                           "ssm": jnp.where(fresh[..., None], 0.0,
                                            m_i["ssm"])}
                    y, new = ssm_mod.mamba_decode_step(
                        cfg, _layer(params["mamba"], i), h, m_i)
                    new = jax.tree.map(lambda n, o: n.astype(o.dtype), new,
                                       m_i)
                    st = {**st, "mamba": _put_layer(st["mamba"], new, i)}
            else:
                with jax.named_scope("attn"):
                    # the head-major slice seen as (B, T, K, hd): a view
                    kv_i = jax.tree.map(lambda c: jnp.swapaxes(c, 1, 2),
                                        _layer(st["attn"], i))
                    y, kv_i = attn.decode_attention(
                        cfg, _layer(params["attn"], i), h, kv_i, pos,
                        rope=cfg.rope)
                    kv_i = jax.tree.map(lambda c: jnp.swapaxes(c, 1, 2), kv_i)
                    st = {**st, "attn": _put_layer(st["attn"], kv_i, i)}
            x = x + rm * y
            h = L.rms_norm(x, _layer(norms["ln_post"], l), eps)
            y, hit = _moe_hybrid_experts(cfg, params, h, l)
            x = x + rm * y
            hits.append(hit[:, 0])
        return x, st, jnp.stack(hits)

    return _scan_in_place(
        period, x, None, {"mamba": state["mamba"], "attn": state["attn"]},
        length=cfg.num_layers // len(cfg.layer_pattern))


def decode_step(cfg: ArchConfig, params: dict, state: dict, tokens: jax.Array
                ) -> tuple[jax.Array, dict]:
    """tokens: (B,) int32 — one step. Returns (logits (B, V), new_state).

    ``state["pos"]`` is a per-slot (B,) position vector (a legacy scalar is
    broadcast); each batch row attends within its own stream only.
    """
    pos = jnp.broadcast_to(jnp.asarray(state["pos"], jnp.int32),
                           (tokens.shape[0],))
    x = L.embed_tokens(cfg, params["embedding"], tokens[:, None])
    new_state: dict[str, Any] = {"pos": pos + 1}

    if cfg.family == "ssm":
        def body(x, inp):
            p_l, st = inp
            xn = L.rms_norm(x, p_l["ln1"], cfg.norm_eps)
            y, wkv, tm_x = rwkv_mod.rwkv_time_mix(
                cfg, p_l["tm"], xn, mode="probe",
                state=st["wkv"], last_x=st["tm_x"].astype(xn.dtype))
            x = x + y
            xn2 = L.rms_norm(x, p_l["ln2"], cfg.norm_eps)
            y2, cm_x = rwkv_mod.rwkv_channel_mix(
                cfg, p_l["tm"], xn2, last_x=st["cm_x"].astype(xn2.dtype))
            x = x + y2
            st_new = {"wkv": wkv, "tm_x": tm_x.astype(jnp.bfloat16),
                      "cm_x": cm_x.astype(jnp.bfloat16)}
            return x, st_new

        x, new_rwkv = jax.lax.scan(body, x, (params["layers"], state["rwkv"]))
        new_state["rwkv"] = new_rwkv
    elif cfg.family == "hybrid":
        ng, tail = hybrid_groups(cfg)
        ae = cfg.attn_every
        shared = params["shared_attn"]
        mamba_states = jax.tree.map(
            lambda v: v.reshape((ng, ae) + v.shape[1:]), state["mamba"])

        def gbody(x, inp, kv_g):
            p_g, m_g = inp
            xn = L.rms_norm(x, shared["ln"], cfg.norm_eps)
            y, kv_new = attn.decode_attention(cfg, shared["attn"], xn, kv_g, pos)
            x = x + y
            m_new = []
            for i in range(ae):
                p_i = jax.tree.map(lambda v: v[i], p_g)
                m_i = jax.tree.map(lambda v: v[i], m_g)
                xn = L.rms_norm(x, p_i["ln"], cfg.norm_eps)
                y, m_i2 = ssm_mod.mamba_decode_step(cfg, p_i["mamba"], xn, m_i)
                x = x + y
                m_new.append(m_i2)
            m_new = jax.tree.map(lambda *vs: jnp.stack(vs), *m_new)
            return x, kv_new, m_new

        x, kv_new, m_new = _scan_layers_in_place(
            gbody, x, (params["groups"], mamba_states), state["attn"])
        new_state["attn"] = kv_new
        new_state["mamba"] = jax.tree.map(
            lambda v: v.reshape((ng * ae,) + v.shape[2:]), m_new)
        if tail:
            def tbody(x, inp):
                p_l, m_l = inp
                xn = L.rms_norm(x, p_l["ln"], cfg.norm_eps)
                y, m_l2 = ssm_mod.mamba_decode_step(cfg, p_l["mamba"], xn, m_l)
                return x + y, m_l2

            x, mt_new = jax.lax.scan(tbody, x,
                                     (params["tail"], state["mamba_tail"]))
            new_state["mamba_tail"] = mt_new
    elif cfg.family == "moe_hybrid":
        x, st, hits = _moe_hybrid_decode(cfg, params, state, x, pos)
        new_state.update(st)
        new_state["expert_hits"] = hits.reshape(cfg.num_layers, -1)
    elif cfg.is_encdec:
        def body(x, inp, kv_l):
            p_l, ck, cv = inp
            xn = L.rms_norm(x, p_l["ln1"], cfg.norm_eps)
            y, kv_new = attn.decode_attention(cfg, p_l["attn"], xn, kv_l, pos)
            x = x + y
            xn = L.rms_norm(x, p_l["ln_x"], cfg.norm_eps)
            y, _ = attn.decode_attention(cfg, p_l["xattn"], xn, {}, pos,
                                         kv_memory=(ck, cv), rope=False)
            x = x + y
            xn = L.rms_norm(x, p_l["ln2"], cfg.norm_eps)
            x = x + L.mlp_apply(cfg, p_l["mlp"], xn)
            return x, kv_new, None

        x, kv_new, _ = _scan_layers_in_place(
            body, x, (params["layers"], state["cross_k"], state["cross_v"]),
            state["self"])
        new_state["self"] = kv_new
        new_state["cross_k"] = state["cross_k"]
        new_state["cross_v"] = state["cross_v"]
    else:
        def body(x, p_l, kv_l):
            xn = L.rms_norm(x, p_l["ln1"], cfg.norm_eps)
            y, kv_new = attn.decode_attention(
                cfg, p_l["attn"], xn, kv_l, pos, window=cfg.sliding_window)
            x = x + y
            xn = L.rms_norm(x, p_l["ln2"], cfg.norm_eps)
            if cfg.num_experts:
                y2, _ = moe_mod.moe_apply(cfg, p_l["moe"], xn)
            else:
                y2 = L.mlp_apply(cfg, p_l["mlp"], xn)
            return x + y2, kv_new, None

        x, kv_new, _ = _scan_layers_in_place(body, x, params["layers"],
                                             state["kv"])
        new_state["kv"] = kv_new

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = L.lm_logits(cfg, params["embedding"], x)
    return logits[:, 0], new_state


def prefill_chunk_supported(cfg: ArchConfig) -> bool:
    """Whether :func:`prefill_chunk` can feed this model's prompts: a dense
    decoder with full attention and no experts. Recurrent state (RWKV,
    Mamba), expert layers, a sliding-window ring and encoder memory have no
    chunk path; their prompts go through ``decode_step`` a token a step."""
    return (cfg.family == "dense" and not cfg.sliding_window
            and not cfg.num_experts)


def prefill_chunk(cfg: ArchConfig, params: dict, state: dict, slot, tokens,
                  start, n_valid) -> dict:
    """Feed one slot a chunk of its prompt; returns the new state.

    ``tokens`` is ``(C,)`` int32: the first ``n_valid`` are the slot's
    prompt tokens at positions ``start … start + n_valid - 1``, the rest
    padding. Every layer's K and V rows of those tokens are written into
    the slot's rows of the cache (``attention.prefill_attention``), with the
    stack carried in place by the layer loop, and ``pos[slot]`` becomes
    ``start + n_valid``: the state one-token feeding through
    :func:`decode_step` would leave, up to rounding. No other slot's rows or
    position change. No logits: the last prompt token goes through
    ``decode_step``, which emits the first output token. Only for models
    :func:`prefill_chunk_supported` accepts.
    """
    x = L.embed_tokens(cfg, params["embedding"], tokens[None])

    def body(x, p_l, kv_l):
        xn = L.rms_norm(x, p_l["ln1"], cfg.norm_eps)
        y, kv_new = attn.prefill_attention(cfg, p_l["attn"], xn, kv_l, slot,
                                           start, n_valid)
        x = x + y
        xn = L.rms_norm(x, p_l["ln2"], cfg.norm_eps)
        return x + L.mlp_apply(cfg, p_l["mlp"], xn), kv_new, None

    _, kv, _ = _scan_layers_in_place(body, x, params["layers"], state["kv"])
    return {**state, "kv": kv,
            "pos": state["pos"].at[slot].set(start + n_valid)}
