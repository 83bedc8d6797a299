"""Attention: GQA/MQA/MHA, RoPE, sliding-window, chunked (flash-style) prefill,
sequence-sharded decode, cross-attention.

Two execution modes:
  mode="exec"  — lax.scan over query chunks (small HLO; production artifact)
  mode="probe" — unrolled python loop with exact causal/window KV slices.
                 This matches what the Pallas flash kernel does on real TPU
                 (skips fully-masked KV blocks) and is used by the roofline
                 cost probes so HLO FLOPs reflect the intended math.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.parallel.sharding import PDef, shard_act
from repro.models.layers import apply_rope

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def attention_defs(cfg: ArchConfig, cross: bool = False) -> dict:
    d, h, k, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    defs = {
        "wq": PDef((d, h, hd), ("fsdp", "heads", None)),
        "wk": PDef((d, k, hd), ("fsdp", "kv_heads", None)),
        "wv": PDef((d, k, hd), ("fsdp", "kv_heads", None)),
        "wo": PDef((h, hd, d), ("heads", None, "fsdp")),
    }
    if cfg.qkv_bias and not cross:
        defs["bq"] = PDef((h, hd), ("heads", None), init="zeros")
        defs["bk"] = PDef((k, hd), ("kv_heads", None), init="zeros")
        defs["bv"] = PDef((k, hd), ("kv_heads", None), init="zeros")
    return defs


def _project_q(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    return shard_act(q, ("batch", "seq_inner", "act_heads", None))


def _project_kv(cfg: ArchConfig, p: dict, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    k = shard_act(k, ("batch", "seq_inner", "act_kv_heads", None))
    v = shard_act(v, ("batch", "seq_inner", "act_kv_heads", None))
    return k, v


def _repeat_kv(x: jax.Array, num_heads: int) -> jax.Array:
    """(B, T, K, hd) -> (B, T, H, hd) by repeating each KV head H/K times."""
    b, t, k, hd = x.shape
    if k == num_heads:
        return x
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, t, k, num_heads // k, hd))
    return x.reshape(b, t, num_heads, hd)


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array, mask: Optional[jax.Array],
          scale: float) -> jax.Array:
    """q: (B,Sq,H,hd)  k,v: (B,Skv,H,hd)  mask: (Sq,Skv) or (B,1,Sq,Skv)."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None, None]
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _causal_window_mask(q_pos: jax.Array, k_pos: jax.Array, window: int) -> jax.Array:
    m = k_pos[None, :] <= q_pos[:, None]
    if window:
        m &= k_pos[None, :] > (q_pos[:, None] - window)
    return m


# ---------------------------------------------------------------------------
# Full-sequence attention (train / prefill), chunked over queries
# ---------------------------------------------------------------------------

def attention(
    cfg: ArchConfig,
    p: dict,
    x: jax.Array,
    *,
    kv_x: Optional[jax.Array] = None,
    causal: bool = True,
    window: int = 0,
    rope: bool = True,
    mode: str = "exec",
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Self- (kv_x=None) or cross-attention over full sequences."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    scale = cfg.attention_multiplier or hd ** -0.5
    kv_src = x if kv_x is None else kv_x
    t = kv_src.shape[1]

    q = _project_q(cfg, p, x)
    k, v = _project_kv(cfg, p, kv_src)
    if rope and kv_x is None:
        pos = positions if positions is not None else jnp.arange(s)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    k = _repeat_kv(k, cfg.num_heads)
    v = _repeat_kv(v, cfg.num_heads)

    chunk = min(cfg.attn_chunk, s)
    if s % chunk:
        chunk = s  # irregular length: single chunk
    nc = s // chunk

    if nc == 1:
        mask = None
        if causal:
            pos = jnp.arange(s)
            mask = _causal_window_mask(pos, pos, window)
        out = _sdpa(q, k, v, mask, scale)
    elif mode == "probe":
        # Unrolled with exact KV slices — models the Pallas flash kernel's
        # block skipping (no FLOPs on fully-masked KV blocks).
        outs = []
        for i in range(nc):
            qi = q[:, i * chunk:(i + 1) * chunk]
            if causal:
                lo = max(0, i * chunk - window + 1) if window else 0
                lo = (lo // chunk) * chunk
                hi = (i + 1) * chunk
                ki, vi = k[:, lo:hi], v[:, lo:hi]
                mask = _causal_window_mask(
                    jnp.arange(i * chunk, hi), jnp.arange(lo, hi), window)
            else:
                ki, vi, mask = k, v, None
            outs.append(_sdpa(qi, ki, vi, mask, scale))
        out = jnp.concatenate(outs, axis=1)
    else:
        # lax.scan over query chunks against full KV with a position mask.
        # The chunk body is checkpointed: backward recomputes each chunk's
        # probabilities instead of saving all nc of them (flash-bwd memory).
        @jax.checkpoint
        def chunk_attn(qi, i, k, v):
            if causal:
                q_pos = i * chunk + jnp.arange(chunk)
                mask = _causal_window_mask(q_pos, jnp.arange(t), window)
            else:
                mask = None
            return _sdpa(qi, k, v, mask, scale)

        def body(_, qi_idx):
            qi, i = qi_idx
            return None, chunk_attn(qi, i, k, v)

        q_chunks = q.reshape(b, nc, chunk, cfg.num_heads, hd).transpose(1, 0, 2, 3, 4)
        _, out = jax.lax.scan(body, None, (q_chunks, jnp.arange(nc)))
        out = out.transpose(1, 0, 2, 3, 4).reshape(b, s, cfg.num_heads, hd)

    out = shard_act(out, ("batch", "seq_inner", "act_heads", None))
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


# ---------------------------------------------------------------------------
# Decode (single new token against a KV cache)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ArchConfig, batch: int, max_len: int, window: int = 0) -> dict:
    """Cache for ONE layer (callers stack over layers). Sequence-sharded."""
    hd = cfg.resolved_head_dim
    length = min(max_len, window) if window else max_len
    shape = (batch, length, cfg.num_kv_heads, hd)
    return {
        "k": jnp.zeros(shape, jnp.bfloat16),
        "v": jnp.zeros(shape, jnp.bfloat16),
    }


def cache_logical_axes() -> dict:
    return {
        "k": ("kv_batch", "kv_seq", "act_kv_heads", None),
        "v": ("kv_batch", "kv_seq", "act_kv_heads", None),
    }


def _write_row(cache: jax.Array, row: jax.Array, slot: jax.Array) -> jax.Array:
    """``cache`` (B, T, K, hd) with ``row`` (B, 1, K, hd), cast to the cache's
    dtype, written at sequence index ``slot[b]`` of each batch row ``b``.

    A one-hot select over the sequence axis, not a scatter: a scatter makes
    the compiler copy the layer's slice into a row-major layout and back,
    while the select fuses into the attention dots and into the in-place
    write of the slice that ``decode_step``'s layer loop makes.
    """
    hit = jnp.arange(cache.shape[1])[None, :] == slot[:, None]
    return jnp.where(hit[:, :, None, None], row.astype(cache.dtype), cache)


def decode_attention(
    cfg: ArchConfig,
    p: dict,
    x: jax.Array,
    cache: dict,
    pos: jax.Array,
    *,
    window: int = 0,
    kv_memory: Optional[tuple[jax.Array, jax.Array]] = None,
    rope: bool = True,
) -> tuple[jax.Array, dict]:
    """x: (B, 1, D); pos: current position — a scalar (one shared position
    stream) or a (B,) vector (per-slot position streams: each batch row
    carries its own stream, so continuous-batching slots never alias cache
    positions across the requests sharing a slot). Returns (out, new_cache).

    ``cache`` is one layer's slice of the stacked self-attention cache;
    ``new_cache`` is that slice with each slot's new K and V row written at
    ``pos`` (``pos % length`` in a sliding-window ring) by :func:`_write_row`,
    in the cache's bfloat16. ``decode_step`` writes it back into the stack
    at the layer's index, so under donation the cache is updated in place.
    Cross-attention (``kv_memory``) is read only and returns ``cache`` as is.

    The cache sequence axis is sharded ("kv_seq"); softmax statistics combine
    across shards via GSPMD all-reduce (flash-decode style SP).
    """
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    scale = cfg.attention_multiplier or hd ** -0.5
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))

    q = _project_q(cfg, p, x)
    if rope:
        q = apply_rope(q, pos[:, None], cfg.rope_theta)

    if kv_memory is not None:  # cross-attention: static precomputed memory
        k, v = kv_memory
        mask = None
        new_cache = cache
    else:
        k_new, v_new = _project_kv(cfg, p, x)
        if rope:
            k_new = apply_rope(k_new, pos[:, None], cfg.rope_theta)
        length = cache["k"].shape[1]
        slot = (pos % length) if window else pos
        k = _write_row(cache["k"], k_new, slot)
        v = _write_row(cache["v"], v_new, slot)
        k = shard_act(k, ("kv_batch", "kv_seq", "act_kv_heads", None), essential=True)
        v = shard_act(v, ("kv_batch", "kv_seq", "act_kv_heads", None), essential=True)
        new_cache = {"k": k, "v": v}
        idx = jnp.arange(length)
        if window:
            # ring buffer: once wrapped, every slot holds one of the last
            # `length` positions; before wrapping only slots <= pos are live.
            mask = (idx[None, :] <= pos[:, None]) | (pos[:, None] >= length)
        else:
            # per-row causality doubles as slot-reset hygiene: rows whose
            # stream restarted at 0 can only see cache entries they have
            # (re)written since the reset.
            mask = idx[None, :] <= pos[:, None]

    # grouped GQA: no materialized head-repeat of the cache (a full extra
    # cache-sized copy per step when heads/kv_heads is large, e.g. grok's 6x)
    kh = k.shape[2]
    g = cfg.num_heads // kh
    qg = q.reshape(b, q.shape[1], kh, g, hd)
    scores = jnp.einsum("bqkgd,btkd->bkgqt", qg, k).astype(jnp.float32) * scale
    if mask is not None:
        # mask: (B, T) -> align with (b, kh, g, 1, T)
        scores = jnp.where(mask[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqt,btkd->bqkgd", probs, v)
    out = out.reshape(b, q.shape[1], cfg.num_heads, hd)
    out = shard_act(out, ("batch", None, "act_heads", None))
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), new_cache


def prefill_attention(
    cfg: ArchConfig,
    p: dict,
    x: jax.Array,
    cache: dict,
    slot: jax.Array,
    start: jax.Array,
    n_valid: jax.Array,
) -> tuple[jax.Array, dict]:
    """A chunk of one slot's prompt against that slot's cache rows.

    ``x`` is ``(1, C, D)``: token ``i`` of the chunk sits at position
    ``start + i``, and the first ``n_valid`` are prompt tokens (the rest
    padding). ``cache`` is one layer's slice of the stacked cache, ``(B, T,
    K, hd)``; the slot's ``(T, K, hd)`` slab is read once, the chunk's first
    ``n_valid`` K and V rows are selected into it at ``start …``, cast to
    the cache's bfloat16, and the slab is put back at ``slot``: no other
    slot's rows and no other row of this slot change. Query ``i`` attends
    over the slab's rows ``<= start + i``, so it reads the prompt's earlier
    rows exactly as ``decode_attention`` would read them back. Returns
    ``(out (1, C, D), new_cache)``.
    """
    c = x.shape[1]
    hd = cfg.resolved_head_dim
    scale = cfg.attention_multiplier or hd ** -0.5
    length = cache["k"].shape[1]
    positions = start + jnp.arange(c)

    q = apply_rope(_project_q(cfg, p, x), positions, cfg.rope_theta)
    k_new, v_new = _project_kv(cfg, p, x)
    k_new = apply_rope(k_new, positions, cfg.rope_theta)
    idx = jnp.arange(length)
    fresh = ((idx >= start) & (idx < start + n_valid))[:, None, None]

    def write(leaf, rows):
        slab = jax.lax.dynamic_index_in_dim(leaf, slot, 0, keepdims=False)
        hit = (idx[:, None] - start) == jnp.arange(c)[None, :]
        placed = jnp.einsum("tc,ckd->tkd", hit.astype(leaf.dtype),
                            rows[0].astype(leaf.dtype))
        slab = jnp.where(fresh, placed, slab)
        return jax.lax.dynamic_update_index_in_dim(leaf, slab, slot, 0), slab

    k_cache, k = write(cache["k"], k_new)
    v_cache, v = write(cache["v"], v_new)

    kh = k.shape[1]
    g = cfg.num_heads // kh
    qg = q.reshape(c, kh, g, hd)
    scores = jnp.einsum("qkgd,tkd->kgqt", qg, k).astype(jnp.float32) * scale
    mask = idx[None, :] <= positions[:, None]
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("kgqt,tkd->qkgd", probs, v).reshape(1, c, cfg.num_heads,
                                                         hd)
    return (jnp.einsum("bshk,hkd->bsd", out, p["wo"]),
            {"k": k_cache, "v": v_cache})
