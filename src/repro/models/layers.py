"""Shared model primitives: norms, RoPE, MLPs, embeddings."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.parallel.sharding import PDef, shard_act


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm_defs(d: int) -> dict:
    return {"scale": PDef((d,), ("unsharded",), init="ones", dtype=jnp.float32)}


def rms_norm(x: jax.Array, p: dict, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * p["scale"]).astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # (head_dim/2,)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    freqs = rope_frequencies(x.shape[-1], theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------

def mlp_defs(cfg: ArchConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.mlp_type == "swiglu":
        return {
            "w_gate": PDef((d, f), ("fsdp", "ffn")),
            "w_up": PDef((d, f), ("fsdp", "ffn")),
            "w_down": PDef((f, d), ("ffn", "fsdp")),
        }
    return {
        "w_up": PDef((d, f), ("fsdp", "ffn")),
        "w_down": PDef((f, d), ("ffn", "fsdp")),
    }


def mlp_apply(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    if cfg.mlp_type == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    else:
        h = jax.nn.gelu(x @ p["w_up"])
    h = shard_act(h, ("batch", "seq_inner", "act_ffn"))
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# Embeddings / LM head
# ---------------------------------------------------------------------------

def embedding_defs(cfg: ArchConfig) -> dict:
    v, d = cfg.padded_vocab(), cfg.d_model
    defs = {"embed": PDef((v, d), ("vocab", "fsdp"), scale=1.0, init="fan_in")}
    if not cfg.tie_embeddings:
        defs["unembed"] = PDef((d, v), ("fsdp", "vocab"))
    return defs


def embed_tokens(cfg: ArchConfig, p: dict, tokens: jax.Array) -> jax.Array:
    out = jnp.take(p["embed"], tokens, axis=0)
    if cfg.embedding_multiplier != 1.0:
        out = out * cfg.embedding_multiplier
    return shard_act(out, ("batch", "seq", "embed"), essential=True)


def lm_logits(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    # vocab-TP head: seq gathered (seq_inner), vocab model-sharded — keeps
    # the unembed grad partial at (D, V/tp) instead of a full (D, V) f32
    # buffer per device (the dominant train temp before this layout).
    x = shard_act(x, ("batch", "seq_inner", "embed"), essential=True)
    if cfg.tie_embeddings:
        logits = x @ p["embed"].T
    else:
        logits = x @ p["unembed"]
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return shard_act(logits, ("batch", "seq_inner", "act_vocab"), essential=True)


def cross_entropy_loss(
    logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None,
    z_loss: float = 1e-4,
) -> jax.Array:
    """Masked CE with z-loss, written to partition cleanly when the vocab
    dim is model-sharded: max/sum reduce via GSPMD all-reduce (small stats)
    and the label pick is a one-hot contraction (no gather/scatter)."""
    logits = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    z = jnp.sum(jnp.exp(logits - m), axis=-1)
    lse = jnp.log(z) + m[..., 0]
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    # must match the logits layout exactly or GSPMD all-gathers logits
    onehot = shard_act(onehot, ("batch", "seq_inner", "act_vocab"), essential=True)
    picked = jnp.einsum("...v,...v->...", logits, onehot)
    nll = shard_act(lse - picked, ("batch", "seq_inner"), essential=True)
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
