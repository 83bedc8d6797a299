"""Plain float32 reference of the dense decoder (StableLM-style blocks).

Written from the block equations, with nothing taken from ``repro``: token
embedding; per layer RMSNorm, multi-head attention with a bias on q, k and v
and rotary position embedding over the whole head (halves split), a
residual, RMSNorm, a SwiGLU MLP and a residual; a final RMSNorm and an
untied output head. ``configs/<name>.json`` lists where this departs from
the published model. Every matrix product runs at ``HIGHEST`` precision.

With ``int8=True`` the same model is computed one precision step below the
configuration's bfloat16: int8 weights (symmetric, one scale per output
column), and int8 values (one scale per row) wherever the system keeps
bfloat16 ones: each product's input, the residual stream after every add,
and the cached keys and values. It is the control that the benchmark's
comparison must reject.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.weights import Leaf

HIGHEST = jax.lax.Precision.HIGHEST


def layout(m: dict) -> dict:
    """The parameter tree that the dense path reads, and how each leaf is
    drawn: matrices at 1 / sqrt(fan in), so that attention and the MLP add
    as much to the residual stream as the token's embedding does, at any
    width. The output then depends on the context, and a cache that goes
    wrong shows in the logits."""
    d, h, k, hd = (m["hidden_size"], m["num_attention_heads"],
                   m["num_key_value_heads"], m["head_dim"])
    f, v, n = m["intermediate_size"], m["vocab_size"], m["num_hidden_layers"]
    scale = Leaf((n, d), "float32", "one_plus", 0.1)
    fd, fo, ff = d ** -0.5, (h * hd) ** -0.5, f ** -0.5  # 1 / sqrt(fan in)
    attn = {"wq": Leaf((n, d, h, hd), std=fd), "wk": Leaf((n, d, k, hd), std=fd),
            "wv": Leaf((n, d, k, hd), std=fd), "wo": Leaf((n, h, hd, d), std=fo)}
    if m["use_qkv_bias"]:
        attn.update(bq=Leaf((n, h, hd), std=0.1), bk=Leaf((n, k, hd), std=0.1),
                    bv=Leaf((n, k, hd), std=0.1))
    return {
        "embedding": {"embed": Leaf((v, d), std=1.0),
                      "unembed": Leaf((d, v), std=fd)},
        "final_norm": {"scale": Leaf((d,), "float32", "one_plus", 0.1)},
        "layers": {
            "ln1": {"scale": scale},
            "attn": attn,
            "ln2": {"scale": scale},
            "mlp": {"w_gate": Leaf((n, d, f), std=fd),
                    "w_up": Leaf((n, d, f), std=fd),
                    "w_down": Leaf((n, f, d), std=ff)},
        },
    }


def quantize(x, axis):
    """Symmetric int8 rounding along ``axis`` (one scale per slice)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def lower(x, int8: bool):
    """A stored activation: kept in float32, or rounded to int8 by rows."""
    return quantize(x, -1) if int8 else x


def matmul(x, w, int8: bool):
    """x (..., n) @ w (n, m) in float32, or in int8 for the control."""
    if int8:
        x, w = quantize(x, -1), quantize(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """x (L, heads, hd): rotate the two halves of each head by position."""
    length, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def logits_fn(m: dict, int8: bool = False):
    """A jitted ``(params, tokens (L,)) -> logits (L, V)`` in float32."""
    d, h, k, hd = (m["hidden_size"], m["num_attention_heads"],
                   m["num_key_value_heads"], m["head_dim"])
    eps, theta = m["norm_eps"], m["rope_theta"]

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        length = x.shape[0]
        a = p["attn"]
        hn = rms_norm(x, p["ln1"]["scale"], eps)
        q = matmul(hn, a["wq"].reshape(d, h * hd), int8).reshape(length, h, hd)
        kk = matmul(hn, a["wk"].reshape(d, k * hd), int8).reshape(length, k, hd)
        vv = matmul(hn, a["wv"].reshape(d, k * hd), int8).reshape(length, k, hd)
        if "bq" in a:
            q, kk, vv = q + a["bq"], kk + a["bk"], vv + a["bv"]
        q, kk = rotary(q, theta), lower(rotary(kk, theta), int8)
        vv = lower(vv, int8)
        kk, vv = jnp.repeat(kk, h // k, axis=1), jnp.repeat(vv, h // k, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, kk, precision=HIGHEST) * hd ** -0.5
        causal = jnp.tril(jnp.ones((length, length), bool))
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vv,
                       precision=HIGHEST)
        x = lower(x + matmul(o.reshape(length, h * hd),
                             a["wo"].reshape(h * hd, d), int8), int8)
        hn = rms_norm(x, p["ln2"]["scale"], eps)
        mlp = p["mlp"]
        act = jax.nn.silu(matmul(hn, mlp["w_gate"], int8)) * matmul(
            hn, mlp["w_up"], int8)
        return lower(x + matmul(act, mlp["w_down"], int8), int8), None

    @jax.jit
    def run(params, tokens):
        x = lower(params["embedding"]["embed"][tokens].astype(jnp.float32),
                  int8)
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = rms_norm(x, params["final_norm"]["scale"], eps)
        return matmul(x, params["embedding"]["unembed"].astype(jnp.float32),
                      int8)

    return run
