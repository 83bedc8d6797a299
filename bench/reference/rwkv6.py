"""Plain float32 reference of the RWKV-6 ("Finch") language model.

Written from the block equations, with nothing taken from ``repro``, in the
recurrent form, one token at a time:

    time mix:  x_r..x_w = lerp(xn_t, xn_{t-1}, mu)
               r, k, v, g = x_r W_r, x_k W_k, x_v W_v, x_g W_g
               w_t = exp(-exp(d + tanh(x_w A) B))
               o_t = r_t (S + diag(u) k_t v_t^T);  S <- diag(w_t) S + k_t v_t^T
               y = (headnorm(o) * silu(g)) W_o
    channel mix: k = relu(x_k C_k)^2;  y = sigmoid(x_r C_r) * (k C_v)

with RMSNorm before each mix, a final RMSNorm and an untied head.
``configs/<name>.json`` lists where this departs from the published model.
Every matrix product runs at ``HIGHEST`` precision. ``int8=True`` gives the
control, as in ``dense.py``: int8 weights, product inputs, residual stream,
keys and values; the WKV state stays in float32, as the system keeps it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.dense import HIGHEST, lower, matmul, rms_norm
from bench.weights import Leaf


def layout(m: dict) -> dict:
    """The parameter tree that the RWKV path reads, and how each leaf is
    drawn: matrices at 1 / sqrt(fan in) (see ``dense.layout``), decays that
    span long and short memories, ``mu`` over the whole interpolation."""
    d, f, v, n = (m["hidden_size"], m["intermediate_size"], m["vocab_size"],
                  m["num_hidden_layers"])
    hs, r = m["head_size"], m["time_decay_extra_dim"]
    h = m["attention_hidden_size"] // hs
    scale = Leaf((n, d), "float32", "one_plus", 0.1)
    fd, ff = d ** -0.5, f ** -0.5  # 1 / sqrt(fan in)
    return {
        "embedding": {"embed": Leaf((v, d), std=1.0),
                      "unembed": Leaf((d, v), std=fd)},
        "final_norm": {"scale": Leaf((d,), "float32", "one_plus", 0.1)},
        "layers": {
            "ln1": {"scale": scale},
            "tm": {
                "mu": Leaf((n, 5, d), init="uniform"),
                "w_r": Leaf((n, d, d), std=fd), "w_k": Leaf((n, d, d), std=fd),
                "w_v": Leaf((n, d, d), std=fd), "w_g": Leaf((n, d, d), std=fd),
                "w_o": Leaf((n, d, d), std=fd),
                "decay_base": Leaf((n, d), "float32", "uniform", lo=-6.0,
                                   hi=0.0),
                "decay_A": Leaf((n, d, r), std=fd),
                "decay_B": Leaf((n, r, d), std=0.5 * r ** -0.5),
                "bonus_u": Leaf((n, h, hs), "float32", std=0.5),
                "ln_wkv": Leaf((n, h, hs), "float32", "one_plus", 0.1),
                "mu_c": Leaf((n, 2, d), init="uniform"),
                "c_k": Leaf((n, d, f), std=fd), "c_v": Leaf((n, f, d), std=ff),
                "c_r": Leaf((n, d, d), std=fd),
            },
            "ln2": {"scale": scale},
        },
    }


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], 0)


def logits_fn(m: dict, int8: bool = False):
    """A jitted ``(params, tokens (L,)) -> logits (L, V)`` in float32."""
    d, hs, eps = m["hidden_size"], m["head_size"], m["norm_eps"]
    h = m["attention_hidden_size"] // hs

    def wkv(r, k, v, w, u):
        """(L, H, hs) each -> (L, H, hs): the recurrence, token by token."""
        def step(s, inp):
            rt, kt, vt, wt = inp
            kv = kt[:, :, None] * vt[:, None, :]
            out = jnp.einsum("hk,hkv->hv", rt, s + u[:, :, None] * kv,
                             precision=HIGHEST)
            return wt[:, :, None] * s + kv, out

        _, out = jax.lax.scan(step, jnp.zeros((h, hs, hs), jnp.float32),
                              (r, k, v, w))
        return out

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        length = x.shape[0]
        t = p["tm"]
        xn = rms_norm(x, p["ln1"]["scale"], eps)
        xp = _shift(xn)
        mix = [xn + (xp - xn) * t["mu"][i] for i in range(5)]

        def heads(a):
            return a.reshape(length, h, hs)

        r = heads(matmul(mix[0], t["w_r"], int8))
        k = heads(lower(matmul(mix[1], t["w_k"], int8), int8))
        v = heads(lower(matmul(mix[2], t["w_v"], int8), int8))
        g = matmul(mix[3], t["w_g"], int8)
        lora = matmul(jnp.tanh(matmul(mix[4], t["decay_A"], int8)),
                      t["decay_B"], int8)
        w = heads(jnp.exp(-jnp.exp(t["decay_base"] + lora)))
        o = wkv(r, k, v, w, t["bonus_u"])
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
        o = (o * t["ln_wkv"]).reshape(length, d) * jax.nn.silu(g)
        x = lower(x + matmul(o, t["w_o"], int8), int8)
        xn = rms_norm(x, p["ln2"]["scale"], eps)
        xp = _shift(xn)
        xk = xn + (xp - xn) * t["mu_c"][0]
        xr = xn + (xp - xn) * t["mu_c"][1]
        kc = jnp.square(jax.nn.relu(matmul(xk, t["c_k"], int8)))
        y = jax.nn.sigmoid(matmul(xr, t["c_r"], int8)) * matmul(kc, t["c_v"],
                                                                int8)
        return lower(x + y, int8), None

    @jax.jit
    def run(params, tokens):
        x = lower(params["embedding"]["embed"][tokens].astype(jnp.float32),
                  int8)
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = rms_norm(x, params["final_norm"]["scale"], eps)
        return matmul(x, params["embedding"]["unembed"].astype(jnp.float32),
                      int8)

    return run
