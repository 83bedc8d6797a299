"""Plain float32 reference of Granite 4.0-H (``GraniteMoeHybrid``) at one
chip's share of its experts.

Written from the published equations, with nothing taken from ``repro``:

    h = embed(tok) * embedding_multiplier
    per layer:
      h = h + residual_multiplier * mixer(rms_in(h))
      x = rms_post(h)
      g = softmax(top_k(x @ W_router))      # over the k chosen logits
      h = h + residual_multiplier * (sum_{e in top_k, held} g_e E_e(x) + S(x))
    E_e, S: [a | b] = x @ W_in;  y = (silu(a) * b) @ W_out
    logits = rms_final(h) @ embed.T / logits_scaling

The mixer is a layer's entry of ``layer_types``:

* Mamba2, one token at a time (no chunked scan), ``n_groups`` 1:
  ``[z | xBC | dt] = x W_in``; ``xBC`` through a causal depthwise conv of
  ``mamba_d_conv`` taps with a bias, then silu; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; per head ``S <- exp(dt A) S + dt x B^T``,
  ``y = S C + D x``; ``y = rmsnorm(y * silu(z)) * scale``; ``y W_out``.
* attention: grouped-query, causal, no position embedding (NoPE), scores
  scaled by ``attention_multiplier``.

The chip holds ``num_local_experts`` of the router's ``router_experts``,
the first ones by id (the configuration's ``assumed``): the router scores
all of them, and only the held experts' part is added, as in the program.
``configs/<name>.json`` lists where this departs from the published model.
Every matrix product runs at ``HIGHEST`` precision. The layers run in one
``lax.scan``, each step upcasting only its own layer's weights, so that the
reference fits beside the bfloat16 weights on one chip.

``int8=True`` gives the control, as in ``dense.py``: int8 weights and
product inputs, residual stream, keys and values and the conv's input; the
SSM state stays in float32, as the system keeps it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.dense import HIGHEST, lower, matmul, rms_norm
from bench.weights import Leaf


def _sizes(m: dict) -> dict:
    d, h = m["hidden_size"], m["num_attention_heads"]
    di = m["mamba_expand"] * d
    return {"d": d, "h": h, "k": m["num_key_value_heads"], "hd": d // h,
            "di": di, "ns": m["mamba_d_state"], "nh": m["mamba_n_heads"],
            "conv": di + 2 * m["mamba_n_groups"] * m["mamba_d_state"],
            "f": m["intermediate_size"], "fs": m["shared_intermediate_size"],
            "e": m["router_experts"], "eh": m["num_local_experts"],
            "v": m["vocab_size"], "n": m["num_hidden_layers"]}


def kinds(m: dict) -> list[str]:
    """The mixer of each layer this chip holds: the first
    ``num_hidden_layers`` of ``layer_types``."""
    return m["layer_types"][:m["num_hidden_layers"]]


def layout(m: dict) -> dict:
    """The parameter tree that the moe_hybrid path reads, and how each leaf
    is drawn: matrices at 1 / sqrt(fan in) (see ``dense.layout``); decays
    (``A_log``, ``dt_bias``) that span long and short memories; conv taps
    that mix the last few tokens. The embedding, which is also the output
    head, at 1 / (embedding_multiplier sqrt(hidden_size)): the stream
    starts at unit norm, and a token's logit of itself (12 |e|^2 through
    the tied head) stays below the spread that the layers' output gives
    the logits, at any width. Drawn larger, every position would choose
    its own input token, whatever the context, and a broken cache or state
    would not show."""
    s = _sizes(m)
    d, di, nh, n = s["d"], s["di"], s["nh"], s["n"]
    nm, na = kinds(m).count("mamba"), kinds(m).count("attention")
    fd, fi = d ** -0.5, di ** -0.5
    scale = Leaf((n, d), "float32", "one_plus", 0.1)
    return {
        "embedding": {"embed": Leaf(
            (s["v"], d), std=1.0 / (m["embedding_multiplier"] * d ** 0.5))},
        "final_norm": {"scale": Leaf((d,), "float32", "one_plus", 0.1)},
        "mamba": {
            "in_proj": Leaf((nm, d, 2 * di + 2 * s["ns"] + nh), std=fd),
            "conv_w": Leaf((nm, m["mamba_d_conv"], s["conv"]), std=0.5),
            "conv_b": Leaf((nm, s["conv"]), std=0.1),
            "A_log": Leaf((nm, nh), init="uniform", lo=0.0, hi=2.5),
            "D": Leaf((nm, nh), init="one_plus", std=0.1),
            "dt_bias": Leaf((nm, nh), init="uniform", lo=-6.0, hi=-2.0),
            "norm_scale": Leaf((nm, di), "float32", "one_plus", 0.1),
            "out_proj": Leaf((nm, di, d), std=fi),
        },
        "attn": {
            "wq": Leaf((na, d, s["h"], s["hd"]), std=fd),
            "wk": Leaf((na, d, s["k"], s["hd"]), std=fd),
            "wv": Leaf((na, d, s["k"], s["hd"]), std=fd),
            "wo": Leaf((na, s["h"], s["hd"], d), std=d ** -0.5),
        },
        "layers": {
            "ln_in": {"scale": scale},
            "ln_post": {"scale": scale},
            "moe": {"router": Leaf((n, d, s["e"]), "float32", std=fd),
                    "w_in": Leaf((n, s["eh"], d, 2 * s["f"]), std=fd),
                    "w_out": Leaf((n, s["eh"], s["f"], d),
                                  std=s["f"] ** -0.5)},
            "shared": {"w_in": Leaf((n, d, 2 * s["fs"]), std=fd),
                       "w_out": Leaf((n, s["fs"], d), std=s["fs"] ** -0.5)},
        },
    }


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _glu(h, f):
    return jax.nn.silu(h[..., :f]) * h[..., f:]


def logits_fn(m: dict, int8: bool = False):
    """A jitted ``(params, tokens (L,)) -> logits (L, V)`` in float32."""
    s = _sizes(m)
    d, h, k, hd, di, ns, nh = (s["d"], s["h"], s["k"], s["hd"], s["di"],
                               s["ns"], s["nh"])
    eps, rm = m["rms_norm_eps"], m["residual_multiplier"]
    top = m["num_experts_per_tok"]
    is_attn = jnp.array([t == "attention" for t in kinds(m)])
    order = {"mamba": 0, "attention": 0}
    index = []
    for t in kinds(m):  # each layer's index in its mixer's stack
        index.append(order[t])
        order[t] += 1
    index = jnp.array(index, jnp.int32)

    def mamba(p, x):
        length = x.shape[0]
        zxbcdt = matmul(x, p["in_proj"], int8)
        z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:di + s["conv"]],
                      zxbcdt[:, di + s["conv"]:])
        xbc = lower(xbc, int8)
        taps = p["conv_w"].shape[0]
        padded = jnp.concatenate([jnp.zeros((taps - 1, s["conv"])), xbc], 0)
        xbc = jax.nn.silu(sum(padded[i:i + length] * p["conv_w"][i]
                              for i in range(taps)) + p["conv_b"])
        xs = xbc[:, :di].reshape(length, nh, di // nh)
        b, c = xbc[:, di:di + ns], xbc[:, di + ns:]
        dt = jax.nn.softplus(dt + p["dt_bias"])  # (L, nh)
        a = -jnp.exp(p["A_log"])

        def step(state, inp):
            xt, bt, ct, dtt = inp
            state = (jnp.exp(dtt * a)[:, None, None] * state
                     + dtt[:, None, None] * xt[:, :, None] * bt[None, None])
            return state, jnp.einsum("hdn,n->hd", state, ct,
                                     precision=HIGHEST)

        _, y = jax.lax.scan(step, jnp.zeros((nh, di // nh, ns)),
                            (xs, b, c, dt))
        y = (y + p["D"][:, None] * xs).reshape(length, di)
        y = y * jax.nn.silu(z)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
        return matmul(y * p["norm_scale"], p["out_proj"], int8)

    def attention(p, x):
        length = x.shape[0]
        q = matmul(x, p["wq"].reshape(d, h * hd), int8).reshape(length, h, hd)
        kk = lower(matmul(x, p["wk"].reshape(d, k * hd), int8), int8)
        vv = lower(matmul(x, p["wv"].reshape(d, k * hd), int8), int8)
        kk = jnp.repeat(kk.reshape(length, k, hd), h // k, axis=1)
        vv = jnp.repeat(vv.reshape(length, k, hd), h // k, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, kk, precision=HIGHEST)
        sc = sc * m["attention_multiplier"]
        causal = jnp.tril(jnp.ones((length, length), bool))
        sc = jnp.where(causal[None], sc, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), vv,
                       precision=HIGHEST)
        return matmul(o.reshape(length, h * hd), p["wo"].reshape(h * hd, d),
                      int8)

    def experts(p, x):
        """The held experts' part, weighted, plus the shared expert."""
        logits = matmul(x, p["moe"]["router"], int8)
        chosen, ids = jax.lax.top_k(logits, top)
        g = jax.nn.softmax(chosen, -1)  # (L, top)
        y = _glu(matmul(x, p["shared"]["w_in"], int8), s["fs"])
        y = matmul(y, p["shared"]["w_out"], int8)
        for e in range(s["eh"]):
            ge = jnp.sum(jnp.where(ids == e, g, 0.0), -1)  # (L,)
            ye = _glu(matmul(x, p["moe"]["w_in"][e], int8), s["f"])
            y = y + ge[:, None] * matmul(ye, p["moe"]["w_out"][e], int8)
        return y

    def layer(params, x, inp):
        attn_here, i, lp = inp
        lp = _f32(lp)
        hn = rms_norm(x, lp["ln_in"]["scale"], eps)
        y = jax.lax.cond(
            attn_here,
            lambda: attention(_f32(jax.tree.map(lambda a: a[i],
                                                params["attn"])), hn),
            lambda: mamba(_f32(jax.tree.map(lambda a: a[i],
                                            params["mamba"])), hn))
        x = lower(x + rm * y, int8)
        hn = rms_norm(x, lp["ln_post"]["scale"], eps)
        return lower(x + rm * experts(lp, hn), int8), None

    @jax.jit
    def run(params, tokens):
        embed = params["embedding"]["embed"]
        x = lower(embed[tokens].astype(jnp.float32)
                  * m["embedding_multiplier"], int8)
        x, _ = jax.lax.scan(lambda x, inp: layer(params, x, inp), x,
                            (is_attn, index, params["layers"]))
        x = rms_norm(x, params["final_norm"]["scale"], eps)
        return matmul(x, embed.astype(jnp.float32).T, int8) / m[
            "logits_scaling"]

    return run
