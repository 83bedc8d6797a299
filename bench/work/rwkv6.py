"""Work that one decode step of RWKV-6 needs, from shapes alone.

A step serves ``active`` slots, one token each. It needs every weight once
(the embedding only for the rows it looks up), and reads and writes each
active slot's recurrent state once: the WKV matrix of every head and the
two token-shift vectors of every layer. The WKV update and readout cost
7 hs^2 FLOPs per head and token (decay, outer product, add; bonus, add,
readout). ``rows`` is unused: the state does not grow with the context.
"""
from __future__ import annotations

BF16, F32 = 2, 4


def _sizes(m: dict):
    d, f, v, n = (m["hidden_size"], m["intermediate_size"], m["vocab_size"],
                  m["num_hidden_layers"])
    hs, r = m["head_size"], m["time_decay_extra_dim"]
    h = m["attention_hidden_size"] // hs
    layer = 5 * d * d + 2 * d * r + 2 * d * f + d * d  # matrix entries
    vectors_bf16 = 7 * d  # mu (5, d) and mu_c (2, d)
    vectors_f32 = d + 2 * h * hs + 2 * d  # decay, bonus, head norm, 2 norms
    return d, v, n, h, hs, layer, vectors_bf16, vectors_f32


def step_flops(m: dict, active: int, rows: int) -> float:
    d, v, n, h, hs, layer, _, _ = _sizes(m)
    per_token = n * (2 * layer + 7 * h * hs * hs) + 2 * d * v
    return float(active * per_token)


def step_bytes(m: dict, active: int, rows: int) -> float:
    d, v, n, h, hs, layer, vb, vf = _sizes(m)
    weights = (n * (layer + vb) + d * v) * BF16 + (n * vf + d) * F32
    state = n * (h * hs * hs * F32 + 2 * d * BF16)  # one slot, all layers
    return float(weights + active * d * BF16 + active * 2 * state)
