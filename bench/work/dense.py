"""Work that one decode step of the dense model needs, from shapes alone.

A step serves ``active`` slots, one token each; slot ``i`` has ``pos_i``
earlier tokens in its cache. The step needs every weight once (the
embedding only for the rows it looks up), reads the ``sum(pos_i)`` live
cache rows of every layer and writes one row per slot. FLOPs count a
multiply and an add as two. Nothing here depends on how the program lays
out or computes the step, so a program that reads less than this still
reads at least this much.
"""
from __future__ import annotations

BF16, F32 = 2, 4


def _sizes(m: dict):
    d, h, k, hd = (m["hidden_size"], m["num_attention_heads"],
                   m["num_key_value_heads"], m["head_dim"])
    f, v, n = m["intermediate_size"], m["vocab_size"], m["num_hidden_layers"]
    layer = d * (h + 2 * k) * hd + h * hd * d + 3 * d * f  # matrix entries
    bias = (h + 2 * k) * hd if m["use_qkv_bias"] else 0
    return d, h, k, hd, v, n, layer, bias


def step_flops(m: dict, active: int, rows: int) -> float:
    """FLOPs of one step: ``active`` tokens, ``rows`` = sum of their
    cache positions."""
    d, h, k, hd, v, n, layer, _ = _sizes(m)
    per_token = n * 2 * layer + 2 * d * v
    attn = n * 2 * 2 * h * hd * (rows + active)  # scores and values, incl. self
    return float(active * per_token + attn)


def step_bytes(m: dict, active: int, rows: int) -> float:
    """Bytes of one step: weights once, live cache rows read, one row written
    per slot."""
    d, h, k, hd, v, n, layer, bias = _sizes(m)
    weights = (n * (layer + bias) + d * v) * BF16 + (2 * n + 1) * d * F32
    embed_rows = active * d * BF16
    row = n * 2 * k * hd * BF16  # one position's keys and values, all layers
    return float(weights + embed_rows + (rows + active) * row)
