"""Work that one decode step of Granite 4.0-H at one chip's share needs,
from shapes alone.

A step serves ``active`` slots, one token each; ``rows`` is the sum of
their cache positions. The step needs:

* every weight once, the embedding only once (the tied output head reads
  it whole; the lookup reads ``active`` rows of it again), and all
  ``num_local_experts`` held experts of every layer: at 24 or more active
  tokens, a held expert goes unhit in a layer with probability
  ``(1 - 10/72)^24``, about 3%, so the step reads nearly all of them;
* each active slot's Mamba2 state read and written once: the SSM state
  (float32) and the conv's last ``mamba_d_conv - 1`` inputs (bfloat16) of
  every Mamba layer;
* the attention layers' live KV rows read, and one row written per slot.

FLOPs count a multiply and an add as two. A token's routed work is its
expected share of the held experts, ``num_experts_per_tok *
num_local_experts / router_experts`` expert FFNs; the SSM update and
readout cost ``6 hd ns`` FLOPs per head and token (decay, the outer
product of two, add; the readout's two).
"""
from __future__ import annotations

from bench.reference.granite_hybrid import _sizes, kinds

BF16, F32 = 2, 4


def _counts(m: dict):
    s = _sizes(m)
    d, di, ns, nh, conv = s["d"], s["di"], s["ns"], s["nh"], s["conv"]
    taps = m["mamba_d_conv"]
    mamba = d * (2 * di + 2 * ns + nh) + di * d  # matrix entries
    mamba_vec = taps * conv + conv + 3 * nh  # conv taps and bias, A, D, dt
    attn = d * (s["h"] + 2 * s["k"]) * s["hd"] + s["h"] * s["hd"] * d
    expert = 3 * d * s["f"]
    shared = 3 * d * s["fs"]
    state = nh * (di // nh) * ns * F32 + (taps - 1) * conv * BF16
    return s, kinds(m).count("mamba"), kinds(m).count("attention"), (
        mamba, mamba_vec, attn, expert, shared, state)


def step_flops(m: dict, active: int, rows: int) -> float:
    s, nm, na, (mamba, _, attn, expert, shared, _) = _counts(m)
    routed = m["num_experts_per_tok"] * s["eh"] / s["e"]
    per_token = (nm * (2 * mamba + 6 * s["di"] * s["ns"])
                 + na * 2 * attn
                 + s["n"] * (2 * s["d"] * s["e"] + routed * 2 * expert
                             + 2 * shared)
                 + 2 * s["d"] * s["v"])
    scores = na * 2 * 2 * s["h"] * s["hd"] * (rows + active)
    return float(active * per_token + scores)


def step_bytes(m: dict, active: int, rows: int) -> float:
    s, nm, na, (mamba, mamba_vec, attn, expert, shared, state) = _counts(m)
    d, n = s["d"], s["n"]
    weights = ((nm * (mamba + mamba_vec) + na * attn
                + n * (s["eh"] * expert + shared) + s["v"] * d) * BF16
               + (nm * s["di"] + n * (d * s["e"] + 2 * d) + d) * F32)
    kv_row = na * 2 * s["k"] * s["hd"] * BF16  # all attention layers
    return float(weights + active * d * BF16 + active * 2 * nm * state
                 + (rows + active) * kv_row)
