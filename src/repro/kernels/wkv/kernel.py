"""Pallas TPU kernel for the chunked WKV6 recurrence (RWKV "Finch").

TPU adaptation: the per-token scalar recurrence (a GPU warp-level pattern in
the reference CUDA kernel) is re-blocked into chunk-parallel MXU matmuls —
intra-chunk contributions become a (cs × cs) masked matmul, the cross-chunk
state is a (D × D) f32 VMEM scratch carried across the sequential chunk grid
dimension. This is the standard GPU→TPU re-codesign: recurrence → blocked
scan so the MXU (not the VPU) does the heavy lifting.

Grid: (B*H, num_chunks) — chunk axis fastest (sequential), state persists.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_out_ref, state_ref,
                *, num_chunks: int, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)  # (cs, D)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0]  # (1, D) -> broadcast
    S = state_ref[...]  # (D, Dv)

    cs = r.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (cs, cs), 1)
    # inclusive prefix sum over the chunk as a lower-triangular ones matmul:
    # the chip's compiler has no cumsum lowering
    cum = jnp.dot(jnp.where(col <= row, 1.0, 0.0), lw,
                  precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=jnp.float32)  # (cs, D)
    q_dec = jnp.exp(cum - lw)  # decay chunk-start -> t-1
    k_dec = jnp.exp(-cum)
    A = (r * q_dec) @ (k * k_dec).T  # (cs, cs)
    A = jnp.where(col < row, A, 0.0)
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)  # (cs, 1)
    out = A @ v + diag * v
    out = out + (r * q_dec) @ S

    total = cum[cs - 1:]  # (1, D)
    carry_k = k * jnp.exp(total - cum)
    state_ref[...] = S * jnp.exp(total).T + carry_k.T @ v
    o_ref[0] = out.astype(o_ref.dtype)

    @pl.when(ci == num_chunks - 1)
    def _emit_state():
        s_out_ref[0] = state_ref[...]


def wkv_pallas(r, k, v, lw, u, *, chunk: int = 64, interpret: bool = False):
    """r,k,v,lw: (B, H, S, D); u: (H, D). Returns (out, final_state (B,H,D,D))."""
    b, h, s, d = r.shape
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    def flat(x):
        return x.reshape(b * h, s, d)

    u_flat = jnp.broadcast_to(u[None], (b, h, d)).reshape(b * h, 1, d)

    out, s_out = pl.pallas_call(
        functools.partial(_wkv_kernel, num_chunks=nc, chunk=chunk),
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, d), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, d, d), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), r.dtype),
            jax.ShapeDtypeStruct((b * h, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        interpret=interpret,
    )(flat(r), flat(k), flat(v), flat(lw), u_flat)
    return out.reshape(b, h, s, d), s_out.reshape(b, h, d, d)
