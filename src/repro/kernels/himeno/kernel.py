"""Pallas TPU kernel for the Himeno 19-point stencil Jacobi sweep.

TPU adaptation of the paper's GPU-offloaded loop nest: the i-axis becomes the
sequential grid dimension; each grid step holds three overlapping (1, J, K)
pressure slabs in VMEM (the same array bound three times with shifted
index_maps — the BlockSpec halo idiom), computes the full 34-FLOP/point
stencil on the VPU, and writes one slab + one partial-gosa tile. j/k
shifts are register-level static slices, so HBM traffic is exactly one read
of each operand and one write of the result — the transfer-batching insight
of the paper's [31] expressed as VMEM blocking.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_GOSA_TILE = (8, 128)  # one f32 vreg tile


def _jacobi_kernel(p_m1, p_0, p_p1, a, b, c, bnd, wrk1,
                   p_out, gosa_out, *, omega: float, num_i: int):
    i = pl.program_id(0)
    pm, pc, pp = p_m1[0], p_0[0], p_p1[0]  # (J, K) slabs

    C = slice(1, -1)
    P, N = slice(2, None), slice(0, -2)

    s0 = (
        a[0, 0][C, C] * pp[C, C]
        + a[1, 0][C, C] * pc[P, C]
        + a[2, 0][C, C] * pc[C, P]
        + b[0, 0][C, C] * (pp[P, C] - pp[N, C] - pm[P, C] + pm[N, C])
        + b[1, 0][C, C] * (pc[P, P] - pc[N, P] - pc[P, N] + pc[N, N])
        + b[2, 0][C, C] * (pp[C, P] - pm[C, P] - pp[C, N] + pm[C, N])
        + c[0, 0][C, C] * pm[C, C]
        + c[1, 0][C, C] * pc[N, C]
        + c[2, 0][C, C] * pc[C, N]
        + wrk1[0][C, C]
    )
    ss = (s0 * a[3, 0][C, C] - pc[C, C]) * bnd[0][C, C]
    interior = (i > 0) & (i < num_i - 1)
    ss = jnp.where(interior, ss, 0.0)

    # interior update without a scatter (Mosaic lowers none): the zero
    # border of the padded increment passes the boundary through unchanged
    p_out[0] = (pc + omega * jnp.pad(ss, ((1, 1), (1, 1)))).astype(p_out.dtype)
    gosa_out[...] = jnp.full(gosa_out.shape,
                             jnp.sum(jnp.square(ss.astype(jnp.float32))))


def himeno_jacobi_pallas(p, a, b, c, bnd, wrk1, *, omega: float = 0.8,
                         interpret: bool = False):
    """One Jacobi sweep via pallas_call. p: (I,J,K) f32. Returns (p_new, gosa)."""
    num_i, J, K = p.shape

    def idx_shift(d):
        return lambda i: (jnp.clip(i + d, 0, num_i - 1), 0, 0)

    p_spec = lambda d: pl.BlockSpec((1, J, K), idx_shift(d))
    coef = lambda n: pl.BlockSpec((n, 1, J, K), lambda i: (0, i, 0, 0))
    plain = pl.BlockSpec((1, J, K), lambda i: (i, 0, 0))
    # each step's partial gosa fills one (8, 128) f32 tile: a rank-1 block
    # of one element is not tile-aligned, so the chip's compiler refuses it
    gosa_spec = pl.BlockSpec((1,) + _GOSA_TILE, lambda i: (i, 0, 0))

    p_new, gosa_parts = pl.pallas_call(
        functools.partial(_jacobi_kernel, omega=omega, num_i=num_i),
        grid=(num_i,),
        in_specs=[p_spec(-1), p_spec(0), p_spec(+1),
                  coef(4), coef(3), coef(3), plain, plain],
        out_specs=[plain, gosa_spec],
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype),
                   jax.ShapeDtypeStruct((num_i,) + _GOSA_TILE, jnp.float32)],
        interpret=interpret,
    )(p, p, p, a, b, c, bnd, wrk1)
    return p_new, jnp.sum(gosa_parts[:, 0, 0])
