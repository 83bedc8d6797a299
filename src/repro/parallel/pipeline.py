"""GPipe-style pipeline parallelism over a mesh axis (e.g. the "pod" axis).

Layers are split into S contiguous stages; each stage's parameter slice is
sharded onto its device group; microbatches stream through a
collective_permute ring. Backward is plain autodiff through ppermute, giving
the standard GPipe fill/drain schedule (bubble fraction (S-1)/(M+S-1)).

Self-contained shard_map implementation, exercised by tests on a host mesh
and available as a multi-pod option (DESIGN.md §5): with pod=2, cross-pod
traffic becomes one activation ppermute per microbatch per boundary instead
of every layer's FSDP gather — the right trade when inter-pod bandwidth is
the scarce resource.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(
    mesh: Mesh,
    axis: str,
    stage_fn: Callable,  # (stage_params, x) -> y, same shape
    stacked_params,      # leaves: (num_stages, ...) — sharded over `axis`
    microbatches: jax.Array,  # (M, mb, ...) — replicated input stream
) -> jax.Array:
    """Returns (M, mb, ...) outputs after all S stages."""
    num_stages = mesh.shape[axis]
    m_count = microbatches.shape[0]
    steps = m_count + num_stages - 1
    perm = [(i, i + 1) for i in range(num_stages - 1)]

    def per_stage(params_local, mbs):
        # params_local leaves: (1, ...) — this stage's slice
        params_local = jax.tree.map(lambda v: v[0], params_local)
        s = jax.lax.axis_index(axis)
        zero = jnp.zeros_like(mbs[0])
        state = zero
        outs = []
        for t in range(steps):
            inject = mbs[t] if t < m_count else zero
            x_in = jnp.where(s == 0, inject, state)
            y = stage_fn(params_local, x_in)
            if t >= num_stages - 1:
                # finished microbatch leaves the last stage
                outs.append(jnp.where(s == num_stages - 1, y, 0.0))
            state = jax.lax.ppermute(y, axis, perm)
        out = jnp.stack(outs)  # (M, mb, ...) nonzero only on last stage
        return jax.lax.psum(out, axis)

    in_specs = (
        jax.tree.map(lambda _: P(axis), stacked_params),
        P(),  # microbatch stream replicated across stages
    )
    fn = shard_map(per_stage, mesh=mesh, in_specs=in_specs, out_specs=P(),
                   check_vma=False)
    return fn(stacked_params, microbatches)


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_microbatches + num_stages - 1)
