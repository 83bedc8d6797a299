"""Seeded weights, made on the device in one jitted call.

The layout of each family (names, shapes, dtypes, how each leaf is drawn)
is the reference's (``bench/reference/<family>.py``): the reference and the
system under test read the same tree, and the reference makes it again from
the seed rather than taking the system's arrays.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class Leaf:
    shape: tuple
    dtype: str = "bfloat16"
    init: str = "normal"  # normal: std * N(0,1); one_plus: 1 + std * N(0,1); uniform: [lo, hi)
    std: float = 0.02
    lo: float = 0.0
    hi: float = 1.0


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number: the low and high 32 bits both count."""
    seed %= 2**64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _draw(key, leaf: Leaf):
    if leaf.init == "uniform":
        v = jax.random.uniform(key, leaf.shape, jnp.float32, leaf.lo, leaf.hi)
    else:
        v = jax.random.normal(key, leaf.shape, jnp.float32) * leaf.std
        if leaf.init == "one_plus":
            v = v + 1.0
    return v.astype(leaf.dtype)


def make_params(layout, seed: int):
    """Every leaf of ``layout`` drawn from ``seed``, on the default device."""
    leaves, treedef = jax.tree.flatten(
        layout, is_leaf=lambda x: isinstance(x, Leaf))

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(
            treedef, [_draw(k, leaf) for k, leaf in zip(keys, leaves)])

    return build(seed_key(seed))


def shapes_of(layout):
    """(shape, dtype) of every leaf, for comparison with another tree."""
    return jax.tree.map(lambda leaf: (tuple(leaf.shape), jnp.dtype(leaf.dtype)),
                        layout, is_leaf=lambda x: isinstance(x, Leaf))
