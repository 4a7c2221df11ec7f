"""Seeded random weights, made on the device in one jitted call.

A family module describes its leaves as `Leaf(shape, std, mean)` in the
program's parameter layout; `make` draws every leaf from the seed in one
program and returns them in the served dtype. The benchmark makes the
weights itself, so the reference reads nothing the program made.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Leaf:
    shape: tuple
    std: float
    mean: float = 0.0


def _is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any seed up to 64 bits (two 32-bit folds)."""
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.key(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def make(layout: dict, seed: int, dtype) -> dict:
    leaves, tree = jax.tree.flatten(layout, is_leaf=_is_leaf)

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(leaves))
        return [(lf.mean + lf.std * jax.random.normal(k, lf.shape,
                                                      jnp.float32)
                 ).astype(dtype) for k, lf in zip(keys, leaves)]

    return jax.tree.unflatten(tree, draw(seed_key(seed)))


def check_layout(params: dict, program_shapes: dict) -> None:
    """Raise unless `params` has the program's tree, shapes and dtypes."""
    got = jax.tree.map(lambda a: (a.shape, jnp.dtype(a.dtype)), params)
    want = jax.tree.map(lambda a: (a.shape, jnp.dtype(a.dtype)),
                        program_shapes)
    if got != want:
        raise ValueError("benchmark weights do not match the program's "
                         f"parameter layout:\n got  {got}\n want {want}")
