"""Seeded weights, made on the device in the type they are served in.

A leaf is (name, shape, kind): ``normal`` (std 0.02, the GPT-2
initialisation), ``ones`` or ``zeros``. Leaf ``i`` of a spec depends on
(seed, i) alone, so the plain reference can make any leaf again without
taking an array from the program.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from .schedule import seed_words

Leaf = Tuple[str, Tuple[int, ...], str]
INIT_STD = 0.02


def _key(lo, hi):
    return jax.random.fold_in(jax.random.fold_in(jax.random.key(0), lo), hi)


def leaf(lo, hi, index, shape, kind, dtype):
    """Leaf ``index`` of the seed ``(lo, hi)``; traceable (``index`` may be
    a traced integer, ``shape``/``kind``/``dtype`` are static)."""
    if kind == "normal":
        k = jax.random.fold_in(_key(lo, hi), index)
        return (INIT_STD * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    raise ValueError(f"unknown leaf kind {kind!r}")


def make_all(spec: Sequence[Leaf], seed: int, dtype) -> List[jax.Array]:
    """Every leaf of ``spec`` in ONE jitted call."""
    lo, hi = seed_words(seed)

    def build(lo, hi):
        return [leaf(lo, hi, i, tuple(shape), kind, dtype)
                for i, (_, shape, kind) in enumerate(spec)]

    return jax.jit(build)(jnp.uint32(lo), jnp.uint32(hi))


def make_leaf(spec: Sequence[Leaf], seed: int, index: int, dtype) -> jax.Array:
    """Leaf ``index`` alone (bit-equal to ``make_all``'s)."""
    lo, hi = seed_words(seed)
    _, shape, kind = spec[index]
    return _leaf_jit(jnp.uint32(lo), jnp.uint32(hi), jnp.uint32(index),
                     tuple(shape), kind, jnp.dtype(dtype))


_leaf_jit = jax.jit(leaf, static_argnums=(3, 4, 5))
