"""Seeded random weights, made by the benchmark in one jitted call.

Each architecture (`bench/archs/<arch>.py`) names its weights and their
shapes, draws each one, and chooses its dtype: the type the serving path
keeps its parameters in. A flat dict of arrays is the benchmark's own
layout, which the architecture's reference reads as it is; the module's
`to_program` hands the same arrays to the system under test in the shape of
its parameter tree, so the reference takes nothing the program has made.
"""
from __future__ import annotations

import functools

import jax


def seed_key(seed: int):
    """A PRNG key from a seed of any size (two 32-bit halves folded in)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _maker(items: tuple, draw):
    def make(key):
        keys = jax.random.split(key, len(items))
        return {name: draw(k, name, shape)
                for k, (name, shape) in zip(keys, items)}
    return jax.jit(make)


def make(shapes: dict, draw, seed: int) -> dict:
    """Every weight of `shapes` (name -> shape), on the default device, from
    `seed`: one key per name in sorted order, `draw(key, name, shape)`."""
    items = tuple(sorted((k, tuple(v)) for k, v in shapes.items()))
    return _maker(items, draw)(seed_key(seed))
