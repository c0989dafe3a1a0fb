"""Weights from ``--seed``, made by the benchmark for the program and for
the plain reference alike: one jitted call on the device, every leaf drawn
from its own fold of the seed, so the same seed gives the same model
whoever asks, in whatever order and in however many parts.

A ``table`` is ``{name: (shape, kind)}``, as a family's ``leaf_table``
gives it (``benchmark/families/<family>.py``); the names are those by which
the program takes its weights. The rule is the published initialisation:
N(0, 0.02) for a ``matrix`` (embeddings too), ones for a ``scale`` and
zeros for a ``bias``; scales and biases here get a small seeded
perturbation so that a path that dropped one would show in ``correct``.
"""
from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp

INIT_STD = 0.02
BIAS_STD = 0.01


def num_params(table: dict) -> int:
    return sum(math.prod(shape) for shape, _ in table.values())


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _leaf(key, name: str, shape, kind: str):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    x = jax.random.normal(k, shape, jnp.float32)
    if kind == "matrix":
        return INIT_STD * x
    if kind == "scale":
        return 1.0 + BIAS_STD * x
    return BIAS_STD * x


def make_weights(table: dict, seed: int, dtype=jnp.float32,
                 only=None) -> dict:
    """Every leaf of ``table``, drawn in float32 and rounded to ``dtype``
    (the type the configuration trains or serves in), from one jitted call.
    ``only`` draws a part: the leaves whose name starts with it (a prefix)
    or is in it (a set), with the values the whole call gives them, so that
    a reference can hold one layer at a time."""
    if isinstance(only, str):
        table = {n: t for n, t in table.items() if n.startswith(only)}
    elif only is not None:
        table = {n: table[n] for n in only}

    @jax.jit
    def make(key):
        return {n: _leaf(key, n, s, k).astype(dtype)
                for n, (s, k) in table.items()}

    return make(seed_key(seed))


@jax.jit
def _held_in_float32(tree: dict) -> dict:
    return {n: v.astype(jnp.float32) for n, v in tree.items()}


def for_program(family, config: dict, seed: int) -> dict:
    """The leaves of a configuration's model as the program gets them: in
    the type the configuration trains or serves in."""
    return make_weights(family.leaf_table(config["model"]), seed,
                        config["precision"]["parameters"])


def for_reference(family, config: dict, seed: int):
    """``leaves_of(only=None)``, as a family's reference draws its leaves:
    the values the program got, held in float32, all of them or the part
    that ``only`` names. A reference that asks for all can hold all, and
    gets the same dict each time. Two calls and not one: inside one program
    the TPU compiler drops a cast down and up again as excess precision."""
    table = family.leaf_table(config["model"])
    dtype = jnp.dtype(config["precision"]["parameters"])

    def draw(only):
        low = make_weights(table, seed, dtype, only)
        return low if dtype == jnp.float32 else _held_in_float32(low)

    whole = functools.cache(lambda: draw(None))
    return lambda only=None: whole() if only is None else draw(only)
