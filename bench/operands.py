"""Seeded operands, made on the device in one jitted call.

The same ``--seed`` gives the same operands on every run. A seed may be any
non-negative integer, also one wider than 32 bits: it is spread into
threefry keys by numpy's ``SeedSequence``, one key per matrix.

A mix lists its operand pairs by the kind of their entries:

* ``"normal"``: standard normal entries;
* ``"integers"``: whole numbers drawn evenly from ``[-max, max]``. With
  ``max`` small, every divide sum, leaf product and combine sum of a
  Strassen product is a whole number that float32 holds exactly, and that
  bfloat16 does not once it passes 256: the product of a program that
  accumulates in float32 equals the exact product rounded once, and one that
  holds its partial sums in bfloat16 does not.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

KINDS = ("normal", "integers")


def key_words(seed: int, count: int) -> np.ndarray:
    """``count`` threefry key words pairs, (count, 2) uint32, from ``seed``."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    state = np.random.SeedSequence(seed).generate_state(2 * count, dtype=np.uint32)
    return state.reshape(count, 2)


def operand_pairs(
    seed: int,
    kinds: Sequence[str],
    a_shape: Sequence[int],
    b_shape: Sequence[int],
    dtype: str,
    sharding=None,
    integer_max: int = 8,
) -> List[Tuple["jax.Array", "jax.Array"]]:  # noqa: F821
    """One (A, B) operand pair in ``dtype`` per entry of ``kinds``.

    One jitted call makes all of them where ``sharding`` puts them (device
    0 when None).
    """
    import jax
    import jax.numpy as jnp

    for kind in kinds:
        if kind not in KINDS:
            raise ValueError(f"unknown operand kind {kind!r}; known: {KINDS}")
    words = jnp.asarray(key_words(seed, 2 * len(kinds)))
    shapes = [tuple(a_shape), tuple(b_shape)] * len(kinds)
    dt = jnp.dtype(dtype)

    def one(key, shape, kind):
        if kind == "normal":
            return jax.random.normal(key, shape, dt)
        return jax.random.randint(key, shape, -integer_max, integer_max + 1).astype(dt)

    def make(w):
        keys = [jax.random.wrap_key_data(w[i], impl="threefry2x32") for i in range(len(shapes))]
        return [one(k, s, kinds[i // 2]) for i, (k, s) in enumerate(zip(keys, shapes))]

    out_shardings = None if sharding is None else [sharding] * len(shapes)
    arrays = jax.jit(make, out_shardings=out_shardings)(words)
    jax.block_until_ready(arrays)
    return [(arrays[2 * p], arrays[2 * p + 1]) for p in range(len(kinds))]
