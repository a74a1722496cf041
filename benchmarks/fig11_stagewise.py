"""Paper Fig. 11 / Tables VIII-X: stage-wise time breakdown.

Times Stark's three sections separately — divide levels, leaf batched
multiply, combine levels — by jitting each phase as its own program
(the Spark analogue of per-stage wall-clock from the event log). Confirms
the paper's finding: leaf multiplication dominates at small b; the
divide/combine share grows with depth.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.common import emit, rand, time_fn
from repro.core.coefficients import STRASSEN
from repro.core.strassen import combine_level, divide_level

SIZES = (1024,)
DEPTHS = (1, 2, 3)


def _divide_phase(a, b, depth):
    ac, bc = STRASSEN.a_coef, STRASSEN.b_coef
    ta, tb = a[None], b[None]
    for _ in range(depth):
        ta = divide_level(ta, ac)
        tb = divide_level(tb, bc)
    return ta, tb


def _leaf_phase(ta, tb):
    return jnp.einsum("mij,mjk->mik", ta, tb)


def _combine_phase(prod, depth):
    cc = STRASSEN.c_coef
    for _ in range(depth):
        prod = combine_level(prod, cc)
    return prod[0]


def run():
    rows = []
    for n in SIZES:
        a, b = rand((n, n)), rand((n, n))
        for depth in DEPTHS:
            div = jax.jit(functools.partial(_divide_phase, depth=depth))
            t_div = time_fn(div, a, b)
            ta, tb = jax.block_until_ready(div(a, b))
            leaf = jax.jit(_leaf_phase)
            t_leaf = time_fn(leaf, ta, tb)
            prod = jax.block_until_ready(leaf(ta, tb))
            comb = jax.jit(functools.partial(_combine_phase, depth=depth))
            t_comb = time_fn(comb, prod)
            total = t_div + t_leaf + t_comb
            rows.append(
                emit(
                    f"fig11/stark/n{n}/b{2**depth}", total,
                    f"divide={t_div/total:.0%};leaf={t_leaf/total:.0%};combine={t_comb/total:.0%}",
                )
            )
    return rows
