"""Fused Strassen Pallas kernels — the beyond-paper TPU adaptation.

Stark materializes every divide/combine level through a Spark shuffle:
quadrants are replicated (4 copies of A11, 2 of A12, ...) and written to
disk between stages. On TPU the same linear maps are memory-bound
elementwise ops, so we fuse them:

* :func:`divide_pallas` / :func:`combine_pallas` — one level's 18 block
  additions in a single HBM pass (read 4 quadrant tiles, write 7 operand
  tiles, or read 7 product tiles, write 4 C tiles). No replication is ever
  materialized — the coefficient matrix is folded into the kernel as
  compile-time +/-1 constants.

* :func:`strassen1_matmul_pallas` — a full "DFS step in-kernel" (CAPS
  vocabulary): the LAST recursion level's divide, 7 leaf products, and
  combine all happen per-tile in VMEM. A and B quadrant tiles are read
  once from HBM; the 7 operand combinations, 7 MXU matmuls into 7 fp32
  accumulators, and the 4-quadrant combine never touch HBM. This removes
  the (7/4)^1 intermediate blowup of the last level — the dominant HBM
  term — and is the kernel :func:`repro.core.backend.matmul` uses for
  kind='strassen_fused'.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.coefficients import Scheme, STRASSEN, get_scheme
from repro.kernels.common import default_interpret, pick_block

__all__ = [
    "divide_pallas",
    "combine_pallas",
    "strassen1_matmul_pallas",
]


def _signed_sum(refs_slice, coefs) -> jax.Array:
    """Sum_q coefs[q] * refs_slice[q] with compile-time-skipped zeros."""
    acc = None
    for q, c in enumerate(coefs):
        c = float(c)
        if c == 0.0:
            continue
        term = refs_slice[q]
        if c == -1.0:
            term = -term
        elif c != 1.0:
            term = c * term
        acc = term if acc is None else acc + term
    assert acc is not None
    return acc


def _divide_kernel(coef: np.ndarray, x_ref, o_ref):
    """(1, 4, bh, bw) quadrant tile -> (1, r, bh, bw) operand tile."""
    quads = [x_ref[0, q] for q in range(4)]
    for p in range(coef.shape[0]):
        o_ref[0, p] = _signed_sum(quads, coef[p]).astype(o_ref.dtype)


def divide_pallas(
    x: jax.Array,
    coef: np.ndarray,
    *,
    block: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One divide level on quadrant layout: (m, 4, h, w) -> (m, r, h, w).

    Equivalent to ``einsum('pq,mqij->mpij', coef, x)`` but with the adds
    fused into one read of x — Stark's flatMapToPair+groupByKey+flatMap
    divide stage as a single HBM pass.
    """
    if interpret is None:
        interpret = default_interpret()
    m, four, h, w = x.shape
    assert four == 4, x.shape
    r = coef.shape[0]
    bh = pick_block(h, block, interpret=interpret)
    bw = pick_block(w, block, interpret=interpret)
    return pl.pallas_call(
        functools.partial(_divide_kernel, np.asarray(coef)),
        grid=(m, h // bh, w // bw),
        in_specs=[pl.BlockSpec((1, 4, bh, bw), lambda s, i, j: (s, 0, i, j))],
        out_specs=pl.BlockSpec((1, r, bh, bw), lambda s, i, j: (s, 0, i, j)),
        out_shape=jax.ShapeDtypeStruct((m, r, h, w), x.dtype),
        interpret=interpret,
        name="strassen_divide",
    )(x)


def _combine_kernel(c_coef: np.ndarray, p_ref, o_ref):
    """(1, r, bh, bw) product tile -> (1, 4, bh, bw) C-quadrant tile."""
    r = c_coef.shape[1]
    prods = [p_ref[0, p] for p in range(r)]
    for k in range(4):
        o_ref[0, k] = _signed_sum(prods, c_coef[k]).astype(o_ref.dtype)


def combine_pallas(
    products: jax.Array,
    c_coef: np.ndarray,
    *,
    block: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One combine level on quadrant layout: (m, r, h, w) -> (m, 4, h, w)."""
    if interpret is None:
        interpret = default_interpret()
    m, r, h, w = products.shape
    assert r == c_coef.shape[1], (products.shape, c_coef.shape)
    bh = pick_block(h, block, interpret=interpret)
    bw = pick_block(w, block, interpret=interpret)
    return pl.pallas_call(
        functools.partial(_combine_kernel, np.asarray(c_coef)),
        grid=(m, h // bh, w // bw),
        in_specs=[pl.BlockSpec((1, r, bh, bw), lambda s, i, j: (s, 0, i, j))],
        out_specs=pl.BlockSpec((1, 4, bh, bw), lambda s, i, j: (s, 0, i, j)),
        out_shape=jax.ShapeDtypeStruct((m, 4, h, w), products.dtype),
        interpret=interpret,
        name="strassen_combine",
    )(products)


def _strassen1_kernel(scheme: Scheme, precision, aq_ref, bq_ref, o_ref, acc_ref):
    """One (s, i, j, k) grid step of the fused one-level Strassen matmul.

    VMEM residency per step: 4 A-quadrant tiles, 4 B-quadrant tiles, the
    r=7 fp32 accumulators, and (at the last k) the 4 output tiles. Operand
    combos exist only in VREGs.
    """
    r = scheme.n_mults

    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a_quads = [aq_ref[0, q] for q in range(4)]
    b_quads = [bq_ref[0, q] for q in range(4)]
    for p in range(r):
        left = _signed_sum(a_quads, scheme.a_coef[p])
        right = _signed_sum(b_quads, scheme.b_coef[p])
        acc_ref[p] += jnp.dot(
            left, right, preferred_element_type=jnp.float32, precision=precision
        )

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _flush():
        prods = [acc_ref[p] for p in range(r)]
        for k in range(4):
            o_ref[0, k] = _signed_sum(prods, scheme.c_coef[k]).astype(o_ref.dtype)


def _leaf_precision(precision: Optional[str]):
    """Map a jax precision name onto the two that Mosaic's dot supports.

    'default' / 'fastest' / 'bfloat16' (and None) run at Mosaic's default;
    every stronger request runs at HIGHEST, never below what was asked.
    """
    if precision in (None, "default", "fastest", "bfloat16"):
        return None
    if precision in ("high", "highest", "tensorfloat32", "float32"):
        return jax.lax.Precision.HIGHEST
    raise ValueError(f"unknown matmul precision {precision!r}")


def strassen1_matmul_pallas(
    aq: jax.Array,
    bq: jax.Array,
    *,
    scheme: Scheme | str = STRASSEN,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 256,
    out_dtype=None,
    precision: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused one-level Strassen on quadrant layout.

    Args:
      aq: (mb, 4, M2, K2) A-quadrants (batched over mb leaves).
      bq: (mb, 4, K2, N2) B-quadrants.
      precision: jax precision name for the 7 leaf products (see
        :func:`_leaf_precision`); None is Mosaic's default.

    Returns:
      (mb, 4, M2, N2) C-quadrants.
    """
    if isinstance(scheme, str):
        scheme = get_scheme(scheme)
    if interpret is None:
        interpret = default_interpret()
    mb, four, m2, k2 = aq.shape
    _, _, _, n2 = bq.shape
    assert four == 4 and bq.shape[:2] == (mb, 4) and bq.shape[2] == k2
    bm, bn, bk = (
        pick_block(d, blk, interpret=interpret)
        for d, blk in ((m2, block_m), (n2, block_n), (k2, block_k))
    )
    out_dtype = out_dtype or aq.dtype
    return pl.pallas_call(
        functools.partial(_strassen1_kernel, scheme, _leaf_precision(precision)),
        grid=(mb, m2 // bm, n2 // bn, k2 // bk),
        in_specs=[
            pl.BlockSpec((1, 4, bm, bk), lambda s, i, j, kk: (s, 0, i, kk)),
            pl.BlockSpec((1, 4, bk, bn), lambda s, i, j, kk: (s, 0, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, 4, bm, bn), lambda s, i, j, kk: (s, 0, i, j)),
        out_shape=jax.ShapeDtypeStruct((mb, 4, m2, n2), out_dtype),
        scratch_shapes=[pltpu.VMEM((scheme.n_mults, bm, bn), jnp.float32)],
        interpret=interpret,
        name="strassen_fused_leaf",
    )(aq, bq)
