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
from repro.kernels.common import cdiv, default_interpret, pick_block
from repro.obs.metrics import get_metrics

__all__ = [
    "divide_pallas",
    "combine_pallas",
    "strassen1_matmul_pallas",
    "LEAF_BLOCK",
]

# The fused leaf's narrow tile: every quadrant outside the wide rule is
# tiled by the largest aligned block up to it.
LEAF_BLOCK = 256
# The wide output tile (bm, bn). A (s, i, j, k) step reads 4 A and 4 B
# tiles for 7 products; at 256^3 that is 224 FLOP per byte, under a v5e's
# ridge of 240 (197 TFLOP/s over 819 GB/s), so the leaf streamed its
# operands. At 1024x512 (bk = 256) it is 597 FLOP per byte. On a v5e this
# tile ran the d2 leaves of 16384^2 and of a 32768^2 row stripe 29-31%
# faster than 256^3, 3.4-3.7% faster than 512x512x256 and 0.9-1.5% faster
# than 512x512x512.
_WIDE_TILE = (1024, 512)
# Mosaic's default scoped VMEM limit on a v5e. Blocks whose estimate, with
# headroom, exceeds it carry a limit of their own: every wide tiling does
# (54 MiB for bf16 in and f32 out, under 70 MiB for any dtypes and scheme,
# inside a v5e core's 128 MiB), no narrow one does (at most 12 MiB).
_SCOPED_VMEM_DEFAULT = 16 << 20


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


def _leaf_vmem_bytes(
    bm: int, bn: int, bk: int, in_itemsize: int, out_itemsize: int, n_mults: int
) -> int:
    """VMEM the leaf's blocks hold: the 4+4 quadrant input tiles and the 4
    output tiles, each double-buffered, and the n_mults f32 accumulators."""
    inputs = 2 * 4 * (bm * bk + bk * bn) * in_itemsize
    outputs = 2 * 4 * bm * bn * out_itemsize
    return inputs + outputs + n_mults * bm * bn * 4


def _leaf_vmem_limit(need: int) -> Optional[int]:
    """The scoped VMEM limit to compile ``need`` bytes of blocks with: None
    (the compiler's default) where they fit it with half again to spare,
    else that much rounded up to a MiB. Mosaic's own scratch can take
    more than a quarter again: at 512x512x512 a limit of 1.25 x 23 MiB is
    refused on a v5e."""
    limit = cdiv(need * 3 // 2, 1 << 20) << 20
    return None if limit <= _SCOPED_VMEM_DEFAULT else limit


def _leaf_blocks(m2: int, k2: int, n2: int, *, interpret: bool) -> tuple[int, int, int]:
    """The leaf's (bm, bn, bk) for (m2, k2) @ (k2, n2) quadrants.

    Quadrants whose m2 is a multiple of 1024 and n2 of 512 take the wide
    1024x512 output tile; every other shape keeps the narrow 256 ladder of
    :func:`pick_block`, and so the kernel it always compiled to.
    """
    bk = pick_block(k2, LEAF_BLOCK, interpret=interpret)
    wm, wn = _WIDE_TILE
    if m2 % wm == 0 and n2 % wn == 0:
        return wm, wn, bk
    bm, bn = (pick_block(d, LEAF_BLOCK, interpret=interpret) for d in (m2, n2))
    return bm, bn, bk


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
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
    out_dtype=None,
    precision: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused one-level Strassen on quadrant layout.

    Each call (under ``jit``, each trace) bumps the process counter
    ``kernels.strassen_leaf.blocks.<bm>x<bn>x<bk>``: it counts the kernels
    built per block triple, not the products they run.

    Args:
      aq: (mb, 4, M2, K2) A-quadrants (batched over mb leaves).
      bq: (mb, 4, K2, N2) B-quadrants.
      block_m, block_n, block_k: explicit preferred blocks; where all are
        None the blocks come from the quadrant's shape (:func:`_leaf_blocks`).
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
    out_dtype = jnp.dtype(out_dtype or aq.dtype)
    if block_m is block_n is block_k is None:
        bm, bn, bk = _leaf_blocks(m2, k2, n2, interpret=interpret)
    else:
        bm, bn, bk = (
            pick_block(d, blk or LEAF_BLOCK, interpret=interpret)
            for d, blk in ((m2, block_m), (n2, block_n), (k2, block_k))
        )
    get_metrics().counter(f"kernels.strassen_leaf.blocks.{bm}x{bn}x{bk}").inc()
    vmem_limit = _leaf_vmem_limit(
        _leaf_vmem_bytes(
            bm, bn, bk, aq.dtype.itemsize, out_dtype.itemsize, scheme.n_mults
        )
    )
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
        compiler_params=(
            None if vmem_limit is None
            else pltpu.CompilerParams(vmem_limit_bytes=vmem_limit)
        ),
        interpret=interpret,
        name="strassen_fused_leaf",
    )(aq, bq)
