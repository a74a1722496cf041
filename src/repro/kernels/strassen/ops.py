"""Jitted public wrappers composing the fused Strassen kernels.

Three pipelines, in increasing distance from the paper:

* :func:`strassen_matmul_stages` — paper-faithful staging (every divide /
  combine level materialized, like Stark's shuffles) but with each stage's
  adds fused by the divide/combine kernels and leaves on the MXU kernel.
* :func:`strassen_matmul_fused`  — the beyond-paper pipeline: unrolled
  BFS levels down to the last, which runs entirely in-kernel
  (divide + 7 products + combine per tile). Used by backend 'strassen_fused'.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.coefficients import get_scheme
from repro.core.strassen import (
    accumulation_dtype,
    combine_level,
    combine_level_quadrants,
    divide_level,
    divide_level_quadrants,
    merge_quadrants,
    split_quadrants,
)
from repro.kernels.common import cdiv
from repro.kernels.matmul.matmul import batched_matmul_pallas
from repro.kernels.strassen.strassen import (
    LEAF_BLOCK,
    combine_pallas,
    divide_pallas,
    strassen1_matmul_pallas,
)

__all__ = [
    "strassen_matmul_stages",
    "strassen_matmul_fused",
    "strassen_matmul_fused_padded",
]

# The lane alignment a quadrant larger than the leaf's narrow block must meet.
_LEAF_ALIGN = 128


@functools.partial(jax.jit, static_argnames=("depth", "scheme_name", "interpret"))
def strassen_matmul_stages(
    a: jax.Array,
    b: jax.Array,
    *,
    depth: int = 1,
    scheme_name: str = "strassen",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Stage-by-stage Stark pipeline with per-stage Pallas kernels."""
    scheme = get_scheme(scheme_name)
    ta, tb = a[None], b[None]
    for _ in range(depth):
        ta = divide_pallas(split_quadrants(ta), scheme.a_coef, interpret=interpret)
        ta = ta.reshape(-1, *ta.shape[2:])
        tb = divide_pallas(split_quadrants(tb), scheme.b_coef, interpret=interpret)
        tb = tb.reshape(-1, *tb.shape[2:])
    prod = batched_matmul_pallas(ta, tb, interpret=interpret)
    for _ in range(depth):
        grouped = prod.reshape(-1, scheme.n_mults, *prod.shape[1:])
        quads = combine_pallas(grouped, scheme.c_coef, interpret=interpret)
        prod = merge_quadrants(quads)
    return prod[0]


@functools.partial(jax.jit, static_argnames=("depth", "scheme_name", "interpret", "precision"))
def strassen_matmul_fused(
    a: jax.Array,
    b: jax.Array,
    *,
    depth: int = 1,
    scheme_name: str = "strassen",
    interpret: Optional[bool] = None,
    precision: Optional[str] = None,
) -> jax.Array:
    """Fused pipeline: last level runs fully in-kernel (DFS step in VMEM).

    depth-1 outer levels are unrolled BFS levels (shardable; exact
    signed sums, see :func:`repro.core.strassen.divide_level`); the final
    level never materializes its 7/4x intermediates, runs its products at
    the caller's ``precision`` and always accumulates in fp32 on the MXU
    (the kernel's preferred_element_type).
    """
    if depth < 1:
        raise ValueError("fused pipeline needs depth >= 1")
    scheme = get_scheme(scheme_name)
    a_coef, b_coef, c_coef = scheme.a_coef, scheme.b_coef, scheme.c_coef

    out_dtype = jnp.result_type(a, b)
    leaf = functools.partial(
        strassen1_matmul_pallas, scheme=scheme, precision=precision,
        interpret=interpret,
    )
    if depth == 1:
        cq = leaf(split_quadrants(a)[None], split_quadrants(b)[None], out_dtype=out_dtype)
        return merge_quadrants(cq[0])

    # Every outer level addresses quadrants by static slices; the last
    # divide writes the leaf's (m*7, 4, h, w) operands directly and the
    # first combine reads its output in place, so no level relayouts.
    # At depth 2 the divide reads A and B as they are: through a unit batch
    # axis, XLA on a TPU lays the leaf operands out in another order and
    # then copies them.
    ta, tb = (a, b) if depth == 2 else (a[None], b[None])
    for _ in range(depth - 2):
        ta = divide_level(ta, a_coef)
        tb = divide_level(tb, b_coef)
    # The kernel hands its products over in the accumulation dtype,
    # rounding once after the last combine.
    acc_dtype = accumulation_dtype(out_dtype)
    cq = leaf(
        divide_level_quadrants(ta, a_coef), divide_level_quadrants(tb, b_coef),
        out_dtype=acc_dtype,
    )
    prod = combine_level_quadrants(cq, c_coef, out_dtype if depth == 2 else acc_dtype)
    for _ in range(depth - 2):
        prod = combine_level(prod, c_coef)
    return prod[0].astype(out_dtype)


def _padded_dim(d: int, depth: int) -> int:
    """Smallest size >= d whose depth-level quadrant the leaf can tile.

    The quadrant is ``size / 2**depth``. Up to the leaf's narrow block
    it is one whole block, legal at any size; above it, it must be a
    multiple of the 128-lane alignment so an aligned block divides it.
    """
    step = 2**depth
    q = cdiv(d, step)
    if q > LEAF_BLOCK:
        q = cdiv(q, _LEAF_ALIGN) * _LEAF_ALIGN
    return q * step


@functools.partial(
    jax.jit, static_argnames=("depth", "scheme_name", "interpret", "precision")
)
def strassen_matmul_fused_padded(
    a: jax.Array,
    b: jax.Array,
    *,
    depth: int = 1,
    scheme_name: str = "strassen",
    interpret: Optional[bool] = None,
    precision: Optional[str] = None,
) -> jax.Array:
    """Fused pipeline for arbitrary (M, K) @ (K, N), odd dims included.

    Zero-pads each dim so the leaf kernel gets a legal TPU tiling (see
    :func:`_padded_dim`), runs :func:`strassen_matmul_fused`, and slices
    back. Padding rows/columns contribute exactly zero to every M-term
    (the scheme is bilinear), so the unpadded block of C is exact — the
    same argument Stark uses for its non-power-of-two Block layout.
    """
    m, k = a.shape
    n = b.shape[1]
    mp, kp, np_ = (_padded_dim(d, depth) for d in (m, k, n))
    if (mp, kp, np_) == (m, k, n):
        return strassen_matmul_fused(
            a, b, depth=depth, scheme_name=scheme_name,
            interpret=interpret, precision=precision,
        )
    a_p = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    b_p = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    out = strassen_matmul_fused(
        a_p, b_p, depth=depth, scheme_name=scheme_name,
        interpret=interpret, precision=precision,
    )
    return out[:m, :n]
