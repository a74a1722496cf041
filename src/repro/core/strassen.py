"""Strassen matrix multiplication in JAX — serial and batched-BFS forms.

This is the paper's algorithm (Stark) re-expressed TPU-natively:

* :func:`strassen_recursive` — Algorithm 1 of the paper (single node,
  driver-side recursion). Reference implementation.
* :func:`divide_level` / :func:`combine_level` — one *level* of the
  distributed recursion. These are the JAX analogue of Stark's
  flatMapToPair/groupByKey/flatMap divide stage and its combine stage:
  a whole level is processed in parallel as one einsum against a constant
  coefficient matrix. The batch index plays the role of the paper's
  M-index tag (base-7 digits = tag path, see coefficients.leaf_tag_path).
* :func:`strassen_matmul` — the full pipeline: ``depth`` divide levels,
  one batched leaf-multiplication stage (the paper's Algorithm 4 —
  "multiply blocks serially [in parallel executors]" becomes one batched
  einsum or a Pallas MXU kernel), and ``depth`` combine levels.

Rectangular support: the paper (like Strassen 1969) treats square 2^p
matrices "for mathematical brevity". Splitting M, K and N in half each
level makes the identical scheme valid for any (M, K) @ (K, N) with all
three dims divisible by 2**depth; :mod:`repro.core.backend` pads odd dims.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.coefficients import Scheme, STRASSEN, get_scheme

__all__ = [
    "strassen_recursive",
    "split_quadrants",
    "merge_quadrants",
    "divide_level",
    "divide_level_quadrants",
    "combine_level",
    "combine_level_quadrants",
    "strassen_matmul",
    "leaf_count",
    "accumulation_dtype",
]

LeafFn = Callable[[jax.Array, jax.Array], jax.Array]


def accumulation_dtype(dtype) -> jnp.dtype:
    """dtype the leaf products and combine levels are held in.

    Sub-f32 inputs (bf16) keep products and combine sums in f32 and round
    once at the end, instead of at every product and level sum: on 2048^2
    Gaussian bf16 operands (CPU backend) the relative Frobenius error goes
    from 1.2e-2 to 9.8e-3 at depth 2 and from 2.5e-2 to 2.0e-2 at depth 3,
    against 1.7e-3 for one bf16 matmul. What remains is the divide levels
    rounding their operand sums to bf16 — the MXU's input type — which
    about doubles the error per level.
    """
    return jnp.promote_types(dtype, jnp.float32)


def leaf_count(scheme: Scheme, depth: int) -> int:
    """Number of leaf multiplications: the paper's 7^(p-q) (= b^2.807)."""
    return scheme.n_mults**depth


def _block(x: jax.Array, i: int, j: int, n: int) -> jax.Array:
    """Block (i, j) of x's two minor dims cut into an n x n grid.

    A static slice: quadrants are addressed in place, never by reshaping a
    minor dim into (2, half), which on a TPU re-tiles the whole array.
    """
    r, c = x.shape[-2] // n, x.shape[-1] // n
    return x[..., i * r:(i + 1) * r, j * c:(j + 1) * c]


def _grid(blocks, n: int) -> jax.Array:
    """Inverse of :func:`_block`: the row-major n*n blocks as one matrix.

    Each block is written in place into one buffer; a concatenate would
    first materialize the blocks and then copy them (measured slower on a
    TPU v5e, and XLA there may pick a transposed layout to feed it).
    """
    *lead, h, w = blocks[0].shape
    out = jnp.zeros((*lead, n * h, n * w), blocks[0].dtype)
    for k, blk in enumerate(blocks):
        i, j = divmod(k, n)
        out = out.at[..., i * h:(i + 1) * h, j * w:(j + 1) * w].set(blk)
    return out


def _sub_block(q: int, s: int) -> tuple[int, int]:
    """Position of sub-block s of quadrant q in the 4 x 4 grid of a matrix."""
    return 2 * (q // 2) + s // 2, 2 * (q % 2) + s % 2


def _check_even(shape) -> None:
    if shape[-2] % 2 or shape[-1] % 2:
        raise ValueError(f"need even dims, got {shape}")


def split_quadrants(x: jax.Array) -> jax.Array:
    """(..., r, c) -> (..., 4, r/2, c/2), quadrants row-major [11, 12, 21, 22].

    This is the paper's "Divide" of a sub-matrix into four equal quadrants
    (Fig. 3 "index reordering"), vectorized over any leading batch dims.
    """
    _check_even(x.shape)
    return jnp.stack([_block(x, q // 2, q % 2, 2) for q in range(4)], axis=-3)


def merge_quadrants(q: jax.Array) -> jax.Array:
    """Inverse of :func:`split_quadrants`: (..., 4, hr, hc) -> (..., 2hr, 2hc)."""
    if q.ndim < 3 or q.shape[-3] != 4:
        raise ValueError(f"need (..., 4, hr, hc), got {q.shape}")
    return _grid([q[..., k, :, :] for k in range(4)], 2)


def _signed_sum(terms, coefs) -> jax.Array:
    """``sum_q coefs[q] * terms[q]`` as an elementwise signed sum.

    The scheme coefficients are constants (0, +-1 for every registered
    scheme), so each output block is a fused sum of input blocks in the
    input dtype — no dot: on a TPU a dot at DEFAULT precision would round
    f32 operands to bf16, putting bf16 error into f32 products (and into
    the f32 accumulation of bf16 ones) whatever precision the leaves use.
    """
    acc = None
    for term, c in zip(terms, np.asarray(coefs).tolist()):
        if c == 0.0:
            continue
        if c == -1.0:
            term = -term
        elif c != 1.0:
            term = term * jnp.asarray(c, term.dtype)
        acc = term if acc is None else acc + term
    return acc if acc is not None else jnp.zeros_like(terms[0])


def divide_level(x: jax.Array, coef: np.ndarray) -> jax.Array:
    """One divide level: (m, r, c) -> (m*rank, r/2, c/2).

    ``coef`` is the scheme's (rank, 4) a_coef or b_coef, a constant (not a
    traced array). Equivalent to Stark's divide stage: replicate quadrants
    into the rank groups (flatMapToPair + groupByKey) and form each group's
    signed sum (flatMap) — here one fused elementwise pass. Leaf ordering
    is level-major: output index is m_old * rank + p, so base-rank digits
    of the final leaf index reproduce the paper's M-index tag path.
    """
    m, r, c = x.shape
    _check_even(x.shape)
    quads = [_block(x, q // 2, q % 2, 2) for q in range(4)]
    out = jnp.stack([_signed_sum(quads, row) for row in coef], axis=1)
    return out.reshape(m * out.shape[1], r // 2, c // 2)


def divide_level_quadrants(x: jax.Array, coef: np.ndarray) -> jax.Array:
    """:func:`divide_level` then :func:`split_quadrants`, in one pass.

    (m, r, c) or (r, c) -> (m*rank, 4, r/4, c/4): entry [p, s] is the
    signed sum over quadrants q of sub-block s of quadrant q, each a static
    slice of x. This is the fused leaf's operand layout, written directly.
    """
    *lead, r, c = x.shape
    if r % 4 or c % 4:
        raise ValueError(f"need dims divisible by 4, got {x.shape}")
    out = jnp.zeros((*lead, len(coef), 4, r // 4, c // 4), x.dtype)
    for p, row in enumerate(coef):
        for s in range(4):
            sub = [_block(x, *_sub_block(q, s), 4) for q in range(4)]
            out = out.at[..., p, s, :, :].set(_signed_sum(sub, row))
    return out.reshape(-1, 4, r // 4, c // 4)


def combine_level(products: jax.Array, c_coef: np.ndarray) -> jax.Array:
    """One combine level: (m*rank, hr, hc) -> (m, 2hr, 2hc).

    ``c_coef`` is the scheme's (4, rank) combine matrix (a constant).
    Equivalent to Stark's combine stage (map + groupByKey + flatMap over
    M-index tags).
    """
    rank = c_coef.shape[1]
    mr, hr, hc = products.shape
    if mr % rank:
        raise ValueError(f"batch {mr} not divisible by rank {rank}")
    prod = products.reshape(mr // rank, rank, hr, hc)
    parts = [prod[:, p] for p in range(rank)]
    return _grid([_signed_sum(parts, row) for row in c_coef], 2)


def combine_level_quadrants(cq: jax.Array, c_coef: np.ndarray, dtype) -> jax.Array:
    """:func:`merge_quadrants` then :func:`combine_level`, in one pass.

    (m*rank, 4, h, w) -> (m, 4h, 4w) in ``dtype``: the fused leaf's output
    read as ``cq[:, p, s]``; block s of C-quadrant k is the signed sum over
    p, cast to ``dtype`` in the same expression.
    """
    rank = c_coef.shape[1]
    mr, four, h, w = cq.shape
    if mr % rank or four != 4:
        raise ValueError(f"need (m*{rank}, 4, h, w), got {cq.shape}")
    cq = cq.reshape(mr // rank, rank, 4, h, w)
    blocks = [None] * 16
    for k, row in enumerate(c_coef):
        for s in range(4):
            i, j = _sub_block(k, s)
            parts = [cq[:, p, s] for p in range(rank)]
            blocks[4 * i + j] = _signed_sum(parts, row).astype(dtype)
    return _grid(blocks, 4)


def _default_leaf(a: jax.Array, b: jax.Array, *, precision=None) -> jax.Array:
    """Batched leaf multiply: einsum('mij,mjk->mik'). The paper's Algorithm 4.

    Products come out in :func:`accumulation_dtype`.
    """
    return jnp.einsum(
        "mij,mjk->mik", a, b, precision=precision,
        preferred_element_type=accumulation_dtype(jnp.result_type(a, b)),
    )


def strassen_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    depth: int,
    scheme: Scheme | str = STRASSEN,
    leaf_fn: Optional[LeafFn] = None,
    precision=None,
    constrain_a=None,
    constrain_b=None,
    constrain_out=None,
) -> jax.Array:
    """Batched-BFS Strassen: ``depth`` unrolled recursion levels.

    This is Stark's flattened recursion (Fig. 2): each of the ``depth``
    divide levels runs fully in parallel, the 7^depth leaf products form a
    single parallel stage, and combine levels rebuild C bottom-up. Under
    jit the entire pipeline is one XLA program.

    Args:
      a: (M, K); b: (K, N). M, K, N divisible by 2**depth.
      depth: number of Strassen levels (the paper's p - q).
      scheme: coefficient scheme (strassen | winograd | naive8).
      leaf_fn: batched leaf multiply (m, i, j) x (m, j, k) -> (m, i, k).
        Defaults to a batched einsum; the Pallas MXU kernel plugs in here.
      precision: jax matmul precision for the default leaf.
      constrain_a/b/out: optional per-level sharding hooks (m, r, c) ->
        array. Under GSPMD the quadrant reshapes break sharding
        propagation (operands silently replicate, measured 3x compute /
        6x collectives on internlm2 train) — the backend passes hooks
        that re-pin each level to the caller's layout.
    """
    if isinstance(scheme, str):
        scheme = get_scheme(scheme)
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise ValueError(f"bad matmul shapes {a.shape} @ {b.shape}")
    if depth < 0:
        raise ValueError("depth must be >= 0")
    step = 2**depth
    for d in (*a.shape, b.shape[1]):
        if d % step:
            raise ValueError(f"dim {d} not divisible by 2**depth={step}")

    if leaf_fn is None:
        leaf_fn = functools.partial(_default_leaf, precision=precision)

    a_coef, b_coef, c_coef = scheme.a_coef, scheme.b_coef, scheme.c_coef

    # Divide phase: depth levels, each one parallel einsum.
    ta = a[None]  # (1, M, K)
    tb = b[None]
    for _ in range(depth):
        ta = divide_level(ta, a_coef)
        tb = divide_level(tb, b_coef)
        if constrain_a is not None:
            ta = constrain_a(ta)
        if constrain_b is not None:
            tb = constrain_b(tb)

    # Leaf phase: one batched multiply of rank^depth blocks.
    out_dtype = jnp.result_type(a, b)
    prod = leaf_fn(ta, tb).astype(accumulation_dtype(out_dtype))
    if constrain_out is not None:
        prod = constrain_out(prod)

    # Combine phase: depth levels bottom-up, in the accumulation dtype.
    for _ in range(depth):
        prod = combine_level(prod, c_coef)
        if constrain_out is not None:
            prod = constrain_out(prod)

    return prod[0].astype(out_dtype)


def strassen_recursive(
    a: jax.Array,
    b: jax.Array,
    *,
    threshold: int = 64,
    scheme: Scheme | str = STRASSEN,
) -> jax.Array:
    """Paper Algorithm 1: serial recursive Strassen (single node reference).

    Recurses until the smallest dim reaches ``threshold``, then multiplies
    naively (the paper's Breeze/BLAS leaf call -> jnp.dot here).
    """
    if isinstance(scheme, str):
        scheme = get_scheme(scheme)
    m, k = a.shape
    n = b.shape[1]
    if min(m, k, n) <= threshold or m % 2 or k % 2 or n % 2:
        return a @ b
    aq = [_block(a, q // 2, q % 2, 2) for q in range(4)]
    bq = [_block(b, q // 2, q % 2, 2) for q in range(4)]
    prods = [
        strassen_recursive(
            _signed_sum(aq, scheme.a_coef[p]), _signed_sum(bq, scheme.b_coef[p]),
            threshold=threshold, scheme=scheme,
        )
        for p in range(scheme.n_mults)
    ]
    return _grid([_signed_sum(prods, row) for row in scheme.c_coef], 2)
