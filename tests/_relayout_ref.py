"""Reshape-and-transpose formulation of the Strassen quadrant levels.

The library addresses quadrants by static slices of the two minor dims.
This module keeps the formulation it replaced: quadrants by reshaping a
minor dim into (2, half) and moving axes, whole levels materialized between
the fused leaf and the outer levels. Both compute every output element with
the same terms in the same order, so tests compare them bit for bit.
"""
import jax.numpy as jnp
import numpy as np

from repro.core.coefficients import get_scheme
from repro.core.strassen import accumulation_dtype
from repro.kernels.strassen.ops import _padded_dim
from repro.kernels.strassen.strassen import strassen1_matmul_pallas


def split_quadrants(x):
    *lead, r, c = x.shape
    x = x.reshape(*lead, 2, r // 2, 2, c // 2)
    return jnp.moveaxis(x, -2, -3).reshape(*lead, 4, r // 2, c // 2)


def merge_quadrants(q):
    *lead, _, hr, hc = q.shape
    q = jnp.moveaxis(q.reshape(*lead, 2, 2, hr, hc), -3, -2)
    return q.reshape(*lead, 2 * hr, 2 * hc)


def coef_combination(coef, parts):
    rows = []
    for row in np.asarray(coef):
        acc = None
        for q, c in enumerate(row.tolist()):
            if c == 0.0:
                continue
            term = parts[:, q]
            if c == -1.0:
                term = -term
            elif c != 1.0:
                term = term * jnp.asarray(c, parts.dtype)
            acc = term if acc is None else acc + term
        rows.append(acc if acc is not None else jnp.zeros_like(parts[:, 0]))
    return jnp.stack(rows, axis=1)


def divide_level(x, coef):
    m, r, c = x.shape
    out = coef_combination(coef, split_quadrants(x))
    return out.reshape(m * out.shape[1], r // 2, c // 2)


def combine_level(products, c_coef):
    rank = c_coef.shape[1]
    mr, hr, hc = products.shape
    prod = products.reshape(mr // rank, rank, hr, hc)
    return merge_quadrants(coef_combination(c_coef, prod))


def strassen_matmul(a, b, *, depth, scheme="strassen"):
    scheme = get_scheme(scheme)
    ta, tb = a[None], b[None]
    for _ in range(depth):
        ta = divide_level(ta, scheme.a_coef)
        tb = divide_level(tb, scheme.b_coef)
    out_dtype = jnp.result_type(a, b)
    prod = jnp.einsum(
        "mij,mjk->mik", ta, tb, preferred_element_type=accumulation_dtype(out_dtype)
    )
    for _ in range(depth):
        prod = combine_level(prod, scheme.c_coef)
    return prod[0].astype(out_dtype)


def strassen_matmul_fused(a, b, *, depth, scheme_name="strassen", interpret=None):
    scheme = get_scheme(scheme_name)
    out_dtype = jnp.result_type(a, b)
    ta, tb = a[None], b[None]
    for _ in range(depth - 1):
        ta = divide_level(ta, scheme.a_coef)
        tb = divide_level(tb, scheme.b_coef)
    cq = strassen1_matmul_pallas(
        split_quadrants(ta), split_quadrants(tb), scheme=scheme,
        out_dtype=accumulation_dtype(out_dtype) if depth > 1 else out_dtype,
        interpret=interpret,
    )
    prod = merge_quadrants(cq)
    for _ in range(depth - 1):
        prod = combine_level(prod, scheme.c_coef)
    return prod[0].astype(out_dtype)


def strassen_matmul_fused_padded(a, b, *, depth, scheme_name="strassen"):
    m, k = a.shape
    n = b.shape[1]
    mp, kp, np_ = (_padded_dim(d, depth) for d in (m, k, n))
    a_p = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    b_p = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    out = strassen_matmul_fused(a_p, b_p, depth=depth, scheme_name=scheme_name)
    return out[:m, :n]
