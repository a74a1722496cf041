"""Paper Table VII: theoretical vs actual LEAF-stage computation cost.

The paper caches the leaf blocks and times just the leaf multiplications,
showing the minima of theoretical and measured cost shift together across
partition sizes. We reproduce it: for each depth (partition size
b = 2**depth) time ONLY the batched leaf multiply on precomputed divided
operands, and emit the theoretical per-core cost b^2.807 * (n/b)^3 /
min(b^2.807, cores) alongside (cores=1 here).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from benchmarks.common import emit, rand, time_fn
from repro.core.coefficients import STRASSEN
from repro.core.strassen import divide_level

SIZES = (1024,)
DEPTHS = (1, 2, 3, 4)


def run():
    rows = []
    for n in SIZES:
        a, b = rand((n, n)), rand((n, n))
        ac, bc = STRASSEN.a_coef, STRASSEN.b_coef
        for depth in DEPTHS:
            ta, tb = a[None], b[None]
            for _ in range(depth):
                ta = divide_level(ta, ac)
                tb = divide_level(tb, bc)
            ta, tb = jax.block_until_ready((ta, tb))
            leaf = jax.jit(lambda x, y: jnp.einsum("mij,mjk->mik", x, y))
            t = time_fn(leaf, ta, tb)
            blk = n >> depth
            theory_flops = (7**depth) * 2.0 * blk**3
            rows.append(
                emit(
                    f"table7/stark_leaf/n{n}/b{2**depth}", t,
                    f"leaves={7**depth};blk={blk};theory_gflop={theory_flops/1e9:.2f}",
                )
            )
            # Marlin/MLLib analogue: b^3 leaf multiplications of the same block size
            naive_leaves = (2**depth) ** 3
            mb = jnp.broadcast_to(ta[:1], (naive_leaves, blk, blk)).copy()
            t2 = time_fn(leaf, mb, mb)
            rows.append(
                emit(
                    f"table7/marlin_leaf/n{n}/b{2**depth}", t2,
                    f"leaves={naive_leaves};vs_stark={t2/t:.2f}x",
                )
            )
    return rows
