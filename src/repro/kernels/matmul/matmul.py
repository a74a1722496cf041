"""Tiled MXU matmul Pallas kernel — Stark's leaf block multiply, TPU-native.

In the paper, leaf blocks are multiplied on a single node via Breeze -> JNI
-> BLAS. On TPU the analogue is an MXU-tiled kernel: blocks of A and B are
staged HBM -> VMEM per BlockSpec, multiplied on the 128x128 systolic array
with fp32 accumulation in a VMEM scratch, and written back once per (i, j)
tile after the K reduction completes.

Grid layout: (M/bm, N/bn, K/bk) with K innermost so the accumulator lives
across the contraction; the batched variant prepends the leaf index m —
the paper's M-index tag — as the outermost, embarrassingly parallel axis.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import default_interpret, pick_block

__all__ = ["matmul_pallas", "batched_matmul_pallas"]


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref):
    """One (i, j, k) grid step: acc += A[i,k] @ B[k,j]; flush at last k."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 256,
    out_dtype: Optional[jnp.dtype] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """C = A @ B with (bm, bn, bk) VMEM tiles and fp32 accumulation.

    Default 256^3 tiles: working set = (bm*bk + bk*bn)*2B (bf16 operands)
    + bm*bn*4B (fp32 acc) = 512 KiB — comfortably inside the ~16 MiB VMEM
    budget, with arithmetic intensity bk/2 = 128 FLOP/byte, well past the
    197e12/819e9 = 241 FLOP/byte... per-tile reuse is what the K-innermost
    ordering buys (each A tile read once per j).
    """
    if interpret is None:
        interpret = default_interpret()
    (m, k), (k2, n) = a.shape, b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk = (
        pick_block(d, blk, interpret=interpret)
        for d, blk in ((m, block_m), (n, block_n), (k, block_k))
    )
    out_dtype = out_dtype or a.dtype
    return pl.pallas_call(
        _matmul_kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)


def _batched_matmul_kernel(a_ref, b_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[0], b_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def batched_matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 256,
    out_dtype: Optional[jnp.dtype] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Stark's leaf stage: (m, i, j) x (m, j, k) -> (m, i, k).

    The leading axis m = 7^depth is the flattened recursion-tag batch; it is
    the outermost grid axis, so on-device it is a serial loop with zero
    cross-iteration traffic while under pjit/shard_map it is the axis the
    mesh shards (each chip sees only its m-slice).
    """
    if interpret is None:
        interpret = default_interpret()
    (mb, m, k), (_, k2, n) = a.shape, b.shape
    assert k == k2 and b.shape[0] == mb, (a.shape, b.shape)
    bm, bn, bk = (
        pick_block(d, blk, interpret=interpret)
        for d, blk in ((m, block_m), (n, block_n), (k, block_k))
    )
    out_dtype = out_dtype or a.dtype
    return pl.pallas_call(
        _batched_matmul_kernel,
        grid=(mb, m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda s, i, j, kk: (s, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda s, i, j, kk: (s, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda s, i, j, kk: (s, i, j)),
        out_shape=jax.ShapeDtypeStruct((mb, m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)
