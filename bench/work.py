"""Operations and bytes of a cell's work, counted from its own shapes.

Nothing here asks the program how it computed: the counts follow from the
cell's fixed shapes, depth and scheme rank, so a change to the program can
not move the yardstick that its kernels are held to.
"""
from __future__ import annotations

import dataclasses
import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")

# Products a level of the Strassen/Winograd recursion turns one multiply into.
STRASSEN_RANK = 7


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float


def itemsize(dtype: str) -> int:
    sizes = {"bfloat16": 2, "float16": 2, "float32": 4, "float64": 8, "int8": 1}
    if dtype not in sizes:
        raise ValueError(f"no item size for dtype {dtype!r}")
    return sizes[dtype]


def dense_product(m: int, k: int, n: int) -> float:
    """FLOPs of the plain (M, K) @ (K, N) product: 2 M K N."""
    return 2.0 * m * k * n


def strassen_leaves(m: int, k: int, n: int, depth: int) -> Work:
    """The 7**depth leaf products of ``depth`` Strassen levels.

    Each leaf multiplies (m/2^d, k/2^d) by (k/2^d, n/2^d); bytes are left 0
    here because where the operands live depends on the kernel.
    """
    step = 2**depth
    if m % step or k % step or n % step:
        raise ValueError(f"({m}, {k}, {n}) not divisible by 2**{depth}")
    lm, lk, ln = m // step, k // step, n // step
    return Work(flops=STRASSEN_RANK**depth * 2.0 * lm * lk * ln, bytes=0.0)


def fused_leaf_kernel(
    m: int, k: int, n: int, depth: int, in_dtype: str = "bfloat16"
) -> Work:
    """One call of the fused Strassen leaf kernel at ``depth`` levels.

    The outer ``depth - 1`` levels are materialized, so the kernel gets
    7**(depth-1) sub-problems as quadrant tiles: operands
    (7**(d-1), 4, m/2^d, k/2^d) and (7**(d-1), 4, k/2^d, n/2^d) in the input
    dtype, and a result (7**(d-1), 4, m/2^d, n/2^d) in float32 when outer
    levels remain to combine (the input dtype at depth 1). Inside it runs
    all 7**depth leaf products. Bytes are each operand read once and the
    result written once: the least traffic the call can make.
    """
    if depth < 1:
        raise ValueError("the fused leaf needs depth >= 1")
    leaves = strassen_leaves(m, k, n, depth)
    step = 2**depth
    lm, lk, ln = m // step, k // step, n // step
    batch = STRASSEN_RANK ** (depth - 1) * 4
    in_b = itemsize(in_dtype)
    out_b = 4 if depth > 1 else in_b
    nbytes = batch * ((lm * lk + lk * ln) * in_b + lm * ln * out_b)
    return Work(flops=leaves.flops, bytes=float(nbytes))


def load_peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The published peaks of one device kind; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {os.path.basename(path)}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


def least_seconds(work: Work, peaks: dict) -> float:
    """The roofline's least time: the larger of FLOPs over peak FLOP/s and
    bytes over peak HBM bytes/s."""
    return max(work.flops / peaks["bf16_flops_per_s"], work.bytes / peaks["hbm_bytes_per_s"])
