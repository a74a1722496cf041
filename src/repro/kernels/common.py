"""Shared Pallas kernel utilities.

Every kernel accepts ``interpret=`` and defaults to interpret mode when the
default backend is not a TPU. On a TPU the same call sites compile to
Mosaic; on CPU hosts (the test suite) they run through the Pallas
interpreter, slowly but bit-faithfully at the algorithm level.
"""
from __future__ import annotations

import functools

import jax

__all__ = ["default_interpret", "pick_block", "cdiv"]


@functools.lru_cache(None)
def default_interpret() -> bool:
    """True when the default backend has no TPU (interpret the kernel)."""
    return jax.default_backend() != "tpu"


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def pick_block(dim: int, preferred: int, align: int = 128, *, interpret: bool) -> int:
    """Largest block <= preferred that divides dim, preferring MXU alignment.

    TPU MXU wants the trailing two tile dims in multiples of (8, 128) for
    fp32 and (16, 128) for bf16; ``preferred`` should already be a multiple
    of 128. A dim no larger than ``preferred`` is one whole block, which
    Mosaic accepts at any size. A larger dim with no aligned divisor has no
    legal TPU tiling: compiled kernels raise here (callers pad, as
    :func:`repro.kernels.strassen.ops.strassen_matmul_fused_padded` does),
    and only interpret mode falls back to any divisor.
    """
    if dim <= preferred:
        return dim
    b = preferred
    while b >= align:
        if dim % b == 0:
            return b
        b -= align
    if not interpret:
        raise ValueError(
            f"dim {dim} has no block <= {preferred} that is a multiple of "
            f"{align}; pad it to a multiple of {align} before the kernel"
        )
    b = preferred
    while b > 1:
        if dim % b == 0:
            return b
        b -= 1
    return 1
