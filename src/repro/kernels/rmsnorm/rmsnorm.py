"""Fused RMSNorm Pallas kernel.

Memory-bound op: one HBM read of x, one write — the unfused XLA form can
rematerialize x twice (square+mean, then scale). Rows are tiled (br, D)
into VMEM; the reduction and rescale stay in VREGs, fp32 math.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import default_interpret, pick_block

__all__ = ["rmsnorm_pallas"]


def _rmsnorm_kernel(eps: float, x_ref, w_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)  # (br, D)
    w = w_ref[...].astype(jnp.float32)  # (1, D)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * w
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm_pallas(
    x: jax.Array,
    w: jax.Array,
    *,
    eps: float = 1e-6,
    block_rows: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """y = x * rsqrt(mean(x^2) + eps) * w over the last dim.

    Args:
      x: (R, D) rows to normalize (callers flatten leading dims).
      w: (D,) scale.
    """
    if interpret is None:
        interpret = default_interpret()
    r, d = x.shape
    assert w.shape == (d,), (x.shape, w.shape)
    br = pick_block(r, block_rows, align=8, interpret=interpret)
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps),
        grid=(r // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), x.dtype),
        interpret=interpret,
    )(x, w[None, :])
