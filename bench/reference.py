"""The plain reference that decides ``correct``, and its two controls.

The reference is ``A @ B`` in float32 at HIGHEST precision (six bf16 passes
on a TPU, float32 accuracy), computed on the device in column blocks so
that it fits beside what the run still holds. It imports nothing of the
program and takes nothing the program made but the output under test.

Two numbers are compared, each on the operand pairs of its kind
(``bench/operands.py``):

* ``rel_fro``, on normal operands: ``||C - R||_F / ||R||_F``. (The worst
  512 x 512 tile's ratio was tried too and dropped: Strassen's error is
  uneven over the quadrants, so its sound readings came within 2.6x of the
  control's and no limit could sit between them.)
* ``int_mismatches``, on small whole-number operands: the count of entries
  of C that differ from the exact product rounded once to C's dtype. The
  reference is exact there: every product and partial sum is a whole
  number far below 2**24.

Each control is the reference put in the program's place one precision step
below what the configuration states (bf16 operands, f32 accumulation):

* ``control_product``: the operands rounded to float8_e4m3fn, multiplied
  and accumulated in float32, the result rounded to the output dtype;
* ``control_bf16_accumulation``: bf16 operands whose partial products, one
  per block of the inner dimension, and their running sum are held in
  bfloat16.

Every rounding is a program of its own whose result is an array of the
lower type, so no compiler can fold a round trip away.
"""
from __future__ import annotations

import functools
import math

import numpy as np

BLOCK = 4096
CONTROL_DTYPE = "float8_e4m3fn"


@functools.lru_cache(maxsize=None)
def _block_fns():
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    def ref_block(a, b, c0, width):
        bc = jax.lax.dynamic_slice_in_dim(b, c0, width, axis=1).astype(f32)
        return jnp.matmul(a.astype(f32), bc, precision=jax.lax.Precision.HIGHEST)

    @functools.partial(jax.jit, static_argnames=("width",))
    def block_sums(a, b, out, c0, *, width):
        ref = ref_block(a, b, c0, width)
        got = jax.lax.dynamic_slice_in_dim(out, c0, width, axis=1).astype(f32)
        return jnp.sum(jnp.square(got - ref)), jnp.sum(jnp.square(ref))

    @functools.partial(jax.jit, static_argnames=("width", "dtype"))
    def rounded_block(a, b, c0, *, width, dtype):
        return ref_block(a, b, c0, width).astype(dtype)

    @functools.partial(jax.jit, static_argnames=("width",))
    def block_mismatches(out, ref, c0, *, width):
        got = jax.lax.dynamic_slice_in_dim(out, c0, width, axis=1).astype(f32)
        return jnp.sum((got != ref.astype(f32)).astype(jnp.int32))

    return block_sums, rounded_block, block_mismatches


def _prepared(a, b, out):
    """``out`` beside ``a`` and the column block width, or None when the
    shape is wrong."""
    import jax

    m, n = a.shape[0], b.shape[1]
    if tuple(out.shape) != (m, n):
        return None
    if isinstance(out, np.ndarray):
        out = jax.device_put(out, a.sharding)
    width = min(BLOCK, n)
    if n % width:
        raise ValueError(f"{n} columns do not split into blocks of {width}")
    return out, width


def error_numbers(a, b, out) -> dict:
    """``rel_fro`` of ``out`` against ``a @ b``.

    ``a``, ``b`` are device arrays (sharded or not); ``out`` a device array
    or a host array, which is put beside ``a`` first. A NaN anywhere makes
    the number NaN, which no limit passes.
    """
    got = _prepared(a, b, out)
    if got is None:
        return {"rel_fro": math.inf}
    out, width = got
    block_sums = _block_fns()[0]
    d2 = r2 = 0.0
    for c0 in range(0, b.shape[1], width):
        bd, br = block_sums(a, b, out, np.int32(c0), width=width)
        d2, r2 = d2 + float(bd), r2 + float(br)
    return {"rel_fro": math.sqrt(d2 / r2)}


def exact_numbers(a, b, out) -> dict:
    """``int_mismatches``: entries of ``out`` that differ from ``a @ b``
    rounded once to ``out``'s dtype. ``a`` and ``b`` hold small whole
    numbers, so the float32 reference is the exact product."""
    got = _prepared(a, b, out)
    if got is None:
        return {"int_mismatches": math.inf}
    out, width = got
    _, rounded_block, block_mismatches = _block_fns()
    count = 0
    for c0 in range(0, b.shape[1], width):
        ref = rounded_block(a, b, np.int32(c0), width=width, dtype=str(out.dtype))
        count += int(block_mismatches(out, ref, np.int32(c0), width=width))
    return {"int_mismatches": count}


NUMBERS = {"normal": error_numbers, "integers": exact_numbers}


def numbers(kind: str, a, b, out) -> dict:
    """The numbers compared on an operand pair of ``kind``."""
    return NUMBERS[kind](a, b, out)


def control_product(a, b, out_dtype: str):
    """The reference at float8 operands in the program's place."""
    import jax
    import jax.numpy as jnp

    quantize = jax.jit(lambda x: x.astype(CONTROL_DTYPE))
    qa, qb = quantize(a), quantize(b)
    mm = jax.jit(
        lambda x, y: jnp.matmul(
            x.astype(jnp.float32), y.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST
        ).astype(out_dtype)
    )
    return jax.block_until_ready(mm(qa, qb))


def control_bf16_accumulation(a, b, blocks: int):
    """The reference in the program's place with its accumulation one step
    down: the inner dimension in ``blocks`` equal blocks (a depth-d Strassen
    leaf's inner dimension is k / 2**d), each block's product rounded to
    bfloat16 and summed into a bfloat16 result."""
    import jax
    import jax.numpy as jnp

    k = a.shape[1]
    if k % blocks:
        raise ValueError(f"inner dimension {k} does not split into {blocks} blocks")
    w = k // blocks

    @jax.jit
    def part(x, y, k0):
        xs = jax.lax.dynamic_slice_in_dim(x, k0, w, axis=1).astype(jnp.float32)
        ys = jax.lax.dynamic_slice_in_dim(y, k0, w, axis=0).astype(jnp.float32)
        return jnp.matmul(xs, ys, precision=jax.lax.Precision.HIGHEST).astype(jnp.bfloat16)

    add = jax.jit(lambda s, p: (s + p).astype(jnp.bfloat16))
    acc = part(a, b, np.int32(0))
    for j in range(1, blocks):
        acc = add(acc, part(a, b, np.int32(j * w)))
    return jax.block_until_ready(acc)
