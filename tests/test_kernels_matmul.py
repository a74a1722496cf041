"""Shape/dtype sweeps for the matmul Pallas kernels vs the pure-jnp oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.matmul.ops import batched_matmul, matmul
from repro.kernels.matmul.ref import batched_matmul_ref, matmul_ref

RNG = np.random.default_rng(0)


def _rand(shape, dtype):
    x = RNG.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: 2e-4, jnp.bfloat16: 2e-1}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "m,k,n,bm,bn,bk",
    [
        (128, 128, 128, 128, 128, 128),
        (256, 128, 64, 128, 64, 64),
        (64, 192, 128, 32, 128, 64),
        (512, 256, 256, 256, 256, 128),
        (8, 16, 8, 8, 8, 16),  # tiny, interpret-only shapes
    ],
)
def test_matmul_sweep(m, k, n, bm, bn, bk, dtype):
    a, b = _rand((m, k), dtype), _rand((k, n), dtype)
    got = matmul(a, b, block_m=bm, block_n=bn, block_k=bk)
    want = matmul_ref(a, b)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=TOL[dtype], rtol=TOL[dtype]
    )
    assert got.dtype == a.dtype


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mb,m,k,n", [(7, 64, 64, 64), (49, 32, 32, 32), (1, 128, 64, 128)])
def test_batched_matmul_sweep(mb, m, k, n, dtype):
    a, b = _rand((mb, m, k), dtype), _rand((mb, k, n), dtype)
    got = batched_matmul(a, b, block_m=64, block_n=64, block_k=64)
    want = batched_matmul_ref(a, b)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=TOL[dtype], rtol=TOL[dtype]
    )


def test_matmul_nondivisible_blocks_fall_back():
    # pick_block must find a divisor; result still correct.
    a, b = _rand((96, 80), jnp.float32), _rand((80, 112), jnp.float32)
    got = matmul(a, b, block_m=128, block_n=128, block_k=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(matmul_ref(a, b)), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize(
    "dim,interpret,want",
    [(200, False, 200), (768, False, 256), (640, False, 128), (1000, True, 250)],
)
def test_pick_block_alignment(dim, interpret, want):
    """A dim up to the preferred block is one whole block; a larger one
    takes the largest 128-aligned divisor, and only interpret mode may
    fall back to an unaligned divisor."""
    from repro.kernels.common import pick_block

    assert pick_block(dim, 256, interpret=interpret) == want


def test_pick_block_raises_without_aligned_divisor_when_compiled():
    from repro.kernels.common import pick_block

    with pytest.raises(ValueError, match="multiple of 128"):
        pick_block(1000, 256, interpret=False)
