"""Fused Strassen kernels vs oracles: divide/combine/fused-matmul sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.coefficients import get_scheme
from repro.core.strassen import merge_quadrants, split_quadrants
from repro.kernels.common import pick_block
from repro.kernels.strassen import strassen as K
from repro.kernels.strassen.ops import (
    strassen_matmul_fused,
    strassen_matmul_fused_padded,
    strassen_matmul_stages,
)
from repro.kernels.strassen.ref import (
    combine_ref,
    divide_ref,
    strassen1_full_ref,
    strassen1_matmul_ref,
)
from repro.kernels.strassen.strassen import (
    combine_pallas,
    divide_pallas,
    strassen1_matmul_pallas,
)
from repro.obs.metrics import get_metrics

RNG = np.random.default_rng(1)
TOL = {jnp.float32: 5e-4, jnp.bfloat16: 5e-1}


def _rand(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape).astype(np.float32), dtype)


@pytest.mark.parametrize("scheme_name", ["strassen", "winograd", "naive8"])
@pytest.mark.parametrize("m,h,w", [(1, 64, 64), (7, 32, 64), (4, 128, 128)])
def test_divide_kernel(scheme_name, m, h, w):
    scheme = get_scheme(scheme_name)
    x = _rand((m, 4, h, w))
    got = divide_pallas(x, scheme.a_coef, block=64)
    want = divide_ref(x, scheme.a_coef)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


@pytest.mark.parametrize("scheme_name", ["strassen", "winograd"])
@pytest.mark.parametrize("m,h,w", [(1, 64, 64), (7, 32, 32)])
def test_combine_kernel(scheme_name, m, h, w):
    scheme = get_scheme(scheme_name)
    x = _rand((m, scheme.n_mults, h, w))
    got = combine_pallas(x, scheme.c_coef, block=32)
    want = combine_ref(x, scheme.c_coef)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mb,m2,k2,n2", [(1, 64, 64, 64), (7, 32, 64, 32), (2, 128, 128, 128)])
def test_strassen1_kernel_vs_ref(mb, m2, k2, n2, dtype):
    aq = _rand((mb, 4, m2, k2), dtype)
    bq = _rand((mb, 4, k2, n2), dtype)
    got = strassen1_matmul_pallas(aq, bq, block_m=32, block_n=32, block_k=32)
    want = strassen1_matmul_ref(aq, bq)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("pipeline", [strassen_matmul_stages, strassen_matmul_fused])
def test_full_pipelines_vs_plain_matmul(depth, pipeline):
    a, b = _rand((128, 128)), _rand((128, 128))
    got = pipeline(a, b, depth=depth)
    want = strassen1_full_ref(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("depth", [1, 2])
def test_fused_vs_ref_dtypes(depth, dtype):
    """Fused leaf vs the pure-jnp oracle across dtypes (bf16 accumulates in
    fp32 inside the kernel, so the oracle's fp32 pipeline is the target)."""
    a, b = _rand((128, 96), dtype), _rand((96, 64), dtype)
    got = strassen_matmul_fused(a, b, depth=depth)
    want = strassen1_full_ref(a, b)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n", [(33, 65, 17), (100, 60, 36), (127, 129, 64)])
def test_fused_padded_odd_shapes(m, k, n, dtype):
    """Odd/non-divisible dims route through the zero-padded fused pipeline
    and stay exact on the unpadded block."""
    a, b = _rand((m, k), dtype), _rand((k, n), dtype)
    for depth in (1, 2):
        got = strassen_matmul_fused_padded(a, b, depth=depth)
        assert got.shape == (m, n) and got.dtype == a.dtype
        want = strassen1_full_ref(a, b)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=TOL[dtype], rtol=TOL[dtype],
        )


def test_fused_padded_noop_on_divisible_shapes():
    a, b = _rand((64, 64)), _rand((64, 64))
    got = strassen_matmul_fused_padded(a, b, depth=2)
    want = strassen_matmul_fused(a, b, depth=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0, rtol=0)


@pytest.mark.parametrize("dim", [40, 256, 257, 500, 513, 1000, 1536])
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_fused_padding_gives_tileable_quadrants(dim, depth):
    """The padded size splits into depth-level quadrants the leaf tiles
    legally on a TPU: one whole block (<= 256) or a multiple of 128."""
    from repro.kernels.strassen.ops import _padded_dim

    padded = _padded_dim(dim, depth)
    q, rem = divmod(padded, 2**depth)
    assert rem == 0 and padded >= dim
    assert q <= 256 or q % 128 == 0
    assert padded - dim < 2**depth * 128


@pytest.mark.parametrize("pipeline", ["strassen", "fused"])
@pytest.mark.parametrize("depth", [1, 2])
def test_bf16_products_round_once(pipeline, depth):
    """bf16 leaf products and combine levels are held in f32 and rounded
    once: on small-integer operands every intermediate is exact, so the
    result is bit-identical to one bf16 matmul with f32 accumulation."""
    from repro.core.strassen import strassen_matmul

    ints = np.random.default_rng(3).integers(-8, 9, size=(2, 128, 128))
    a, b = (jnp.asarray(x, jnp.bfloat16) for x in ints)
    if pipeline == "fused":
        got = strassen_matmul_fused(a, b, depth=depth)
    else:
        got = strassen_matmul(a, b, depth=depth)
    want = jnp.matmul(a, b, preferred_element_type=jnp.float32).astype(jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_fused_winograd_scheme():
    a, b = _rand((64, 64)), _rand((64, 64))
    got = strassen_matmul_fused(a, b, depth=1, scheme_name="winograd")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(strassen1_full_ref(a, b)), atol=5e-4, rtol=5e-4
    )


def test_quadrant_roundtrip_kernel_layout():
    x = _rand((3, 64, 48))
    assert np.allclose(np.asarray(merge_quadrants(split_quadrants(x))), np.asarray(x))


def _bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (64, 128, 192)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("scheme_name", ["strassen", "winograd"])
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_fused_bits_match_relayout_formulation(depth, scheme_name, dtype, m, k, n):
    """Slice-addressed outer levels give the bits of the reshape/transpose
    formulation: every element keeps its terms, their order and dtypes."""
    import _relayout_ref as ref

    a, b = _rand((m, k), dtype), _rand((k, n), dtype)
    got = strassen_matmul_fused(a, b, depth=depth, scheme_name=scheme_name)
    want = ref.strassen_matmul_fused(a, b, depth=depth, scheme_name=scheme_name)
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_fused_padded_bits_match_relayout_formulation(depth, dtype):
    import _relayout_ref as ref

    a, b = _rand((100, 60), dtype), _rand((60, 36), dtype)
    got = strassen_matmul_fused_padded(a, b, depth=depth)
    want = ref.strassen_matmul_fused_padded(a, b, depth=depth)
    np.testing.assert_array_equal(_bits(got), _bits(want))


def _narrow_blocks(m2, k2, n2):
    return tuple(pick_block(d, 256, interpret=False) for d in (m2, n2, k2))


@pytest.mark.parametrize(
    "m2,k2,n2,in_dtype,out_dtype,wide",
    [
        (4096, 4096, 4096, jnp.bfloat16, jnp.float32, True),  # dense cell, d2
        (2048, 8192, 8192, jnp.bfloat16, jnp.float32, True),  # mesh cell's stripe, d2
        (4096, 4096, 4096, jnp.float32, jnp.float32, True),
        (4096, 4096, 4096, jnp.bfloat16, jnp.bfloat16, True),  # d1 of 8192^2
        (1024, 128, 512, jnp.bfloat16, jnp.bfloat16, True),
        (512, 4096, 4096, jnp.bfloat16, jnp.float32, False),
        (768, 768, 768, jnp.bfloat16, jnp.bfloat16, False),  # d1 of 1536^2
        (640, 4096, 640, jnp.bfloat16, jnp.float32, False),
        (4096, 4096, 768, jnp.bfloat16, jnp.float32, False),
        (16, 1536, 4096, jnp.bfloat16, jnp.float32, False),  # skinny projection
        (384, 384, 384, jnp.bfloat16, jnp.float32, False),
        (256, 256, 256, jnp.float32, jnp.float32, False),
        (64, 64, 64, jnp.bfloat16, jnp.bfloat16, False),
    ],
)
def test_leaf_blocks_from_quadrant_shape(m2, k2, n2, in_dtype, out_dtype, wide):
    """1024x512 output tiles where m2 and n2 allow them, the 256 ladder
    elsewhere; every tiling's VMEM estimate lies under the limit it is
    compiled with (Mosaic's 16 MiB default where none is passed), and the
    narrow ones pass none, so they compile as they always did."""
    blocks = K._leaf_blocks(m2, k2, n2, interpret=False)
    narrow = _narrow_blocks(m2, k2, n2)
    assert blocks == ((1024, 512, narrow[2]) if wide else narrow)
    for n_mults in (7, 8):  # Strassen/Winograd, naive8
        need = K._leaf_vmem_bytes(
            *blocks, jnp.dtype(in_dtype).itemsize, jnp.dtype(out_dtype).itemsize,
            n_mults,
        )
        limit = K._leaf_vmem_limit(need)
        assert (limit is not None) == wide
        assert need < (limit or 16 << 20) <= 96 << 20


@pytest.mark.parametrize("dtype,rel_fro", [(jnp.float32, 1e-5), (jnp.bfloat16, 1e-2)])
def test_strassen1_kernel_wide_blocks_vs_ref(dtype, rel_fro):
    """The 1024x512 tiling against the f32 oracle. Over k2 = 1024 the
    kernel's bf16 signed sums stray further per entry than TOL allows, so
    the bound is on the relative Frobenius error (about 4.5e-3 for one
    bf16 Strassen level)."""
    aq = _rand((1, 4, 1024, 1024), dtype)
    bq = _rand((1, 4, 1024, 1024), dtype)
    got = np.asarray(strassen1_matmul_pallas(aq, bq, out_dtype=jnp.float32))
    want = np.asarray(strassen1_matmul_ref(aq, bq), np.float32)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < rel_fro


def test_strassen1_wide_blocks_bits_match_narrow():
    """On whole-number operands every sum is exact: the 1024x512 tiling
    gives the bits of the 256^3 one."""
    ints = np.random.default_rng(5).integers(-8, 9, size=(2, 1, 4, 1024, 1024))
    aq, bq = (jnp.asarray(x, jnp.bfloat16) for x in ints)
    wide = strassen1_matmul_pallas(aq, bq, out_dtype=jnp.float32)
    narrow = strassen1_matmul_pallas(
        aq, bq, block_m=256, block_n=256, block_k=256, out_dtype=jnp.float32
    )
    np.testing.assert_array_equal(_bits(wide), _bits(narrow))


def test_leaf_counts_block_triples_per_trace():
    def count(triple):
        return get_metrics().counter(f"kernels.strassen_leaf.blocks.{triple}").value

    def trace(m2, k2, n2):
        a = jax.ShapeDtypeStruct((7, 4, m2, k2), jnp.bfloat16)
        b = jax.ShapeDtypeStruct((7, 4, k2, n2), jnp.bfloat16)
        jax.eval_shape(
            lambda x, y: strassen1_matmul_pallas(x, y, out_dtype=jnp.float32), a, b
        )

    before = count("1024x512x256"), count("128x128x128")
    trace(2048, 8192, 8192)
    trace(128, 128, 128)
    assert (count("1024x512x256"), count("128x128x128")) == (before[0] + 1, before[1] + 1)
