"""Core Strassen: scheme identities, pipelines, tags, cost model, hypothesis."""
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # fall back to the vendored grid shim
    from _propshim import given, settings, strategies as st

from repro.core import (
    NAIVE8,
    STRASSEN,
    WINOGRAD,
    MatmulBackend,
    combine_level,
    divide_level,
    leaf_count,
    matmul,
    merge_quadrants,
    split_quadrants,
    strassen_matmul,
    strassen_recursive,
)
from repro.core.coefficients import leaf_index_from_path, leaf_tag_path
from repro.core.strassen import combine_level_quadrants, divide_level_quadrants
from repro.core.cost_model import (
    CostModel,
    marlin_stages,
    mllib_stages,
    paper_stage_count,
    stark_stages,
    total_cost,
)

RNG = np.random.default_rng(7)


def _rand(shape):
    return jnp.asarray(RNG.standard_normal(shape).astype(np.float32))


# ---------------------------------------------------------------- schemes
@pytest.mark.parametrize("scheme", [STRASSEN, WINOGRAD, NAIVE8])
def test_scheme_bilinear_identity(scheme):
    scheme.validate()


def test_scheme_rank():
    assert STRASSEN.n_mults == 7 and WINOGRAD.n_mults == 7 and NAIVE8.n_mults == 8
    assert abs(STRASSEN.exponent() - 2.807) < 1e-3


# ---------------------------------------------------------------- pipeline
@pytest.mark.parametrize("scheme", ["strassen", "winograd", "naive8"])
@pytest.mark.parametrize("depth", [0, 1, 2, 3])
def test_strassen_matmul_square(scheme, depth):
    a, b = _rand((64, 64)), _rand((64, 64))
    got = strassen_matmul(a, b, depth=depth, scheme=scheme)
    np.testing.assert_allclose(np.asarray(got), np.asarray(a @ b), atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("m,k,n", [(128, 64, 32), (32, 96, 64), (256, 32, 128)])
def test_strassen_matmul_rectangular(m, k, n):
    a, b = _rand((m, k)), _rand((k, n))
    got = strassen_matmul(a, b, depth=2, scheme="strassen")
    np.testing.assert_allclose(np.asarray(got), np.asarray(a @ b), atol=2e-3, rtol=2e-3)


def test_strassen_recursive_matches_paper_alg1():
    a, b = _rand((128, 128)), _rand((128, 128))
    got = strassen_recursive(a, b, threshold=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(a @ b), atol=2e-3, rtol=2e-3)


def test_divide_combine_roundtrip_identity_scheme():
    """combine(c_coef) after divide must invert for the naive8 scheme.

    naive8's C row-space reproduces each quadrant from disjoint products, so
    divide->(identity leaf on matching pairs)->combine equals plain matmul.
    """
    a, b = _rand((32, 32)), _rand((32, 32))
    got = strassen_matmul(a, b, depth=3, scheme="naive8")
    np.testing.assert_allclose(np.asarray(got), np.asarray(a @ b), atol=2e-3, rtol=2e-3)


def test_quadrant_roundtrip():
    x = _rand((5, 64, 48))
    np.testing.assert_array_equal(np.asarray(merge_quadrants(split_quadrants(x))), np.asarray(x))


def test_leaf_count_matches_paper():
    # paper: b^log2(7) leaf multiplications for b = 2^depth splits
    for depth in range(5):
        b = 2**depth
        assert leaf_count(STRASSEN, depth) == 7**depth
        assert abs(leaf_count(STRASSEN, depth) - b ** np.log2(7)) < 1e-6 * 7**depth


# ---------------------------------------------------------------- tags
def test_tag_bijection():
    for depth in (1, 2, 3):
        seen = set()
        for i in range(7**depth):
            path = leaf_tag_path(i, depth)
            assert len(path) == depth and all(0 <= d < 7 for d in path)
            assert leaf_index_from_path(path) == i
            seen.add(path)
        assert len(seen) == 7**depth


def test_divide_level_ordering_matches_tags():
    """Leaf index base-7 digits must equal the per-level M-index path."""
    a = _rand((1, 16, 16))
    coef = jnp.asarray(STRASSEN.a_coef)
    lvl1 = divide_level(a, coef)  # (7, 8, 8)
    lvl2 = divide_level(lvl1, coef)  # (49, 4, 4)
    # Recompute leaf (i, j) directly from the tag path and compare.
    for idx in (0, 8, 13, 48):
        i, j = leaf_tag_path(idx, 2)
        q1 = split_quadrants(a[0])
        step1 = jnp.einsum("q,qij->ij", coef[i].astype(a.dtype), q1)
        q2 = split_quadrants(step1)
        want = jnp.einsum("q,qij->ij", coef[j].astype(a.dtype), q2)
        np.testing.assert_allclose(np.asarray(lvl2[idx]), np.asarray(want), atol=1e-5)


def _bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("scheme", [STRASSEN, WINOGRAD, NAIVE8])
def test_levels_bits_match_relayout_formulation(scheme, dtype):
    """Slice-addressed levels at batch m > 1 give the bits of the
    reshape/transpose formulation, alone and fused with the quadrant split
    (divide) or merge (combine) of the Pallas leaf's layout."""
    import _relayout_ref as ref

    rank = scheme.n_mults
    x = _rand((3, 32, 48)).astype(dtype)
    prods = _rand((3 * rank, 16, 24))
    leaf = _rand((3 * rank, 4, 8, 12))
    pairs = [
        (split_quadrants(x), ref.split_quadrants(x)),
        (merge_quadrants(leaf), ref.merge_quadrants(leaf)),
        (divide_level(x, scheme.a_coef), ref.divide_level(x, scheme.a_coef)),
        (combine_level(prods, scheme.c_coef), ref.combine_level(prods, scheme.c_coef)),
        (divide_level_quadrants(x, scheme.b_coef),
         ref.split_quadrants(ref.divide_level(x, scheme.b_coef))),
        (combine_level_quadrants(leaf, scheme.c_coef, dtype),
         ref.combine_level(ref.merge_quadrants(leaf), scheme.c_coef).astype(dtype)),
    ]
    for got, want in pairs:
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("scheme", ["strassen", "winograd", "naive8"])
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_strassen_matmul_bits_match_relayout_formulation(depth, scheme, dtype):
    import _relayout_ref as ref

    a, b = _rand((64, 128)).astype(dtype), _rand((128, 96)).astype(dtype)
    got = strassen_matmul(a, b, depth=depth, scheme=scheme)
    want = ref.strassen_matmul(a, b, depth=depth, scheme=scheme)
    np.testing.assert_array_equal(_bits(got), _bits(want))


# ---------------------------------------------------------------- backend
def test_backend_fallback_below_min_dim():
    x, w = _rand((8, 64)), _rand((64, 32))
    be = MatmulBackend(kind="strassen", depth=2, min_dim=4096)
    assert be.effective_depth(8, 64, 32) == 0
    np.testing.assert_allclose(np.asarray(matmul(x, w, be)), np.asarray(x @ w), atol=1e-5)


def test_backend_effective_depth_divisibility():
    be = MatmulBackend(kind="strassen", depth=3, min_dim=2)
    assert be.effective_depth(12, 12, 12) == 2  # 12 -> 6 -> 3 (odd stops)
    assert be.effective_depth(16, 16, 16) == 3


# ---------------------------------------------------------------- hypothesis
@settings(max_examples=25, deadline=None)
@given(
    depth=st.integers(min_value=0, max_value=2),
    scheme=st.sampled_from(["strassen", "winograd"]),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    logm=st.integers(min_value=2, max_value=5),
    logk=st.integers(min_value=2, max_value=5),
    logn=st.integers(min_value=2, max_value=5),
)
def test_property_strassen_equals_matmul(depth, scheme, seed, logm, logk, logn):
    rng = np.random.default_rng(seed)
    m, k, n = 2**logm, 2**logk, 2**logn
    a = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    got = strassen_matmul(a, b, depth=depth, scheme=scheme)
    np.testing.assert_allclose(np.asarray(got), np.asarray(a @ b), atol=3e-3, rtol=3e-3)


@settings(max_examples=25, deadline=None)
@given(
    scheme=st.sampled_from([STRASSEN, WINOGRAD, NAIVE8]),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_property_linearity_of_levels(scheme, seed):
    """divide/combine are linear: divide(x+y) == divide(x) + divide(y)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((2, 8, 8)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((2, 8, 8)).astype(np.float32))
    coef = jnp.asarray(scheme.a_coef)
    lhs = divide_level(x + y, coef)
    rhs = divide_level(x, coef) + divide_level(y, coef)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), atol=1e-5)


# ---------------------------------------------------------------- cost model
def test_paper_stage_count_eq25():
    assert paper_stage_count(2**14, 2**4) == 2 * 4 + 2  # p=14, q=10
    assert paper_stage_count(4096, 2) == 2 * 1 + 2


def test_cost_model_orders_systems_like_paper():
    """Paper Fig. 8: Stark < Marlin <= MLLib at large sizes, any b."""
    for b in (8, 16, 32):
        stark = total_cost("stark", 16384, b, cores=25)
        marlin = total_cost("marlin", 16384, b, cores=25)
        mllib = total_cost("mllib", 16384, b, cores=25)
        assert stark < marlin and stark < mllib, (b, stark, marlin, mllib)


def test_cost_model_u_curve():
    """Paper Fig. 9: running time vs partition count is U-shaped."""
    costs = [total_cost("stark", 8192, b, cores=25) for b in (2, 4, 8, 16, 32, 64)]
    mins = int(np.argmin(costs))
    assert 0 < mins < len(costs) - 1, costs  # interior minimum


def test_cost_model_leaf_dominates_small_b():
    """Paper §V-E: leaf multiplication dominates at small partition counts."""
    model = CostModel()
    sections = model.by_section(stark_stages(8192, 4), cores=25)
    assert sections["leaf"] > sections["divide"]
    assert sections["leaf"] > sections["combine"]


def test_cost_model_overlap_prices_stages_at_max_not_sum():
    """overlap=True models latency-hidden transfers (the oot scheduler's
    async wave pipeline): each stage costs max(comp, comm) instead of
    comp + comm, so the overlapped total is never larger and strictly
    smaller whenever a stage carries both streams."""
    model = CostModel()
    stages = stark_stages(8192, 16)
    seq = model.total(stages, cores=25)
    ovl = model.total(stages, cores=25, overlap=True)
    assert ovl < seq
    for s in stages:
        both = s.wall_clock(25, model.t_flop, model.t_elem)
        hid = s.wall_clock(25, model.t_flop, model.t_elem, overlap=True)
        assert hid <= both
        pf = max(min(s.parallelization, 25), 1.0)
        assert hid == pytest.approx(
            max(s.computation * model.t_flop, s.communication * model.t_elem) / pf
        )
    # by_section sums respect the same discount
    sec_seq = model.by_section(stages, cores=25)
    sec_ovl = model.by_section(stages, cores=25, overlap=True)
    assert set(sec_ovl) == set(sec_seq)
    assert sum(sec_ovl.values()) == pytest.approx(ovl)
    assert all(sec_ovl[k] <= sec_seq[k] for k in sec_seq)


def test_cost_model_stark_fewer_leaf_flops():
    """Stark does b^2.807 leaf multiplies vs b^3 (the paper's core claim)."""
    n, b = 8192, 16
    stark_leaf = sum(s.computation for s in stark_stages(n, b) if s.section == "leaf")
    marlin_leaf = sum(s.computation for s in marlin_stages(n, b) if s.section == "leaf")
    mllib_leaf = sum(s.computation for s in mllib_stages(n, b) if s.section == "leaf")
    assert stark_leaf < marlin_leaf == mllib_leaf
    np.testing.assert_allclose(stark_leaf / marlin_leaf, 7**4 / 16**3, rtol=1e-6)
