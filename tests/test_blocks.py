"""Tagged BlockMatrix runtime: tag codec/algebra, stores, out-of-core Strassen."""
import dataclasses
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.blocks import tags
from repro.blocks.blockmatrix import (
    ArenaStore,
    BlockMatrix,
    DictStore,
    MemmapStore,
    make_store,
)
from repro.blocks.scheduler import (
    StrassenScheduler,
    leaf_bytes,
    min_depth_for_budget,
    pipelined_leaf_bytes,
    strassen_oot_matmul,
)
from repro.core import autotune
from repro.core.autotune import Calibration, Candidate
from repro.core.backend import VALID_KINDS, MatmulBackend, matmul, resolve_auto, route_for
from repro.core.coefficients import get_scheme, leaf_index_from_path, leaf_tag_path

RNG = np.random.default_rng(7)

CALIB = Calibration(
    t_flop=1e-11, t_elem=1e-9, t_h2d=2e-9,
    device_kind="test", device_count=1,
)

# The scheduler's leaf dispatch defaults to kind='auto'; tests pin the
# leaves to the naive matmul so no calibration micro-benchmark runs.
NAIVE_LEAVES = MatmulBackend(kind="naive")


@pytest.fixture(autouse=True)
def _synthetic_calibration(monkeypatch):
    monkeypatch.setattr(autotune, "_CALIBRATION", CALIB)
    monkeypatch.setattr(autotune, "_PROCESS_CACHES", {})
    resolve_auto.cache_clear()


def _rand(shape, dtype=np.float32):
    return RNG.standard_normal(shape).astype(dtype)


def _rel_err(got, want):
    got = np.asarray(got, dtype=np.float32)
    want = np.asarray(want, dtype=np.float32)
    return float(np.abs(got - want).max() / (np.abs(want).max() or 1.0))


# ---------------------------------------------------------------- tag codec
@pytest.mark.parametrize("scheme_name", ["strassen", "winograd"])
@pytest.mark.parametrize("depth", [1, 2, 3, 4])
def test_tag_round_trips_match_leaf_tag_path(scheme_name, depth):
    """encode/decode agree with coefficients.leaf_tag_path at every depth
    (both rank-7 schemes share the base-7 M-index alphabet)."""
    rank = get_scheme(scheme_name).n_mults
    step = max(1, rank**depth // 50)
    for index in range(0, rank**depth, step):
        path = tags.decode(index, depth, rank)
        assert path == leaf_tag_path(index, depth)
        assert tags.encode(path, rank) == index == leaf_index_from_path(path)
        assert tags.from_string(tags.to_string(path)) == path


def test_tag_codec_base4_and_bounds():
    for depth in (1, 2, 3):
        for index in range(4**depth):
            path = tags.decode(index, depth, tags.Q_BASE)
            assert tags.encode(path, tags.Q_BASE) == index
    with pytest.raises(ValueError):
        tags.decode(7, 1, 7 - 1)  # index out of range for base 6
    with pytest.raises(ValueError):
        tags.encode((7,), 7)  # digit out of range
    with pytest.raises(ValueError):
        tags.parent(())


def test_tag_child_parent_and_strings():
    p = tags.child(tags.child((), 3), 0)
    assert p == (3, 0)
    assert tags.parent(p) == (3,)
    assert tags.to_string(p) == "3,0"
    assert tags.from_string("") == ()


@pytest.mark.parametrize("scheme_name", ["strassen", "winograd", "naive8"])
def test_tag_algebra_reproduces_matmul_tensor(scheme_name):
    """The divide/combine tag expansion is exactly the block-matmul tensor
    at depth 1 and 2 — the multi-level Scheme.validate."""
    tags.validate_algebra(scheme_name, 1)
    tags.validate_algebra(scheme_name, 2)


def test_operand_terms_coefficients_multiply_down_levels():
    scheme = get_scheme("strassen")
    # M6 at level 0 uses A-coeffs (-1, 0, 1, 0): two terms per level.
    terms = tags.operand_terms((5, 5), scheme, "a")
    assert len(terms) == 4
    coeffs = sorted(c for _, c in terms)
    assert coeffs == [-1.0, -1.0, 1.0, 1.0]
    with pytest.raises(ValueError):
        tags.operand_terms((0,), scheme, "c")


# --------------------------------------------------------------- BlockMatrix
def _stores(slot_bytes, tmp_path):
    return [
        DictStore(),
        ArenaStore(slot_bytes, capacity=8),
        MemmapStore(str(tmp_path / "spill")),
    ]


@pytest.mark.parametrize("dtype_name", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(64, 64), (65, 33), (100, 7), (30, 50)])
def test_blockmatrix_dense_round_trip(shape, dtype_name, tmp_path):
    """from_dense/to_dense is exact for odd/padded shapes in f32 and bf16
    across every store backend."""
    dtype = jnp.dtype(dtype_name)
    arr = np.asarray(jnp.asarray(_rand(shape)).astype(dtype))
    block = (16, 16)
    for store in _stores(16 * 16 * 4, tmp_path):
        bm = BlockMatrix.from_dense(arr, block, store, tag="A:")
        assert bm.to_dense().tobytes() == arr.tobytes()
        assert bm.block(0, 0).shape == block  # padded in storage
        meta = bm.meta()
        assert meta["dtype"] == dtype_name and meta["shape"] == tuple(shape)
        store.close()


def test_blockmatrix_shape_extension_and_free(tmp_path):
    arr = _rand((40, 24))
    store = DictStore()
    bm = BlockMatrix.from_dense(arr, (16, 16), store, tag="A:", shape=(64, 32))
    dense = bm.to_dense()
    assert dense.shape == (64, 32)
    np.testing.assert_array_equal(dense[:40, :24], arr)
    assert not dense[40:].any() and not dense[:, 24:].any()
    assert store.nbytes() > 0
    bm.free()
    assert store.nbytes() == 0


def test_arena_store_reuses_slots_and_reports_footprint():
    store = ArenaStore(slot_bytes=256, capacity=2)
    blk = np.arange(64, dtype=np.float32).reshape(8, 8)
    for i in range(10):  # 10 puts through 2-slot segments with deletes
        store.put((i, 0, "A:"), blk)
        store.delete((i, 0, "A:"))
    store.put((0, 0, "B:"), blk[:4])
    np.testing.assert_array_equal(store.get((0, 0, "B:")), blk[:4])
    assert store.arena_bytes() == 2 * 256  # deletes recycled one segment
    with pytest.raises(ValueError):
        store.put((1, 0, "B:"), np.zeros((9, 9), np.float32))


def test_arena_store_mixed_dtypes():
    store = ArenaStore(slot_bytes=64 * 4, capacity=4)
    f32 = _rand((8, 8))
    bf16 = np.asarray(jnp.asarray(_rand((8, 8))).astype(jnp.bfloat16))
    store.put((0, 0, "C:"), f32)
    store.put((0, 0, "A:"), bf16)
    np.testing.assert_array_equal(store.get((0, 0, "C:")), f32)
    assert store.get((0, 0, "A:")).tobytes() == bf16.tobytes()


def test_memmap_store_spills_npy_files_and_cleans_up(tmp_path):
    root = str(tmp_path / "spill")
    store = MemmapStore(root)
    blk = _rand((8, 8))
    store.put((0, 1, "C:2,3"), blk)
    files = os.listdir(root)
    assert len(files) == 1 and files[0].endswith(".npy")
    np.testing.assert_array_equal(np.asarray(store.get((0, 1, "C:2,3"))), blk)
    assert store.nbytes() >= blk.nbytes
    store.delete((0, 1, "C:2,3"))
    assert os.listdir(root) == []
    # self-owned temp dirs are removed on close
    owned = MemmapStore()
    owned.put((0, 0, "A:"), blk)
    root2 = owned.root
    owned.close()
    assert not os.path.isdir(root2)


def test_memmap_store_preserves_bf16(tmp_path):
    store = MemmapStore(str(tmp_path / "spill"))
    blk = np.asarray(jnp.asarray(_rand((4, 4))).astype(jnp.bfloat16))
    store.put((0, 0, "A:"), blk)
    got = store.get((0, 0, "A:"))
    assert got.dtype == blk.dtype
    assert np.asarray(got).tobytes() == blk.tobytes()


def test_make_store_specs():
    assert isinstance(make_store("dict"), DictStore)
    assert isinstance(make_store("arena", slot_bytes=64), ArenaStore)
    mm = make_store("memmap")
    assert isinstance(mm, MemmapStore)
    mm.close()
    with pytest.raises(ValueError):
        make_store("s3")


# ------------------------------------------------------- out-of-core Strassen
@pytest.mark.parametrize("store_kind", ["dict", "arena", "memmap"])
def test_oot_depth2_budget_below_operands(store_kind):
    """The acceptance shape: depth 2 with a device budget smaller than
    either operand still matches jnp.matmul, in >= 2 staging waves, with
    tracked peak device bytes inside the budget."""
    m, k, n = 200, 136, 168
    a, b = _rand((m, k)), _rand((k, n))
    budget = min(a.nbytes, b.nbytes) // 2
    out, stats = strassen_oot_matmul(
        a, b, depth=2, budget_bytes=budget, backend=NAIVE_LEAVES, store=store_kind
    )
    assert _rel_err(out, a @ b) < 2e-3
    assert stats.waves >= 2
    assert stats.peak_device_bytes <= budget
    assert stats.leaves == 49
    assert stats.h2d_bytes > 0 and stats.d2h_bytes > 0


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_oot_depths_and_schemes(depth):
    a, b = _rand((128, 128)), _rand((128, 128))
    for scheme in ("strassen", "winograd"):
        out, stats = strassen_oot_matmul(
            a, b, depth=depth, budget_bytes=4 * leaf_bytes(128, 128, 128, depth, a.dtype),
            scheme=scheme, backend=NAIVE_LEAVES,
        )
        assert _rel_err(out, a @ b) < 2e-3, (scheme, depth)
        assert stats.leaves == 7**depth


def test_oot_bf16_parity_within_1e2():
    """bf16 depth-2 parity vs the dense bf16 matmul stays within the CI
    gate's 1e-2 (f32 staging keeps one rounding per value)."""
    a = jnp.asarray(_rand((160, 96))).astype(jnp.bfloat16)
    b = jnp.asarray(_rand((96, 128))).astype(jnp.bfloat16)
    a_h, b_h = np.asarray(a), np.asarray(b)
    out, stats = strassen_oot_matmul(
        a_h, b_h, depth=2, budget_bytes=a_h.nbytes, backend=NAIVE_LEAVES
    )
    assert out.dtype == a_h.dtype
    assert stats.stage_dtype == "float32"
    assert _rel_err(out, jnp.matmul(a, b)) < 1e-2


def test_oot_block_grain_and_prefetch_off():
    a, b = _rand((96, 96)), _rand((96, 96))
    out, stats = strassen_oot_matmul(
        a, b, depth=2, budget_bytes=a.nbytes, block=8,
        backend=NAIVE_LEAVES, prefetch=False,
    )
    assert _rel_err(out, a @ b) < 2e-3
    assert not stats.prefetch


def test_oot_budget_too_small_raises_with_min_depth():
    a, b = _rand((256, 256)), _rand((256, 256))
    with pytest.raises(ValueError, match="use depth >="):
        strassen_oot_matmul(a, b, depth=1, budget_bytes=4096, backend=NAIVE_LEAVES)
    assert min_depth_for_budget(256, 256, 256, 3 * 64 * 64 * 4, np.float32) == 2
    with pytest.raises(ValueError):
        min_depth_for_budget(2**20, 2**20, 2**20, 1, np.float32, max_depth=4)


def test_oot_scheduler_validates_config():
    with pytest.raises(ValueError):
        StrassenScheduler(depth=0, budget_bytes=1 << 20)
    with pytest.raises(ValueError):
        StrassenScheduler(depth=1, budget_bytes=0)


# ------------------------------------------------- async wave pipeline
@pytest.mark.parametrize("store_kind", ["dict", "arena", "memmap"])
def test_oot_pipelined_matches_sync_bitexact_f32(store_kind):
    """The async 2-deep pipeline runs the identical leaf schedule as the
    synchronous loop — f32 results are bit-exact across every store, and
    both runs' modeled peaks respect the budget."""
    m, k, n = 200, 136, 168
    a, b = _rand((m, k)), _rand((k, n))
    # one pipelined wave slot — still smaller than either operand
    budget = pipelined_leaf_bytes(m, k, n, 2, np.float32)
    assert budget < min(a.nbytes, b.nbytes)
    kw = dict(depth=2, budget_bytes=budget, backend=NAIVE_LEAVES, store=store_kind)
    out_pipe, st_pipe = strassen_oot_matmul(a, b, **kw)
    out_sync, st_sync = strassen_oot_matmul(a, b, prefetch=False, **kw)
    assert st_pipe.prefetch and st_pipe.waves >= 2
    assert not st_sync.prefetch
    assert np.array_equal(out_pipe, out_sync)
    assert _rel_err(out_pipe, a @ b) < 2e-3
    st_pipe.assert_within_budget()
    st_sync.assert_within_budget()
    assert st_sync.overlap_efficiency == 0.0


@pytest.mark.parametrize("store_kind", ["dict", "arena", "memmap"])
def test_oot_pipelined_bf16_parity_all_stores(store_kind):
    """bf16 pipelined == bf16 sync bit-for-bit, and both stay inside the
    CI gate's 1e-2 vs the dense bf16 matmul."""
    a = jnp.asarray(_rand((160, 96))).astype(jnp.bfloat16)
    b = jnp.asarray(_rand((96, 128))).astype(jnp.bfloat16)
    a_h, b_h = np.asarray(a), np.asarray(b)
    budget = pipelined_leaf_bytes(160, 96, 128, 2, a_h.dtype)
    kw = dict(depth=2, budget_bytes=budget, backend=NAIVE_LEAVES, store=store_kind)
    out_pipe, st_pipe = strassen_oot_matmul(a_h, b_h, **kw)
    out_sync, _ = strassen_oot_matmul(a_h, b_h, prefetch=False, **kw)
    assert st_pipe.prefetch and st_pipe.waves >= 2
    assert out_pipe.dtype == a_h.dtype
    assert out_pipe.tobytes() == out_sync.tobytes()
    assert _rel_err(out_pipe, jnp.matmul(a, b)) < 1e-2


def test_oot_overlap_telemetry_on_forced_multiwave_run():
    """A forced multi-wave pipelined run reports strictly positive
    overlap_efficiency, carries ordered per-wave timestamps that show the
    interleave (wave k+1 staged before wave k's fetch), and lands in the
    process's recent-stats ring."""
    from repro.blocks.scheduler import recent_oot_stats, reset_oot_stats

    reset_oot_stats()
    a, b = _rand((192, 192)), _rand((192, 192))
    budget = pipelined_leaf_bytes(192, 192, 192, 2, a.dtype)  # one pipelined slot
    out, stats = strassen_oot_matmul(
        a, b, depth=2, budget_bytes=budget, backend=NAIVE_LEAVES
    )
    assert _rel_err(out, a @ b) < 2e-3
    assert stats.prefetch and stats.wave_size == 1 and stats.waves == 49
    # the modeled peak charges both in-flight waves in full plus the
    # prefetch, saturating a one-slot budget exactly
    assert stats.peak_device_bytes == budget
    assert 0.0 < stats.overlap_efficiency <= 1.0
    assert len(stats.wave_events) == stats.waves
    for e in stats.wave_events:
        assert (
            e["issue_start"] <= e["issue_end"] <= e["dispatch_end"]
            <= e["fetch_start"] <= e["fetch_end"]
        )
    # the pipeline interleave: wave 1's staging is issued before wave 0's
    # D2H fence, so its transfer overlaps wave 0's in-flight compute
    assert stats.wave_events[1]["issue_start"] < stats.wave_events[0]["fetch_start"]
    ring = recent_oot_stats()
    assert ring and ring[-1]["overlap_efficiency"] == stats.overlap_efficiency
    assert ring[-1]["wave_events"] == stats.wave_events
    reset_oot_stats()
    assert recent_oot_stats() == []


def test_oot_budget_counts_inflight_pipeline_slot():
    """Wave sizing charges the full in-flight pipeline: the slot is two
    whole leaf working sets (the previous wave's operands stay pinned by
    its unfenced executions) plus one more wave of operand prefetch —
    budgets below that degrade to synchronous staging instead of
    exceeding the budget, and the pipelined depth picker deepens until
    the slot fits."""
    m = k = n = 192
    per_leaf = leaf_bytes(m, k, n, 2, np.float32)
    slot = pipelined_leaf_bytes(m, k, n, 2, np.float32)
    # the slot exceeds 2x one leaf by exactly one wave of operand bytes
    assert 2 * per_leaf < slot < 3 * per_leaf
    a, b = _rand((m, k)), _rand((k, n))
    # Regression (review): a 2x-leaf budget — the old slot size — cannot
    # hold the pipelined peak; the scheduler must run synchronously.
    out, stats = strassen_oot_matmul(
        a, b, depth=2, budget_bytes=2 * per_leaf, backend=NAIVE_LEAVES
    )
    assert _rel_err(out, a @ b) < 2e-3
    assert not stats.prefetch and stats.wave_size == 2
    assert stats.overlap_efficiency == 0.0
    stats.assert_within_budget()
    assert min_depth_for_budget(m, k, n, 2 * per_leaf, np.float32) == 2
    assert min_depth_for_budget(m, k, n, 2 * per_leaf, np.float32, pipelined=True) == 3
    assert min_depth_for_budget(m, k, n, slot, np.float32, pipelined=True) == 2
    # a doctored peak trips the budget assertion
    stats.peak_device_bytes = stats.budget_bytes + 1
    with pytest.raises(AssertionError, match="exceeded the budget"):
        stats.assert_within_budget()


def _inject_failing_leaf(monkeypatch, fail_at: int) -> dict:
    """Make the fail_at-th leaf multiply raise, mid-pipeline."""
    calls = {"n": 0}
    real = StrassenScheduler._leaf_matmul

    def boom(self, a_dev, b_dev):
        calls["n"] += 1
        if calls["n"] == fail_at:
            raise RuntimeError("injected leaf failure")
        return real(self, a_dev, b_dev)

    monkeypatch.setattr(StrassenScheduler, "_leaf_matmul", boom)
    return calls


@pytest.mark.parametrize("store_kind", ["dict", "memmap"])
def test_oot_failing_leaf_cleans_caller_store_and_device_buffers(
    store_kind, tmp_path, monkeypatch
):
    """A leaf failure mid-pipeline (prefetched wave in flight) must not
    leak: every block the run created is dropped from a caller-provided
    store (spilled npy files included), unrelated keys survive — other
    runs' blocks under the same "A:"/"B:"/"C:" tag space included, since
    tags are not run-scoped — and the in-flight device buffers are
    released even while the exception's traceback still pins the
    scheduler frame."""
    import jax

    a, b = _rand((96, 96)), _rand((96, 96))
    store = (
        DictStore() if store_kind == "dict"
        else MemmapStore(str(tmp_path / "spill"))
    )
    keep = np.ones((2, 2), np.float32)
    store.put((0, 0, "keep"), keep)
    # another (interleaved/earlier) scheduler run's block: tag-prefix
    # matching would destroy it, per-run key tracking must not
    foreign = np.full((2, 2), 7.0, np.float32)
    store.put((99, 99, "A:0"), foreign)
    _inject_failing_leaf(monkeypatch, fail_at=5)
    baseline = sum(not x.is_deleted() for x in jax.live_arrays())
    with pytest.raises(RuntimeError, match="injected leaf failure") as excinfo:
        strassen_oot_matmul(
            a, b, depth=2, budget_bytes=4 * leaf_bytes(96, 96, 96, 2, a.dtype),
            backend=NAIVE_LEAVES, store=store,
        )
    # excinfo still holds the traceback here, so the frame's device
    # references are alive — release must have been explicit
    assert excinfo.traceback
    assert sum(not x.is_deleted() for x in jax.live_arrays()) <= baseline
    leftover = [kk for kk in store.keys() if kk[2][:2] in ("A:", "B:", "C:")]
    assert leftover == [(99, 99, "A:0")]
    np.testing.assert_array_equal(np.asarray(store.get((0, 0, "keep"))), keep)
    np.testing.assert_array_equal(np.asarray(store.get((99, 99, "A:0"))), foreign)
    if store_kind == "memmap":
        assert len(os.listdir(store.root)) == 2  # only the unrelated keys
    store.close()


def test_oot_failing_leaf_removes_owned_memmap_spill_dir(monkeypatch):
    """When the scheduler built the memmap store itself, a failing run
    removes the whole temp spill directory."""
    roots = []
    real_init = MemmapStore.__init__

    def spying_init(self, root=None):
        real_init(self, root)
        roots.append(self.root)

    monkeypatch.setattr(MemmapStore, "__init__", spying_init)
    _inject_failing_leaf(monkeypatch, fail_at=3)
    a, b = _rand((96, 96)), _rand((96, 96))
    with pytest.raises(RuntimeError, match="injected leaf failure"):
        strassen_oot_matmul(
            a, b, depth=1, budget_bytes=a.nbytes, backend=NAIVE_LEAVES,
            store="memmap",
        )
    assert roots and not os.path.isdir(roots[0])


# ------------------------------------------- autotune strassen_oot family
def test_oot_candidates_enumerate_only_with_budget():
    cands = autotune.enumerate_candidates(512, 512, 512, min_dim=64, max_depth=2)
    assert not any(c.kind == "strassen_oot" for c in cands)
    cands = autotune.enumerate_candidates(
        512, 512, 512, min_dim=64, max_depth=2, oot_budget=16 << 20
    )
    oot = [c for c in cands if c.kind == "strassen_oot"]
    assert {(c.scheme, c.depth) for c in oot} == {
        ("strassen", 1), ("strassen", 2), ("winograd", 1), ("winograd", 2),
    }


def test_oot_respects_min_dim_crossover_when_dense_fits():
    """Below min_dim the out-of-core family must not enumerate (measured
    24x slower than naive at n=128) — unless the dense working set cannot
    fit the budget, where out-of-core is feasibility, not preference."""
    cands = autotune.enumerate_candidates(
        128, 128, 128, min_dim=192, max_depth=2, oot_budget=2 << 20
    )
    assert not any(c.kind == "strassen_oot" for c in cands)
    d = autotune.autotune(
        128, 128, 128, min_dim=192, max_depth=2, calibration=CALIB, oot_budget=2 << 20
    )
    assert d.kind == "naive"
    # dense infeasible: oot enumerates even below min_dim
    tiny = 3 * 48 * 48 * 4  # < 128^2 dense working set, >= one depth-2 leaf
    cands = autotune.enumerate_candidates(
        128, 128, 128, min_dim=192, max_depth=2, oot_budget=tiny
    )
    assert cands and all(c.kind == "strassen_oot" for c in cands)


def test_oot_only_candidates_when_dense_exceeds_budget():
    """When A+B+C cannot fit the budget at once, every on-device candidate
    is infeasible and enumeration keeps only the out-of-core family."""
    budget = 64 << 20
    cands = autotune.enumerate_candidates(
        8192, 8192, 8192, min_dim=1024, max_depth=2, oot_budget=budget
    )
    assert cands and all(c.kind == "strassen_oot" for c in cands)
    for c in cands:
        assert leaf_bytes(8192, 8192, 8192, c.depth, np.float32) <= budget
    d = autotune.autotune(
        8192, 8192, 8192, min_dim=1024, max_depth=2,
        calibration=CALIB, oot_budget=budget,
    )
    assert d.kind == "strassen_oot"


def test_oot_predicted_terms_include_t_h2d():
    cand = Candidate(kind="strassen_oot", scheme="strassen", depth=2)
    terms = autotune.predict_cost_terms(cand, 4096, 4096, 4096, CALIB)
    assert set(terms) == {"t_flop", "t_elem", "t_h2d"}
    assert terms["t_h2d"] > 0
    assert autotune.predict_seconds(cand, 4096, 4096, 4096, CALIB) == pytest.approx(
        sum(terms.values())
    )
    # staging term scales with t_h2d — checked with the async pipeline's
    # overlap discount off, since the discount is piecewise in flop time
    # and deliberately non-linear in t_h2d; local/naive candidates never
    # touch the term either way
    raw = autotune.predict_cost_terms(
        cand, 4096, 4096, 4096, CALIB, oot_overlap=False
    )
    hot = dataclasses.replace(CALIB, t_h2d=CALIB.t_h2d * 10)
    assert autotune.predict_cost_terms(
        cand, 4096, 4096, 4096, hot, oot_overlap=False
    )["t_h2d"] == pytest.approx(raw["t_h2d"] * 10)
    for other in (Candidate(kind="naive"), Candidate(kind="strassen", depth=2)):
        assert autotune.predict_cost_terms(other, 4096, 4096, 4096, CALIB)[
            "t_h2d"
        ] == 0.0


def test_oot_overlap_discount_hides_staged_transfer_cost():
    """Default cost prediction models the 2-deep wave pipeline: H2D time
    covered by leaf compute shrinks to the exposed fraction; transfer
    beyond the compute stays fully priced on top of it."""
    cand = Candidate(kind="strassen_oot", scheme="strassen", depth=2)
    raw = autotune.predict_cost_terms(
        cand, 4096, 4096, 4096, CALIB, oot_overlap=False
    )
    dft = autotune.predict_cost_terms(cand, 4096, 4096, 4096, CALIB)
    assert dft["t_flop"] == pytest.approx(raw["t_flop"])
    assert 0.0 < dft["t_h2d"] < raw["t_h2d"]
    frac = autotune.OOT_OVERLAP_EXPOSED_FRACTION
    hidden = min(raw["t_h2d"], raw["t_flop"])
    assert dft["t_h2d"] == pytest.approx(
        max(raw["t_h2d"] - raw["t_flop"], 0.0) + frac * hidden
    )
    # transfer-bound regime: only the compute-covered slice is discounted
    hot = dataclasses.replace(CALIB, t_h2d=CALIB.t_h2d * 100)
    raw_hot = autotune.predict_cost_terms(
        cand, 4096, 4096, 4096, hot, oot_overlap=False
    )
    assert raw_hot["t_h2d"] > raw_hot["t_flop"]
    assert autotune.predict_cost_terms(cand, 4096, 4096, 4096, hot)[
        "t_h2d"
    ] == pytest.approx(
        raw_hot["t_h2d"] - raw_hot["t_flop"] + frac * raw_hot["t_flop"]
    )
    # the discounted prediction still decomposes exactly
    assert autotune.predict_seconds(cand, 4096, 4096, 4096, hot) == pytest.approx(
        sum(autotune.predict_cost_terms(cand, 4096, 4096, 4096, hot).values())
    )


def test_predict_terms_decomposition_sums_for_all_kinds():
    for cand in [
        Candidate(kind="naive"),
        Candidate(kind="strassen", scheme="strassen", depth=2),
        Candidate(kind="winograd", scheme="winograd", depth=2),
        Candidate(kind="strassen_fused", scheme="strassen", depth=2),
        Candidate(kind="strassen_oot", scheme="winograd", depth=3),
    ]:
        terms = autotune.predict_cost_terms(cand, 2048, 2048, 2048, CALIB)
        assert autotune.predict_seconds(
            cand, 2048, 2048, 2048, CALIB
        ) == pytest.approx(sum(terms.values()))


def test_oot_execute_and_telemetry_terms():
    tel = autotune.get_telemetry()
    tel.reset()
    d = autotune.autotune(
        4096, 4096, 4096, min_dim=1024, max_depth=2,
        calibration=CALIB, oot_budget=8 << 20,
    )
    (event,) = tel.events
    assert event.terms is not None and set(event.terms) == {
        "t_flop", "t_elem", "t_h2d",
    }
    # run the decision's route small (same kind) to keep suite time sane
    be = route_for(dataclasses.replace(d, depth=1), NAIVE_LEAVES)
    assert (be.kind, be.scheme_name) == ("strassen_oot", d.scheme)
    a, b = _rand((96, 96)), _rand((96, 96))
    got = matmul(jnp.asarray(a), jnp.asarray(b), be)
    assert _rel_err(got, a @ b) < 2e-3


def test_cache_key_oot_budget_separates():
    kw = dict(device_kind="cpu", device_count=1, schemes=("strassen",),
              min_dim=1024, max_depth=2)
    k_plain = autotune.cache_key(512, 512, 512, jnp.float32, **kw)
    k_oot = autotune.cache_key(512, 512, 512, jnp.float32, oot_budget=1 << 20, **kw)
    assert k_plain != k_oot
    assert autotune.cache_key(512, 512, 512, jnp.float32, oot_budget=None, **kw) == k_plain


# ----------------------------------------------------- backend kind routing
def test_backend_kind_validation_lists_registered_kinds():
    with pytest.raises(ValueError) as err:
        MatmulBackend(kind="strassen_typo")
    for kind in VALID_KINDS:
        assert kind in str(err.value)
    for kind in VALID_KINDS:  # every registered kind constructs
        MatmulBackend(kind=kind)


def test_backend_oot_kind_routes_through_block_runtime():
    a, b = _rand((120, 88)), _rand((88, 96))
    be = MatmulBackend(
        kind="strassen_oot", depth=2, min_dim=1, device_budget=a.nbytes
    )
    got = matmul(jnp.asarray(a), jnp.asarray(b), be)
    assert _rel_err(got, a @ b) < 2e-3
    # leading batch dims flatten/restore like every other kind
    x = _rand((2, 4, 88))
    got = matmul(jnp.asarray(x), jnp.asarray(b), be)
    assert _rel_err(got, x @ b) < 2e-3


def test_backend_oot_deepens_when_budget_demands():
    a, b = _rand((256, 256)), _rand((256, 256))
    budget = 3 * 64 * 64 * 4 + 1  # fits depth-2 leaves only
    be = MatmulBackend(kind="strassen_oot", depth=1, min_dim=1, device_budget=budget)
    got = matmul(jnp.asarray(a), jnp.asarray(b), be)
    assert _rel_err(got, a @ b) < 2e-3


def test_backend_oot_rejects_jit():
    import jax

    be = MatmulBackend(kind="strassen_oot", depth=1, min_dim=1)
    a, b = jnp.zeros((64, 64)), jnp.zeros((64, 64))
    with pytest.raises(ValueError, match="cannot run under jit"):
        jax.jit(lambda x, y: matmul(x, y, be))(a, b)


def test_auto_with_budget_never_picks_oot_under_jit():
    """kind='auto' + device_budget inside jit resolves without the
    eager-only family (the decision would otherwise crash the trace) —
    even at shapes where the eager resolution WOULD pick strassen_oot."""
    import jax

    m = k = n = 256
    budget = 3 * 32 * 32 * 4  # dense working set infeasible at 256^2
    be = MatmulBackend(kind="auto", depth=3, min_dim=1, device_budget=budget)
    a, b = _rand((m, k)), _rand((k, n))
    # eagerly, the budget forces the out-of-core family...
    d = autotune.autotune(
        m, k, n, min_dim=1, max_depth=3, calibration=CALIB, oot_budget=budget
    )
    assert d.kind == "strassen_oot"
    # ...but under jit the same backend resolves to a traceable plan.
    got = jax.jit(lambda x, y: matmul(x, y, be))(jnp.asarray(a), jnp.asarray(b))
    assert _rel_err(got, a @ b) < 3e-3


def test_resolve_auto_routes_oot_decision(monkeypatch):
    from repro.core.autotune import Decision

    decision = Decision(kind="strassen_oot", scheme="strassen", depth=2, predicted_s=1e-3)
    monkeypatch.setattr(autotune, "autotune", lambda *a, **k: decision)
    be = MatmulBackend(kind="auto", depth=2, min_dim=1, device_budget=1 << 20)
    resolved = resolve_auto(4096, 4096, 4096, "float32", be)
    assert resolved.kind == "strassen_oot" and resolved.depth == 2
    assert resolved.device_budget == 1 << 20


def test_resolve_auto_preserves_oot_decision_scheme(monkeypatch):
    """A winograd oot decision must execute winograd — the scheme rides
    along as the resolved backend's single schemes entry."""
    from repro.core.autotune import Decision

    decision = Decision(kind="strassen_oot", scheme="winograd", depth=1, predicted_s=1e-3)
    real = autotune.autotune

    def fake(m, *a, **k):  # only the outer shape resolves out-of-core —
        # the scheduler's own leaf dispatch must keep resolving normally
        return decision if m == 2048 else real(m, *a, **k)

    monkeypatch.setattr(autotune, "autotune", fake)
    be = MatmulBackend(kind="auto", depth=2, min_dim=1, device_budget=1 << 20)
    resolved = resolve_auto(2048, 2048, 2048, "float32", be)
    assert resolved.scheme_name == "winograd"
    a, b = _rand((96, 96)), _rand((96, 96))
    got = matmul(jnp.asarray(a), jnp.asarray(b), resolved)
    assert _rel_err(got, a @ b) < 2e-3


def test_jitted_launchers_exclude_oot_from_backend_choices():
    """train/serve/dryrun run every matmul under jit, where the eager-only
    kind can never execute — their --backend menus must not offer it."""
    import importlib.util

    from repro.core.backend import EAGER_ONLY_KINDS, JIT_SAFE_KINDS

    assert "strassen_oot" in EAGER_ONLY_KINDS
    assert "strassen_oot" not in JIT_SAFE_KINDS
    assert set(JIT_SAFE_KINDS) | set(EAGER_ONLY_KINDS) == set(VALID_KINDS)
    for mod_name in ("train", "serve", "dryrun"):
        spec = importlib.util.find_spec(f"repro.launch.{mod_name}")
        with open(spec.origin) as f:
            assert "JIT_SAFE_KINDS" in f.read(), mod_name


def test_calibration_round_trips_t_h2d():
    d = CALIB.to_dict()
    assert d["t_h2d"] == CALIB.t_h2d
    assert Calibration.from_dict(d) == CALIB
    # pre-t_h2d cache entries still load (field defaults to 0.0)
    legacy = {k: v for k, v in d.items() if k != "t_h2d"}
    assert Calibration.from_dict(legacy).t_h2d == 0.0


def test_calibration_snapshot_reports_without_running():
    snap = autotune.calibration_snapshot()
    assert snap is not None and snap["t_h2d"] == CALIB.t_h2d
