"""Device-resident subtrees of the out-of-core runtime: the level rule,
bit parity with the leaf waves, stats, counters, spans and the ladder."""
import jax
import ml_dtypes
import numpy as np
import pytest

from repro import obs
from repro.blocks.recovery import ChaosConfig
from repro.blocks.scheduler import (
    PlanScheduler,
    _leaf_dims,
    strassen_oot_matmul,
    subtree_bytes,
    subtree_level,
)
from repro.core.backend import MatmulBackend
from repro.obs import metrics as obs_metrics

NAIVE_LEAVES = MatmulBackend(kind="naive")
BF16 = ml_dtypes.bfloat16


def _operands(shape_a, shape_b, dtype, integers, seed=0):
    rng = np.random.default_rng(seed)
    if integers:
        draw = lambda s: rng.integers(-8, 9, s).astype(np.float32)  # noqa: E731
    else:
        draw = lambda s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    return draw(shape_a).astype(dtype), draw(shape_b).astype(dtype)


def _threshold(m, k, n, depth, dtype):
    """W(depth - 1) at the scheduler's padded dims: the smallest budget
    that takes the rule."""
    lm, lk, ln = _leaf_dims(m, k, n, depth)
    return subtree_bytes(lm << depth, lk << depth, ln << depth, depth, depth - 1, dtype)


def _run(a, b, depth, budget, **kw):
    kw.setdefault("backend", NAIVE_LEAVES)
    return strassen_oot_matmul(a, b, depth=depth, budget_bytes=budget, **kw)


def _counter(name):
    return obs_metrics.get_metrics().snapshot()["counters"].get(name, 0.0)


@pytest.mark.parametrize(
    "shape, depth, budget, level, w",
    [
        ((8192, 8192, 8192), 2, 268_435_456, 1, 251_658_240),
        ((4096, 4096, 4096), 1, 268_435_456, 0, 251_658_240),
        ((16384, 16384, 16384), 2, 1 << 30, 1, 1_006_632_960),
        ((8192, 8192, 8192), 2, 251_658_239, None, None),
    ],
)
def test_subtree_level_rule(shape, depth, budget, level, w):
    m, k, n = shape
    assert subtree_level(m, k, n, depth, budget, BF16) == level
    if level is not None:
        assert subtree_bytes(m, k, n, depth, level, BF16) == w <= budget
        if level > 0:
            assert subtree_bytes(m, k, n, depth, level - 1, BF16) > budget
    # bf16 inputs are sized at their f32 accumulation dtype
    assert subtree_bytes(m, k, n, depth, depth - 1, BF16) == subtree_bytes(
        m, k, n, depth, depth - 1, np.float32
    )


@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("scheme", ["strassen", "winograd", "naive8"])
def test_subtree_path_bit_identical_to_leaf_waves(scheme, depth):
    """Every subtree level gives the leaf waves' bits: square, odd and
    padded, f32 and bf16, normal and whole-number operands."""
    problems = [
        ((128, 128), (128, 128), np.float32, False),
        ((128, 128), (128, 128), np.float32, True),
        ((200, 136), (136, 168), np.float32, False),
        ((128, 128), (128, 128), BF16, False),
    ]
    for shape_a, shape_b, dtype, integers in problems:
        a, b = _operands(shape_a, shape_b, dtype, integers)
        m, k = shape_a
        n = shape_b[1]
        w = _threshold(m, k, n, depth, dtype)
        want, base = _run(a, b, depth, w - 1, scheme=scheme)
        assert base.subtree_level is None and base.rung == "pipeline"
        for level in range(depth):
            lm, lk, ln = _leaf_dims(m, k, n, depth)
            budget = subtree_bytes(
                lm << depth, lk << depth, ln << depth, depth, level, dtype
            )
            got, st = _run(a, b, depth, budget, scheme=scheme)
            assert st.subtree_level == level and st.rung == "subtree"
            assert got.dtype == want.dtype
            assert np.array_equal(got, want), (shape_a, dtype, integers, level)
        if integers:
            np.testing.assert_array_equal(want, a @ b)


def test_subtree_stats_and_counter():
    obs.reset_tracing()
    obs.configure(enabled=True)
    try:
        a, b = _operands((256, 256), (256, 256), np.float32, False)
        budget = _threshold(256, 256, 256, 2, np.float32)
        before = _counter("oot.subtrees")
        h2d_before = _counter("oot.h2d_bytes")
        d2h_before = _counter("oot.d2h_bytes")
        out, st = _run(a, b, 2, budget)
        tr = obs.get_tracer()
        assert st.subtree_level == 1 and st.subtrees == 7 == st.waves
        assert _counter("oot.subtrees") - before == 7
        assert st.leaves == 49 and st.rung == "subtree" and st.degrades == 0
        # each subtree stages its two 128^2 f32 operands once and fetches C
        assert st.h2d_bytes == 7 * 2 * 128 * 128 * 4 == 917_504
        assert st.d2h_bytes == 7 * 128 * 128 * 4 == 458_752
        assert _counter("oot.h2d_bytes") - h2d_before == st.h2d_bytes
        assert _counter("oot.d2h_bytes") - d2h_before == st.d2h_bytes
        assert st.peak_device_bytes == budget == 245_760
        st.assert_within_budget()
        # the five phases, in order, each once under the root
        (root,) = tr.find("oot.matmul")
        phases = [
            tr.find(name)
            for name in (
                "oot.ingest", "oot.divide", "oot.leaf_waves", "oot.combine",
                "oot.assemble",
            )
        ]
        assert all(len(p) == 1 and p[0].parent_id == root.span_id for p in phases)
        spans = [p[0] for p in phases]
        assert all(x.t1 <= y.t0 for x, y in zip(spans, spans[1:]))
        # one wave per subtree under the leaf waves' span names
        for name in ("wave.stage", "wave.dispatch", "wave.fetch", "wave.compute"):
            assert len(tr.find(name)) == 7
        assert len(st.wave_events) == 7 and st.overlap_efficiency == 0.0
        assert not tr.find("leaf.mul")
        np.testing.assert_allclose(out, a @ b, rtol=2e-3, atol=2e-3)
    finally:
        obs.configure(enabled=False)
        obs.reset_tracing()


@pytest.mark.parametrize("mode", ["recovery", "chaos"])
def test_recovering_run_keeps_leaf_waves(mode):
    """Lineage replays stored leaves, so a run that keeps it stages leaves
    even where a subtree would fit."""
    a, b = _operands((192, 192), (192, 192), np.float32, False)
    budget = _threshold(192, 192, 192, 2, np.float32)
    want, base = _run(a, b, 2, budget - 1)
    kw = (
        dict(recovery=True) if mode == "recovery"
        else dict(chaos=ChaosConfig(drop=0.05, seed=3))
    )
    got, st = _run(a, b, 2, budget, **kw)
    assert st.subtree_level is None and st.subtrees == 0
    assert st.rung == "pipeline"
    assert np.array_equal(got, want)
    # the same budget takes the rule without lineage
    _, plain = _run(a, b, 2, budget)
    assert plain.subtree_level == 1


def test_subtree_oom_degrades_to_pipeline(monkeypatch):
    """A device-OOM in the subtree rung lands on the leaf waves' first
    rung, with one degrade event, the leaf waves' bits, and no device
    buffer left behind."""
    a, b = _operands((192, 192), (192, 192), np.float32, False)
    budget = _threshold(192, 192, 192, 2, np.float32)
    want, _ = _run(a, b, 2, budget - 1)

    def oom(self, *args):
        def program(a_dev, b_dev):
            raise RuntimeError("RESOURCE_EXHAUSTED: injected subtree OOM")

        return program

    monkeypatch.setattr(PlanScheduler, "_subtree_program", oom)
    baseline = sum(not x.is_deleted() for x in jax.live_arrays())
    got, st = _run(a, b, 2, budget)
    assert st.rung == "pipeline" and st.degrades == 1
    (ev,) = st.degrade_events
    assert (ev["from"], ev["to"]) == ("subtree", "pipeline")
    assert "RESOURCE_EXHAUSTED" in ev["cause"]
    assert st.subtree_level is None
    assert np.array_equal(got, want)
    assert sum(not x.is_deleted() for x in jax.live_arrays()) <= baseline
