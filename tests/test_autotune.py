"""Autotuned kind='auto' dispatcher: correctness, guards, cache, cost model."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # fall back to the vendored grid shim
    from _propshim import given, settings, strategies as st

from repro.core import autotune, compat
from repro.core.autotune import (
    Calibration,
    Candidate,
    Decision,
    TuningCache,
    cache_key,
    enumerate_candidates,
    predict_seconds,
)
from repro.core import backend as backend_mod
from repro.core.backend import MatmulBackend, matmul, resolve_auto, route_for
from repro.core.cost_model import paper_stage_count, total_cost

RNG = np.random.default_rng(17)

# Fixed synthetic constants: decisions in these tests must never depend on
# the machine the suite happens to run on.
CALIB = Calibration(t_flop=1e-11, t_elem=1e-9, device_kind="test", device_count=1)


def _rand(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape).astype(np.float32), dtype)


def _auto_backend(**kw):
    kw.setdefault("kind", "auto")
    kw.setdefault("depth", 2)
    return MatmulBackend(**kw)


@pytest.fixture(autouse=True)
def _synthetic_calibration(monkeypatch):
    """No micro-benchmarks and no cross-test lru_cache leakage."""
    monkeypatch.setattr(autotune, "_CALIBRATION", CALIB)
    monkeypatch.setattr(autotune, "_PROCESS_CACHES", {})
    resolve_auto.cache_clear()


# ------------------------------------------------------------- correctness
@settings(max_examples=20, deadline=None)
@given(
    logm=st.integers(min_value=5, max_value=8),
    logk=st.integers(min_value=5, max_value=8),
    logn=st.integers(min_value=5, max_value=8),
    min_dim=st.sampled_from([1, 64, 4096]),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_property_auto_matches_matmul(logm, logk, logn, min_dim, seed):
    rng = np.random.default_rng(seed)
    m, k, n = 2**logm, 2**logk, 2**logn
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    got = matmul(x, w, _auto_backend(min_dim=min_dim))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(x @ w), atol=3e-3, rtol=3e-3
    )


@pytest.mark.parametrize("shape", [(96, 96, 96), (100, 60, 36), (33, 65, 17)])
def test_auto_odd_and_non_pow2_shapes(shape):
    """Divisibility guard: odd dims route to shallower depth or naive."""
    m, k, n = shape
    x, w = _rand((m, k)), _rand((k, n))
    got = matmul(x, w, _auto_backend(min_dim=1))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(x @ w), atol=3e-3, rtol=3e-3
    )


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-3), (jnp.bfloat16, 1.5e-1)])
def test_auto_dtypes(dtype, tol):
    x, w = _rand((128, 128), dtype), _rand((128, 128), dtype)
    got = matmul(x, w, _auto_backend(min_dim=1))
    want = jnp.matmul(x, w)
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32), np.asarray(want, dtype=np.float32),
        atol=tol, rtol=tol,
    )


def test_auto_under_jit_and_batched_lead_dims():
    x, w = _rand((4, 32, 128)), _rand((128, 64))
    be = _auto_backend(min_dim=1)
    got = jax.jit(lambda a, b: matmul(a, b, be))(x, w)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(x @ w), atol=3e-3, rtol=3e-3
    )


# ------------------------------------------------------------------ guards
def test_never_selects_strassen_below_min_dim():
    for m, k, n in [(512, 512, 512), (1023, 1024, 1024), (64, 4096, 4096)]:
        cands = enumerate_candidates(m, k, n, min_dim=1024)
        assert cands == [Candidate(kind="naive")], (m, k, n, cands)
        d = autotune.autotune(m, k, n, min_dim=1024, calibration=CALIB)
        assert d.kind == "naive" and d.depth == 0


def test_depth_respects_divisibility_per_level():
    # 1028 = 4 * 257: two halvings possible, not three.
    cands = enumerate_candidates(1028, 1028, 1028, min_dim=1, max_depth=3)
    depths = {c.depth for c in cands if c.kind == "strassen"}
    assert depths == {1, 2}


def test_enumeration_matches_backend_effective_depth():
    be = MatmulBackend(kind="strassen", depth=3, min_dim=256)
    for dims in [(1024, 1024, 1024), (512, 2048, 1024), (640, 640, 640)]:
        cands = enumerate_candidates(*dims, min_dim=256, max_depth=3)
        max_enum = max((c.depth for c in cands if c.kind == "strassen"), default=0)
        assert max_enum == be.effective_depth(*dims), dims


def test_larger_shapes_prefer_strassen_smaller_prefer_naive():
    """The §V-C crossover under fixed constants: selection flips with n."""
    small = autotune.autotune(256, 256, 256, calibration=CALIB, min_dim=1024)
    large = autotune.autotune(8192, 8192, 8192, calibration=CALIB, min_dim=1024)
    assert small.kind == "naive"
    assert large.kind in ("strassen", "winograd", "strassen_fused")
    assert large.depth >= 1


# ------------------------------------------------------------------- cache
def test_cache_round_trip_no_remeasure(tmp_path, monkeypatch):
    path = os.path.join(tmp_path, "tuning.json")
    cache = TuningCache(path)
    d1 = autotune.autotune(
        4096, 4096, 4096, calibration=CALIB, cache=cache, measure=True, top_k=1
    )
    assert d1.source == "measured" and d1.measured_s is not None
    assert os.path.exists(path)

    # Fresh load: identical decision, and neither measurement nor
    # calibration may run again.
    def boom(*a, **k):
        raise AssertionError("re-measured on a warm cache")

    monkeypatch.setattr(autotune, "measure_seconds", boom)
    monkeypatch.setattr(autotune, "calibrate", boom)
    cache2 = TuningCache(path)
    assert cache2.calibration == CALIB  # calibration persists alongside
    d2 = autotune.autotune(4096, 4096, 4096, cache=cache2, measure=True, top_k=1)
    assert d2.source == "cache"
    assert (d2.kind, d2.scheme, d2.depth) == (d1.kind, d1.scheme, d1.depth)
    assert d2.measured_s == d1.measured_s


def test_cache_key_separates_dtype_and_shape():
    kw = dict(device_kind="cpu", device_count=1, schemes=("strassen",),
              min_dim=1024, max_depth=2)
    k1 = cache_key(512, 512, 512, jnp.float32, **kw)
    k2 = cache_key(512, 512, 512, jnp.bfloat16, **kw)
    k3 = cache_key(512, 512, 1024, jnp.float32, **kw)
    assert len({k1, k2, k3}) == 3


def _parent_cache(path, key):
    with open(path, "w") as f:
        json.dump({
            "decisions": {key: {
                "kind": "strassen", "scheme": "strassen", "depth": 1,
                "predicted_s": 1e-3, "measured_s": None, "source": "predicted",
            }},
            "calibration": {
                "t_flop": 1e-11, "t_elem": 1e-9, "device_kind": "test",
                "device_count": 1, "t_coll": 4e-9, "t_h2d": 2e-9,
            },
        }, f)


def test_parent_format_cache_with_t_coll_loads(tmp_path):
    """A cache written while the autotuner priced mesh strategies carries
    t_coll in its calibration: it loads, and the key is dropped."""
    path = os.path.join(tmp_path, "tuning.json")
    _parent_cache(path, "k")
    cache = TuningCache(path)
    assert cache.calibration == Calibration(
        t_flop=1e-11, t_elem=1e-9, device_kind="test", device_count=1, t_h2d=2e-9
    )
    assert "t_coll" not in cache.calibration.to_dict()
    assert cache.get("k").kind == "strassen"


def test_local_cache_keys_are_unchanged(tmp_path):
    """Local matmul decisions keep their key byte for byte, so a cache a
    parent-format process wrote still answers them."""
    dev = jax.devices()[0].device_kind
    key = f"512x512x512|float32|{dev}:1|local|strassen,winograd|min1024|d2"
    path = os.path.join(tmp_path, "tuning.json")
    _parent_cache(path, key)
    d = autotune.autotune(512, 512, 512, max_depth=2, cache=TuningCache(path))
    assert (d.source, d.kind, d.depth) == ("cache", "strassen", 1)


def test_backend_resolution_is_cached_per_shape(monkeypatch):
    be = _auto_backend(min_dim=1)
    calls = []
    real = autotune.autotune

    def counting(*a, **k):
        calls.append(a[:3])
        return real(*a, **k)

    monkeypatch.setattr(autotune, "autotune", counting)
    x, w = _rand((64, 64)), _rand((64, 64))
    matmul(x, w, be)
    matmul(x, w, be)  # same shape: lru-cached, no second decision
    assert len(calls) == 1


# -------------------------------------------------- cost model regressions
def test_paper_stage_count_matches_eq25():
    """Stark's Spark-stage count is 2(p-q)+2 — pinned against eq. 25."""
    for p, q in [(10, 8), (12, 8), (14, 10), (14, 4)]:
        n, b = 2**p, 2 ** (p - q)
        assert paper_stage_count(n, b) == 2 * (p - q) + 2


def test_stark_vs_mllib_advantage_monotone_in_n():
    """Predicted stark/mllib ratio decreases monotonically with n (§V-C)."""
    ratios = [
        total_cost("stark", n, 16, cores=25) / total_cost("mllib", n, 16, cores=25)
        for n in (2048, 4096, 8192, 16384, 32768)
    ]
    assert all(a > b for a, b in zip(ratios, ratios[1:])), ratios


def test_jax_crossover_monotone_in_n():
    """Auto model: strassen-vs-naive predicted ratio falls monotonically."""
    c = Candidate(kind="strassen", scheme="strassen", depth=1)
    naive = Candidate(kind="naive")
    ratios = [
        predict_seconds(c, n, n, n, CALIB) / predict_seconds(naive, n, n, n, CALIB)
        for n in (512, 1024, 2048, 4096, 8192, 16384)
    ]
    assert all(a > b for a, b in zip(ratios, ratios[1:])), ratios


def test_calibrated_constants_positive():
    calib = autotune.calibrate(sample_dim=64, repeats=1)
    assert calib.t_flop > 0.0 and calib.t_elem > 0.0
    assert calib.device_kind and calib.device_count >= 1


def test_predictions_positive_and_naive_flops_exact():
    assert predict_seconds(Candidate(kind="naive"), 100, 200, 300, CALIB) == (
        pytest.approx(2.0 * 100 * 200 * 300 * CALIB.t_flop)
    )
    for c in enumerate_candidates(2048, 2048, 2048, min_dim=1, max_depth=3):
        assert predict_seconds(c, 2048, 2048, 2048, CALIB) > 0.0


# -------------------------------------------------- fused Pallas candidate
def test_fused_enumerates_when_leaf_runs():
    """strassen_fused appears at every usable depth on hosts where the
    Pallas leaf runs (interpret mode on this CPU suite)."""
    assert compat.pallas_leaf_mode() in ("compiled", "interpret")
    cands = enumerate_candidates(4096, 4096, 4096, min_dim=1, max_depth=2)
    fused = {c.depth for c in cands if c.kind == "strassen_fused"}
    assert fused == {1, 2}
    assert all(c.scheme == "strassen" for c in cands if c.kind == "strassen_fused")


def test_pallas_leaf_mode_propagates_compile_errors_on_tpu(monkeypatch):
    """On a TPU a failing probe is a bug to surface: it raises instead of
    reporting 'none' (which would silently route around the kernel)."""
    import repro.kernels.strassen.strassen as ks

    def broken(*args, **kwargs):
        raise RuntimeError("Mosaic refused the kernel")

    monkeypatch.setattr(ks, "strassen1_matmul_pallas", broken)
    monkeypatch.setattr(compat.jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        compat.pallas_leaf_mode.__wrapped__()
    monkeypatch.setattr(compat.jax, "default_backend", lambda: "cpu")
    assert compat.pallas_leaf_mode.__wrapped__() == "none"


def test_cached_calibration_reused_only_on_its_device_kind():
    """Decisions and calibration are keyed by device_kind (one TPU
    generation's constants must not price another's candidates)."""
    dev = jax.devices()[0]
    cache = TuningCache()
    cache.calibration = dataclasses.replace(CALIB, device_kind="another chip")
    assert autotune._cached_calibration(cache, dev) is None
    cache.calibration = dataclasses.replace(CALIB, device_kind=dev.device_kind)
    assert autotune._cached_calibration(cache, dev) == cache.calibration
    own = autotune.Telemetry()
    autotune.autotune(
        4096, 4096, 4096, calibration=CALIB, cache=TuningCache(), telemetry=own
    )
    assert f"|{dev.device_kind}:1|" in own.events[-1].key


def test_fused_not_enumerated_without_pallas(monkeypatch):
    monkeypatch.setattr(compat, "pallas_leaf_mode", lambda: "none")
    cands = enumerate_candidates(4096, 4096, 4096, min_dim=1, max_depth=2)
    assert not any(c.kind == "strassen_fused" for c in cands)


def test_fused_selected_at_scale_and_executes():
    """Under the fixed constants the fused pipeline wins once dims clear
    the crossover; the candidate executes exactly (checked at a small
    shape — interpret-mode Pallas at 8192 would dominate suite time)."""
    d = autotune.autotune(8192, 8192, 8192, calibration=CALIB, min_dim=1024)
    assert d.kind == "strassen_fused" and d.depth >= 1
    be = route_for(d, MatmulBackend(min_dim=1))
    assert (be.kind, be.depth) == ("strassen_fused", d.depth)
    x, w = _rand((256, 256)), _rand((256, 256))
    got = matmul(x, w, be)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(x @ w), atol=3e-3, rtol=3e-3
    )


def test_resolve_auto_routes_through_fused_backend(monkeypatch):
    """A fused decision resolves to a kind='strassen_fused' backend and the
    matmul wrapper routes through the Pallas pipeline."""
    be = _auto_backend(min_dim=1)
    decision = Decision(
        kind="strassen_fused", scheme="strassen", depth=1, predicted_s=1e-3
    )
    monkeypatch.setattr(autotune, "autotune", lambda *a, **k: decision)
    resolved = resolve_auto(256, 256, 256, "float32", be)
    assert resolved.kind == "strassen_fused" and resolved.depth == 1
    x, w = _rand((256, 256)), _rand((256, 256))
    got = matmul(x, w, be)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(x @ w), atol=3e-3, rtol=3e-3
    )


def test_fused_predicted_cheaper_than_unfused_strassen():
    """The fused leaf skips the last level's materialized M-terms, so at
    equal depth its predicted cost must be strictly below plain BFS."""
    for depth in (1, 2, 3):
        fused = Candidate(kind="strassen_fused", scheme="strassen", depth=depth)
        plain = Candidate(kind="strassen", scheme="strassen", depth=depth)
        n = 8192
        assert predict_seconds(fused, n, n, n, CALIB) < predict_seconds(
            plain, n, n, n, CALIB
        )


# ------------------------------------------------------- call-site caching
def test_cache_key_site_tag_separates_and_composes():
    kw = dict(device_kind="cpu", device_count=1, schemes=("strassen",),
              min_dim=1024, max_depth=2)
    k_plain = cache_key(512, 512, 512, jnp.float32, **kw)
    k_q = cache_key(512, 512, 512, jnp.float32, site="attn.wq", **kw)
    k_up = cache_key(512, 512, 512, jnp.float32, site="mlp.up", **kw)
    assert len({k_plain, k_q, k_up}) == 3
    assert k_q.startswith(k_plain)


def test_site_lookup_falls_back_to_generic_in_predicted_mode():
    cache = TuningCache()
    d1 = autotune.autotune(4096, 4096, 4096, calibration=CALIB, cache=cache)
    # the generic entry answers a tagged lookup without a new resolution
    d2 = autotune.autotune(
        4096, 4096, 4096, calibration=CALIB, cache=cache, site="attn.wq"
    )
    assert d2.source == "cache"
    assert (d2.kind, d2.depth) == (d1.kind, d1.depth)
    assert len(cache.entries) == 1


def test_measured_site_decisions_diverge(monkeypatch):
    """Under measure mode, two sites of the same shape hold separate
    entries — the point of call-site keys."""
    cache = TuningCache()
    times = iter([3.0, 1.0, 2.0, 1.0, 2.0, 3.0])  # distinct winners per site

    monkeypatch.setattr(
        autotune, "measure_seconds", lambda *a, **k: next(times)
    )
    d_q = autotune.autotune(
        4096, 4096, 4096, calibration=CALIB, cache=cache,
        measure=True, top_k=3, site="attn.wq",
    )
    d_up = autotune.autotune(
        4096, 4096, 4096, calibration=CALIB, cache=cache,
        measure=True, top_k=3, site="mlp.up",
    )
    assert len(cache.entries) == 2
    assert (d_q.kind, d_q.depth) != (d_up.kind, d_up.depth)


def test_resolve_auto_site_is_part_of_memo_key(monkeypatch):
    be = _auto_backend(min_dim=1)
    calls = []
    real = autotune.autotune

    def counting(*a, **k):
        calls.append(k.get("site"))
        return real(*a, **k)

    monkeypatch.setattr(autotune, "autotune", counting)
    x, w = _rand((64, 64)), _rand((64, 64))
    matmul(x, w, be, site="attn.wq")
    matmul(x, w, be, site="attn.wq")  # lru hit
    matmul(x, w, be, site="mlp.up")  # new site: new resolution
    assert calls == ["attn.wq", "mlp.up"]


# ------------------------------------------------------------- telemetry
def test_telemetry_records_hits_misses_and_kinds():
    tel = autotune.get_telemetry()
    tel.reset()
    cache = TuningCache()
    autotune.autotune(4096, 4096, 4096, calibration=CALIB, cache=cache)
    autotune.autotune(4096, 4096, 4096, calibration=CALIB, cache=cache)
    snap = tel.snapshot()
    assert snap["cache_misses"] == 1 and snap["cache_hits"] == 1
    assert sum(snap["kinds"].values()) == 2
    first, second = snap["decisions"]
    assert first["cache_hit"] is False and second["cache_hit"] is True
    assert first["kind"] == second["kind"]
    assert first["predicted_s"] > 0.0
    tel.reset()
    assert tel.snapshot()["cache_hits"] == 0 and not tel.snapshot()["decisions"]


def test_warm_for_model_emits_site_tagged_telemetry():
    from repro.configs import get_smoke_config

    tel = autotune.get_telemetry()
    tel.reset()
    cfg = get_smoke_config("phi4_mini_3_8b")
    cfg = dataclasses.replace(cfg, matmul_autotune=True)
    n = autotune.warm_for_model(cfg, tokens=(1, 64))
    assert n > 0
    sites = {e.site for e in tel.events}
    assert {"attn.wq", "mlp.up"} <= sites
    assert None not in sites
    # predicted-mode decisions dedupe to shape-only entries: equal-shape
    # sites share one cache row instead of storing identical copies
    cache = autotune.process_cache(cfg.matmul_backend.tuning_cache)
    assert cache.entries and not any("|site:" in k for k in cache.entries)


# ------------------------------------------------------------ the routes
ROUTES = [
    ("naive", "strassen", 0),
    ("strassen", "strassen", 2),
    ("winograd", "winograd", 2),
    ("strassen_fused", "strassen", 2),
    ("strassen_oot", "winograd", 1),
]


@pytest.mark.parametrize("kind,scheme,depth", ROUTES, ids=[r[0] for r in ROUTES])
def test_decision_maps_to_its_route(kind, scheme, depth):
    """route_for takes kind, depth and scheme from the decision and keeps
    the caller's other fields; the backend it returns computes x @ w."""
    decision = Decision(kind=kind, scheme=scheme, depth=depth, predicted_s=1e-3)
    be = route_for(decision, _auto_backend(min_dim=16, precision="highest"))
    assert (be.kind, be.depth, be.measure) == (kind, depth, False)
    assert (be.min_dim, be.precision) == (16, "highest")
    if kind != "naive":
        assert be.scheme_name == scheme
    x, w = _rand((128, 128)), _rand((128, 128))
    assert be.effective_depth(128, 128, 128) == depth
    got = matmul(x, w, be)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(x @ w), atol=3e-3, rtol=3e-3
    )


def test_route_for_keeps_budget_and_cache_of_the_caller():
    be = _auto_backend(device_budget=1 << 20, tuning_cache="t.json", measure=True)
    cand = Candidate(kind="strassen_oot", scheme="strassen", depth=3)
    got = route_for(cand, be)
    assert got == dataclasses.replace(
        be, kind="strassen_oot", depth=3, schemes=("strassen",), measure=False
    )


def test_measured_autotune_times_each_candidate_through_its_route(monkeypatch):
    """measure=True times every top-k candidate as backend.matmul on the
    backend route_for makes of it, and nothing else."""
    routes = []
    real = backend_mod.matmul

    def spy(x, w, be=backend_mod.NAIVE_BACKEND, *a, **k):
        routes.append((be.kind, be.schemes, be.depth, be.min_dim))
        return real(x, w, be, *a, **k)

    monkeypatch.setattr(backend_mod, "matmul", spy)
    cands = enumerate_candidates(256, 256, 256, min_dim=64, max_depth=2)
    top = sorted(cands, key=lambda c: predict_seconds(c, 256, 256, 256, CALIB))[:3]
    d = autotune.autotune(
        256, 256, 256, calibration=CALIB, min_dim=64, max_depth=2,
        measure=True, top_k=3,
    )
    assert d.source == "measured"
    assert set(routes) == {(c.kind, (c.scheme,), c.depth, 64) for c in top}


def test_measured_oot_candidate_runs_at_the_routes_deepened_depth(monkeypatch):
    """A strassen_oot candidate whose depth leaves no pipelined wave slot is
    timed at the depth the route deepens to, as a decision for it runs."""
    from repro.blocks import scheduler

    m = k = n = 256
    budget = scheduler.pipelined_leaf_bytes(m, k, n, 2, np.float32)
    assert scheduler.pipelined_leaf_bytes(m, k, n, 1, np.float32) > budget
    deepened = scheduler.min_depth_for_budget(m, k, n, budget, np.float32, pipelined=True)
    depths = []
    real = scheduler.strassen_oot_matmul

    def spy(*a, depth, **k):
        depths.append(depth)
        return real(*a, depth=depth, **k)

    monkeypatch.setattr(scheduler, "strassen_oot_matmul", spy)
    cand = Candidate(kind="strassen_oot", scheme="strassen", depth=1)
    autotune.measure_seconds(cand, m, k, n, jnp.float32, oot_budget=budget, repeats=1)
    assert depths and set(depths) == {deepened} and deepened > cand.depth


def test_fused_sharded_strategy_matches_matmul():
    """The shard_map'd Pallas fused leaf computes the exact product on the
    conftest host mesh (interpret mode on CPU), including shapes that need
    the M-stripe padding path."""
    from repro.core.compat import make_mesh
    from repro.core.distributed import strassen_fused_sharded

    if jax.device_count() < 2:
        pytest.skip("needs the conftest multi-device host platform")
    mesh = make_mesh((jax.device_count() // 2, 2), ("data", "model"))
    for (m, k, n) in [(256, 128, 192), (200, 200, 200)]:
        x, w = _rand((m, k)), _rand((k, n))
        for depth in (1, 2):
            got = strassen_fused_sharded(x, w, mesh=mesh, depth=depth)
            assert got.shape == (m, n)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(x @ w), atol=3e-3, rtol=3e-3
            )
    be = MatmulBackend(kind="strassen_fused", depth=1, min_dim=1, mesh=mesh)
    x, w = _rand((256, 128)), _rand((128, 192))
    got = matmul(x, w, be)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(x @ w), atol=3e-3, rtol=3e-3
    )


# ---------------------------------------------------------- config plumbing
def test_model_config_autotune_flag_rewrites_backend():
    from repro.configs import get_smoke_config

    cfg = get_smoke_config("phi4_mini_3_8b")
    assert cfg.matmul_backend.kind != "auto"
    cfg_auto = dataclasses.replace(cfg, matmul_autotune=True)
    assert cfg_auto.matmul_backend.kind == "auto"
    assert hash(cfg_auto) is not None  # stays usable as a static jit arg


def test_warm_for_model_counts_resolutions():
    from repro.configs import get_smoke_config

    cfg = get_smoke_config("phi4_mini_3_8b")
    cfg = dataclasses.replace(cfg, matmul_autotune=True)
    n = autotune.warm_for_model(cfg, tokens=(1, 64))
    assert n > 0
    # every warmed shape now resolves from the lru cache: no new decisions
    info_before = resolve_auto.cache_info().currsize
    autotune.warm_for_model(cfg, tokens=(1, 64))
    assert resolve_auto.cache_info().currsize == info_before


def test_reset_telemetry_and_caller_owned_log():
    """reset_telemetry() zeroes the process log (how Engine scopes its
    stats per instance), and autotune(telemetry=...) records to a
    caller-owned Telemetry, leaving the process log untouched."""
    tel = autotune.get_telemetry()
    tel.reset()
    autotune.autotune(4096, 4096, 4096, calibration=CALIB, cache=TuningCache())
    assert tel.snapshot()["cache_misses"] == 1
    assert autotune.reset_telemetry() is tel
    snap = tel.snapshot()
    assert snap["cache_hits"] == 0 and snap["cache_misses"] == 0
    assert not snap["decisions"]
    own = autotune.Telemetry()
    autotune.autotune(
        4096, 4096, 4096, calibration=CALIB, cache=TuningCache(), telemetry=own
    )
    assert own.cache_misses == 1 and len(own.events) == 1
    assert tel.snapshot()["cache_misses"] == 0  # process log untouched
