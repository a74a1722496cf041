"""Serving engine: determinism, temperature, cache accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import model as M
from repro.serving.engine import Engine, ServeConfig


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("phi4_mini_3_8b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    return cfg, params, prompts


def test_greedy_generation_deterministic(setup):
    cfg, params, prompts = setup
    eng = Engine(cfg, params, ServeConfig(max_seq=64, temperature=0.0))
    t1, s1 = eng.generate(prompts, 8)
    t2, _ = eng.generate(prompts, 8)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    assert t1.shape == (2, 8)
    assert s1["cache_pos"] == 8 + 8 - 1  # prompt + generated - last not written


def test_temperature_sampling_varies_by_seed(setup):
    cfg, params, prompts = setup
    eng = Engine(cfg, params, ServeConfig(max_seq=64, temperature=5.0))
    t1, _ = eng.generate(prompts, 12, seed=0)
    t2, _ = eng.generate(prompts, 12, seed=1)
    assert not np.array_equal(np.asarray(t1), np.asarray(t2))


def test_greedy_matches_manual_argmax_rollout(setup):
    cfg, params, prompts = setup
    eng = Engine(cfg, params, ServeConfig(max_seq=64, temperature=0.0))
    toks, _ = eng.generate(prompts, 4)
    # manual rollout through full forward passes
    cur = prompts
    manual = []
    for _ in range(4):
        logits, _ = M.apply_train(params, {"tokens": cur, "labels": cur}, cfg)
        nxt = jnp.argmax(logits[:, -1], -1)[:, None]
        manual.append(nxt)
        cur = jnp.concatenate([cur, nxt], axis=1)
    np.testing.assert_array_equal(
        np.asarray(toks), np.asarray(jnp.concatenate(manual, axis=1))
    )


def test_engine_scopes_autotune_telemetry(setup):
    """Engine construction zeroes the process autotune telemetry, so each
    instance's stats cover its own resolutions instead of interleaving
    with a previous engine's, and autotune_stats() surfaces the
    out-of-core scheduler's recent runs under "oot"."""
    from repro.core import autotune
    from repro.core.autotune import Calibration, TuningCache

    cfg, params, _ = setup
    calib = Calibration(
        t_flop=1e-11, t_elem=1e-9, t_h2d=2e-9,
        device_kind="test", device_count=1,
    )
    # pollute the process log the way a previous engine's resolutions would
    autotune.autotune(4096, 4096, 4096, calibration=calib, cache=TuningCache())
    assert autotune.get_telemetry().snapshot()["cache_misses"] >= 1
    # ... and the process-global oot ring the way a previous engine's
    # strassen_oot runs would
    from repro.blocks.scheduler import recent_oot_stats, strassen_oot_matmul
    from repro.core.backend import MatmulBackend

    a = np.ones((64, 64), np.float32)
    strassen_oot_matmul(
        a, a, depth=1, budget_bytes=a.nbytes * 4,
        backend=MatmulBackend(kind="naive"),
    )
    assert recent_oot_stats()
    eng = Engine(cfg, params, ServeConfig(max_seq=64))
    snap = eng.autotune_stats()
    assert snap["cache_hits"] == 0 and snap["cache_misses"] == 0
    assert snap["decisions"] == []
    assert snap["oot"] == []


# ------------------------------------------------- request-based engine API


@pytest.fixture(scope="module")
def cont_setup():
    cfg = get_smoke_config("phi4_mini_3_8b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params, **kw):
    args = dict(max_seq=64, temperature=0.0, slots=3, page_size=8, sync_interval=2)
    args.update(kw)
    return Engine(cfg, params, ServeConfig(**args))


def test_generate_shim_matches_legacy_static_path(setup):
    """The compat shim on the request loop is token-exact with the
    pre-redesign static loop, including the eos truncation rule."""
    cfg, params, prompts = setup
    eng = Engine(cfg, params, ServeConfig(max_seq=64, temperature=0.0))
    t_old, s_old = eng._generate_static(prompts, 8)
    t_new, s_new = eng.generate(prompts, 8)
    np.testing.assert_array_equal(np.asarray(t_old), np.asarray(t_new))
    assert s_new["cache_pos"] == s_old["cache_pos"]
    # eos case: pick a token the greedy run actually emits mid-stream
    eos = int(np.asarray(t_old)[0, 4])
    eng2 = Engine(cfg, params, ServeConfig(max_seq=64, temperature=0.0, eos_id=eos))
    t_old2, _ = eng2._generate_static(prompts, 8)
    t_new2, _ = eng2.generate(prompts, 8)
    np.testing.assert_array_equal(np.asarray(t_old2), np.asarray(t_new2))


def test_generate_shim_parity_recurrent_arch():
    """Parity must also hold for archs with no paged KV at all (pure
    slot-indexed recurrent state)."""
    cfg = get_smoke_config("xlstm_1_3b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    eng = Engine(cfg, params, ServeConfig(max_seq=64, temperature=0.0))
    t_old, _ = eng._generate_static(prompts, 6)
    t_new, _ = eng.generate(prompts, 6)
    np.testing.assert_array_equal(np.asarray(t_old), np.asarray(t_new))


def test_mid_decode_admission_keeps_survivor_tokens_exact(cont_setup):
    """A request admitted while another is mid-decode must not perturb
    the resident request's greedy tokens (vs running it alone)."""
    cfg, params = cont_setup
    p0 = np.arange(5) % cfg.vocab
    p1 = (np.arange(9) * 3) % cfg.vocab

    solo = _engine(cfg, params)
    want0 = solo.submit(p0, 10).result()
    solo2 = _engine(cfg, params)
    want1 = solo2.submit(p1, 6).result()

    eng = _engine(cfg, params)
    h0 = eng.submit(p0, 10)
    for _ in range(3):  # h0 several steps into decode
        eng.step()
    h1 = eng.submit(p1, 6)  # admitted mid-decode
    eng.run()
    assert h0.tokens() == want0
    assert h1.tokens() == want1


def test_eviction_frees_pages_and_keeps_survivors(cont_setup):
    cfg, params = cont_setup
    eng = _engine(cfg, params)
    p = np.arange(6) % cfg.vocab
    solo = _engine(cfg, params)
    want = solo.submit(p, 12).result()

    h_keep = eng.submit(p, 12)
    h_evict = eng.submit(p[::-1].copy(), 12)
    for _ in range(3):
        eng.step()
    pages_mid = eng.serve_stats()["pages_in_use"]
    assert pages_mid > 0
    h_evict.cancel()
    assert h_evict.state.value == "evicted"
    assert h_evict.finish_reason == "evicted"
    assert eng.serve_stats()["pages_in_use"] < pages_mid
    eng.run()
    assert h_keep.tokens() == want
    assert eng.serve_stats()["pages_in_use"] == 0


def test_page_accounting_no_leak_over_churn(cont_setup):
    """N submit/finish/evict cycles must return the pool to exactly
    full-free every time (the double-free guard makes leaks loud)."""
    cfg, params = cont_setup
    eng = _engine(cfg, params, slots=2)
    rng = np.random.default_rng(2)
    for cycle in range(4):
        hs = [
            eng.submit(rng.integers(0, cfg.vocab, size=4 + i), 5 + i)
            for i in range(3)
        ]
        if cycle % 2:
            eng.step()
            hs[0].cancel()
        eng.run()
        st = eng.serve_stats()
        assert st["pages_in_use"] == 0, (cycle, st)
        assert st["pages_free"] == st["page_budget"], (cycle, st)
        assert st["slots_active"] == 0 and st["queue_depth"] == 0


def test_admission_reject_on_exhausted_budget(cont_setup):
    cfg, params = cont_setup
    # budget: one request's worth of pages -> second concurrent submit rejected
    eng = _engine(cfg, params, slots=2, page_budget=2, admission="reject")
    h0 = eng.submit(np.arange(4), 8)  # needs ceil(11/8)=2 pages
    h1 = eng.submit(np.arange(4), 8)
    assert h0.state.value != "rejected"
    assert h1.state.value == "rejected" and h1.finish_reason == "rejected"
    assert eng.serve_stats()["requests"]["rejected"] == 1
    eng.run()
    assert h0.finish_reason == "length"
    # budget free again -> next submit admitted
    h2 = eng.submit(np.arange(4), 8)
    assert h2.state.value != "rejected"
    eng.run()
    assert h2.finish_reason == "length"


def test_admission_queue_waits_for_capacity(cont_setup):
    cfg, params = cont_setup
    eng = _engine(cfg, params, slots=1)
    h0 = eng.submit(np.arange(4), 6)
    h1 = eng.submit(np.arange(4), 6)
    assert h1.state.value == "queued"  # one slot, h0 holds it
    assert eng.serve_stats()["queue_depth"] == 1
    eng.run()
    assert h0.finish_reason == "length" and h1.finish_reason == "length"
    assert len(h1.tokens()) == 6


def test_submit_never_fit_raises(cont_setup):
    cfg, params = cont_setup
    eng = _engine(cfg, params)
    with pytest.raises(ValueError):
        eng.submit(np.arange(60), 10)  # beyond max_seq=64


def test_streaming_callback_and_event_ordering(cont_setup):
    cfg, params = cont_setup
    eng = _engine(cfg, params, slots=2, sync_interval=3)
    events = []
    hs = [
        eng.submit(np.arange(3 + i), 7, on_token=lambda h, ev: events.append(ev))
        for i in range(3)
    ]
    streamed = list(eng.stream(hs))
    # callbacks fired once per token, in per-request index order
    byreq = {}
    for ev in events:
        byreq.setdefault(ev.request_id, []).append(ev)
    assert set(byreq) == {h.id for h in hs}
    for h in hs:
        evs = byreq[h.id]
        assert [e.index for e in evs] == list(range(7))
        assert [e.token for e in evs] == h.tokens()
    # stream() yields the same events
    assert sorted((e.request_id, e.index, e.token) for e in streamed) == sorted(
        (e.request_id, e.index, e.token) for e in events
    )
    # per-request TTFT/latency telemetry populated
    ttft, gaps = hs[0].latency_stats()
    assert ttft is not None and ttft >= 0
    assert len(gaps) == 6


def test_static_gang_batching_mode(cont_setup):
    """batching='static' (the benchmark baseline) gang-schedules: no
    admission while any request is resident, same tokens as continuous."""
    cfg, params = cont_setup
    prompts = [np.arange(4), np.arange(5), np.arange(6)]
    want = []
    for p in prompts:
        want.append(_engine(cfg, params).submit(p, 6).result())

    eng = _engine(cfg, params, slots=2, batching="static")
    hs = [eng.submit(p, 6) for p in prompts]
    assert hs[2].state.value == "queued"  # gang of 2 admitted, third waits
    eng.step()
    assert hs[2].state.value == "queued"  # still: gang must drain first
    eng.run()
    assert [h.tokens() for h in hs] == want
    assert eng.serve_stats()["requests"]["finished"] == 3


def test_serve_config_apply_to_and_validation(cont_setup):
    import dataclasses as dc

    cfg, _ = cont_setup
    sc = ServeConfig(tuning_cache="/tmp/tc.json")
    auto_cfg = dc.replace(
        cfg, matmul_backend=dc.replace(cfg.matmul_backend, kind="auto")
    )
    out = sc.apply_to(auto_cfg)
    assert out.matmul_backend.tuning_cache == "/tmp/tc.json"
    # non-auto backends and explicit caches are left alone
    assert sc.apply_to(cfg).matmul_backend.tuning_cache == cfg.matmul_backend.tuning_cache
    pre = dc.replace(auto_cfg, matmul_backend=dc.replace(auto_cfg.matmul_backend, tuning_cache="x"))
    assert sc.apply_to(pre).matmul_backend.tuning_cache == "x"
    with pytest.raises(ValueError):
        ServeConfig(admission="maybe")
    with pytest.raises(ValueError):
        ServeConfig(batching="dynamic")
    with pytest.raises(ValueError):
        ServeConfig(slots=0)
