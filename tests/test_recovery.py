"""Lineage-based fault tolerance: chaos injection, recovery, degradation.

Covers the robustness layer end to end: the deterministic chaos harness
(ChaosStore / FlakyLeaf), bit-exact lineage recompute through
RecoveringStore, the scheduler's leaf retry + degradation ladder, chaos
cleanup across store backends, checkpoint digest verification, the
straggler stop path, and per-request fault isolation in the serving
engine.
"""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.blocks import tags
from repro.blocks.blockmatrix import ArenaStore, DictStore, MemmapStore
from repro.blocks.recovery import (
    BlockLossError,
    ChaosConfig,
    ChaosStore,
    FlakyLeaf,
    InjectedFault,
    Lineage,
    RecoveringStore,
    block_checksum,
)
from repro.blocks.scheduler import (
    StrassenScheduler,
    leaf_bytes,
    strassen_oot_matmul,
)
from repro.core import autotune
from repro.core.autotune import Calibration
from repro.core.backend import MatmulBackend, resolve_auto
from repro.core.coefficients import get_scheme
from repro.obs import metrics as obs_metrics
from repro.runtime.checkpoint import CheckpointError, load_pytree, save_pytree
from repro.runtime.elastic import StragglerMonitor

RNG = np.random.default_rng(11)

CALIB = Calibration(
    t_flop=1e-11, t_elem=1e-9, t_h2d=2e-9,
    device_kind="test", device_count=1,
)

# Pin the leaves to the naive matmul so no calibration micro-bench runs.
NAIVE_LEAVES = MatmulBackend(kind="naive")


@pytest.fixture(autouse=True)
def _synthetic_calibration(monkeypatch):
    monkeypatch.setattr(autotune, "_CALIBRATION", CALIB)
    monkeypatch.setattr(autotune, "_PROCESS_CACHES", {})
    resolve_auto.cache_clear()
    # fault.* / elastic.* counter assertions below are per-test deltas
    obs_metrics.reset_metrics()


def _rand(shape, dtype=np.float32):
    return RNG.standard_normal(shape).astype(dtype)


def _rel_err(got, want):
    got = np.asarray(got, dtype=np.float32)
    want = np.asarray(want, dtype=np.float32)
    return float(np.abs(got - want).max() / (np.abs(want).max() or 1.0))


def _counters():
    return obs_metrics.get_metrics().snapshot()["counters"]


# ------------------------------------------------------- injection harness
def test_chaos_config_validation_and_flags():
    with pytest.raises(ValueError, match="drop"):
        ChaosConfig(drop=1.5)
    with pytest.raises(ValueError, match="corrupt"):
        ChaosConfig(corrupt=-0.1)
    quiet = ChaosConfig()
    assert not quiet.injects_store_faults and not quiet.injects_leaf_faults
    assert ChaosConfig(drop=0.1).injects_store_faults
    assert ChaosConfig(corrupt=0.1).injects_store_faults
    assert ChaosConfig(leaf_fail_rate=0.1).injects_leaf_faults
    assert ChaosConfig(fail_leaf_calls=(3,)).injects_leaf_faults


def test_block_checksum_is_content_addressed():
    blk = _rand((16, 16))
    ref = block_checksum(blk)
    assert block_checksum(blk.copy()) == ref
    assert block_checksum(np.asfortranarray(blk)) == ref  # layout-agnostic
    bad = blk.copy()
    bad.view(np.uint8).reshape(-1)[5] ^= 0x01  # single bit
    assert block_checksum(bad) != ref


def test_chaos_store_deterministic_fault_schedule():
    def run(seed):
        rng = np.random.default_rng(0)
        inner = DictStore()
        keys = [(0, i, "A:0") for i in range(8)]
        for k in keys:
            inner.put(k, rng.standard_normal((4, 4)).astype(np.float32))
        chaos = ChaosStore(inner, drop=0.25, corrupt=0.25, seed=seed)
        schedule = []
        for t in range(60):
            k = keys[t % 8]
            try:
                chaos.get(k)
            except KeyError:  # dropped: the reader would recompute; re-seed
                inner.put(k, rng.standard_normal((4, 4)).astype(np.float32))
            schedule.append((chaos.injected_drops, chaos.injected_corruptions))
        return schedule

    base = run(0)
    assert base == run(0)  # same seed -> identical fault schedule
    assert base != run(3)  # schedule is seed-addressed, not incidental
    drops, corruptions = base[-1]
    assert drops > 0 and corruptions > 0


def test_chaos_store_injection_counts_match_obs_counters():
    inner = DictStore()
    key = (0, 0, "A:0")
    inner.put(key, np.zeros((4, 4), np.float32))
    chaos = ChaosStore(inner, corrupt=1.0, seed=0)
    got = np.asarray(chaos.get(key))
    assert chaos.injected_corruptions == 1
    assert got.view(np.uint8).reshape(-1).max() > 0  # exactly one byte flipped
    # a drop deletes the stored block: the reader sees a plain KeyError
    chaos2 = ChaosStore(inner, drop=1.0, seed=0)
    with pytest.raises(KeyError):
        chaos2.get(key)
    assert chaos2.injected_drops == 1 and key not in inner
    snap = _counters()
    assert snap["fault.injected_corruptions"] == 1.0
    assert snap["fault.injected_drops"] == 1.0


def test_flaky_leaf_fail_calls_and_seeded_rate():
    leaf = FlakyLeaf(fail_calls=(0, 2))
    with pytest.raises(InjectedFault):
        leaf.check()
    leaf.check()
    with pytest.raises(InjectedFault):
        leaf.check()
    leaf.check()
    assert leaf.calls == 4 and leaf.injected == 2
    assert _counters()["fault.injected_leaf_failures"] == 2.0

    def pattern(seed):
        fl = FlakyLeaf(fail_rate=0.3, seed=seed)
        out = []
        for _ in range(40):
            try:
                fl.check()
                out.append(False)
            except InjectedFault:
                out.append(True)
        return out

    assert pattern(1) == pattern(1)
    assert any(pattern(1)) and not all(pattern(1))
    assert pattern(1) != pattern(2)


# ------------------------------------------------ lineage recompute/healing
def _root_lineage(a, b, bam=4, bak=4, bbn=4):
    return Lineage(
        scheme=get_scheme("strassen"), depth=1, a=a, b=b,
        pm=a.shape[0], pk=a.shape[1], pn=b.shape[1],
        bam=bam, bak=bak, bbn=bbn,
        acc_dtype=np.dtype(np.float32), stage_dtype=np.dtype(np.float32),
        leaf_matmul=lambda x, y: x @ y,
    )


def test_recovering_store_heals_lost_and_corrupt_blocks_bit_identically():
    a, b = _rand((8, 8)), _rand((8, 8))
    inner = DictStore()
    store = RecoveringStore(inner, _root_lineage(a, b))
    tag = "A:" + tags.to_string(())
    blocks = {}
    for i in range(2):
        for j in range(2):
            blk = np.ascontiguousarray(a[i * 4:(i + 1) * 4, j * 4:(j + 1) * 4])
            blocks[(i, j, tag)] = blk
            store.put((i, j, tag), blk)

    # loss: the inner store forgets a block; the read heals it in place
    inner.delete((0, 1, tag))
    healed = store.get((0, 1, tag))
    np.testing.assert_array_equal(np.asarray(healed), blocks[(0, 1, tag)])
    assert store.lost_blocks == 1 and store.recovered_blocks == 1
    assert (0, 1, tag) in inner  # re-put so later reads are clean

    # corruption: flip a stored byte; the checksum catches what the store
    # API cannot, and the recompute reproduces the put-time crc exactly
    bad = np.array(inner.get((1, 0, tag)))
    bad.view(np.uint8).reshape(-1)[0] ^= 0xFF
    inner.put((1, 0, tag), bad)
    healed = store.get((1, 0, tag))
    np.testing.assert_array_equal(np.asarray(healed), blocks[(1, 0, tag)])
    assert store.corrupt_blocks == 1 and store.recovered_blocks == 2
    assert store.recompute_mismatches == 0
    snap = _counters()
    assert snap["fault.lost_blocks"] == 1.0
    assert snap["fault.corrupt_blocks"] == 1.0
    assert snap["fault.recomputed_blocks"] == 2.0


def test_recovering_store_unrecoverable_paths_are_loud():
    # no lineage attached: a lost block is a hard error, counted
    bare = RecoveringStore(DictStore())
    bare.put((0, 0, "A:"), np.ones((2, 2), np.float32))
    bare.inner.delete((0, 0, "A:"))
    with pytest.raises(BlockLossError, match="no lineage"):
        bare.get((0, 0, "A:"))
    # lineage attached but the tag is not a lineage-addressable node
    a, b = _rand((8, 8)), _rand((8, 8))
    store = RecoveringStore(DictStore(), _root_lineage(a, b))
    store.put((0, 0, "X:junk"), np.ones((2, 2), np.float32))
    store.inner.delete((0, 0, "X:junk"))
    with pytest.raises(BlockLossError):
        store.get((0, 0, "X:junk"))
    assert _counters()["fault.unrecoverable"] == 2.0


@pytest.mark.parametrize("store_kind", ["dict", "memmap"])
def test_chaos_run_output_bit_identical_to_fault_free_run(store_kind):
    """Seeded drops + corruptions across the whole recursion tree (root
    re-ingest, deeper divides, leaf products, combine partials) must heal
    to the byte: the put-time crc re-verification (recompute_mismatches)
    proves each healed block, and the final output proves the run."""
    a, b = _rand((64, 64)), _rand((64, 64))
    budget = 4 * leaf_bytes(64, 64, 64, 2, a.dtype)
    clean, _ = strassen_oot_matmul(
        a, b, depth=2, budget_bytes=budget, backend=NAIVE_LEAVES
    )
    out, stats = strassen_oot_matmul(
        a, b, depth=2, budget_bytes=budget, backend=NAIVE_LEAVES,
        store=store_kind, chaos=ChaosConfig(drop=0.06, corrupt=0.04, seed=0),
    )
    assert np.array_equal(np.asarray(out), np.asarray(clean))
    assert stats.recovered_blocks > 0
    assert stats.recovered_blocks == stats.lost_blocks + stats.corrupt_blocks
    assert stats.unrecovered_faults == 0
    # injection happens below the recovery layer, so nested re-injections
    # during a recompute can exceed the detected count but never trail it
    assert stats.injected_faults >= stats.recovered_blocks
    assert stats.degrades == 0
    stats.assert_within_budget()


# --------------------------------------------------- retry + degradation
def test_transient_leaf_fault_is_retried_in_place():
    a, b = _rand((64, 64)), _rand((64, 64))
    budget = 4 * leaf_bytes(64, 64, 64, 1, a.dtype)
    clean, _ = strassen_oot_matmul(
        a, b, depth=1, budget_bytes=budget, backend=NAIVE_LEAVES
    )
    out, stats = strassen_oot_matmul(
        a, b, depth=1, budget_bytes=budget, backend=NAIVE_LEAVES,
        chaos=ChaosConfig(fail_leaf_calls=(1,)), retries=2, retry_backoff_s=0.0,
    )
    assert np.array_equal(np.asarray(out), np.asarray(clean))
    assert stats.leaf_retries >= 1
    assert stats.injected_faults == 1
    assert stats.degrades == 0  # absorbed by the retry, not the ladder
    assert _counters()["fault.retries"] >= 1.0


def test_exhausted_retries_walk_the_degradation_ladder():
    a, b = _rand((64, 64)), _rand((64, 64))
    budget = 4 * leaf_bytes(64, 64, 64, 1, a.dtype)
    clean, clean_stats = strassen_oot_matmul(
        a, b, depth=1, budget_bytes=budget, backend=NAIVE_LEAVES
    )
    assert clean_stats.rung == "pipeline"  # precondition: rung 0 is async
    out, stats = strassen_oot_matmul(
        a, b, depth=1, budget_bytes=budget, backend=NAIVE_LEAVES,
        chaos=ChaosConfig(fail_leaf_calls=(0,)), retries=0,
    )
    # sync rung is bit-identical to the pipeline (existing invariant), so
    # a degraded run still reproduces the fault-free bytes
    assert np.array_equal(np.asarray(out), np.asarray(clean))
    assert stats.rung == "sync" and stats.degrades == 1
    (ev,) = stats.degrade_events
    assert ev["from"] == "pipeline" and ev["to"] == "sync"
    assert "InjectedFault" in ev["cause"]
    assert _counters()["fault.degrade"] == 1.0


def test_ladder_degrades_on_oom_and_propagates_unknown_errors(monkeypatch):
    a, b = _rand((64, 64)), _rand((64, 64))
    budget = 4 * leaf_bytes(64, 64, 64, 1, a.dtype)
    real = StrassenScheduler._attempt
    calls = {"n": 0}

    def oom_once(self, *args, **kwargs):
        calls["n"] += 1
        if calls["n"] == 1:
            raise MemoryError("simulated allocator exhaustion")
        return real(self, *args, **kwargs)

    monkeypatch.setattr(StrassenScheduler, "_attempt", oom_once)
    out, stats = strassen_oot_matmul(
        a, b, depth=1, budget_bytes=budget, backend=NAIVE_LEAVES
    )
    assert _rel_err(out, a @ b) < 2e-3
    assert stats.rung == "sync" and stats.degrades == 1
    assert "MemoryError" in stats.degrade_events[0]["cause"]

    # anything that is not a fault/OOM is a bug: one attempt, no ladder
    boom_calls = {"n": 0}

    def always_boom(self, *args, **kwargs):
        boom_calls["n"] += 1
        raise RuntimeError("not a fault, a bug")

    monkeypatch.setattr(StrassenScheduler, "_attempt", always_boom)
    with pytest.raises(RuntimeError, match="not a fault"):
        strassen_oot_matmul(
            a, b, depth=1, budget_bytes=budget, backend=NAIVE_LEAVES
        )
    assert boom_calls["n"] == 1


@pytest.mark.parametrize("store_kind", ["dict", "arena", "memmap"])
def test_unrecovered_chaos_fault_cleans_stores_and_device_buffers(
    store_kind, tmp_path
):
    """An injected fault that exhausts the policy (retries=0, degrade off)
    must fail as cleanly as any other error: no device-buffer leak, every
    run-created block dropped from the caller's store, foreign runs'
    blocks — same "A:"/"B:"/"C:" tag space — untouched."""
    a, b = _rand((96, 96)), _rand((96, 96))
    if store_kind == "dict":
        store = DictStore()
    elif store_kind == "memmap":
        store = MemmapStore(str(tmp_path / "spill"))
    else:
        store = ArenaStore(slot_bytes=64 * 1024, capacity=64)
    keep = np.ones((2, 2), np.float32)
    store.put((0, 0, "keep"), keep)
    foreign = np.full((2, 2), 7.0, np.float32)
    store.put((99, 99, "A:0"), foreign)
    baseline = sum(not x.is_deleted() for x in jax.live_arrays())
    with pytest.raises(InjectedFault):
        strassen_oot_matmul(
            a, b, depth=2,
            budget_bytes=4 * leaf_bytes(96, 96, 96, 2, a.dtype),
            backend=NAIVE_LEAVES, store=store,
            chaos=ChaosConfig(fail_leaf_calls=(4,)), retries=0, degrade=False,
        )
    assert sum(not x.is_deleted() for x in jax.live_arrays()) <= baseline
    leftover = [kk for kk in store.keys() if kk[2][:2] in ("A:", "B:", "C:")]
    assert leftover == [(99, 99, "A:0")]
    np.testing.assert_array_equal(np.asarray(store.get((0, 0, "keep"))), keep)
    np.testing.assert_array_equal(np.asarray(store.get((99, 99, "A:0"))), foreign)
    if store_kind == "memmap":
        assert len(os.listdir(store.root)) == 2  # only the unrelated keys
    store.close()


# ------------------------------------------------- checkpoint verification
def test_checkpoint_digest_mismatch_raises(tmp_path):
    tree = {"w": jnp.arange(6.0), "b": jnp.ones((2, 2))}
    path = save_pytree(tree, str(tmp_path), step=1)
    npz = os.path.join(path, "arrays.npz")
    with open(npz, "rb") as f:
        raw = bytearray(f.read())
    raw[-1] ^= 0xFF
    with open(npz, "wb") as f:
        f.write(bytes(raw))
    with pytest.raises(CheckpointError, match="digest mismatch"):
        load_pytree(tree, path)


def test_checkpoint_partial_and_torn_writes_raise(tmp_path):
    tree = {"w": jnp.ones(3)}
    path = save_pytree(tree, str(tmp_path), step=1)
    os.remove(os.path.join(path, "arrays.npz"))
    with pytest.raises(CheckpointError, match="missing arrays"):
        load_pytree(tree, path)

    path2 = save_pytree(tree, str(tmp_path), step=2)
    with open(os.path.join(path2, "manifest.json"), "w") as f:
        f.write("{")  # torn mid-write
    with pytest.raises(CheckpointError, match="torn manifest"):
        load_pytree(tree, path2)

    path3 = save_pytree(tree, str(tmp_path), step=3)
    with open(os.path.join(path3, "manifest.json"), "w") as f:
        json.dump({"complete": False}, f)
    with pytest.raises(CheckpointError, match="not marked complete"):
        load_pytree(tree, path3)

    path4 = save_pytree(tree, str(tmp_path), step=4)
    os.remove(os.path.join(path4, "manifest.json"))
    with pytest.raises(CheckpointError, match="missing manifest"):
        load_pytree(tree, path4)


def test_checkpoint_save_is_atomic_on_failure(tmp_path, monkeypatch):
    """A save that dies mid-write must leave neither a step dir nor a tmp
    dir behind — the atomic-replace contract load verification rests on."""
    tree = {"w": jnp.ones(3)}

    def boom(*args, **kwargs):
        raise OSError("disk full")

    monkeypatch.setattr(np, "savez", boom)
    with pytest.raises(OSError, match="disk full"):
        save_pytree(tree, str(tmp_path), step=1)
    assert os.listdir(tmp_path) == []


def test_checkpoint_missing_key_and_digestless_back_compat(tmp_path):
    tree = {"w": jnp.arange(4.0), "b": jnp.zeros(2)}
    path = save_pytree(tree, str(tmp_path), step=1)
    mpath = os.path.join(path, "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    # checkpoints written before digests existed still load (skip verify)
    del manifest["digest"]
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    restored = load_pytree(tree, path)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(4.0))
    # a payload missing one array is a partial checkpoint, not a default
    npz = os.path.join(path, "arrays.npz")
    data = dict(np.load(npz))
    data.pop(sorted(data)[0])
    np.savez(npz, **data)
    with pytest.raises(CheckpointError, match="payload missing"):
        load_pytree(tree, path)


# ----------------------------------------------------- straggler stop path
def test_straggler_monitor_gauges_reason_and_counter():
    mon = StragglerMonitor(window=8, threshold=2.0, patience=2)
    flagged = False
    for i in range(12):
        mon.start_step()
        time.sleep(0.001 if i < 8 else 0.02)
        flagged = mon.end_step() or flagged
    assert flagged
    reason = mon.flag_reason()
    assert reason["median"] > 2.0 and reason["streak"] >= 2
    snap = obs_metrics.get_metrics().snapshot()
    assert snap["gauges"]["elastic.step_over_median"]["max"] > 2.0
    assert snap["gauges"]["elastic.slow_streak"]["max"] >= 2
    assert snap["counters"]["elastic.straggler_flags"] >= 1.0


def test_train_loop_stop_on_straggler_checkpoints_and_stops(tmp_path, monkeypatch):
    from repro.configs import get_smoke_config
    from repro.launch import train as train_mod
    from repro.optim.adamw import AdamWConfig

    class FlagAtThree:
        def __init__(self):
            self._steps = 0

        def start_step(self):
            pass

        def end_step(self):
            self._steps += 1
            return self._steps >= 3

        def flag_reason(self):
            return {"median": 9.9, "streak": 3}

        @property
        def median_step_time(self):
            return 0.001

    monkeypatch.setattr(train_mod, "StragglerMonitor", FlagAtThree)
    cfg = get_smoke_config("phi4_mini_3_8b")
    opt = AdamWConfig(lr=1e-2, warmup_steps=2, total_steps=50)
    stats = {}
    _, history = train_mod.train_loop(
        cfg, opt, steps=10, batch=2, seq=8, ckpt_dir=str(tmp_path),
        save_every=1000, log_every=1000, stats_out=stats,
        stop_on_straggler=True,
    )
    assert stats["straggler"] == {"median": 9.9, "streak": 3}
    assert len(history) == 3  # stopped at the flag, not at steps
    # force-saved despite save_every never aligning, evidence in the manifest
    assert os.path.isdir(tmp_path / "step_00000003")
    with open(tmp_path / "step_00000003" / "manifest.json") as f:
        manifest = json.load(f)
    assert manifest["extra"]["straggler"] == {"median": 9.9, "streak": 3}
    assert train_mod.STRAGGLER_EXIT_CODE == 75

    # library default: the flag logs and training continues to completion
    stats2 = {}
    _, history2 = train_mod.train_loop(
        cfg, opt, steps=5, batch=2, seq=8, ckpt_dir=None,
        log_every=1000, stats_out=stats2,
    )
    assert len(history2) == 5 and "straggler" not in stats2


# ------------------------------------------------ serving fault isolation
@pytest.fixture(scope="module")
def serve_setup():
    from repro.configs import get_smoke_config
    from repro.models import model as M

    cfg = get_smoke_config("phi4_mini_3_8b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params, **kw):
    from repro.serving.engine import Engine, ServeConfig

    args = dict(max_seq=64, temperature=0.0, slots=3, page_size=8, sync_interval=2)
    args.update(kw)
    return Engine(cfg, params, ServeConfig(**args))


def test_poisoned_decode_evicts_only_culprit_survivors_bit_exact(serve_setup):
    cfg, params = serve_setup
    p0 = np.arange(5) % cfg.vocab
    p1 = (np.arange(9) * 3) % cfg.vocab
    want0 = _engine(cfg, params).submit(p0, 10).result()
    want1 = _engine(cfg, params).submit(p1, 8).result()

    eng = _engine(cfg, params)
    h0 = eng.submit(p0, 10)
    h_bad = eng.submit(p1[::-1].copy(), 12, _inject_fault_at=2)
    h1 = eng.submit(p1, 8)
    eng.run()
    assert h_bad.finish_reason == "error"
    assert h_bad.state.value == "evicted"
    assert len(h_bad.tokens()) == 2  # tokens computed pre-fault still deliver
    assert h0.tokens() == want0
    assert h1.tokens() == want1
    st = eng.serve_stats()
    assert st["pages_in_use"] == 0
    assert st["requests"]["errors"] == 1
    # serving fault counters land on the engine's private registry
    snap = eng.metrics.snapshot()["counters"]
    assert snap["fault.injected_faults"] >= 1.0
    assert snap["fault.evicted_requests"] >= 1.0


def test_prefill_fault_isolated_from_survivor(serve_setup):
    cfg, params = serve_setup
    p = np.arange(6) % cfg.vocab
    want = _engine(cfg, params).submit(p, 8).result()
    eng = _engine(cfg, params)
    h_bad = eng.submit(p[::-1].copy(), 8, _inject_fault_at=0)
    h_ok = eng.submit(p, 8)
    eng.run()
    assert h_bad.finish_reason == "error" and h_bad.tokens() == []
    assert h_ok.tokens() == want
    assert eng.serve_stats()["pages_in_use"] == 0


def test_request_timeout_watchdog_evicts(serve_setup):
    cfg, params = serve_setup
    eng = _engine(cfg, params, request_timeout_s=1e-4)
    h = eng.submit(np.arange(5) % cfg.vocab, 50)
    eng.run()
    assert h.finish_reason == "timeout"
    assert h.state.value == "evicted"
    st = eng.serve_stats()
    assert st["pages_in_use"] == 0
    assert st["requests"]["timeouts"] == 1
    assert eng.metrics.snapshot()["counters"]["fault.timeouts"] >= 1.0


def test_serve_config_rejects_negative_timeout():
    from repro.serving.engine import ServeConfig

    with pytest.raises(ValueError, match="request_timeout_s"):
        ServeConfig(max_seq=64, request_timeout_s=-1.0)
