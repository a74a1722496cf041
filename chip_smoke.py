#!/usr/bin/env python3
"""Smoke run of Stark's main paths on a TPU, through the public entry points.

    python chip_smoke.py               # one chip: phases 0-4
    python chip_smoke.py --four-chips  # four chips: the mesh phase only

Phases (one process; each phase's gates fail the run):

0. device    — a TPU backend, and the fused Pallas leaf compiles on it.
1. in-core   — seeded bf16 A @ B at 8192^2 and 16384^2 (the paper's sizes)
               through ``backend.matmul`` with the naive, strassen,
               strassen_fused (depth 2 and 3) and auto backends; relative
               Frobenius error against an f32 HIGHEST reference within
               :func:`err_gate` of the Strassen levels the product ran.
2. oot       — the tagged-block out-of-core runtime (kind='strassen_oot')
               on a 16384^2 bf16 product under a 1 GiB device budget:
               >= 2 waves, modeled peak within budget, the ladder's first
               rung (device-resident subtrees where the budget holds one,
               as 1 GiB holds a level-1 subtree here; else the async
               pipeline), no degradation, the same error gate.
3. spin      — SPIN block-recursive inversion (``backend.inverse``,
               kind='spin_oot') of a 4096^2 f32 SPD matrix under a budget
               that nests its multiplies out of core, at an explicit
               'highest' matmul precision.
4. serve     — phi4-mini-3.8b at full width (random weights from --seed)
               behind the continuous-batching Engine: 6 requests, every one
               finishes by length; two are checked against the static
               decode loop and a cache-less forward (exact up to a bf16
               near tie, see :func:`phase_serve`); logits are finite.

``--four-chips`` runs the mesh route of ``backend.matmul``
(``MatmulBackend(kind="strassen_fused", mesh=...)``, which runs
strassen_fused_sharded) on a 2x2 mesh of the chips present at 16384^2 bf16
against a sharded naive matmul and the f32 reference, and checks the output
is spread over 4 devices and that no device held the whole problem.

Times printed here are smoke timings (compile included where stated), not
benchmark numbers. The last stdout line is the result JSON; it is printed
only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ERR_GATE = 1e-2  # bf16 products: same ceiling as fig8_scaling.py --smoke
SPIN_GATE = 1e-5  # f32 at 'highest': the CPU smoke's bound
GiB = 1 << 30
MiB = 1 << 20


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def err_gate(levels: int) -> float:
    """Relative Frobenius error ceiling of a bf16 product with ``levels``
    Strassen levels: 1e-2 up to one level, doubling with each further one.

    Each level rounds its operand sums to bf16 (the MXU's input type) and
    its combine cancels terms larger than C, so the error about doubles per
    level whatever the accumulation dtype: 2048^2 Gaussian operands on the
    CPU backend give 1.7e-3 naive, then 4.5e-3, 9.8e-3 and 2.0e-2 at
    depths 1-3 — each about half its ceiling here.
    """
    return ERR_GATE * 2 ** max(levels - 1, 0)


# ------------------------------------------------------------------ helpers
def make_problem(n: int, seed: int, dtype=None):
    """Seeded (A, B) on device and their f32 HIGHEST reference product."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.bfloat16
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    gen = jax.jit(lambda k: jax.random.normal(k, (n, n), dtype))
    a, b = gen(ka), gen(kb)
    ref = reference_matmul(a, b)
    return a, b, ref


def reference_matmul(a, b):
    import jax
    import jax.numpy as jnp

    f = jax.jit(
        lambda x, y: jnp.matmul(
            x.astype(jnp.float32), y.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
    )
    return jax.block_until_ready(f(a, b))


def rel_err(out, ref) -> float:
    """Relative Frobenius error ||out - ref|| / ||ref||, on device."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(
        lambda o, r: jnp.linalg.norm((o.astype(jnp.float32) - r).ravel())
        / jnp.linalg.norm(r.ravel())
    )
    return float(f(out, ref))


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


# ------------------------------------------------------------- phase 1
def phase_incore(sizes=(8192, 16384), seed: int = 0) -> None:
    import jax

    from repro.core import autotune
    from repro.core.backend import MatmulBackend, matmul

    backends = [
        ("naive", MatmulBackend(kind="naive")),
        ("strassen/d2", MatmulBackend(kind="strassen", depth=2)),
        ("strassen_fused/d2", MatmulBackend(kind="strassen_fused", depth=2)),
        ("strassen_fused/d3", MatmulBackend(kind="strassen_fused", depth=3)),
        ("auto/d3", MatmulBackend(kind="auto", depth=3, min_dim=1024)),
    ]
    for n in sizes:
        a, b, ref = make_problem(n, seed)
        for name, be in backends:
            autotune.reset_telemetry()
            t0 = time.perf_counter()
            compiled = jax.jit(lambda x, y, be=be: matmul(x, y, be)).lower(a, b).compile()
            compile_s = time.perf_counter() - t0
            out = jax.block_until_ready(compiled(a, b))
            t0 = time.perf_counter()
            out = jax.block_until_ready(compiled(a, b))
            warm_s = time.perf_counter() - t0
            err = rel_err(out, ref)
            del out
            log(
                f"phase1 n={n} {name:<18} rel_err={err:.3e} "
                f"[smoke timing: trace+compile {compile_s:.2f}s, warm call {warm_s:.4f}s]"
            )
            levels = be.depth if be.kind != "naive" else 0
            if be.kind == "auto":
                events = autotune.get_telemetry().events
                check(len(events) == 1, f"auto resolved {len(events)} decisions, expected 1")
                ev = events[0]
                log(
                    f"phase1 n={n} auto decision: kind={ev.kind} "
                    f"scheme={ev.scheme} depth={ev.depth} source={ev.source}"
                )
                levels = ev.depth if ev.kind != "naive" else 0
            gate = err_gate(levels)
            check(err <= gate, f"phase1 {name} n={n}: rel_err {err:.3e} > {gate:.0e}")
        del a, b, ref


# ------------------------------------------------------------- phase 2
def phase_oot(n: int = 16384, budget: int = GiB, seed: int = 1) -> None:
    import jax
    import numpy as np

    from repro.blocks.scheduler import attach_stats_ring
    from repro.core import autotune
    from repro.core.backend import MatmulBackend, matmul
    from repro.obs import metrics as obs_metrics

    a, b, ref = make_problem(n, seed)
    a_h, b_h = np.asarray(a), np.asarray(b)
    del a, b
    operand_bytes = a_h.nbytes + b_h.nbytes + n * n * a_h.itemsize
    check(operand_bytes > budget, "phase2 problem fits the budget; nothing is out of core")
    ring = attach_stats_ring()
    degrade = obs_metrics.get_metrics().counter("fault.degrade")
    degrades_before = degrade.value
    autotune.reset_telemetry()
    t0 = time.perf_counter()
    out = matmul(a_h, b_h, MatmulBackend(kind="strassen_oot", device_budget=budget))
    out = jax.block_until_ready(out)
    wall = time.perf_counter() - t0
    err = rel_err(out, ref)
    runs = [r for r in ring.snapshot() if r["op"] == "matmul"]
    check(len(runs) == 1, f"phase2 expected one out-of-core run, saw {len(runs)}")
    st = runs[0]
    # Strassen levels the product went through: the out-of-core recursion
    # plus whatever the leaf's auto dispatcher chose for the leaf shape.
    leaf = [e for e in autotune.get_telemetry().events if e.site == "blocks.leaf"]
    leaf_levels = max((e.depth for e in leaf if e.kind != "naive"), default=0)
    for e in {(e.kind, e.scheme, e.depth, e.source) for e in leaf}:
        log(f"phase2 leaf decision: kind={e[0]} scheme={e[1]} depth={e[2]} source={e[3]}")
    log(
        f"phase2 n={n} budget={budget} A+B+C={operand_bytes} depth={st['depth']} "
        f"leaves={st['leaves']} waves={st['waves']} wave_size={st['wave_size']} "
        f"peak_device_bytes={st['peak_device_bytes']} prefetch={st['prefetch']} "
        f"rung={st['rung']} subtree_level={st['subtree_level']} degrades={st['degrades']} "
        f"overlap_efficiency={st['overlap_efficiency']:.3f} rel_err={err:.3e}"
    )
    log(
        f"phase2 [smoke timing: wall {wall:.2f}s incl. compile; divide "
        f"{st['divide_s']:.2f}s, leaf {st['leaf_s']:.2f}s, combine {st['combine_s']:.2f}s]"
    )
    check(st["waves"] >= 2, f"phase2 ran {st['waves']} wave(s), need >= 2")
    check(st["peak_device_bytes"] <= budget, "phase2 peak device bytes over budget")
    if st["subtree_level"] is None:
        check(st["prefetch"] and st["rung"] == "pipeline", "phase2 async pipeline was off")
    else:
        check(st["rung"] == "subtree", f"phase2 subtrees ran on rung {st['rung']}")
    check(
        st["degrades"] == 0 and degrade.value == degrades_before,
        f"phase2 degraded: {st['degrade_events']}",
    )
    gate = err_gate(st["depth"] + leaf_levels)
    check(err <= gate, f"phase2 rel_err {err:.3e} > {gate:.0e}")


# ------------------------------------------------------------- phase 3
def phase_spin(n: int = 4096, budget: int = 32 * MiB, seed: int = 2) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.blocks.scheduler import attach_stats_ring
    from repro.core.backend import MatmulBackend, inverse

    precision = "highest"
    g = jax.random.normal(jax.random.PRNGKey(seed), (n, n), jnp.float32)
    # Well-conditioned SPD (eigenvalues in [2, 6]): every leading block is
    # invertible, which the SPIN recursion needs.
    spd = jax.jit(
        lambda g: jnp.matmul(g, g.T, precision=precision) / n + 2.0 * jnp.eye(n)
    )(g)
    a_h = np.asarray(spd)
    check(2 * a_h.nbytes > budget, "phase3 dense inverse fits the budget")
    with jax.default_matmul_precision(precision):
        want = jax.block_until_ready(jnp.linalg.inv(spd))
    ring = attach_stats_ring()
    t0 = time.perf_counter()
    got = inverse(
        a_h,
        MatmulBackend(kind="auto", device_budget=budget, precision=precision),
        kind="spin_oot",
    )
    got = jax.block_until_ready(got)
    wall = time.perf_counter() - t0
    err = rel_err(got, want)
    inv_runs = [r for r in ring.snapshot() if r["op"] == "inverse"]
    check(len(inv_runs) == 1, f"phase3 expected one inverse run, saw {len(inv_runs)}")
    st = inv_runs[0]
    log(
        f"phase3 n={n} f32 precision={precision} budget={budget} depth={st['depth']} "
        f"nested_oot_muls={st['oot_runs']} waves={st['waves']} "
        f"peak_device_bytes={st['peak_device_bytes']} degrades={st['degrades']} "
        f"rel_err={err:.3e} [smoke timing: wall {wall:.2f}s incl. compile]"
    )
    check(st["oot_runs"] >= 1, "phase3 no multiply nested into the out-of-core scheduler")
    check(st["peak_device_bytes"] <= budget, "phase3 peak device bytes over budget")
    check(st["degrades"] == 0, f"phase3 degraded: {st['degrade_events']}")
    check(err <= SPIN_GATE, f"phase3 rel_err {err:.3e} > {SPIN_GATE}")


# ------------------------------------------------------------- phase 4
PROMPT_LENS = (32, 80, 128, 176, 224, 256)
NEW_TOKENS = 32
PARITY_REQUESTS = (0, 5)


def phase_serve(cfg=None, seed: int = 0, serve_kw=None) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models import model as M
    from repro.serving.engine import Engine, ServeConfig

    cfg = cfg or get_config("phi4_mini_3_8b")
    serve_kw = serve_kw or dict(max_seq=512, slots=4, page_size=16)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(lambda k: M.init_params(cfg, k))(jax.random.PRNGKey(seed))
    )
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(
        f"phase4 model={cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"vocab={cfg.vocab} dtype={cfg.dtype} weights={n_bytes} bytes "
        f"[smoke timing: init {time.perf_counter() - t0:.2f}s]"
    )
    engine = Engine(cfg, params, ServeConfig(**serve_kw))
    prompts = [
        np.asarray(
            jax.random.randint(jax.random.PRNGKey(seed + 1 + i), (plen,), 0, cfg.vocab),
            np.int32,
        )
        for i, plen in enumerate(PROMPT_LENS)
    ]
    t0 = time.perf_counter()
    handles = [engine.submit(p, NEW_TOKENS) for p in prompts]
    n_events = sum(1 for _ in engine.stream(handles))
    wall = time.perf_counter() - t0
    for h, p in zip(handles, prompts):
        ttft, _ = h.latency_stats()
        log(
            f"phase4 req {h.id}: prompt={p.size} tokens={len(h.tokens())} "
            f"finish={h.finish_reason} [smoke timing: ttft {ttft:.3f}s incl. compile]"
        )
    log(f"phase4 {len(handles)} requests / {n_events} tokens [smoke timing: {wall:.2f}s]")
    log(f"phase4 serve_stats: {json.dumps(engine.serve_stats(), sort_keys=True)}")
    for h in handles:
        check(h.finish_reason == "length", f"phase4 req {h.id} finished {h.finish_reason}")
        check(len(h.tokens()) == NEW_TOKENS, f"phase4 req {h.id}: {len(h.tokens())} tokens")
    # Parity with the static loop, judged by a cache-less forward. The
    # engine (paged cache, bucketed gathers) and the static loop (dense
    # max_seq cache) reduce attention over different lengths, so in bf16
    # their logits differ by rounding and a near-tie argmax may break
    # either way. Gates: the two agree token for token up to the first
    # divergence, if any; there both tokens are within ``tol`` of the top
    # logit of an independent teacher-forced forward; and every engine
    # token is greedy within ``tol`` of it. ``tol`` is 4x the measured
    # disagreement between the static prefill and that forward at the
    # same position, and at least 2 bf16 ulps of the top logit.
    forward = jax.jit(lambda p, t: M.apply_train(p, {"tokens": t}, cfg)[0])
    for i in PARITY_REQUESTS:
        prompt, s = prompts[i], prompts[i].size
        engine_toks = handles[i].tokens()
        pre, _ = engine._prefill(
            params, {"tokens": jnp.asarray(prompt[None])},
            M.init_cache(cfg, 1, engine.serve.max_seq),
        )
        pre = np.asarray(pre[0], np.float32)
        static, _ = engine._generate_static(jnp.asarray(prompt[None]), NEW_TOKENS)
        static = [int(t) for t in np.asarray(static)[0]]
        full = jnp.asarray(np.concatenate([prompt, engine_toks])[None], jnp.int32)
        logits = np.asarray(forward(params, full)[0, s - 1 : s - 1 + NEW_TOKENS], np.float32)
        check(
            np.isfinite(pre).all() and np.isfinite(logits).all(),
            f"phase4 req {i}: non-finite logits",
        )
        top = logits.max(axis=-1)
        tol = max(4 * float(np.abs(pre - logits[0]).max()), 2.0**-6 * float(np.abs(top).max()))
        slack = top - logits[np.arange(NEW_TOKENS), engine_toks]
        diverge = next((t for t in range(NEW_TOKENS) if engine_toks[t] != static[t]), None)
        if diverge is None:
            log(f"phase4 req {i}: static-path parity exact ({NEW_TOKENS} tokens)")
        else:
            gap = float(top[diverge] - logits[diverge, static[diverge]])
            log(
                f"phase4 req {i}: static path diverges at token {diverge} on a near tie: "
                f"engine token {engine_toks[diverge]} and static token {static[diverge]} "
                f"are {slack[diverge]:.4g} and {gap:.4g} below the top logit "
                f"{top[diverge]:.4g} (tol {tol:.4g})"
            )
            check(gap <= tol, f"phase4 req {i}: static token {gap:.4g} below top > tol")
        log(f"phase4 req {i}: engine greedy slack max {float(slack.max()):.4g} (tol {tol:.4g})")
        check(float(slack.max()) <= tol, f"phase4 req {i}: engine token not greedy within tol")
    dev = jax.devices()[0]
    log(f"phase4 peak_bytes_in_use={peak_bytes(dev)}")


# ------------------------------------------------------------- four chips
def phase_mesh(n: int = 16384, depth: int = 2, seed: int = 3) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from repro.core import backend
    from repro.kernels.strassen.ops import strassen_matmul_fused

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, have {len(devices)}")
    mesh = Mesh(
        np.asarray(devices).reshape(2, 2), ("data", "model"),
        axis_types=(AxisType.Auto, AxisType.Auto),
    )
    rows = NamedSharding(mesh, P(("data", "model"), None))
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    gen = jax.jit(lambda k: jax.random.normal(k, (n, n), jnp.bfloat16), out_shardings=rows)
    a, b = gen(ka), gen(kb)
    ref = jax.block_until_ready(
        jax.jit(
            lambda x, y: jnp.matmul(
                x.astype(jnp.float32), y.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
            ),
            out_shardings=rows,
        )(a, b)
    )
    naive = jax.block_until_ready(jax.jit(jnp.matmul, out_shardings=rows)(a, b))
    log(f"mesh {dict(mesh.shape)} n={n} naive rel_err={rel_err(naive, ref):.3e}")
    held = [a, b, ref, naive]
    # min_dim=1: the phase pins the depth at every n it is given.
    be = backend.MatmulBackend(kind="strassen_fused", depth=depth, min_dim=1, mesh=mesh)
    name = "strassen_fused_sharded"
    gate = err_gate(depth)
    t0 = time.perf_counter()
    compiled = (
        jax.jit(lambda x, y: backend.matmul(x, y, be), out_shardings=rows)
        .lower(a, b)
        .compile()
    )
    compile_s = time.perf_counter() - t0
    out = jax.block_until_ready(compiled(a, b))
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(a, b))
    warm_s = time.perf_counter() - t0
    err, vs_naive = rel_err(out, ref), rel_err(out, naive.astype(jnp.float32))
    shard_devs = {s.device for s in out.addressable_shards}
    shard_shapes = {s.data.shape for s in out.addressable_shards}
    log(
        f"mesh {name} depth={depth} rel_err={err:.3e} vs_naive={vs_naive:.3e} "
        f"shards on {len(shard_devs)} devices, shard shapes {sorted(shard_shapes)} "
        f"[smoke timing: compile {compile_s:.2f}s, warm call {warm_s:.4f}s]"
    )
    check(err <= gate and vs_naive <= gate, f"mesh {name}: error over {gate:.0e}")
    check(len(shard_devs) == 4, f"mesh {name}: output on {len(shard_devs)} devices")
    check(shard_shapes == {(n // 4, n)}, f"mesh {name}: shards {shard_shapes}")
    held.append(out)

    # What device 0 would hold with nothing sharded: every array this phase
    # keeps, plus the scratch of the single-device rendition of the
    # pipeline (compiled for device 0 only, never run).
    one = jax.ShapeDtypeStruct((n, n), jnp.bfloat16, sharding=SingleDeviceSharding(devices[0]))
    single = jax.jit(lambda x, y: strassen_matmul_fused(x, y, depth=depth))
    temp = single.lower(one, one).compile().memory_analysis().temp_size_in_bytes
    whole = sum(x.nbytes for x in held) + temp
    peaks = [peak_bytes(d) for d in devices]
    log(
        f"mesh peak_bytes_in_use per device={peaks}; device 0 alone would hold "
        f"{whole} bytes (arrays {whole - temp}, single-device scratch {temp})"
    )
    check(all(p > 0 for p in peaks), "mesh: a device reported no memory use")
    check(
        max(peaks) < 0.5 * whole,
        f"mesh: a device peaked at {max(peaks)} of {whole} bytes — not sharded",
    )


# ------------------------------------------------------------------ main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 mesh phase (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0, help="model weight seed")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: no TPU found (jax platform {dev.platform!r}); "
            "this smoke runs only on a TPU",
            file=sys.stderr,
        )
        return 2
    log(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devices)}")

    from repro.core import compat
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    mode = compat.pallas_leaf_mode()
    log(f"phase0 pallas_leaf_mode={mode}")
    if mode != "compiled":
        print(f"chip_smoke: Pallas leaf mode {mode!r}, expected 'compiled'", file=sys.stderr)
        return 1

    if args.four_chips:
        phases = [("mesh", phase_mesh)]
    else:
        phases = [
            ("in-core", phase_incore),
            ("out-of-core", phase_oot),
            ("spin", phase_spin),
            ("serve", lambda: phase_serve(seed=args.seed)),
        ]
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        log(f"== phase {name}")
        try:
            fn()
        except Exception:
            # Reported and counted, never turned into success: a failed
            # phase fails the run. Later phases still run so one chip call
            # shows every fault.
            traceback.print_exc()
            failed.append(name)
        log(f"== phase {name} {'FAILED' if name in failed else 'ok'} "
            f"[smoke timing: {time.perf_counter() - t0:.1f}s]")
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}", file=sys.stderr)
        return 1
    result = {
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
