"""Training launcher: config -> mesh -> data -> jitted step -> ckpt loop.

Real-cluster posture on any device count:
  * fits the canonical mesh to the available devices (elastic),
  * shards params/opt-state/batch via the same logical rules as the
    dry-run (launch/specs.py),
  * auto-resumes from the newest complete checkpoint,
  * straggler watchdog triggers checkpoint+restart recommendation.

CPU-scale usage (see examples/train_e2e.py for the packaged version):
  PYTHONPATH=src python -m repro.launch.train --arch phi4_mini_3_8b \
      --smoke --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import time

import jax

from repro import obs
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core.backend import MatmulBackend
from repro.data.pipeline import DataConfig, SyntheticLM, shard_for_host
from repro.launch.mesh import make_mesh_for
from repro.launch.specs import batch_logical_axes, param_logical_axes, sharding_tree
from repro.models.sharding import DEFAULT_RULES, use_sharding
from repro.optim.adamw import AdamWConfig
from repro.runtime.checkpoint import CheckpointManager, save_pytree
from repro.runtime.elastic import StragglerMonitor
from repro.training.train_step import init_train_state, make_train_step

# Clean exit for "checkpointed and stopped on sustained straggler": the
# job supervisor restarts the run (plan_mesh re-fits to the survivors)
# instead of treating it as a crash. Mirrors EX_TEMPFAIL.
STRAGGLER_EXIT_CODE = 75


def build(cfg, opt_cfg, *, batch, seq, accum, mesh=None, rules=DEFAULT_RULES, seed=0):
    """Returns (state, pipeline, jitted_step). mesh=None -> single device."""
    data = SyntheticLM(cfg, DataConfig(batch=batch, seq_len=seq, seed=seed))
    step = make_train_step(cfg, opt_cfg, accum_steps=accum)

    if mesh is None:
        state = init_train_state(cfg, opt_cfg, jax.random.PRNGKey(seed))
        return state, data, jax.jit(step, donate_argnums=(0,))

    with use_sharding(mesh, rules):
        state_shapes = jax.eval_shape(
            lambda k: init_train_state(cfg, opt_cfg, k), jax.random.PRNGKey(seed)
        )
        state_sh = sharding_tree(state_shapes, mesh, param_logical_axes, rules)
        init_fn = jax.jit(
            lambda k: init_train_state(cfg, opt_cfg, k), out_shardings=state_sh
        )
        state = init_fn(jax.random.PRNGKey(seed))
        sample = data(0)
        batch_shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), sample
        )
        batch_sh = sharding_tree(
            batch_shapes, mesh, lambda p, s: batch_logical_axes(p, s), rules
        )
        jitted = jax.jit(
            step,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        )
    return state, data, jitted


def train_loop(
    cfg,
    opt_cfg,
    *,
    steps,
    batch,
    seq,
    accum=1,
    mesh=None,
    ckpt_dir=None,
    save_every=50,
    log_every=10,
    seed=0,
    stats_out=None,
    stop_on_straggler=False,
):
    """Run the training loop; returns (state, loss history).

    ``stats_out``: optional dict filled with run measurements
    (median_step_time_s, steps_run) — the step-time evidence the summary
    JSON and the autotune-vs-hand-picked comparison report.

    ``stop_on_straggler``: when the watchdog flags a sustained slowdown,
    force-save a checkpoint (regardless of ``save_every`` alignment) and
    stop the loop cleanly; the flag's evidence lands in
    ``stats_out['straggler']`` so the launcher can exit with
    :data:`STRAGGLER_EXIT_CODE`. Off, the flag is logged and training
    continues (the library-default behavior tests rely on).
    """
    state, data, jitted = build(
        cfg, opt_cfg, batch=batch, seq=seq, accum=accum, mesh=mesh, seed=seed
    )
    start = 0
    mgr = None
    if ckpt_dir:
        mgr = CheckpointManager(ckpt_dir, save_every=save_every, keep_last=3)
        resumed, state_r = mgr.restore_latest(state)
        if resumed is not None:
            state, start = state_r, resumed
            print(f"[resume] from step {resumed}")

    watchdog = StragglerMonitor()
    history = []
    with use_sharding(mesh, DEFAULT_RULES) if mesh is not None else _null():
        for step_i in range(start, steps):
            watchdog.start_step()
            state, metrics = jitted(state, data(step_i))
            jax.block_until_ready(metrics["loss"])
            flagged = watchdog.end_step()
            loss = float(metrics["loss"])
            history.append(loss)
            if step_i % log_every == 0 or step_i == steps - 1:
                print(
                    f"step {step_i:5d} loss {loss:.4f} "
                    f"gnorm {float(metrics.get('grad_norm', 0.0)):.3f} "
                    f"lr {float(metrics.get('lr', 0.0)):.2e} "
                    f"({watchdog.median_step_time*1e3:.0f} ms/step)",
                    flush=True,
                )
            if mgr:
                mgr.maybe_save(state, step_i + 1, extra={"loss": loss})
            if flagged:
                reason = watchdog.flag_reason()
                print(
                    f"[straggler] sustained slowdown "
                    f"(step/median x{reason['median']:.2f}, "
                    f"streak {reason['streak']}) — checkpoint + restart advised"
                )
                if stop_on_straggler:
                    if ckpt_dir:
                        save_pytree(
                            state, ckpt_dir, step=step_i + 1,
                            extra={"loss": loss, "straggler": reason},
                        )
                        print(f"[straggler] checkpointed step {step_i + 1}; stopping")
                    if stats_out is not None:
                        stats_out["straggler"] = reason
                    break
                if mgr:
                    mgr.maybe_save(state, step_i + 1, extra={"straggler": True})
    if stats_out is not None:
        stats_out["median_step_time_s"] = watchdog.median_step_time
        stats_out["steps_run"] = len(history)  # executed, not planned
    return state, history


@contextlib.contextmanager
def _null():
    yield


def autotune_step_delta(
    baseline_cfg,
    opt_cfg,
    *,
    auto_step_time,
    steps,
    batch,
    seq,
    accum=1,
    mesh=None,
):
    """Measure the autotuned-vs-hand-picked step-time delta (ROADMAP item).

    Runs a short baseline segment on ``baseline_cfg`` (the hand-picked
    backend; same shapes, no checkpointing) and returns the summary-JSON
    fields: step_time_handpicked_s, step_time_delta_s and — when the
    baseline measured — step_time_delta_pct. Use enough ``steps`` that the
    median is not dominated by the compile step.
    """
    base_stats = {}
    train_loop(
        baseline_cfg, opt_cfg,
        steps=steps, batch=batch, seq=seq, accum=accum, mesh=mesh,
        ckpt_dir=None, log_every=max(steps, 1), stats_out=base_stats,
    )
    base_t = base_stats.get("median_step_time_s", 0.0)
    out = {
        "step_time_handpicked_s": base_t,
        "step_time_delta_s": auto_step_time - base_t,
    }
    if base_t:
        out["step_time_delta_pct"] = 100.0 * (auto_step_time - base_t) / base_t
    print(
        f"[autotune] step time {auto_step_time*1e3:.1f} ms vs hand-picked "
        f"{base_t*1e3:.1f} ms ({out.get('step_time_delta_pct', 0.0):+.1f}%)"
    )
    return out


def main():
    from repro.core.backend import JIT_SAFE_KINDS

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), required=True)
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--mesh", action="store_true", help="build a device mesh")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument(
        "--backend",
        # The train step is jitted: only the jit-safe registered kinds
        # (use repro.launch.blocks_demo for the out-of-core surface).
        choices=list(JIT_SAFE_KINDS),
        default="naive",
        help="matmul routing, validated against the registered kinds; "
        "'auto' sets matmul_autotune=True so every dense projection "
        "resolves from the calibrated dispatcher (--strassen-depth "
        "becomes the max depth it may pick)",
    )
    ap.add_argument("--strassen-depth", type=int, default=1)
    ap.add_argument("--strassen-min-dim", type=int, default=1024)
    ap.add_argument(
        "--compare-steps", type=int, default=0,
        help="with --backend auto: also run this many steps on the "
        "hand-picked (config default) backend and record the measured "
        "step-time delta in the summary JSON",
    )
    ap.add_argument(
        "--no-exit-on-straggler", action="store_true",
        help="keep training through a straggler flag instead of "
        "checkpointing and exiting with code 75 for a supervised restart",
    )
    ap.add_argument("--summary-out", default=None,
                    help="write a run-summary JSON (loss, step time, "
                    "backend, autotune telemetry) here")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome/Perfetto trace of the run here")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.trace_out:
        obs.configure(enabled=True)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    baseline_cfg = cfg  # the hand-picked backend, for --compare-steps
    if args.backend == "auto":
        cfg = dataclasses.replace(
            cfg,
            matmul_autotune=True,
            matmul_backend=MatmulBackend(
                kind="auto", depth=max(args.strassen_depth, 1),
                min_dim=args.strassen_min_dim,
            ),
        )
    elif args.backend != "naive":
        cfg = dataclasses.replace(
            cfg,
            matmul_backend=MatmulBackend(
                kind=args.backend, depth=args.strassen_depth, min_dim=args.strassen_min_dim
            ),
        )
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5), total_steps=args.steps)
    mesh = None
    if args.mesh:
        mesh = make_mesh_for(jax.device_count(), args.model_parallel)
        print(f"mesh: {dict(mesh.shape)}")

    per_host = shard_for_host(args.batch)
    run_stats = {}
    t0 = time.time()
    _, history = train_loop(
        cfg, opt_cfg,
        steps=args.steps, batch=per_host, seq=args.seq, accum=args.accum,
        mesh=mesh, ckpt_dir=args.ckpt_dir, save_every=args.save_every,
        stats_out=run_stats,
        stop_on_straggler=not args.no_exit_on_straggler,
    )
    dt = time.time() - t0
    print(f"done: {len(history)} steps in {dt:.1f}s; loss {history[0]:.3f} -> {history[-1]:.3f}")

    summary = {
        "arch": args.arch,
        "backend": args.backend,
        "steps": args.steps,
        "wall_s": dt,
        "loss_first": history[0],
        "loss_last": history[-1],
        **run_stats,
    }
    if args.backend == "auto":
        from repro.core import autotune

        summary["autotune"] = {
            "kinds": autotune.get_telemetry().kind_counts(),
            "calibration": autotune.calibration_snapshot(),
        }
        if args.compare_steps > 0:
            summary.update(
                autotune_step_delta(
                    baseline_cfg, opt_cfg,
                    auto_step_time=run_stats.get("median_step_time_s", 0.0),
                    steps=args.compare_steps, batch=per_host, seq=args.seq,
                    accum=args.accum, mesh=mesh,
                )
            )
    if args.summary_out:
        import json

        with open(args.summary_out, "w") as f:
            json.dump(summary, f, indent=1)
        print(f"wrote {args.summary_out}")
    if args.trace_out:
        from repro.obs import export

        export.write_trace(args.trace_out, metrics=obs.get_metrics())
        print(f"wrote {args.trace_out}")
    if "straggler" in run_stats:
        raise SystemExit(STRAGGLER_EXIT_CODE)


if __name__ == "__main__":
    main()
