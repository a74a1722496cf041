"""Out-of-core Strassen demo: multiply matrices bigger than the device budget.

Drives :mod:`repro.blocks` end to end — ingest dense operands into a host
block store (dict / RAM arena / npy memmap spill), walk the tagged
recursion tree level by level, stage the 7^depth leaf products through
device memory in budgeted async-pipelined waves, and verify the result
against the dense matmul.

Usage (CPU-scale):
  PYTHONPATH=src python -m repro.launch.blocks_demo --n 1024 \
      --budget-mb 1 --depth 3 --store memmap --check
  PYTHONPATH=src python -m repro.launch.blocks_demo --m 2048 --k 1024 \
      --n 1536 --budget-mb 2 --dtype bfloat16 --store arena

``--depth 0`` picks the shallowest depth whose leaf fits the budget.
Prints the scheduler's execution stats: staging waves, H2D/D2H bytes,
peak device bytes vs the budget, host store peak, and per-phase seconds.
"""
from __future__ import annotations

import argparse
import json

import numpy as np


def main():
    # Scheme choices come from the plan registry, so a newly registered
    # bilinear plan is immediately drivable from this CLI.
    from repro.blocks.plan import BilinearPlan, get_plan, plan_names

    schemes = [
        n for n in plan_names() if isinstance(get_plan(n), BilinearPlan)
    ]
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=1024, help="matrix side (square)")
    ap.add_argument("--m", type=int, default=0, help="rows of A (default --n)")
    ap.add_argument("--k", type=int, default=0, help="cols of A (default --n)")
    ap.add_argument("--depth", type=int, default=0,
                    help="recursion depth; 0 = shallowest that fits the budget")
    ap.add_argument("--budget-mb", type=float, default=64.0,
                    help="peak device bytes the leaf waves may occupy")
    ap.add_argument("--block", type=int, default=0,
                    help="store block side; 0 = one block per leaf")
    ap.add_argument("--store", choices=["dict", "arena", "memmap"], default="dict")
    ap.add_argument("--store-root", default=None,
                    help="spill directory for --store memmap")
    ap.add_argument("--dtype", choices=["float32", "bfloat16"], default="float32")
    ap.add_argument("--scheme", choices=schemes, default="strassen")
    ap.add_argument("--leaf-backend", default="auto",
                    help="matmul routing kind for the leaf waves")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="disable the async 2-deep staging pipeline")
    ap.add_argument("--check", action="store_true",
                    help="verify against the dense jnp.matmul")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="chaos injection: per-get block drop probability "
                    "(corruption and leaf failures are injected at "
                    "proportional rates); recovery recomputes from lineage")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for the deterministic chaos harness")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json-out", default=None, help="write stats JSON here")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome/Perfetto trace of the run here")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro import obs
    from repro.blocks.scheduler import min_depth_for_budget, strassen_oot_matmul
    from repro.core.backend import MatmulBackend

    if args.trace_out:
        obs.configure(enabled=True)

    m = args.m or args.n
    k = args.k or args.n
    n = args.n
    budget = int(args.budget_mb * 2**20)
    dtype = np.dtype(args.dtype) if args.dtype == "float32" else None
    if dtype is None:
        import ml_dtypes

        dtype = np.dtype(ml_dtypes.bfloat16)
    depth = args.depth or min_depth_for_budget(
        m, k, n, budget, dtype, pipelined=not args.no_prefetch
    )

    rng = np.random.default_rng(args.seed)
    a = rng.standard_normal((m, k)).astype(dtype)
    b = rng.standard_normal((k, n)).astype(dtype)
    op_bytes = max(a.nbytes, b.nbytes)
    print(
        f"A {a.shape} @ B {b.shape} {dtype.name}: operands "
        f"{op_bytes / 2**20:.1f} MiB each, device budget "
        f"{budget / 2**20:.1f} MiB "
        f"({'smaller than an operand — out-of-core' if budget < op_bytes else 'fits'}), "
        f"depth {depth} -> {7**depth} leaves",
        flush=True,
    )

    chaos = None
    if args.fault_rate > 0:
        from repro.blocks.recovery import ChaosConfig

        chaos = ChaosConfig(
            drop=args.fault_rate,
            corrupt=args.fault_rate * 0.4,
            leaf_fail_rate=args.fault_rate * 0.5,
            seed=args.chaos_seed,
        )
        print(
            f"chaos: drop {chaos.drop:.3f} / corrupt {chaos.corrupt:.3f} / "
            f"leaf-fail {chaos.leaf_fail_rate:.3f} (seed {chaos.seed}) — "
            "lineage recovery on"
        )

    backend = MatmulBackend(kind=args.leaf_backend, depth=2)
    out, stats = strassen_oot_matmul(
        a, b,
        depth=depth, budget_bytes=budget, scheme=args.scheme, backend=backend,
        block=args.block or None, prefetch=not args.no_prefetch,
        store=args.store, store_root=args.store_root,
        chaos=chaos,
    )

    print(
        f"done in {stats.total_s:.2f}s  "
        f"(divide {stats.divide_s:.2f}s, leaf {stats.leaf_s:.2f}s "
        f"[{stats.waves} waves x {stats.wave_size}], combine {stats.combine_s:.2f}s)"
    )
    print(
        f"pipeline: {'async 2-deep' if stats.prefetch else 'synchronous'} | "
        f"stage {stats.stage_s:.2f}s, fetch {stats.fetch_s:.2f}s, "
        f"overlap efficiency {stats.overlap_efficiency:.2f}"
    )
    print(
        f"device: peak {stats.peak_device_bytes / 2**20:.2f} / "
        f"{stats.budget_bytes / 2**20:.2f} MiB budget | staged "
        f"H2D {stats.h2d_bytes / 2**20:.1f} MiB, D2H {stats.d2h_bytes / 2**20:.1f} MiB "
        f"({stats.stage_dtype} staging)"
    )
    print(f"host store peak: {stats.host_store_peak_bytes / 2**20:.1f} MiB ({args.store})")
    if chaos is not None:
        print(
            f"faults: {stats.injected_faults} injected "
            f"({stats.lost_blocks} lost, {stats.corrupt_blocks} corrupt) | "
            f"{stats.recovered_blocks} recomputed from lineage, "
            f"{stats.leaf_retries} leaf retries, "
            f"{stats.unrecovered_faults} unrecovered | "
            f"rung {stats.rung} ({stats.degrades} degrades)"
        )

    if args.check:
        import jax.numpy as jnp

        want = np.asarray(jnp.matmul(jnp.asarray(a), jnp.asarray(b)))
        scale = float(np.abs(want.astype(np.float32)).max()) or 1.0
        err = float(
            np.abs(out.astype(np.float32) - want.astype(np.float32)).max() / scale
        )
        tol = 1e-2 if dtype.itemsize < 4 else 2e-3
        print(f"parity vs dense: rel err {err:.2e} ({'OK' if err < tol else 'FAIL'})")
        if err >= tol:
            raise SystemExit(1)

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(stats.to_dict(), f, indent=1)
        print(f"wrote {args.json_out}")

    if args.trace_out:
        from repro.obs import export

        export.write_trace(args.trace_out, metrics=obs.get_metrics())
        print(f"wrote {args.trace_out} ({len(obs.get_tracer().spans)} spans)")


if __name__ == "__main__":
    main()
