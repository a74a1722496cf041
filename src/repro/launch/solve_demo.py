"""Out-of-core SPIN solver demo: invert / triangular-solve under a budget.

Drives :mod:`repro.blocks.solve` end to end — build a well-conditioned SPD
(or triangular) input, walk the SPIN block-recursive dataflow plan, run
the dense leaves on device, and route every recursive multiply back
through the tagged out-of-core scheduler whenever its working set exceeds
the device budget. Verifies against ``jnp.linalg.inv`` /
``jax.scipy.linalg.solve_triangular``.

Usage (CPU-scale):
  PYTHONPATH=src python -m repro.launch.solve_demo --n 1024 \
      --budget-mb 1 --op inverse --store memmap --check
  PYTHONPATH=src python -m repro.launch.solve_demo --n 2048 --op trsm \
      --nrhs 512 --budget-mb 2 --dtype bfloat16 --check

``--depth 0`` picks the shallowest depth whose dense leaf fits the
budget. Prints the solver's execution stats: nested out-of-core matmul
runs, staging waves, H2D/D2H bytes, peak device bytes vs the budget, and
(with ``--fault-rate``) the chaos/recovery tallies.
"""
from __future__ import annotations

import argparse
import json

import numpy as np


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=1024, help="matrix side (square)")
    ap.add_argument("--op", choices=["inverse", "trsm"], default="inverse")
    ap.add_argument("--nrhs", type=int, default=0,
                    help="RHS columns for --op trsm (default --n)")
    ap.add_argument("--upper", action="store_true",
                    help="solve an upper-triangular system (--op trsm)")
    ap.add_argument("--depth", type=int, default=0,
                    help="solver recursion depth; 0 = shallowest whose "
                    "dense leaf fits the budget")
    ap.add_argument("--budget-mb", type=float, default=64.0,
                    help="peak device bytes any wave may occupy")
    ap.add_argument("--store", choices=["dict", "arena", "memmap"], default="dict")
    ap.add_argument("--store-root", default=None,
                    help="spill directory for --store memmap")
    ap.add_argument("--dtype", choices=["float32", "bfloat16"], default="float32")
    ap.add_argument("--scheme", choices=["strassen", "winograd"], default="strassen",
                    help="matmul scheme for the nested out-of-core multiplies")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="disable the async staging pipeline in nested multiplies")
    ap.add_argument("--check", action="store_true",
                    help="verify against the dense device solver")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="chaos injection: per-get block drop probability in "
                    "the nested out-of-core multiplies (corruption and leaf "
                    "failures at proportional rates); lineage recovery heals")
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
    from repro.blocks.solve import (
        solver_min_depth_for_budget,
        spin_inverse_oot,
        triangular_solve_oot,
    )

    if args.trace_out:
        obs.configure(enabled=True)

    n = args.n
    nrhs = (args.nrhs or n) if args.op == "trsm" else n
    budget = int(args.budget_mb * 2**20)
    dtype = np.dtype(args.dtype) if args.dtype == "float32" else None
    if dtype is None:
        import ml_dtypes

        dtype = np.dtype(ml_dtypes.bfloat16)
    leaf_kind = "inv" if args.op == "inverse" else (
        "trsm_upper" if args.upper else "trsm_lower"
    )
    depth = args.depth or solver_min_depth_for_budget(
        n, budget, np.result_type(dtype, np.float32),
        nrhs=nrhs if args.op == "trsm" else None, leaf_kind=leaf_kind,
    )

    rng = np.random.default_rng(args.seed)
    g = rng.standard_normal((n, n)).astype(np.float32)
    if args.op == "inverse":
        # Well-conditioned SPD: every leading principal block invertible,
        # which the SPIN recursion requires.
        a = (g @ g.T / n + np.eye(n, dtype=np.float32) * 2.0).astype(dtype)
        operands = (a,)
    else:
        t = np.triu(g) if args.upper else np.tril(g)
        t = (t / np.sqrt(n) + np.eye(n, dtype=np.float32) * 2.0).astype(dtype)
        b = rng.standard_normal((n, nrhs)).astype(dtype)
        operands = (t, b)
    op_bytes = max(x.nbytes for x in operands)
    print(
        f"{args.op} {n}x{n}" + (f" rhs {n}x{nrhs}" if args.op == "trsm" else "")
        + f" {dtype.name}: largest operand {op_bytes / 2**20:.1f} MiB, "
        f"device budget {budget / 2**20:.1f} MiB "
        f"({'smaller than an operand — out-of-core' if budget < op_bytes else 'fits'}), "
        f"solver depth {depth}",
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

    common = dict(
        depth=depth, budget_bytes=budget, scheme=args.scheme,
        prefetch=not args.no_prefetch, store=args.store,
        store_root=args.store_root, chaos=chaos,
    )
    if args.op == "inverse":
        out, stats = spin_inverse_oot(operands[0], **common)
    else:
        out, stats = triangular_solve_oot(
            operands[0], operands[1], lower=not args.upper, **common
        )

    print(
        f"done in {stats.total_s:.2f}s  "
        f"({stats.oot_runs} nested out-of-core multiplies, "
        f"{stats.leaves} matmul leaves in {stats.waves} waves; "
        f"leaf {stats.leaf_s:.2f}s)"
    )
    print(
        f"device: peak {stats.peak_device_bytes / 2**20:.2f} / "
        f"{stats.budget_bytes / 2**20:.2f} MiB budget | staged "
        f"H2D {stats.h2d_bytes / 2**20:.1f} MiB, D2H {stats.d2h_bytes / 2**20:.1f} MiB "
        f"({stats.stage_dtype} staging) | overlap efficiency "
        f"{stats.overlap_efficiency:.2f}"
    )
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

        if args.op == "inverse":
            want = np.asarray(jnp.linalg.inv(jnp.asarray(operands[0])))
        else:
            import jax.scipy.linalg as jsl

            want = np.asarray(jsl.solve_triangular(
                jnp.asarray(operands[0]), jnp.asarray(operands[1]),
                lower=not args.upper,
            ))
        scale = float(np.abs(want.astype(np.float32)).max()) or 1.0
        err = float(
            np.abs(out.astype(np.float32) - want.astype(np.float32)).max() / scale
        )
        tol = 1e-2 if dtype.itemsize < 4 else 1e-5
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
