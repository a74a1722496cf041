"""Autotune crossover sweep: predicted-vs-measured per shape (paper §V-C).

For each size the calibrated dispatcher enumerates every legal candidate on
an 8-way host-platform mesh, records each candidate's predicted seconds,
executes the selected candidate (plus the naive baseline) for a measured
column, and checks the selected path's output against ``jnp.matmul``.

The crossover the paper reports is a *distributed* effect: a single XLA
device has no shuffle term, so the naive matmul wins every single-device
size here (measured 0.9x at 8192^2 on CPU). On the mesh the naive path pays
the SUMMA panel broadcasts — MLLib's coGroup shuffle in JAX clothing — and
the dispatcher flips to a Strassen strategy once dims clear the leaf
threshold, exactly the §V-C picture.

Standalone (reliable device forcing — must happen before jax init):

    PYTHONPATH=src python benchmarks/autotune_sweep.py \
        [--sizes 256,2048,8192] [--out autotune_sweep.json] [--measure]

CI smoke mode — small dims on the forced 8-device host mesh, plus a gate:

    PYTHONPATH=src python benchmarks/autotune_sweep.py --smoke

``--smoke`` shrinks sizes/min_dim so the run finishes in minutes, dumps
the decision telemetry alongside the crossover table, and EXITS NON-ZERO
if the chosen kind at the largest smoke dim regresses to naive (or any
selected path fails the correctness check) — the bench-smoke CI job's
pass/fail signal.

Also registered as the ``autotune`` suite in ``benchmarks.run``; when jax
is already initialized with one device the sweep degrades to local-only
candidates and says so in the JSON.
"""
from __future__ import annotations

import os
import sys

if __name__ == "__main__":
    # 8 host-platform devices, forced before any jax import.
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_root, "src"))
    sys.path.insert(0, _root)  # `benchmarks` package when run as a script

import argparse
import json

import jax
import jax.numpy as jnp


def _make_mesh():
    """(data, model) mesh over whatever devices exist; None if single-device."""
    from repro.core.compat import make_mesh

    d = jax.device_count()
    if d < 2:
        return None
    model = 2
    return make_mesh((d // model, model), ("data", "model"))


def sweep(sizes=(256, 2048, 4096), *, min_dim=1024, max_depth=2, measure=False,
          out_path="autotune_sweep.json", calibration=None, oot_budget=None):
    from benchmarks.common import emit, rand, time_fn
    from repro.core import autotune

    mesh = _make_mesh()
    device_count = jax.device_count() if mesh is not None else 1
    calib = calibration or autotune.calibrate()
    autotune.get_telemetry().reset()
    rows = []
    for n in sizes:
        cands = autotune.enumerate_candidates(
            n, n, n, max_depth=max_depth, min_dim=min_dim, mesh=mesh,
            oot_budget=oot_budget,
        )

        def label_of(kind, scheme, depth):
            if kind == "naive":
                return "naive@d0"
            if kind == scheme:  # local BFS candidate
                return f"{kind}@d{depth}"
            return f"{kind}[{scheme}]@d{depth}"  # mesh strategy per scheme

        predictions = {}
        predicted_terms = {}
        for c in cands:
            label = label_of(c.kind, c.scheme, c.depth)
            terms = autotune.predict_cost_terms(
                c, n, n, n, calib, device_count=device_count
            )
            predictions[label] = sum(terms.values())
            # The per-constant split (t_flop/t_elem/t_coll/t_h2d seconds)
            # is the evidence column: for strassen_oot it shows the
            # host<->device staging term next to compute and traffic.
            predicted_terms[label] = {k: round(v, 6) for k, v in terms.items()}
        decision = autotune.autotune(
            n, n, n,
            min_dim=min_dim, max_depth=max_depth, mesh=mesh,
            calibration=calib, measure=measure, oot_budget=oot_budget,
        )

        a, b = rand((n, n)), rand((n, n))
        naive_fn = jax.jit(lambda x, y: jnp.matmul(x, y))
        want = naive_fn(a, b)
        t_naive = time_fn(naive_fn, a, b, warmup=1, iters=2)
        sel = decision.candidate
        if sel.kind == autotune.OOT_KIND:
            # Host-resident pipeline: eager by construction (no jit).
            def sel_fn(x, y):
                return autotune.execute(sel, x, y, oot_budget=oot_budget)
        else:
            sel_fn = jax.jit(lambda x, y: autotune.execute(sel, x, y, mesh=mesh))
        got = sel_fn(a, b)
        t_sel = time_fn(sel_fn, a, b, warmup=1, iters=2)
        scale = float(jnp.max(jnp.abs(want))) or 1.0
        rel_err = float(jnp.max(jnp.abs(got - want))) / scale

        label = label_of(decision.kind, decision.scheme, decision.depth)
        rows.append({
            "n": n,
            "selected": label,
            "source": decision.source,
            "predicted_s": {k: round(v, 6) for k, v in sorted(predictions.items())},
            "predicted_terms": dict(sorted(predicted_terms.items())),
            "predicted_selected_s": decision.predicted_s,
            "measured_selected_s": t_sel,
            "measured_naive_s": t_naive,
            "rel_err_vs_naive": rel_err,
            "ok": rel_err < 2e-3,
        })
        emit(f"autotune[{n}]->{label}", t_sel,
             f"naive={t_naive*1e6:.1f}us err={rel_err:.2e}")

    payload = {
        "device_kind": calib.device_kind,
        "device_count": device_count,
        "mesh": dict(mesh.shape) if mesh is not None else None,
        "calibration": calib.to_dict(),
        "calibration_source": "pinned" if calibration else "measured",
        "min_dim": min_dim,
        "max_depth": max_depth,
        "oot_budget": oot_budget,
        "rows": rows,
        # Decision telemetry for the run: cache hit/miss counters, chosen
        # kind per resolution, predicted-vs-measured seconds per decision.
        "telemetry": autotune.get_telemetry().snapshot(),
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"# wrote {out_path}", flush=True)
    return payload


def run():
    """benchmarks.run entry point (uses whatever devices jax already has)."""
    sweep()


# Smoke-mode defaults: small enough for a CPU CI runner, large enough that
# the largest dim clears min_dim at depth >= 1 and the mesh strategies can
# out-predict the naive SUMMA term. min_dim sits between the first two
# sizes so the table shows the §V-C flip: 128 -> naive, 256+ -> Strassen.
SMOKE_SIZES = (128, 256, 512)
SMOKE_MIN_DIM = 192


def smoke_calibration():
    """Pinned constants for the CI gate: the pass/fail signal must depend on
    the code's candidate set and cost model, not on whatever t_flop/t_elem
    ratio a loaded shared runner happens to measure at job time. The ratios
    mirror a typical CPU-host fit (elem ~100x flop, coll ~4x elem)."""
    from repro.core import autotune

    dev = jax.devices()[0]
    return autotune.Calibration(
        t_flop=1e-11,
        t_elem=1e-9,
        t_coll=4e-9,
        t_h2d=2e-9,
        device_kind=dev.device_kind,
        device_count=jax.device_count(),
    )


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    # 4096 is the largest default: above it the interpret-mode Pallas leaf
    # (CPU hosts) unrolls thousands of grid steps at trace time and the
    # measured column takes longer than the information is worth. On a real
    # TPU (compiled leaf) pass --sizes 256,2048,8192,16384 to reproduce the
    # paper-scale crossover table.
    ap.add_argument("--sizes", default="256,2048,4096")
    ap.add_argument("--min-dim", type=int, default=1024)
    ap.add_argument("--max-depth", type=int, default=2)
    ap.add_argument("--measure", action="store_true",
                    help="time top-k candidates instead of trusting the model")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: small dims, and fail if the largest dim "
                         "selects naive or any correctness check fails")
    ap.add_argument("--oot-budget-mb", type=float, default=0.0,
                    help="device-memory budget enabling the strassen_oot "
                         "out-of-core candidate family (0 = off); its "
                         "predicted t_h2d term lands in predicted_terms")
    ap.add_argument("--out", default="autotune_sweep.json")
    args = ap.parse_args()
    calibration = None
    oot_budget = int(args.oot_budget_mb * 2**20) or None
    if args.smoke:
        sizes, min_dim = SMOKE_SIZES, SMOKE_MIN_DIM
        calibration = smoke_calibration()
        # Budget the oot family into the smoke table too, so the t_h2d
        # column is exercised on every CI run. 8 MiB: large enough that
        # the dense working set fits at every smoke size (3*512^2*4 =
        # 3 MiB), so oot rows appear as *candidates* without the
        # infeasibility filter hijacking the mesh-crossover story the
        # naive-regression gate asserts.
        oot_budget = oot_budget or (8 << 20)
    else:
        sizes = tuple(int(s) for s in args.sizes.split(","))
        min_dim = args.min_dim
    payload = sweep(
        sizes, min_dim=min_dim, max_depth=args.max_depth,
        measure=args.measure, out_path=args.out, calibration=calibration,
        oot_budget=oot_budget,
    )
    for row in payload["rows"]:
        print(f"# n={row['n']:6d} -> {row['selected']:24s} "
              f"pred {row['predicted_selected_s']:.4f}s "
              f"meas {row['measured_selected_s']:.4f}s "
              f"naive {row['measured_naive_s']:.4f}s ok={row['ok']}")
    if args.smoke:
        top = payload["rows"][-1]
        if not all(r["ok"] for r in payload["rows"]):
            print("# SMOKE FAIL: a selected path failed its correctness check")
            sys.exit(1)
        if top["selected"].startswith("naive"):
            print(f"# SMOKE FAIL: n={top['n']} regressed to naive; "
                  f"predicted table: {top['predicted_s']}")
            sys.exit(1)
        print(f"# smoke ok: n={top['n']} -> {top['selected']}")


if __name__ == "__main__":
    main()
