#!/usr/bin/env python3
"""Measure a cell's run-to-run spread: two sets of runs on the same seeds.

    python3 bench/spread.py --workload <name> --seconds <s> --first-seed <n> \
        [--runs 6] [--sets 2] [--traced 3] [--out <file.jsonl>]

Runs ``bench/run.py`` once per process (this parent never touches JAX, so
each child has the chips to itself): ``--sets`` sets of ``--runs`` runs,
every set on the same seeds, then ``--traced`` runs with ``--trace 1`` on
further seeds. Prints, per set and metric, the median and the spread: the
distance between the first and third quartiles (``statistics.quantiles``)
as a share of the median. A bound is set from the widest spread.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def run_once(workload: str, seed: int, seconds: float, trace: int) -> dict:
    cmd = [sys.executable, os.path.join("bench", "run.py"), "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True, timeout=1300)
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    if p.returncode != 0 or not lines:
        return {"rc": p.returncode, "seed": seed, "trace": trace, "stderr": p.stderr[-2000:]}
    return dict(json.loads(lines[-1]), rc=0, seed=seed, trace=trace)


def spread(values):
    med = statistics.median(values)
    q = statistics.quantiles(values, n=4)
    return med, (q[2] - q[0]) / med


def trimmed(values):
    """The values without the one farthest from their median."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return values[:far] + values[far + 1:]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    seeds = [args.first_seed + i for i in range(args.runs)]
    plan = [(s, seed, 0) for s in range(args.sets) for seed in seeds]
    plan += [("traced", args.first_seed + args.runs + i, 1) for i in range(args.traced)]
    out = None
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        out = open(args.out, "w")
    results = []
    for s, seed, trace in plan:
        r = run_once(args.workload, seed, args.seconds, trace)
        r["set"] = s
        results.append(r)
        if out:  # each run as it ends, so a cut call keeps what it measured
            out.write(json.dumps(r) + "\n")
            out.flush()
        keys = ("set", "seed", "rc", "correct", "metrics", "device", "breakdown", "checks",
                "stderr")
        print(json.dumps({k: r.get(k) for k in keys}), flush=True)
    if out:
        out.close()
    for s in range(args.sets):
        runs = [r for r in results if r["set"] == s and r["rc"] == 0]
        # The metrics, then the parts of set-up and the program's phases
        # (which part of a run varies from one process to the next).
        named = [(name, lambda r, n=name: r["metrics"].get(n, {}).get("value"))
                 for name in sorted({k for r in runs for k in r["metrics"]})]
        for group in ("setup_parts_s", "program_phases_s"):
            named += [(f"{group}.{name}", lambda r, g=group, n=name: r.get(g, {}).get(n))
                      for name in sorted({k for r in runs for k in r.get(group, {})})]
        for name, get in named:
            vals = [v for v in map(get, runs) if v is not None]
            if len(vals) >= 3 and statistics.median(vals) > 0:
                med, sp = spread(vals)
                _, sp_t = spread(trimmed(vals))
                print(f"set {s} {name}: median {med!r} spread {sp:.4%} "
                      f"(farthest run left out: {sp_t:.4%}) over {len(vals)} runs")
    bad = [r for r in results if r["rc"] != 0 or not r.get("correct")]
    print(f"runs {len(results)}, failed or incorrect {len(bad)}")
    return 0 if not bad else 1


if __name__ == "__main__":
    sys.exit(main())
