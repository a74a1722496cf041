#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for. Otherwise the last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device`` and, traced, ``breakdown``; its last key ``checks`` gives each
number compared with the reference beside its limit, and the same lines
end standard error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_START = time.perf_counter()
_HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(_HERE)
if sys.path and os.path.abspath(sys.path[0]) == _HERE:
    sys.path.pop(0)  # keep bench/trace.py from shadowing the standard library
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="directory to keep the profiler trace in (traced runs)")
    args = ap.parse_args(argv)

    from bench import harness

    try:
        process_start = time.perf_counter() - harness.process_age_s()
    except (OSError, ValueError, IndexError):
        process_start = _START
    cell = harness.resolve(harness.load_spec(), args.workload)
    try:
        result = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace),
            process_start=process_start, keep_trace=args.keep_trace,
        )
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(f"correct {result['correct']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(harness.finite(result), allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
