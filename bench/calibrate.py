#!/usr/bin/env python3
"""Read the correctness numbers of a cell's program and of its controls.

    python3 bench/calibrate.py --workload <name> --seeds 12 --control-seeds 3 \
        --first-seed <n> [--out <file.json>]

In one process, on the chips the cell asks for: for each seed, the cell's
entry makes its mix's operand pairs and computes one product of each
through the timed program at the cell's size, and the reference compares
them (the lower readings of each limit). Then each control takes the
program's place on further seeds (the upper readings): the reference with
float8 operands, the reference with bfloat16 accumulation, and, for an
in-core entry, the program itself with its accumulation dtype made the
input dtype. The benchmark's own runs never run a control. Limits in
``limits/<workload>.json`` are set from these readings.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(_HERE)
if sys.path and os.path.abspath(sys.path[0]) == _HERE:
    sys.path.pop(0)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


@contextlib.contextmanager
def program_bf16_accumulation():
    """The in-core program with its leaf products and combine sums held in
    the input dtype (bf16) instead of float32."""
    import jax
    import jax.numpy as jnp

    from repro.core import strassen
    from repro.kernels.strassen import ops

    saved = strassen.accumulation_dtype, ops.accumulation_dtype
    same = lambda dtype: jnp.dtype(dtype)  # noqa: E731
    strassen.accumulation_dtype = ops.accumulation_dtype = same
    jax.clear_caches()
    try:
        yield
    finally:
        strassen.accumulation_dtype, ops.accumulation_dtype = saved
        jax.clear_caches()


def _read(cell, entry, produce, log, label, seed):
    """The numbers of one product per operand pair, worst per number."""
    from bench import reference

    row = {"seed": seed}
    for p, kind in enumerate(cell.traffic["operand_pairs"]):
        a, b = entry.reference_operands(p)
        t0 = time.perf_counter()
        c = produce(p, a, b)
        dt = time.perf_counter() - t0
        nums = reference.numbers(kind, a, b, c)
        del c
        for k, v in nums.items():
            row[k] = v if k not in row else max(row[k], v)
        row.setdefault("product_s", []).append(dt)
    log(f"{label} seed={seed} " + " ".join(f"{k}={row[k]!r}" for k in row if k != "seed"))
    return row


def readings(cell, seeds, control_seeds, require_tpu=True, log=print):
    """{reading: [row per seed]} for the program and each control."""
    from bench import harness, reference

    devices = harness.pick_devices(cell.chips, require_tpu)
    harness.enable_compile_cache()
    route = cell.traffic["route"]
    out = {}

    def program(p, a, b):
        return entry.product(p)

    def reseed(seed):
        entry.pairs = None
        entry.make_operands(seed, cell.traffic["operand_pairs"],
                            int(cell.traffic.get("integer_max", 8)))

    entry = harness.make_entry(cell, seeds[0], devices)
    entry.prepare()
    for seed in seeds:
        reseed(seed)
        out.setdefault("program", []).append(_read(cell, entry, program, log, "program", seed))
    entry.release()

    controls = {
        "control_float8": lambda p, a, b: reference.control_product(a, b, cell.config["dtype"]),
        "control_bf16_accumulation": lambda p, a, b: reference.control_bf16_accumulation(
            a, b, 2 ** route["depth"]),
    }
    for name, produce in controls.items():
        for seed in control_seeds:
            reseed(seed)
            out.setdefault(name, []).append(_read(cell, entry, produce, log, name, seed))
    if cell.config["entry"] == "incore":
        with program_bf16_accumulation():
            entry.prepare()
            for seed in control_seeds:
                reseed(seed)
                out.setdefault("program_bf16_accumulation", []).append(
                    _read(cell, entry, program, log, "program_bf16_accumulation", seed))
            entry.release()
    for key in ("rel_fro", "int_mismatches"):
        lower = max(r[key] for r in out["program"])
        uppers = {name: min(r[key] for r in rows) for name, rows in out.items()
                  if name != "program"}
        ratios = {n: (u / lower if lower else math.inf) for n, u in uppers.items()}
        log(f"{key}: lower {lower!r} upper {uppers} ratio {ratios}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.resolve(harness.load_spec(), args.workload)
    s0 = args.first_seed
    seeds = list(range(s0, s0 + args.seeds))
    control = list(range(s0 + args.seeds, s0 + args.seeds + args.control_seeds))
    try:
        out = readings(cell, seeds, control, log=lambda m: print(m, flush=True))
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    out["workload"] = args.workload
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
