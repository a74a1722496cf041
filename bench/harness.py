"""One run of one cell: set-up, the measured window, metrics, correctness.

Everything is found by name from ``BENCHMARK.json``: the cell names its
configuration and traffic mix, the configuration names its entry, and every
metric is read by ``metrics/<name>.py``. Adding a configuration, a mix or a
metric adds files and edits none.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    limits: dict


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(spec: dict, workload: str, root: str = ROOT) -> Cell:
    """The cell's configuration, mix, metrics and limits, found by name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    bench = os.path.join(root, "bench")
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=_load_json(os.path.join(root, configs[w["config"]]["file"])),
        traffic=_load_json(os.path.join(bench, "traffic", w["traffic"] + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
        limits=_load_json(os.path.join(bench, "limits", workload + ".json")),
    )


def _load_file(kind: str, name: str, root: str = ROOT):
    path = os.path.join(root, "bench", kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: str = ROOT) -> Callable:
    return _load_file("metrics", name, root).read


def entry_class(name: str, root: str = ROOT):
    return _load_file("entries", name, root).Entry


def process_age_s() -> float:
    """Seconds since this process started, from the kernel's process table."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, holding
    every program, so only a checkout's first run of a cell compiles. It
    takes the place of any ``JAX_COMPILATION_CACHE_DIR``: two checkouts
    never share a cache."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return CACHE_DIR


def pick_devices(chips: int, require_tpu: bool = True):
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(
            f"this cell needs {chips} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform} device(s)"
        )
    if len(devices) < chips:
        raise NoChip(f"this cell needs {chips} devices; found {len(devices)}")
    return devices[:chips]


class Compiles:
    """Counts XLA compilations while active (none may fall in the window)."""

    def __init__(self):
        self.count = 0
        self.active = False
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


@dataclasses.dataclass
class Reading:
    """What a metric reader gets: the window, the program's stats, the trace."""

    cell: Cell
    setup_s: float
    latencies: List[float]
    window_s: float
    stats: List[dict]
    trace: Optional[object]
    device_kind: str
    devices: int

    @property
    def count(self) -> int:
        return len(self.latencies)

    @property
    def traced_products(self) -> int:
        return len(self.trace.products) if self.trace is not None else 0


def _memory_peak(devices) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices]
    return max(peaks)


def _worst(values: List[float]) -> float:
    """The worst of a number's readings; NaN where there are none or one is NaN."""
    if not values or any(math.isnan(v) for v in values):
        return math.nan
    return max(values)


def check_outputs(limits: dict, kept, entry, kinds):
    """Compare each kept output with the reference.

    Each output is compared by the numbers of its operand pair's kind
    (``reference.numbers``). Returns the worst reading of each limited
    number beside its limit, and how many outputs failed a limit. A limited
    number that no output gave reads NaN, which no limit passes.
    """
    from bench import reference

    readings = {k: [] for k in limits}
    failed = 0
    for _i, pair, out in kept:
        a, b = entry.reference_operands(pair)
        nums = reference.numbers(kinds[pair], a, b, out)
        nums = {k: v for k, v in nums.items() if k in limits}
        if not all(v <= limits[k]["limit"] for k, v in nums.items()):
            failed += 1
        for k, v in nums.items():
            readings[k].append(v)
    checks = {k: {"value": _worst(readings[k]), "limit": limits[k]["limit"]} for k in limits}
    return checks, failed


def make_entry(cell: Cell, seed: int, devices):
    """The cell's entry with its mix's operand pairs made from ``seed``."""
    entry = entry_class(cell.config["entry"])(cell.config, cell.traffic["route"], devices)
    entry.make_operands(seed, cell.traffic["operand_pairs"],
                        int(cell.traffic.get("integer_max", 8)))
    return entry


def _means(stats: List[dict]) -> dict:
    """Per-product means of the program's timed phases (keys ending in _s)."""
    keys = [k for k, v in (stats[0] if stats else {}).items()
            if k.endswith("_s") and isinstance(v, (int, float))]
    return {k: sum(s[k] for s in stats) / len(stats) for k in keys}


def run_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    process_start: float,
    require_tpu: bool = True,
    keep_trace: Optional[str] = None,
    log=lambda msg: print(msg, file=sys.stderr, flush=True),
) -> dict:
    """Run one cell once; returns the result object (without printing it)."""
    import jax

    import repro  # noqa: F401  (the system under test must be importable)

    from bench import loop

    devices = pick_devices(cell.chips, require_tpu)
    cache = enable_compile_cache()
    log(f"bench: {cell.name} seed={seed} seconds={seconds} trace={int(trace)} "
        f"devices={len(devices)} x {devices[0].device_kind} cache={cache}")
    compiles = Compiles()
    parts = {"start": time.perf_counter() - process_start}
    entry = make_entry(cell, seed, devices)
    parts["operands"] = time.perf_counter() - process_start - sum(parts.values())
    entry.prepare()
    parts["prepare"] = time.perf_counter() - process_start - sum(parts.values())

    tracedir = None
    around = None
    if trace:
        tracedir = keep_trace or tempfile.mkdtemp(prefix="bench_trace_")
        _start_trace(tracedir)
        around = lambda: jax.profiler.TraceAnnotation("bench.product")  # noqa: E731
    window_ctx = jax.profiler.TraceAnnotation("bench.window") if trace else contextlib.nullcontext()
    setup_s = time.perf_counter() - process_start
    compiles.active = True
    with window_ctx:
        result = loop.run(cell.traffic, entry.product, seconds, seed, around)
    compiles.active = False
    summary = None
    if trace:
        jax.profiler.stop_trace()
    memory_peak = _memory_peak(entry.devices)
    entry.release()
    if trace:
        from bench import trace as btrace

        found = [os.path.join(d, f) for d, _, fs in os.walk(tracedir) for f in fs
                 if f.endswith(".xplane.pb")]
        summary = btrace.load(found[0])
        if keep_trace is None:
            shutil.rmtree(tracedir, ignore_errors=True)

    checks, failed = check_outputs(cell.limits, result.kept, entry,
                                   cell.traffic["operand_pairs"])
    correct = (bool(result.kept) and bool(checks) and failed == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))

    reading = Reading(
        cell=cell, setup_s=setup_s, latencies=result.latencies, window_s=result.window_s,
        stats=list(entry.stats), trace=summary, device_kind=devices[0].device_kind,
        devices=len(devices),
    )
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = metric_reader(m["name"])(reading)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    out = {
        "correct": correct,
        "attempted": result.count,
        "failed": failed,
        "metrics": metrics,
        "device": device,
        "compiles_in_window": compiles.count,
        "compared": len(result.kept),
        "setup_parts_s": parts,
    }
    if reading.stats:
        out["program_phases_s"] = _means(reading.stats)
    if summary is not None:
        device["busy_s"] = summary.busy_s()
        device["window_s"] = summary.window_s
        labeler = entry.phase_labeler(summary) if hasattr(entry, "phase_labeler") else None
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary.top_ops(10)],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps(10, labeler)],
        }
    out["checks"] = checks
    return out


def finite(obj):
    """``obj`` with every NaN or infinite number made None (valid JSON)."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    if isinstance(obj, dict):
        return {k: finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [finite(v) for v in obj]
    return obj


def _start_trace(tracedir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tracedir, profiler_options=opts)
