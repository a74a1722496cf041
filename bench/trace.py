"""Reduce a JAX profiler trace (``.xplane.pb``) to per-device op time.

The run marks its traced window with a host annotation ``bench.window`` and
each product with ``bench.product`` (``jax.profiler.TraceAnnotation``); the
profiler puts host and device events on one clock. From the trace this
module takes, for each TPU device plane's ``XLA Ops`` line:

* busy time: the union of the op intervals inside the window;
* each op's summed duration, under its HLO instruction name and opcode;
* the same time by class: the Pallas kernel (``custom-call`` to
  ``tpu_custom_call``), XLA matrix products (``dot``, ``convolution`` and
  ``kOutput`` fusions, the form XLA gives a dot with its epilogue),
  collectives (``all-gather``, ``all-reduce``, ... with their ``-start``
  and ``-done`` halves) and everything else (copies, reshapes, elementwise
  fusions: the divide and combine passes).

On a TPU an op's event name is its HLO text, e.g.
``%fusion.2 = f32[49,4096,4096]{...} fusion(...), kind=kOutput, calls=...``.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
PRODUCT = "bench.product"
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_HOST_PLANE = "/host:CPU"

LEAF_KERNEL = "leaf_kernel"
MATMUL = "matmul"
COLLECTIVE = "collective"
OTHER = "other"

COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
    "collective-broadcast",
    "ragged-all-to-all",
)

_INSTR = re.compile(r"%?([\w.\-]+) = ")
_OPCODE = re.compile(r"\s*([\w\-]+)\(")

Interval = Tuple[float, float]


def parse_hlo_op(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an ``XLA Ops`` event name.

    A name that is not HLO text comes back as (text, text).
    """
    m = _INSTR.match(text)
    if not m:
        return text, text
    rest = text[m.end():]
    if rest.startswith("("):  # tuple-shaped result: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1 :]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    op = _OPCODE.match(rest)
    return m.group(1), (op.group(1) if op else m.group(1))


def classify(text: str) -> str:
    name, opcode = parse_hlo_op(text)
    if opcode == "custom-call" and 'custom_call_target="tpu_custom_call"' in text:
        return LEAF_KERNEL
    if any(opcode.startswith(c) or name.startswith(c) for c in COLLECTIVE_OPS):
        return COLLECTIVE
    if opcode in ("dot", "convolution") or (opcode == "fusion" and "kind=kOutput" in text):
        return MATMUL
    return OTHER


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals (sorted, disjoint result)."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv: Sequence[Interval], t0: float, t1: float) -> List[Interval]:
    return [(max(s, t0), min(e, t1)) for s, e in iv if e > t0 and s < t1]


@dataclasses.dataclass
class DeviceTime:
    plane: str
    busy_ns: float
    busy: List[Interval]
    by_op: Dict[str, float]
    by_class: Dict[str, float]


@dataclasses.dataclass
class TraceSummary:
    window: Interval
    products: List[Interval]
    devices: List[DeviceTime]
    host_events: List[Tuple[str, float, float]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds inside the window, averaged over the devices."""
        return sum(d.busy_ns for d in self.devices) / len(self.devices) * 1e-9

    def idle_share(self) -> float:
        """1 - busy / window for each device, averaged over the devices."""
        w = self.window[1] - self.window[0]
        return sum(1.0 - d.busy_ns / w for d in self.devices) / len(self.devices)

    def class_seconds(self, cls: str) -> List[float]:
        """Seconds of one op class inside the window, per device."""
        return [d.by_class.get(cls, 0.0) * 1e-9 for d in self.devices]

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """The ``n`` ops that took most device time (seconds, mean over devices)."""
        total: Dict[str, float] = {}
        for d in self.devices:
            for k, v in d.by_op.items():
                total[k] = total.get(k, 0.0) + v
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [(k, v * 1e-9 / len(self.devices)) for k, v in ranked]

    def idle_gaps(
        self, n: int = 10,
        labeler: Optional[Callable[[float, float], Dict[str, float]]] = None,
    ) -> List[Tuple[str, float]]:
        """Idle time of device 0 inside the window, summed by what the host did.

        ``labeler(t0, t1)``, where given, splits a gap among named phases
        (ns per name); the rest of the gap is named by the host event that
        overlaps it most (``bench.*`` marks excluded), else "host".
        """
        t0, t1 = self.window
        gaps, cur = [], t0
        for s, e in self.devices[0].busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < t1:
            gaps.append((cur, t1))
        by_label: Dict[str, float] = {}
        for g0, g1 in gaps:
            parts = labeler(g0, g1) if labeler else {}
            for k, v in parts.items():
                by_label[k] = by_label.get(k, 0.0) + v
            rest = (g1 - g0) - sum(parts.values())
            if rest <= 0:
                continue
            best, label = 0.0, "host"
            for name, h0, h1 in self.host_events:
                ov = min(g1, h1) - max(g0, h0)
                if ov > best:
                    best, label = ov, name
            by_label[label] = by_label.get(label, 0.0) + rest
        ranked = sorted(by_label.items(), key=lambda kv: -kv[1])[:n]
        return [(k, v * 1e-9) for k, v in ranked]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns)


def reduce_profile(profile) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`TraceSummary`."""
    planes = list(profile.planes)
    windows, products, host_events = [], [], []
    for plane in planes:
        if plane.name != _HOST_PLANE:
            continue
        for line in plane.lines:
            for name, s, d in _events(line):
                if name == WINDOW:
                    windows.append((s, s + d))
                elif name == PRODUCT:
                    products.append((s, s + d))
                elif d > 0 and not name.startswith("bench."):
                    host_events.append((name, s, s + d))
    devices = []
    ops_lines = []
    for plane in planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops_lines.append((plane.name, list(_events(line))))
    if not ops_lines:
        raise ValueError("trace has no TPU device plane with an 'XLA Ops' line")
    if windows:
        window = min(windows)
    else:
        starts = [s for _, evs in ops_lines for _, s, _ in evs]
        ends = [s + d for _, evs in ops_lines for _, s, d in evs]
        window = (min(starts), max(ends))
    t0, t1 = window
    for plane_name, evs in sorted(ops_lines):
        inside = [(n, s, d) for n, s, d in evs if s + d > t0 and s < t1]
        busy = union(_clip([(s, s + d) for _, s, d in inside], t0, t1))
        by_op: Dict[str, float] = {}
        by_class: Dict[str, float] = {}
        for text, s, d in inside:
            d = min(s + d, t1) - max(s, t0)
            name, opcode = parse_hlo_op(text)
            key = f"{name} {opcode}" if opcode != name else name
            by_op[key] = by_op.get(key, 0.0) + d
            cls = classify(text)
            by_class[cls] = by_class.get(cls, 0.0) + d
        devices.append(
            DeviceTime(
                plane=plane_name,
                busy_ns=sum(e - s for s, e in busy),
                busy=busy,
                by_op=by_op,
                by_class=by_class,
            )
        )
    products = sorted(p for p in products if p[0] >= t0 and p[1] <= t1)
    return TraceSummary(window=window, products=products, devices=devices, host_events=host_events)


def load(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))
