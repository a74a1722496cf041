"""Roofline extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh) cell, per the v5e hardware model:

    compute    = HLO_FLOPs            / (chips * 197e12 FLOP/s)
    memory     = HLO_bytes_accessed   / (chips * 819e9  B/s)
    collective = collective_bytes     / (chips * 50e9   B/s per ICI link)

HLO_FLOPs / bytes come from compiled.cost_analysis(). XLA:CPU reports
cost analysis for the PER-DEVICE partitioned module, so global = value *
chips; we record both and state the convention in EXPERIMENTS.md.

collective_bytes is not in cost_analysis: we parse the post-SPMD HLO text
and sum OPERAND sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute instruction.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict

__all__ = [
    "HW",
    "Hardware",
    "collective_bytes",
    "roofline_terms",
    "model_flops",
]


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str = "tpu-v5e"
    peak_flops: float = 197e12  # bf16 per chip
    hbm_bw: float = 819e9  # bytes/s per chip
    ici_bw: float = 50e9  # bytes/s per link


HW = Hardware()

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# shape token like bf16[256,1024] (layout braces optional)
_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]+(?:e[0-9]+m[0-9]+(?:fn)?)?|pred)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes per collective kind over the HLO module text."""
    totals: Dict[str, int] = {k: 0 for k in _COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if "=" not in stripped:
            continue
        # find which collective op this line APPLIES (rhs op name), e.g.
        # %ag = bf16[8,128] all-gather(bf16[1,128] %x), dims=...
        rhs = stripped.split("=", 1)[1]
        op = None
        for kind in _COLLECTIVE_OPS:
            # match "<shapes> kind(" — op name directly before its args
            if re.search(rf"\]\S*\s+{kind}(-start)?\(", rhs) or rhs.lstrip().startswith(
                kind
            ):
                op = kind
                break
        if op is None:
            continue
        # operand shapes are the shape tokens INSIDE the call parens
        call = rhs[rhs.index("(") + 1 :]
        depth = 1
        args = []
        for ch in call:
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
            args.append(ch)
        arg_str = "".join(args)
        for dtype, dims in _SHAPE_RE.findall(arg_str):
            totals[op] += _shape_bytes(dtype, dims)
    totals["total"] = sum(totals[k] for k in _COLLECTIVE_OPS)
    return totals


def model_flops(param_count: int, active_param_count: int, tokens: int, kind: str) -> float:
    """MODEL_FLOPS = 6*N*D (train) / 2*N*D (inference fwd), N = active params."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * active_param_count * tokens


def roofline_terms(
    *,
    hlo_flops: float,
    hlo_bytes: float,
    coll_bytes: float,
    chips: int,
    per_device: bool,
    hw: Hardware = HW,
) -> Dict[str, float]:
    """Seconds for each roofline term. per_device: cost_analysis convention."""
    scale = 1.0 if per_device else 1.0 / chips
    t_compute = hlo_flops * scale / hw.peak_flops
    t_memory = hlo_bytes * scale / hw.hbm_bw
    t_collective = coll_bytes * scale / hw.ici_bw
    terms = {
        "compute_s": t_compute,
        "memory_s": t_memory,
        "collective_s": t_collective,
    }
    terms["bottleneck"] = max(terms, key=lambda k: terms[k])[: -2]
    terms["bound_s"] = max(t_compute, t_memory, t_collective)
    return terms
