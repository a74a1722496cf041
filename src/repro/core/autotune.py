"""Shape-aware autotuning dispatcher behind ``MatmulBackend(kind="auto")``.

The paper's core empirical result (§V-C) is a *crossover*: Strassen's
7-multiplication scheme only beats the naive path once matrix dims are
large relative to the leaf block, and the §IV stage-wise model predicts
where. This module is the JAX analogue of that calibration + prediction
loop, turned into a dispatcher:

1. :func:`calibrate` runs two on-device micro-benchmarks — a leaf batched
   matmul and a divide-level einsum, mirroring the paper's implicit
   block-matmul / block-add calibration — and fits the environment
   constants ``t_flop`` (seconds per scalar multiply-add) and ``t_elem``
   (seconds per element through a divide/combine level).

2. :func:`enumerate_candidates` lists every kind that can legally run a
   given (M, K, N) on one device: the naive XLA matmul, batched-BFS
   Strassen/Winograd and the Pallas fused-leaf pipeline at each usable
   depth, and — under a device-memory budget — the out-of-core family.

3. :func:`predict_seconds` costs each candidate with the calibrated
   stage model (divide/combine element traffic * t_elem + leaf flops *
   t_flop); :func:`autotune` picks the argmin, or with ``measure=True``
   times the top-k candidates on device and records the measured winner.
   A measurement times ``backend.matmul`` on the backend
   :func:`repro.core.backend.route_for` makes of the candidate — the
   route the decision would take, nothing else.

4. :class:`TuningCache` persists decisions as JSON keyed by
   (shape, dtype, device kind+count, scheme set, min_dim, max_depth, and
   optionally a call-site tag), so jit-traced call sites resolve
   statically from the cache on reuse — no re-calibration, no
   re-measurement. Call-site tags let same-shape projections (e.g. a QKV
   and an MLP projection of equal width) diverge under measured mode.

Three constants, two regimes: ``t_flop``/``t_elem`` come from
intra-device micro-benchmarks; ``t_h2d`` is fit by :func:`calibrate_h2d`
(a device_put + fetch round trip) and prices the *host<->device staging*
traffic of the out-of-core ``strassen_oot`` family (:mod:`repro.blocks`),
whose candidates enumerate when the caller passes a device-memory budget.
Every resolution is logged to the process :class:`Telemetry` (cache
hit/miss, chosen kind, predicted-vs-measured seconds), which the serving
engine exposes in its stats. Real-TPU measured-mode calibration remains a
ROADMAP follow-on.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import tempfile
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.coefficients import get_scheme
from repro.core.strassen import divide_level
from repro.obs import metrics as obs_metrics
from repro.obs import tracer as obs_tracer

__all__ = [
    "Candidate",
    "Decision",
    "Calibration",
    "TuningCache",
    "Telemetry",
    "TelemetryEvent",
    "calibrate",
    "calibrate_h2d",
    "get_calibration",
    "calibration_snapshot",
    "get_telemetry",
    "reset_telemetry",
    "enumerate_candidates",
    "predict_seconds",
    "predict_cost_terms",
    "measure_seconds",
    "autotune",
    "autotune_solver",
    "predict_solver_terms",
    "predict_solver_seconds",
    "cache_key",
    "model_call_sites",
    "warm_for_model",
]

# The einsum pipelines' schemes, each also the kind that runs it.
LOCAL_SCHEMES: Tuple[str, ...] = ("strassen", "winograd")
# The Pallas fused-leaf pipeline: local, but gated on the leaf running
# (compat.pallas_leaf_mode) rather than always-legal like the einsum BFS.
FUSED_KIND = "strassen_fused"
# The out-of-core tagged-block pipeline (repro.blocks): host-resident
# operands staged through device memory in budgeted waves. Enumerated only
# when the caller supplies a device-memory budget (``oot_budget``).
OOT_KIND = "strassen_oot"

# Fraction of the overlappable h2d traffic the async wave pipeline still
# exposes: the pipeline fill (first wave's stage has nothing to hide
# behind) and drain (last fetch) bubbles, roughly one wave each way out of
# the ~8 the scheduler needs before fill/drain amortizes. Used by
# predict_cost_terms when ``oot_overlap`` is on.
OOT_OVERLAP_EXPOSED_FRACTION = 0.125


def _oot_pipeline_fits(
    m: int, k: int, n: int, depth: int, dtype, oot_budget: Optional[int]
) -> bool:
    """Whether the oot scheduler can actually run its async pipeline.

    The 2-deep wave pipeline needs one pipelined wave slot
    (:func:`repro.blocks.scheduler.pipelined_leaf_bytes`) inside the
    budget at this depth; with less room the scheduler silently degrades
    to synchronous staging, so predictions must not take the overlap
    discount. A ``None``/0 budget means the route defaults the budget to
    exactly one pipelined slot, so the pipeline runs.
    """
    if not oot_budget:
        return True
    from repro.blocks.scheduler import pipelined_leaf_bytes

    return pipelined_leaf_bytes(m, k, n, depth, dtype) <= oot_budget


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One executable strategy instance for a fixed (M, K, N)."""

    kind: str  # a routing kind of repro.core.backend (never 'auto')
    scheme: str = "strassen"
    depth: int = 0

    @property
    def is_naive(self) -> bool:
        return self.kind == "naive"


@dataclasses.dataclass(frozen=True)
class Decision:
    """A routing decision plus the evidence it was made on."""

    kind: str
    scheme: str
    depth: int
    predicted_s: float
    measured_s: Optional[float] = None
    source: str = "predicted"  # predicted | measured | cache

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: Dict) -> "Decision":
        return Decision(**d)


@dataclasses.dataclass(frozen=True)
class Calibration:
    """Per-environment constants, the JAX analogue of the paper's §IV fit."""

    t_flop: float  # seconds per scalar multiply-add in the leaf matmul
    t_elem: float  # seconds per element through a divide/combine einsum
    device_kind: str = "cpu"
    device_count: int = 1
    # seconds per element through host<->device staging (device_put + fetch
    # round trip) — prices the out-of-core pipeline's leaf-wave traffic.
    # 0.0 means "not calibrated" (pre-t_h2d cache); falls back to t_elem.
    t_h2d: float = 0.0

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: Dict) -> "Calibration":
        # Caches written while the autotuner priced mesh strategies carry
        # their interconnect constant, t_coll; nothing prices it now.
        return Calibration(**{k: v for k, v in d.items() if k != "t_coll"})


def _time_best(fn, repeats: int = 3) -> float:
    """Best-of-N wall clock for a blocking thunk (compile excluded by warmup)."""
    fn()  # warmup / compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def calibrate_h2d(sample_dim: int = 1024, repeats: int = 3) -> float:
    """Fit ``t_h2d`` from a host->device + device->host staging round trip.

    One ``jax.device_put`` of a host f32 array plus one ``np.asarray``
    fetch — exactly the per-leaf traffic of the out-of-core scheduler's
    staging waves (operands up, product down). The fit is seconds per
    element through the host<->device boundary, the PCIe/ICI analogue of
    ``t_elem``. On hosts where the "device" is host RAM (CPU jax) this is
    close to a memcpy — correctly tiny, so the model only penalizes
    staging where staging actually costs.
    """
    import numpy as np

    x = np.ones((sample_dim, sample_dim), np.float32)
    # A jitted identity, not a bare device_put: calibration can trigger at
    # jit-trace time (resolve_auto runs while a train step traces), and
    # device_put binds under the ambient trace — a jit call with concrete
    # args escapes it, like the other micro-benchmarks.
    identity = jax.jit(lambda v: v)

    def roundtrip():
        dev = identity(x)
        jax.block_until_ready(dev)
        np.asarray(dev)

    t = _time_best(roundtrip, repeats)
    # One pass up, one pass down.
    return t / (2.0 * sample_dim * sample_dim)


def calibrate(sample_dim: int = 256, repeats: int = 3) -> Calibration:
    """Fit (t_flop, t_elem, t_h2d) from on-device micro-benchmarks.

    Leaf benchmark: a rank-7 batched matmul — exactly the shape of the BFS
    leaf stage. Divide benchmark: one :func:`divide_level` einsum — exactly
    the divide/combine stage. Both mirror the paper's implicit calibration
    (it plots theory and experiment in matching units). The staging
    benchmark (:func:`calibrate_h2d`) fits the out-of-core family's
    constant.
    """
    d = sample_dim
    scheme = get_scheme("strassen")
    rank = scheme.n_mults
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (rank, d, d), jnp.float32)
    b = jax.random.normal(key, (rank, d, d), jnp.float32)

    leaf = jax.jit(lambda x, y: jnp.einsum("mij,mjk->mik", x, y))
    t_leaf = _time_best(lambda: jax.block_until_ready(leaf(a, b)), repeats)
    t_flop = t_leaf / (rank * 2.0 * d**3)

    coef = scheme.a_coef
    div = jax.jit(lambda x: divide_level(x, coef))
    t_div = _time_best(lambda: jax.block_until_ready(div(a)), repeats)
    # divide_level: (rank, d, d) -> (rank*rank, d/2, d/2) output elements.
    out_elems = rank * rank * (d // 2) * (d // 2)
    t_elem = t_div / out_elems

    dev = jax.devices()[0]
    return Calibration(
        t_flop=float(t_flop),
        t_elem=float(t_elem),
        device_kind=dev.device_kind,
        device_count=jax.device_count(),
        t_h2d=float(calibrate_h2d(repeats=repeats)),
    )


_CALIBRATION: Optional[Calibration] = None


def get_calibration() -> Calibration:
    """Process-cached calibration (one micro-benchmark pair per process).

    Runs under ``ensure_compile_time_eval``: the first resolution usually
    fires at jit-trace time (resolve_auto inside a traced train step, even
    inside scan bodies), where the micro-benchmarks' jit/device_put calls
    would otherwise stage into the ambient trace instead of executing.
    """
    global _CALIBRATION
    if _CALIBRATION is None:
        with jax.ensure_compile_time_eval():
            _CALIBRATION = calibrate()
    return _CALIBRATION


def calibration_snapshot() -> Optional[Dict]:
    """The current calibration as a dict, or None if none has run yet.

    Never triggers the micro-benchmarks — stats surfaces (e.g.
    ``Engine.autotune_stats``) use this to report t_flop/t_elem/t_h2d
    without paying device time on an engine that resolved every
    decision from a warm cache.
    """
    return _CALIBRATION.to_dict() if _CALIBRATION is not None else None


# --------------------------------------------------------------------------
# Candidate enumeration
# --------------------------------------------------------------------------


def _usable_depth(m: int, k: int, n: int, depth: int, min_dim: int) -> bool:
    """depth levels are usable iff dims stay even and above the crossover floor
    at every level — the same rule as MatmulBackend.effective_depth."""
    for _ in range(depth):
        if m % 2 or k % 2 or n % 2 or min(m, k, n) < min_dim:
            return False
        m, k, n = m // 2, k // 2, n // 2
    return depth > 0


def enumerate_candidates(
    m: int,
    k: int,
    n: int,
    *,
    schemes: Sequence[str] = LOCAL_SCHEMES,
    max_depth: int = 3,
    min_dim: int = 1024,
    oot_budget: Optional[int] = None,
    dtype=jnp.float32,
) -> List[Candidate]:
    """All strategies that can legally run this shape (naive always can).

    ``strassen_fused`` (the Pallas fused-leaf pipeline) enumerates whenever
    the leaf actually runs on this host — compiled on TPU, interpret mode
    on CPU — per :func:`repro.core.compat.pallas_leaf_mode`.

    ``oot_budget`` (device bytes) enables the ``strassen_oot`` out-of-core
    family: one candidate per scheme at every depth whose single leaf fits
    the budget — including depths the in-core rules reject (odd dims: the
    block runtime pads), which is the whole point of the pipeline.
    """
    from repro.core import compat

    cands = [Candidate(kind="naive")]
    depths = [d for d in range(1, max_depth + 1) if _usable_depth(m, k, n, d, min_dim)]
    for scheme in schemes:
        for d in depths:
            cands.append(Candidate(kind=scheme, scheme=scheme, depth=d))
    if depths and "strassen" in schemes and compat.pallas_leaf_mode() != "none":
        for d in depths:
            cands.append(Candidate(kind=FUSED_KIND, scheme="strassen", depth=d))
    if oot_budget:
        from repro.blocks.scheduler import leaf_bytes, min_depth_for_budget

        # A dense on-device multiply needs A + B + C resident at once.
        dense_bytes = (m * k + k * n + m * n) * jnp.dtype(dtype).itemsize
        dense_fits = dense_bytes <= oot_budget
        try:
            d0 = min_depth_for_budget(m, k, n, oot_budget, dtype)
        except ValueError:
            d0 = None
        # Crossover guard: below min_dim the divide/combine + staging
        # overhead dominates exactly as it does for the in-core pipelines
        # (measured 24x at n=128 on the smoke constants) — unless the
        # dense working set cannot fit the budget, where out-of-core is
        # feasibility, not preference.
        if d0 is not None and (min(m, k, n) >= min_dim or not dense_fits):
            # Depths run from the shallowest that fits to max_depth — or
            # deeper when the budget demands it (an out-of-core plan may
            # legally exceed the in-core depth cap; that cap exists to
            # bound divide overhead, not feasibility).
            for scheme in schemes:
                for d in range(d0, max(max_depth, d0) + 1):
                    if leaf_bytes(m, k, n, d, dtype) <= oot_budget and min(
                        m, k, n
                    ) >= 2**d:
                        cands.append(Candidate(kind=OOT_KIND, scheme=scheme, depth=d))
        # When the dense working set exceeds the budget every on-device
        # candidate is infeasible, not merely slow — drop them so the
        # planner cannot pick an impossible plan. Runs LAST so the
        # invariant holds over the full candidate set. (Falls back to the
        # unfiltered list if no oot depth fits either, so callers still
        # get a best-effort decision.)
        if not dense_fits:
            oot_only = [c for c in cands if c.kind == OOT_KIND]
            cands = oot_only or cands
    return cands


# --------------------------------------------------------------------------
# Stage-wise prediction (paper §IV generalized to rectangular JAX stages)
# --------------------------------------------------------------------------


def predict_cost_terms(
    cand: Candidate,
    m: int,
    k: int,
    n: int,
    calib: Calibration,
    *,
    oot_overlap: bool = True,
) -> Dict[str, float]:
    """Per-constant cost decomposition of one candidate's predicted seconds.

    Returns ``{"t_flop": ..., "t_elem": ..., "t_h2d": ...}`` — the seconds
    attributed to each calibrated constant, summing to
    :func:`predict_seconds`. The split is what telemetry reports: it shows
    *why* a candidate wins (compute vs local traffic vs host<->device
    staging).

    ``oot_overlap`` models the scheduler's async wave pipeline (its
    default): staging traffic that fits under the leaf compute is hidden,
    so the ``t_h2d`` term only charges the *exposed* part — the traffic
    exceeding compute plus the fill/drain bubble
    (:data:`OOT_OVERLAP_EXPOSED_FRACTION` of the hidden portion). Pass
    ``oot_overlap=False`` to price the synchronous loop (``prefetch=False``),
    where every staged byte is on the critical path.
    """
    flops_naive = 2.0 * m * k * n
    terms = {"t_flop": 0.0, "t_elem": 0.0, "t_h2d": 0.0}
    if cand.is_naive:
        terms["t_flop"] = flops_naive * calib.t_flop
        return terms

    rank = get_scheme(cand.scheme).n_mults
    l = cand.depth
    fused = cand.kind == FUSED_KIND
    # Levels whose intermediates are materialized: all l for the einsum
    # pipelines, l-1 when the last level runs fused in VMEM.
    lm = l - 1 if fused else l
    elem_cost = 0.0
    # Divide levels i = 0..lm-1: outputs rank^(i+1) quarter-blocks of A and B.
    for i in range(lm):
        e_a = rank ** (i + 1) * (m * k) / 4.0 ** (i + 1)
        e_b = rank ** (i + 1) * (k * n) / 4.0 ** (i + 1)
        elem_cost += e_a + e_b
    # Combine levels i = lm-1..0: outputs rank^i blocks of C at level i.
    for i in range(lm):
        elem_cost += rank**i * (m * n) / 4.0**i
    if fused:
        # The fused level reads its operands once and writes C once; the
        # 7/4x M-term blowup never touches HBM.
        elem_cost += rank ** (l - 1) * (m * k + k * n + m * n) / 4.0 ** (l - 1)
    leaf_flops = flops_naive * (rank / 8.0) ** l
    terms["t_flop"] = leaf_flops * calib.t_flop
    terms["t_elem"] = elem_cost * calib.t_elem

    if cand.kind == OOT_KIND:
        # Out-of-core: divide/combine adds are host-side element traffic;
        # leaf waves run sequentially on one device and every leaf's
        # operands cross the host<->device boundary once each way.
        t_h2d = calib.t_h2d if calib.t_h2d > 0.0 else calib.t_elem
        flop_s = terms["t_flop"]
        h2d_s = rank**l * (m * k + k * n + m * n) / 4.0**l * t_h2d
        if oot_overlap:
            # Async pipeline: staging overlaps leaf compute, so only the
            # traffic exceeding compute is on the critical path — plus the
            # fill/drain bubble, a fixed fraction of the hidden portion.
            hidden = min(h2d_s, flop_s)
            h2d_s = max(h2d_s - flop_s, 0.0) + OOT_OVERLAP_EXPOSED_FRACTION * hidden
        terms["t_h2d"] = h2d_s
    return terms


def predict_seconds(
    cand: Candidate,
    m: int,
    k: int,
    n: int,
    calib: Calibration,
    *,
    oot_overlap: bool = True,
) -> float:
    """Predicted wall-clock for one multiply under the calibrated model.

    Mirrors :mod:`repro.core.cost_model`: each divide/combine level costs
    its output-element traffic * ``t_elem``; the leaf stage costs its flops
    * ``t_flop`` (one program: XLA already uses the whole device, which is
    what t_flop measures). Fused-leaf candidates skip the last level's
    materialized traffic. Out-of-core candidates add the host<->device
    staging term priced at ``t_h2d`` — discounted to the exposed traffic
    when ``oot_overlap`` is on (the scheduler's async pipeline default).
    See :func:`predict_cost_terms` for the per-constant decomposition.
    """
    return sum(predict_cost_terms(cand, m, k, n, calib, oot_overlap=oot_overlap).values())


# --------------------------------------------------------------------------
# Measurement
# --------------------------------------------------------------------------


def measure_seconds(
    cand: Candidate,
    m: int,
    k: int,
    n: int,
    dtype=jnp.float32,
    *,
    min_dim: int = 1024,
    precision=None,
    repeats: int = 2,
    oot_budget: Optional[int] = None,
) -> float:
    """Time one candidate's route end-to-end on device (compile excluded).

    The candidate runs as ``backend.matmul`` on the backend
    :func:`repro.core.backend.route_for` makes of it, with the caller's
    ``min_dim``, ``precision`` and ``oot_budget`` — exactly the route a
    decision for it takes, out-of-core depth deepening included. Jitted
    for the in-device kinds; eager for the host-resident ones, on host
    operands.
    """
    from repro.core import backend as _backend

    route = _backend.route_for(
        cand,
        _backend.MatmulBackend(
            kind="auto", min_dim=min_dim, precision=precision, device_budget=oot_budget
        ),
    )
    ka, kb = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(ka, (m, k), jnp.float32).astype(dtype)
    b = jax.random.normal(kb, (k, n), jnp.float32).astype(dtype)
    if route.kind in _backend.EAGER_ONLY_KINDS:
        import numpy as np

        a_h, b_h = np.asarray(a), np.asarray(b)
        return _time_best(
            lambda: jax.block_until_ready(_backend.matmul(a_h, b_h, route)), repeats
        )
    fn = jax.jit(lambda x, y: _backend.matmul(x, y, route))
    return _time_best(lambda: jax.block_until_ready(fn(a, b)), repeats)


# --------------------------------------------------------------------------
# Persistent tuning cache
# --------------------------------------------------------------------------


def cache_key(
    m: int,
    k: int,
    n: int,
    dtype,
    *,
    device_kind: str,
    device_count: int,
    schemes: Sequence[str],
    min_dim: int,
    max_depth: int,
    topo: str = "local",
    site: Optional[str] = None,
    oot_budget: Optional[int] = None,
) -> str:
    """``topo`` separates candidate families: the matmul kinds resolve under
    ``"local"``, each solver family under its own name, so an entry of one
    can never answer a lookup of another.

    ``site`` is an optional call-site tag (e.g. ``"attn.wq"``) threaded from
    the model stack: tagged entries are keyed per call site, so same-shape
    projections can hold different (measured) decisions. ``site=None``
    yields the shape-only key, which tagged lookups also fall back to in
    predicted mode (the prediction is shape-only anyway).

    ``oot_budget`` keys budget-gated resolutions separately: the candidate
    set (and the right answer) changes with the device-memory cap, and a
    budget-free decision must never answer a budgeted lookup. ``None``
    reproduces the historical key, so existing caches stay valid.
    """
    dt = jnp.dtype(dtype).name
    key = (
        f"{m}x{k}x{n}|{dt}|{device_kind}:{device_count}|{topo}"
        f"|{','.join(schemes)}|min{min_dim}|d{max_depth}"
    )
    if oot_budget:
        key += f"|oot{oot_budget}"
    if site:
        key += f"|site:{site}"
    return key


class TuningCache:
    """JSON-backed decision store: key -> Decision (+ the calibration used).

    Load-then-lookup is the startup path for serving: the engine resolves
    every projection shape from here, so jit tracing never re-measures.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self.entries: Dict[str, Decision] = {}
        self.calibration: Optional[Calibration] = None
        self._suspended = False
        if path and os.path.exists(path):
            self.load(path)

    @contextlib.contextmanager
    def deferred(self):
        """Batch many put/save cycles into one file write (warm-up loops)."""
        self._suspended = True
        try:
            yield self
        finally:
            self._suspended = False
            self.save()

    def load(self, path: str) -> "TuningCache":
        with open(path) as f:
            raw = json.load(f)
        self.entries = {
            k: Decision.from_dict(v) for k, v in raw.get("decisions", {}).items()
        }
        if raw.get("calibration"):
            self.calibration = Calibration.from_dict(raw["calibration"])
        return self

    def save(self, path: Optional[str] = None) -> None:
        path = path or self.path
        if not path or self._suspended:
            return
        payload = {
            "decisions": {k: d.to_dict() for k, d in self.entries.items()},
            "calibration": self.calibration.to_dict() if self.calibration else None,
        }
        # atomic: decisions may be read by a concurrently starting engine
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)

    def get(self, key: str) -> Optional[Decision]:
        return self.entries.get(key)

    def put(self, key: str, decision: Decision) -> None:
        self.entries[key] = decision


# --------------------------------------------------------------------------
# Decision telemetry
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TelemetryEvent:
    """One autotune resolution: where it came from and what it chose."""

    key: str
    site: Optional[str]
    kind: str
    scheme: str
    depth: int
    source: str  # predicted | measured | cache
    cache_hit: bool
    predicted_s: float
    measured_s: Optional[float] = None
    # Per-constant decomposition of predicted_s (t_flop/t_elem/t_h2d
    # seconds, see predict_cost_terms). None on cache hits: the stored
    # decision predates this resolution and its calibration may differ.
    terms: Optional[Dict[str, float]] = None

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)


class Telemetry:
    """Process-wide autotune decision log.

    Every :func:`autotune` call records one event — cache hit or miss, the
    chosen kind, and the predicted (and, under measure mode, measured)
    seconds — so a serving engine or benchmark can report exactly which
    matmul strategy each traced shape resolved to and on what evidence.
    The event log is a ring buffer (``max_events``, default 4096): a
    long-running server with churning prefill shapes keeps the newest
    decisions while the hit/miss counters stay exact totals.
    """

    def __init__(self, max_events: int = 4096) -> None:
        self.max_events = max_events
        self.cache_hits = 0
        self.cache_misses = 0
        self.events: List[TelemetryEvent] = []

    def record(self, event: TelemetryEvent) -> None:
        if event.cache_hit:
            self.cache_hits += 1
        else:
            self.cache_misses += 1
        self.events.append(event)
        if len(self.events) > self.max_events:
            del self.events[: len(self.events) - self.max_events]

    def kind_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for e in self.events:
            out[e.kind] = out.get(e.kind, 0) + 1
        return out

    def snapshot(self) -> Dict:
        return {
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "kinds": self.kind_counts(),
            "decisions": [e.to_dict() for e in self.events],
        }

    def reset(self) -> None:
        self.cache_hits = 0
        self.cache_misses = 0
        self.events = []


_TELEMETRY = Telemetry()


def get_telemetry() -> Telemetry:
    """The process telemetry instance (reset() it between experiments)."""
    return _TELEMETRY


def reset_telemetry() -> Telemetry:
    """Zero the process telemetry and return it.

    Resolutions fire at jit-trace time, so per-engine attribution is
    impossible to scope structurally — instead every surface that owns a
    run (``Engine.__init__``, the benchmark sweeps) resets the process log
    up front so its snapshot reflects only its own resolutions, not a
    previous engine's (the counters used to leak across instances).
    """
    _TELEMETRY.reset()
    return _TELEMETRY


def _cached_calibration(cache: Optional[TuningCache], dev) -> Optional[Calibration]:
    """The cache's calibration if it was fitted on this device kind.

    A cache file can travel between hosts; constants fitted on another
    chip generation (or on CPU) must not price this one's candidates.
    """
    calib = cache.calibration if cache is not None else None
    if calib is not None and calib.device_kind == dev.device_kind:
        return calib
    return None


_PROCESS_CACHES: Dict[str, TuningCache] = {}


def process_cache(path: Optional[str]) -> TuningCache:
    """One shared TuningCache per path (or one anonymous in-memory cache)."""
    key = path or ""
    if key not in _PROCESS_CACHES:
        _PROCESS_CACHES[key] = TuningCache(path)
    return _PROCESS_CACHES[key]


# --------------------------------------------------------------------------
# The dispatcher
# --------------------------------------------------------------------------


def autotune(
    m: int,
    k: int,
    n: int,
    dtype=jnp.float32,
    *,
    min_dim: int = 1024,
    max_depth: int = 3,
    schemes: Sequence[str] = LOCAL_SCHEMES,
    cache: Optional[TuningCache] = None,
    calibration: Optional[Calibration] = None,
    measure: bool = False,
    top_k: int = 3,
    precision=None,
    site: Optional[str] = None,
    oot_budget: Optional[int] = None,
    telemetry: Optional[Telemetry] = None,
) -> Decision:
    """Pick the predicted- (or measured-) fastest strategy for this shape.

    Cache hits return immediately (source='cache') — before calibration, so
    a warm cache costs zero device time. ``measure=True`` times the top-k
    predicted candidates, each through its own route
    (:func:`measure_seconds`), and records the measured winner, the
    theory-vs-practice loop of the paper's §V.

    ``site`` keys the decision per call site (see :func:`cache_key`). In
    predicted mode a tagged miss falls back to the shape-only entry — the
    prediction cannot differ per site — but measured mode never does: a
    measured site decision must come from measuring *that* site's key, so
    e.g. same-width QKV and MLP projections can diverge.

    ``telemetry`` records the resolution to a caller-owned log instead of
    the process one — experiments that must not interleave with a live
    engine's counters pass their own :class:`Telemetry`.
    """
    tel = telemetry if telemetry is not None else _TELEMETRY
    # Every resolution is a span: cache hits close immediately with
    # cache_hit=True; fresh decisions carry the predicted cost-term
    # breakdown (t_flop/t_elem/t_h2d) next to any measured time —
    # the predicted-vs-measured feed the TPU recalibration item needs.
    tr = obs_tracer.get_tracer()
    res_span = tr.begin(
        "autotune.resolve", cat="autotune", site=site, m=m, k=k, n=n,
    )
    dev = jax.devices()[0]
    key_kwargs = dict(
        device_kind=dev.device_kind,
        device_count=1,
        schemes=schemes,
        min_dim=min_dim,
        max_depth=max_depth,
        oot_budget=oot_budget,
    )
    key = cache_key(m, k, n, dtype, site=site, **key_kwargs)
    if cache is not None:
        hit = cache.get(key)
        if hit is None and site and not measure:
            hit = cache.get(cache_key(m, k, n, dtype, **key_kwargs))
        if hit is not None and hit.kind == FUSED_KIND:
            # Re-validate fused decisions against THIS host: a cache warmed
            # where the Pallas leaf ran must not route to it on a build
            # where it cannot (enumeration would have excluded it).
            from repro.core import compat

            if compat.pallas_leaf_mode() == "none":
                hit = None
        if hit is not None:
            decision = dataclasses.replace(hit, source="cache")
            tel.record(
                TelemetryEvent(
                    key=key,
                    site=site,
                    kind=decision.kind,
                    scheme=decision.scheme,
                    depth=decision.depth,
                    source="cache",
                    cache_hit=True,
                    predicted_s=decision.predicted_s,
                    measured_s=decision.measured_s,
                )
            )
            obs_metrics.get_metrics().counter("autotune.cache_hit").inc()
            tr.end(
                res_span, cache_hit=True, kind=decision.kind,
                scheme=decision.scheme, depth=decision.depth, source="cache",
                predicted_s=decision.predicted_s,
                measured_s=decision.measured_s,
            )
            return decision

    calib = calibration or _cached_calibration(cache, dev) or get_calibration()
    cands = enumerate_candidates(
        m, k, n, schemes=schemes, max_depth=max_depth, min_dim=min_dim,
        oot_budget=oot_budget, dtype=dtype,
    )

    def _overlap(c: Candidate) -> bool:
        # Price an oot candidate's overlap discount only when the budget
        # actually leaves the scheduler its pipelined wave slot at that
        # depth — otherwise it silently degrades to synchronous staging
        # and every staged byte is on the critical path.
        return c.kind != OOT_KIND or _oot_pipeline_fits(
            m, k, n, c.depth, dtype, oot_budget
        )

    scored = sorted(
        cands, key=lambda c: predict_seconds(c, m, k, n, calib, oot_overlap=_overlap(c))
    )
    best = scored[0]
    measured = None
    if measure:
        timed = [
            (
                measure_seconds(
                    c, m, k, n, dtype, min_dim=min_dim, precision=precision,
                    oot_budget=oot_budget,
                ),
                c,
            )
            for c in scored[: max(top_k, 1)]
        ]
        measured, best = min(timed, key=lambda t: t[0])
    terms = predict_cost_terms(best, m, k, n, calib, oot_overlap=_overlap(best))
    predicted = sum(terms.values())

    decision = Decision(
        kind=best.kind,
        scheme=best.scheme,
        depth=best.depth,
        predicted_s=float(predicted),
        measured_s=None if measured is None else float(measured),
        source="measured" if measure else "predicted",
    )
    if cache is not None:
        cache.calibration = _cached_calibration(cache, dev) or calib
        # Predicted decisions are shape-only by construction, so a tagged
        # resolution stores under the shape-only key — every other site of
        # the same shape then hits via the fallback instead of duplicating
        # identical entries. Only measured decisions are site-specific.
        store_key = (
            key if (measure or not site) else cache_key(m, k, n, dtype, **key_kwargs)
        )
        cache.put(store_key, decision)
        cache.save()
    tel.record(
        TelemetryEvent(
            key=key,
            site=site,
            kind=decision.kind,
            scheme=decision.scheme,
            depth=decision.depth,
            source=decision.source,
            cache_hit=False,
            predicted_s=decision.predicted_s,
            measured_s=decision.measured_s,
            terms=terms,
        )
    )
    obs_metrics.get_metrics().counter("autotune.cache_miss").inc()
    tr.end(
        res_span, cache_hit=False, kind=decision.kind,
        scheme=decision.scheme, depth=decision.depth, source=decision.source,
        predicted_s=decision.predicted_s, measured_s=decision.measured_s,
        **{f"terms.{t}": v for t, v in terms.items()},
    )
    return decision


# --------------------------------------------------------------------------
# Solver families (SPIN block-recursive inversion / triangular solve)
# --------------------------------------------------------------------------

# Candidate families of the solver ops. Priced with the same calibrated
# constants as the matmul families: t_flop for dense-leaf and recursive
# multiply flops, t_h2d for every staged byte (with the wave pipeline's
# overlap discount where the budget leaves pipeline headroom), t_elem for
# the host-side axpy chains.
INVERSE_OOT_KIND = "inverse_oot"
SOLVE_OOT_KIND = "solve_oot"
_SOLVER_FAMILIES = {"inverse": INVERSE_OOT_KIND, "solve": SOLVE_OOT_KIND}


def predict_solver_terms(
    op: str,
    n: int,
    depth: int,
    calib: Calibration,
    *,
    nrhs: Optional[int] = None,
    oot_budget: Optional[int] = None,
    oot_overlap: bool = True,
) -> Dict[str, float]:
    """Per-constant cost decomposition of one solver run at a given depth.

    The recursion does, per node at level i (2^i nodes, half-size h =
    n / 2^(i+1)): for ``inverse`` six h-sized multiplies and two axpys
    (SPIN's Schur-complement program); for ``solve`` one (h x h) @
    (h x nrhs) multiply and one axpy. The 2^depth dense leaves run one
    device inv (~2 s^3 flops) or trsm (~s^2 nrhs flops). Multiply staging
    is priced at t_h2d with the wave pipeline's exposed-fraction discount
    (:data:`OOT_OVERLAP_EXPOSED_FRACTION`) when ``oot_overlap``.
    """
    if op not in _SOLVER_FAMILIES:
        raise ValueError(
            f"unknown solver op {op!r}; have {sorted(_SOLVER_FAMILIES)}"
        )
    r = n if nrhs is None else nrhs
    t_h2d = calib.t_h2d or calib.t_elem
    flop_s = 0.0
    h2d_elems = 0.0
    elem_s = 0.0
    s = max(1, n >> depth)
    leaves = 1 << depth
    if op == "inverse":
        flop_s += leaves * 2.0 * s**3 * calib.t_flop
        h2d_elems += leaves * 2.0 * s * s
    else:
        flop_s += leaves * float(s) * s * r * calib.t_flop
        h2d_elems += leaves * (s * s + 2.0 * s * r)
    mul_flop_s = 0.0
    for level in range(depth):
        nodes = 1 << level
        h = max(1, n >> (level + 1))
        if op == "inverse":
            mul_flop_s += nodes * 6 * 2.0 * h**3 * calib.t_flop
            h2d_elems += nodes * 6 * 3.0 * h * h
            elem_s += nodes * 2.0 * h * h * calib.t_elem
        else:
            mul_flop_s += nodes * 2.0 * h * h * r * calib.t_flop
            h2d_elems += nodes * (h * h + 2.0 * h * r)
            elem_s += nodes * float(h) * r * calib.t_elem
    flop_s += mul_flop_s
    h2d_s = h2d_elems * t_h2d
    if oot_overlap:
        # The staged traffic rides the scheduler's async pipeline: only the
        # non-overlappable remainder plus the fill/drain bubbles stay on
        # the critical path (same shape as the strassen_oot discount).
        h2d_s = max(h2d_s - mul_flop_s, 0.0) + OOT_OVERLAP_EXPOSED_FRACTION * min(
            h2d_s, mul_flop_s
        )
    return {"flop_s": flop_s, "elem_s": elem_s, "h2d_s": h2d_s}


def predict_solver_seconds(
    op: str,
    n: int,
    depth: int,
    calib: Calibration,
    *,
    nrhs: Optional[int] = None,
    oot_budget: Optional[int] = None,
    oot_overlap: bool = True,
) -> float:
    terms = predict_solver_terms(
        op, n, depth, calib, nrhs=nrhs, oot_budget=oot_budget,
        oot_overlap=oot_overlap,
    )
    return sum(terms.values())


def autotune_solver(
    op: str,
    n: int,
    dtype=jnp.float32,
    *,
    nrhs: Optional[int] = None,
    oot_budget: Optional[int] = None,
    max_depth: int = 10,
    scheme: str = "strassen",
    cache: Optional[TuningCache] = None,
    calibration: Optional[Calibration] = None,
    site: Optional[str] = None,
    telemetry: Optional[Telemetry] = None,
) -> Decision:
    """Pick the predicted-fastest recursion depth for one solver shape.

    ``op`` is 'inverse' or 'solve'. Candidate depths run from the
    smallest whose dense leaf fits ``oot_budget`` (every level halves the
    leaf side) up a few levels — deeper trades dense-leaf cubic work for
    more recursive-multiply traffic, and the calibrated terms arbitrate.
    Decisions cache and telemetry exactly like matmul resolutions, with
    ``topo`` set to the solver family so a solver entry can never answer
    a matmul lookup.
    """
    from repro.blocks.solve import solver_min_depth_for_budget

    family = _SOLVER_FAMILIES.get(op)
    if family is None:
        raise ValueError(
            f"unknown solver op {op!r}; have {sorted(_SOLVER_FAMILIES)}"
        )
    tel = telemetry if telemetry is not None else _TELEMETRY
    tr = obs_tracer.get_tracer()
    res_span = tr.begin(
        "autotune.resolve", cat="autotune", site=site, family=family, n=n,
    )
    dev = jax.devices()[0]
    leaf_kind = "inv" if op == "inverse" else "trsm_lower"
    key = cache_key(
        n, n, n if nrhs is None else nrhs, dtype,
        device_kind=dev.device_kind, device_count=1,
        schemes=(scheme,), min_dim=0, max_depth=max_depth,
        topo=family, site=site, oot_budget=oot_budget,
    )
    if cache is not None:
        hit = cache.get(key)
        if hit is not None:
            decision = dataclasses.replace(hit, source="cache")
            tel.record(
                TelemetryEvent(
                    key=key, site=site, kind=decision.kind,
                    scheme=decision.scheme, depth=decision.depth,
                    source="cache", cache_hit=True,
                    predicted_s=decision.predicted_s,
                    measured_s=decision.measured_s,
                )
            )
            obs_metrics.get_metrics().counter("autotune.cache_hit").inc()
            tr.end(
                res_span, cache_hit=True, kind=decision.kind,
                depth=decision.depth, source="cache",
            )
            return decision

    calib = calibration or _cached_calibration(cache, dev) or get_calibration()
    if oot_budget:
        d_min = solver_min_depth_for_budget(
            n, oot_budget, dtype, nrhs=nrhs, leaf_kind=leaf_kind,
            max_depth=max_depth,
        )
    else:
        d_min = 0
    depths = range(d_min, min(d_min + 3, max_depth) + 1)
    best_depth = min(
        depths,
        key=lambda d: predict_solver_seconds(
            op, n, d, calib, nrhs=nrhs, oot_budget=oot_budget
        ),
    )
    predicted = predict_solver_seconds(
        op, n, best_depth, calib, nrhs=nrhs, oot_budget=oot_budget
    )
    decision = Decision(
        kind=family, scheme=scheme, depth=best_depth,
        predicted_s=float(predicted), source="predicted",
    )
    if cache is not None:
        cache.calibration = _cached_calibration(cache, dev) or calib
        cache.put(key, decision)
        cache.save()
    terms = predict_solver_terms(
        op, n, best_depth, calib, nrhs=nrhs, oot_budget=oot_budget
    )
    tel.record(
        TelemetryEvent(
            key=key, site=site, kind=family, scheme=scheme, depth=best_depth,
            source="predicted", cache_hit=False,
            predicted_s=decision.predicted_s, terms=terms,
        )
    )
    obs_metrics.get_metrics().counter("autotune.cache_miss").inc()
    tr.end(
        res_span, cache_hit=False, kind=family, depth=best_depth,
        source="predicted", predicted_s=decision.predicted_s,
        **{f"terms.{t}": v for t, v in terms.items()},
    )
    return decision


def model_call_sites(cfg) -> List[Tuple[str, int, int]]:
    """(site, d_in, d_out) for every tagged dense projection of a model.

    These are exactly the tags :mod:`repro.models.attention` /
    :mod:`repro.models.mlp` thread through ``linear`` — keep the two lists
    in sync so warmed cache keys match runtime lookups.
    """
    hd = cfg.head_dim or (cfg.d_model // max(cfg.n_heads, 1))
    sites = [
        ("attn.wq", cfg.d_model, cfg.n_heads * hd),
        ("attn.wk", cfg.d_model, cfg.n_kv_heads * hd),
        ("attn.wv", cfg.d_model, cfg.n_kv_heads * hd),
        ("attn.wo", cfg.n_heads * hd, cfg.d_model),
        ("mlp.up", cfg.d_model, cfg.d_ff),
        ("mlp.down", cfg.d_ff, cfg.d_model),
    ]
    if cfg.glu:
        sites.append(("mlp.gate", cfg.d_model, cfg.d_ff))
    return [(s, i, o) for s, i, o in sites if i > 0 and o > 0]


def warm_for_model(
    cfg, *, tokens: Sequence[int] = (1, 128, 2048), batches: Sequence[int] = (1, 8)
) -> int:
    """Pre-resolve decisions for a model's dense-projection call sites.

    Serving startup path: the flattened M a projection sees is batch*seq at
    prefill and batch at decode, so we resolve every (batch * tokens) x
    call-site combination up front, under the same site tags the layers
    pass at trace time. Shapes outside this grid (odd batch sizes,
    untagged call sites) still resolve lazily at trace time — the warm-up
    narrows the cold path, it doesn't guarantee its absence. Returns the
    number of resolutions performed.
    """
    from repro.core import backend as _backend

    be = cfg.matmul_backend
    if be.kind != "auto":
        return 0
    ms = sorted({b * t for b in batches for t in tokens} | set(batches))
    count = 0
    with process_cache(be.tuning_cache).deferred():
        for m in ms:
            for site, d_in, d_out in model_call_sites(cfg):
                _backend.resolve_auto(m, d_in, d_out, cfg.dtype, be, site)
                count += 1
    return count
