"""Level-order out-of-core Strassen executor over tagged block stores.

This is the paper's level-parallel recursion (Fig. 2) re-targeted at the
host/device memory hierarchy instead of a Spark cluster:

* **divide** — for each level, every tree node's seven children are formed
  by signed sums of the parent's quadrant blocks (Stark's
  flatMapToPair/groupByKey/flatMap stage). These are host-side numpy adds
  streaming block-by-block through the :class:`~repro.blocks.blockmatrix
  .BlockStore`, so host working set is O(block), not O(matrix).
* **leaf** — the 7^q leaf products are batched into *waves* sized so that
  (current wave operands + products, the previous wave's still-in-flight
  working set — its operands stay pinned by the unfenced executions, not
  just its un-fetched products — and the prefetched next-wave operands)
  fit a configurable device-memory budget; see
  :func:`pipelined_leaf_bytes`. The wave loop is a 2-deep asynchronous
  pipeline
  keyed off JAX's async dispatch: wave k's products are left in flight
  while wave k+1's operands are ``jax.device_put`` and its multiplies
  dispatched, and the only blocking fence is the explicit
  ``jax.block_until_ready`` at each wave's D2H fetch — so H2D staging,
  leaf compute, and D2H drain of adjacent waves all overlap (the paper's
  Spark pipeline keeping all 7^q multiplies busy, JAMPI's
  shuffle-to-overlapped-transfer move re-targeted at the host<->device
  boundary). Fetched product buffers are released ("donated" into the
  host-side combine accumulation) the moment their bytes land on host,
  so peak device bytes stay inside the budget including in-flight
  prefetch. Per-wave issue/dispatch/fetch timestamps land in
  :class:`OotStats.wave_events` and derive ``overlap_efficiency``.
* **combine** — level-order bottom-up signed sums of the seven child
  products into each parent's quadrants (Stark's combine stage), again
  host-side and block-streaming; child nodes are freed as soon as their
  parent is built.

Peak device bytes are therefore bounded by the budget rather than the
problem size — the paper's "matrices far larger than memory" regime with
device HBM playing the executor and the host store playing HDFS.
"""
from __future__ import annotations

import dataclasses
import threading
import time
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.blocks import tags
from repro.blocks.blockmatrix import (
    BlockMatrix,
    BlockStore,
    make_store,
    signed_block_sum,
)
from repro.blocks.plan import BilinearPlan, as_bilinear_plan
from repro.blocks.recovery import (
    ChaosConfig,
    ChaosStore,
    FaultError,
    FlakyLeaf,
    Lineage,
    RecoveringStore,
)
from repro.core.coefficients import Scheme
from repro.obs import metrics as obs_metrics
from repro.obs import tracer as obs_tracer

__all__ = [
    "OotStats",
    "OotStatsRing",
    "PlanScheduler",
    "StrassenScheduler",
    "strassen_oot_matmul",
    "leaf_bytes",
    "pipelined_leaf_bytes",
    "min_depth_for_budget",
    "attach_stats_ring",
    "recent_oot_stats",
    "reset_oot_stats",
]


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _leaf_dims(m: int, k: int, n: int, depth: int) -> Tuple[int, int, int]:
    step = 2**depth
    return _ceil_div(m, step), _ceil_div(k, step), _ceil_div(n, step)


def _leaf_inout_bytes(m: int, k: int, n: int, depth: int, dtype) -> Tuple[int, int]:
    """(operand bytes A + B, product bytes C) of one leaf multiply.

    Sized at the scheduler's default *staging* dtype — the accumulation
    dtype of ``dtype`` (f32 for bf16 inputs; see
    :class:`StrassenScheduler`) — so budget planning is conservative for
    callers that narrow staging to the compute dtype.
    """
    lm, lk, ln = _leaf_dims(m, k, n, depth)
    item = np.dtype(np.result_type(np.dtype(dtype), np.float32)).itemsize
    return (lm * lk + lk * ln) * item, lm * ln * item


def leaf_bytes(m: int, k: int, n: int, depth: int, dtype) -> int:
    """Device bytes one leaf multiply needs: A + B operands + C product.

    See :func:`_leaf_inout_bytes` for the staging-dtype sizing convention;
    :func:`pipelined_leaf_bytes` for the async pipeline's per-slot peak.
    """
    i, o = _leaf_inout_bytes(m, k, n, depth, dtype)
    return i + o


def pipelined_leaf_bytes(m: int, k: int, n: int, depth: int, dtype) -> int:
    """Device bytes one leaf *slot* occupies at the async pipeline's peak.

    While wave k computes, the 2-deep pipeline concurrently holds, per
    slot: wave k's full working set (A + B + C), wave k-1's full working
    set — its products are not yet fetched and its operands stay pinned
    by the still-in-flight executions until the D2H fence — and wave
    k+1's prefetched operands (A + B). That is ``2 * leaf_bytes`` plus
    one more set of operand bytes; sizing waves (and picking depths) at
    this slot makes the device budget a bound on actual residency, not
    just the quiescent single-wave state.
    """
    i, o = _leaf_inout_bytes(m, k, n, depth, dtype)
    return 2 * (i + o) + i


def min_depth_for_budget(
    m: int,
    k: int,
    n: int,
    budget_bytes: int,
    dtype,
    max_depth: int = 12,
    *,
    pipelined: bool = False,
) -> int:
    """Smallest recursion depth whose leaf working set fits the budget.

    ``pipelined=False`` (feasibility): one leaf's (A, B, C) resident — the
    scheduler can always run, degrading to un-prefetched single-leaf waves.
    ``pipelined=True`` (the async wave pipeline's peak): a leaf slot plus
    its in-flight neighbours — the previous wave's whole working set
    (operands pinned by the unfenced executions, products awaiting D2H)
    and the next wave's (A, B) prefetch — i.e.
    :func:`pipelined_leaf_bytes`; depths chosen this way keep the 2-deep
    pipeline enabled instead of silently falling back to synchronous
    staging.
    """
    size = pipelined_leaf_bytes if pipelined else leaf_bytes
    for depth in range(1, max_depth + 1):
        if size(m, k, n, depth, dtype) <= budget_bytes:
            return depth
    raise ValueError(
        f"no depth <= {max_depth} fits ({m}x{k}x{n}, {np.dtype(dtype).name}) "
        f"leaves into {budget_bytes} bytes"
        + (" with pipeline headroom" if pipelined else "")
    )


@dataclasses.dataclass
class OotStats:
    """Execution telemetry for one out-of-core multiply.

    ``wave_events`` holds one record per staging wave with timestamps
    (seconds since the run started) for the pipeline's three async phases:
    ``issue_start``/``issue_end`` (host->device operand staging),
    ``dispatch_end`` (leaf multiplies issued, not fenced), and
    ``fetch_start``/``fetch_end`` (the D2H ``block_until_ready`` fence +
    host combine write). ``overlap_efficiency`` derives from them: the
    fraction of total transfer time (staging + fetch) issued while another
    wave's compute was in flight — with the 2-deep pipeline only the first
    wave's staging and the last wave's fetch are exposed, so any forced
    multi-wave run reports a strictly positive value; a synchronous run
    (``prefetch=False``) reports 0.0.
    """

    m: int
    k: int
    n: int
    depth: int
    scheme: str
    leaves: int
    waves: int
    wave_size: int
    prefetch: bool
    stage_dtype: str
    budget_bytes: int
    per_leaf_bytes: int
    peak_device_bytes: int
    # The plan's operator ("matmul" | "inverse" | "solve"): rings mix runs
    # from every recursive plan, so consumers filter/attribute by op.
    op: str = "matmul"
    # Nested out-of-core multiplies a solver run spawned (0 for matmul
    # runs — the scheduler itself never nests).
    oot_runs: int = 0
    h2d_bytes: int = 0
    d2h_bytes: int = 0
    # Bytes of the blocks the divide phase reads from the store and
    # writes to it (exact; see PlanScheduler._divide_child).
    divide_bytes: int = 0
    host_store_peak_bytes: int = 0
    # Phases, each the duration of its span: ingest (both operands into
    # the store), divide, leaf waves, combine, assemble (C to dense, crop,
    # cast, roots freed). Together they cover total_s up to bookkeeping.
    ingest_s: float = 0.0
    divide_s: float = 0.0
    leaf_s: float = 0.0
    combine_s: float = 0.0
    assemble_s: float = 0.0
    total_s: float = 0.0
    stage_s: float = 0.0
    fetch_s: float = 0.0
    overlap_efficiency: float = 0.0
    wave_events: List[dict] = dataclasses.field(default_factory=list)
    # Fault-tolerance telemetry (PR 9). ``rung`` is the degradation-ladder
    # rung the run finally completed on; ``degrade_events`` records each
    # transition. ``unrecovered_faults`` counts lineage recomputes that
    # failed the put-time checksum replay — zero on a healthy run, chaos
    # or not. ``injected_faults`` is cumulative across ladder rungs (the
    # flaky-leaf shim's call counter spans attempts).
    rung: str = "pipeline"
    degrades: int = 0
    degrade_events: List[dict] = dataclasses.field(default_factory=list)
    leaf_retries: int = 0
    recovered_blocks: int = 0
    lost_blocks: int = 0
    corrupt_blocks: int = 0
    injected_faults: int = 0
    unrecovered_faults: int = 0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def assert_within_budget(self) -> None:
        """Raise if the modeled pipelined peak exceeded the device budget."""
        if self.peak_device_bytes > self.budget_bytes:
            raise AssertionError(
                f"peak device bytes {self.peak_device_bytes} exceeded the "
                f"budget {self.budget_bytes} (waves={self.waves}, "
                f"wave_size={self.wave_size}, prefetch={self.prefetch})"
            )

    def finalize_overlap(self) -> None:
        """Derive ``overlap_efficiency`` from the per-wave timestamps."""
        total = sum(
            (e["issue_end"] - e["issue_start"]) + (e["fetch_end"] - e["fetch_start"])
            for e in self.wave_events
        )
        if not self.prefetch or len(self.wave_events) < 2 or total <= 0.0:
            self.overlap_efficiency = 0.0
            return
        first, last = self.wave_events[0], self.wave_events[-1]
        exposed = (first["issue_end"] - first["issue_start"]) + (
            last["fetch_end"] - last["fetch_start"]
        )
        self.overlap_efficiency = max(0.0, min(1.0, 1.0 - exposed / total))


class OotStatsRing:
    """Bounded, thread-safe ring of recent OotStats dicts (oldest first).

    Every completed out-of-core run is appended to **all** registered
    rings. The module keeps one default ring behind the legacy
    ``recent_oot_stats()`` / ``reset_oot_stats()`` API; consumers that
    must not observe (or clobber) each other — e.g. two concurrently
    running serving Engines — attach their own via
    :func:`attach_stats_ring` and read/clear only that.
    """

    def __init__(self, maxlen: int = 64) -> None:
        self.maxlen = maxlen
        self._lock = threading.Lock()
        self._items: List[dict] = []

    def append(self, item: dict) -> None:
        with self._lock:
            self._items.append(item)
            if len(self._items) > self.maxlen:
                del self._items[: len(self._items) - self.maxlen]

    def snapshot(self) -> List[dict]:
        with self._lock:
            return list(self._items)

    def clear(self) -> None:
        with self._lock:
            self._items.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._items)


# The default ring (legacy module-level API) plus any attached consumer
# rings. WeakSet: an Engine's ring unregisters when the engine is
# collected — there is no explicit close() on that surface.
_DEFAULT_RING = OotStatsRing()
_RINGS: "weakref.WeakSet[OotStatsRing]" = weakref.WeakSet([_DEFAULT_RING])
_RINGS_LOCK = threading.Lock()


def attach_stats_ring(maxlen: int = 64) -> OotStatsRing:
    """New consumer-owned ring, subscribed to every future run's stats.

    The caller must hold the returned ring (registration is weak);
    clearing it does not disturb the default ring or other consumers.
    """
    ring = OotStatsRing(maxlen)
    with _RINGS_LOCK:
        _RINGS.add(ring)
    return ring


def recent_oot_stats() -> List[dict]:
    """Stats dicts of this process's recent out-of-core runs (oldest first)."""
    return _DEFAULT_RING.snapshot()


def reset_oot_stats() -> None:
    """Clear the **default** ring only; attached rings are unaffected."""
    _DEFAULT_RING.clear()


def _record_run(stats: OotStats) -> None:
    d = stats.to_dict()
    with _RINGS_LOCK:
        rings = list(_RINGS)
    for ring in rings:
        ring.append(d)


class _RunTrackingStore(BlockStore):
    """Forwards to a caller-provided store, recording the keys this run put.

    Tags are not run-scoped, so a failing run must delete exactly the
    blocks *it* created — a tag-prefix sweep would also destroy the blocks
    of other (interleaved or earlier) scheduler runs sharing the store.
    """

    def __init__(self, inner: BlockStore) -> None:
        self.inner = inner
        self.created: set = set()

    def put(self, key, block) -> None:
        self.inner.put(key, block)
        self.created.add(key)

    def get(self, key):
        return self.inner.get(key)

    def delete(self, key) -> None:
        self.inner.delete(key)
        self.created.discard(key)

    def __contains__(self, key) -> bool:
        return key in self.inner

    def keys(self):
        return self.inner.keys()

    def nbytes(self) -> int:
        return self.inner.nbytes()

    def drop_created(self) -> None:
        """Delete every block this run created and has not already freed."""
        for key in list(self.created):
            self.inner.delete(key)
        self.created.clear()

    def close(self) -> None:  # the caller owns the inner store
        pass


class PlanScheduler:
    """Budgeted level-order executor for one bilinear recursive plan.

    The waves/budget/pipeline/degradation machinery below is operator
    agnostic: divide rows, combine rows, rank, tag prefixes, and the op
    label all come from a :class:`repro.blocks.plan.BilinearPlan`. The
    Strassen base-7 and naive base-4 multiplies are simply the first two
    registered plans (wrapping the coefficient tables unchanged, so this
    executor is bit-identical to the pre-plan Strassen scheduler).

    Args:
      depth: recursion depth q (rank^q leaves). Must make a leaf fit the
        budget — see :func:`min_depth_for_budget`.
      budget_bytes: peak device bytes the leaf waves may occupy.
      scheme: coefficient scheme (strassen | winograd | naive8) — the
        historical spelling of ``plan`` for matmul plans.
      plan: the :class:`~repro.blocks.plan.BilinearPlan` to walk (or its
        registry name). Overrides ``scheme`` when given.
      backend: :class:`repro.core.backend.MatmulBackend` routing for the
        leaf multiplies; defaults to ``kind="auto"`` so each leaf shape
        goes through the calibrated dispatcher (and, transitively, any
        registered mesh strategy a future resolve chooses).
      block: target block side for the store partition; ``None`` stores
        one block per leaf operand (the coarsest legal grain).
      prefetch: run the leaf waves as a 2-deep asynchronous pipeline —
        wave k+1's host->device staging and dispatch are issued while
        wave k's products are still in flight, and the only blocking
        fence is each wave's D2H fetch. Automatically disabled (fully
        synchronous stage -> compute -> fetch per wave) when the budget
        cannot hold a pipelined slot (:func:`pipelined_leaf_bytes`: two
        leaves' working sets plus one more wave of operand prefetch).
      stage_dtype: dtype of the staged leaf operands (and so of the leaf
        multiply). ``None`` — the default — stages in the accumulation
        dtype (f32 for bf16 inputs): operand combos never round until the
        final output cast, the Huang-et-al. packing-buffer discipline,
        which holds deep-recursion bf16 parity to ~1e-3. Pass the compute
        dtype explicitly to halve staging volume at the cost of one
        rounding per leaf operand (depth-2 bf16 parity degrades to ~2e-2).
      chaos: deterministic fault injection
        (:class:`repro.blocks.recovery.ChaosConfig`): seeded block
        drop/corrupt probabilities on the store and flaky-leaf dispatch
        failures. Tests/benchmarks/CI only — injection implies
        ``recovery`` unless explicitly disabled.
      recovery: wrap the run's store in a
        :class:`~repro.blocks.recovery.RecoveringStore` (checksum on put,
        verify on get, transparent lineage recompute on loss/corruption).
        ``None`` (default) enables it exactly when ``chaos`` is set; pass
        True to harden a production run against a caller-shared store.
      retries: bounded retry count per leaf multiply (exponential backoff
        from ``retry_backoff_s``). Device-OOM is never retried — it goes
        straight to the degradation ladder.
      retry_backoff_s: first retry's sleep; doubles per attempt.
      degrade: on an unrecovered fault or device-OOM, walk the
        degradation ladder instead of failing the multiply: async
        pipeline -> synchronous staging -> halved wave -> one level
        deeper recursion. Each transition is a ``fault.degrade``
        span/counter and lands in ``OotStats.degrade_events``.
    """

    def __init__(
        self,
        *,
        depth: int,
        budget_bytes: int,
        scheme: Scheme | str = "strassen",
        plan: "BilinearPlan | str | None" = None,
        backend=None,
        block: Optional[int] = None,
        prefetch: bool = True,
        stage_dtype=None,
        chaos: Optional[ChaosConfig] = None,
        recovery: Optional[bool] = None,
        retries: int = 2,
        retry_backoff_s: float = 0.05,
        degrade: bool = True,
    ) -> None:
        if depth < 1:
            raise ValueError("out-of-core recursion needs depth >= 1")
        if budget_bytes <= 0:
            raise ValueError("budget_bytes must be positive")
        if retries < 0 or retry_backoff_s < 0:
            raise ValueError("retries and retry_backoff_s must be >= 0")
        self.depth = depth
        self.budget_bytes = int(budget_bytes)
        self.plan = as_bilinear_plan(plan if plan is not None else scheme)
        if self.plan.leaf_kind != "matmul":
            raise ValueError(
                f"plan {self.plan.name!r} has leaf kind "
                f"{self.plan.leaf_kind!r}; the wave scheduler executes "
                f"matmul-leaf bilinear plans (dataflow plans run on "
                f"repro.blocks.solve)"
            )
        self.scheme = self.plan.scheme
        self.block = block
        self.prefetch = prefetch
        self.stage_dtype = stage_dtype
        self.chaos = chaos
        self.recovery = (chaos is not None) if recovery is None else bool(recovery)
        self.retries = int(retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self.degrade = degrade
        if backend is None:
            from repro.core.backend import MatmulBackend

            backend = MatmulBackend(kind="auto", depth=2, min_dim=1024)
        self.backend = backend

    # ------------------------------------------------------------ internals
    @staticmethod
    def _node_tag(op: str, path: Tuple[int, ...]) -> str:
        return f"{op}:{tags.to_string(path)}"

    def _node(
        self,
        store: BlockStore,
        op: str,
        path: Tuple[int, ...],
        root_shape: Tuple[int, int],
        block_shape: Tuple[int, int],
        dtype,
    ) -> BlockMatrix:
        level = len(path)
        shape = (root_shape[0] >> level, root_shape[1] >> level)
        return BlockMatrix(store, shape, block_shape, dtype, self._node_tag(op, path))

    @staticmethod
    def _signed_sum(get_block, coefs: np.ndarray, acc_dtype) -> np.ndarray:
        """Delegates to :func:`repro.blocks.blockmatrix.signed_block_sum`.

        Shared with lineage recompute (:mod:`repro.blocks.recovery`):
        recovery is bit-exact precisely because both run the same loop.
        """
        return signed_block_sum(get_block, coefs, acc_dtype)

    def _retry_leaf(self, fn, stats: "OotStats", mx):
        """Run one leaf multiply with bounded retry + exponential backoff.

        Only fault-typed failures (:class:`FaultError` — the chaos shim,
        a flaky backend) retry, up to ``self.retries`` times. Device-OOM
        raises immediately — re-issuing the identical allocation cannot
        succeed, only the degradation ladder (smaller waves / deeper
        recursion) can. Unknown exceptions also propagate untouched:
        retrying a genuine bug would mask it and burn the backoff budget.
        """
        from repro.core.backend import is_oom_error

        delay = self.retry_backoff_s
        for attempt in range(self.retries + 1):
            try:
                return fn()
            except Exception as e:
                if (
                    is_oom_error(e)
                    or not isinstance(e, FaultError)
                    or attempt >= self.retries
                ):
                    raise
                stats.leaf_retries += 1
                mx.counter("fault.retries").inc()
                mx.counter(f"fault.retries.{self.plan.op}").inc()
                if delay > 0:
                    time.sleep(delay)
                delay = min(delay * 2, 2.0)

    def _divide_child(
        self,
        parent: BlockMatrix,
        child: BlockMatrix,
        coef_row: np.ndarray,
        acc_dtype,
    ) -> int:
        """child = sum_q coef_row[q] * quadrant_q(parent), block-streamed.

        Returns the bytes moved: every parent block read plus every child
        block written.
        """
        gr, gc = child.grid
        moved = 0

        def read(q: int, i: int, j: int) -> np.ndarray:
            nonlocal moved
            blk = parent.block((q // 2) * gr + i, (q % 2) * gc + j)
            moved += blk.nbytes
            return blk

        for i in range(gr):
            for j in range(gc):
                acc = self._signed_sum(
                    lambda q: read(q, i, j), coef_row, acc_dtype
                ).astype(child.dtype)
                child.put_block(i, j, acc)
                moved += acc.nbytes
        return moved

    def _combine_parent(
        self,
        children: Sequence[BlockMatrix],
        parent: BlockMatrix,
        acc_dtype,
    ) -> None:
        """parent quadrants = sum_p c_coef[k, p] * child_p, block-streamed."""
        gr, gc = children[0].grid
        c_coef = self.plan.combine_coef
        for kq in range(tags.Q_BASE):
            for i in range(gr):
                for j in range(gc):
                    acc = self._signed_sum(
                        lambda p: children[p].block(i, j), c_coef[kq], acc_dtype
                    )
                    parent.put_block(
                        (kq // 2) * gr + i, (kq % 2) * gc + j, acc.astype(parent.dtype)
                    )

    def _leaf_matmul(self, a_dev, b_dev):
        from repro.core import backend as _backend

        return _backend.matmul(a_dev, b_dev, self.backend, site="blocks.leaf")

    # -------------------------------------------------------------- the run
    def matmul(
        self,
        a: np.ndarray,
        b: np.ndarray,
        *,
        store: str | BlockStore = "dict",
        store_root: Optional[str] = None,
    ) -> Tuple[np.ndarray, OotStats]:
        """``a @ b`` with device memory bounded by the budget.

        ``a``/``b`` are host arrays (numpy or anything ``np.asarray``
        accepts, bfloat16 included). ``store`` picks the block residency:
        'dict' | 'arena' | 'memmap' or a ready :class:`BlockStore`.

        Runs the graceful-degradation ladder: the configured mode first,
        then — on an unrecovered fault (retries exhausted, lineage
        recompute impossible) or device-OOM — synchronous staging, a
        halved wave, and finally one level deeper recursion. Every rung
        transition is a ``fault.degrade`` counter + instant span; the
        returned stats carry the completed rung and the transition log.
        Anything that is not a fault/OOM propagates unchanged from the
        first attempt.
        """
        from repro.core.backend import is_oom_error

        # One flaky-leaf shim across the whole ladder: its dispatch-call
        # counter spans attempts, so "fail the Nth leaf multiply" windows
        # pass and the ladder can make progress.
        flaky = None
        if self.chaos is not None and self.chaos.injects_leaf_faults:
            flaky = FlakyLeaf(
                fail_calls=self.chaos.fail_leaf_calls,
                fail_rate=self.chaos.leaf_fail_rate,
                seed=self.chaos.seed + 1,
            )
        rungs: List[Tuple[str, dict]] = []
        if self.prefetch:
            rungs.append(
                ("pipeline", dict(prefetch=True, wave_scale=1.0, depth=self.depth))
            )
        rungs.append(("sync", dict(prefetch=False, wave_scale=1.0, depth=self.depth)))
        rungs.append(
            ("halved-wave", dict(prefetch=False, wave_scale=0.5, depth=self.depth))
        )
        rungs.append(
            ("deeper", dict(prefetch=False, wave_scale=0.5, depth=self.depth + 1))
        )
        if not self.degrade:
            rungs = rungs[:1]
        tr = obs_tracer.get_tracer()
        mx = obs_metrics.get_metrics()
        degrade_log: List[dict] = []
        for idx, (name, overrides) in enumerate(rungs):
            try:
                result, stats = self._attempt(
                    a, b, store=store, store_root=store_root, flaky=flaky,
                    **overrides,
                )
            except Exception as e:
                recoverable = isinstance(e, FaultError) or is_oom_error(e)
                if idx == len(rungs) - 1 or not recoverable:
                    raise
                nxt = rungs[idx + 1][0]
                mx.counter("fault.degrade").inc()
                mx.counter(f"fault.degrade.{self.plan.op}").inc()
                tr.event(
                    "fault.degrade", cat="fault", op=self.plan.op,
                    rung_from=name, rung_to=nxt, cause=type(e).__name__,
                )
                degrade_log.append(
                    {"from": name, "to": nxt, "cause": f"{type(e).__name__}: {e}"[:200]}
                )
                continue
            stats.rung = name
            stats.degrades = len(degrade_log)
            stats.degrade_events = degrade_log
            _record_run(stats)
            return result, stats
        raise AssertionError("degradation ladder must return or raise")

    def _attempt(
        self,
        a: np.ndarray,
        b: np.ndarray,
        *,
        store: str | BlockStore,
        store_root: Optional[str],
        depth: int,
        prefetch: bool,
        wave_scale: float,
        flaky: Optional[FlakyLeaf],
    ) -> Tuple[np.ndarray, OotStats]:
        """One run of the level-order executor at a fixed ladder rung."""
        import jax

        # Spans are the run's single timing source: OotStats (wave_events,
        # phase splits, overlap_efficiency) is DERIVED from them after the
        # fact. When the process tracer is exporting, the spans land there
        # (the trace renders the recursion tree, tag-addressed); otherwise
        # a throwaway run-local tracer carries them just far enough to
        # populate the stats. Either tracer is enabled, so its live spans
        # are also profiler annotations: any running jax.profiler trace
        # shows every product's phases beside the device ops.
        tr = obs_tracer.get_tracer()
        if not tr.enabled:
            tr = obs_tracer.Tracer(enabled=True)
        mx = obs_metrics.get_metrics()
        a = np.asarray(a)
        b = np.asarray(b)
        if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
            raise ValueError(f"bad matmul shapes {a.shape} @ {b.shape}")
        dtype = np.result_type(a.dtype, b.dtype)
        acc_dtype = np.result_type(dtype, np.float32)
        m, k = a.shape
        n = b.shape[1]
        rank = self.plan.rank
        # Tag prefixes come from the plan ("A"/"B"/"C" for matmul plans,
        # so lineage keys and traces are unchanged from the pre-plan era).
        a_name, b_name = self.plan.operands
        c_name = self.plan.result
        a_rows = self.plan.divide_coef[a_name]
        b_rows = self.plan.divide_coef[b_name]

        # Recursion-aligned padded dims and the block partition. With an
        # explicit block grain each leaf dim rounds up to a whole number of
        # blocks so every level's grid halves exactly.
        lm, lk, ln = _leaf_dims(m, k, n, depth)
        if self.block is not None:
            bam = min(self.block, lm)
            bak = min(self.block, lk)
            bbn = min(self.block, ln)
            lm, lk, ln = (
                _ceil_div(lm, bam) * bam,
                _ceil_div(lk, bak) * bak,
                _ceil_div(ln, bbn) * bbn,
            )
        else:
            bam, bak, bbn = lm, lk, ln
        pm, pk, pn = lm << depth, lk << depth, ln << depth

        stage_dtype = (
            np.dtype(self.stage_dtype) if self.stage_dtype is not None else acc_dtype
        )
        itemsize = stage_dtype.itemsize
        in_bytes = (lm * lk + lk * ln) * itemsize
        out_bytes = lm * ln * itemsize
        per_leaf = in_bytes + out_bytes
        # Pipelined wave slot: the 2-deep pipeline keeps, per leaf slot, the
        # current wave's full working set (A + B + C) plus its in-flight
        # neighbours — the previous wave's WHOLE working set (its products
        # are not yet fetched and its operands stay pinned by the unfenced
        # executions until drain's D2H fence) and the next wave's
        # prefetched operands (A + B) — concurrently resident, i.e.
        # 2 * per_leaf + in_bytes (pipelined_leaf_bytes). Sizing waves at
        # that slot makes the budget bound hold at the *pipelined* peak,
        # not just the quiescent single-wave state.
        wave_size = self.budget_bytes // (2 * per_leaf + in_bytes) if prefetch else 0
        if wave_size < 1:
            prefetch = False
            wave_size = self.budget_bytes // per_leaf
        if wave_size < 1:
            raise ValueError(
                f"device budget {self.budget_bytes} B cannot hold one "
                f"{lm}x{lk}x{ln} {np.dtype(dtype).name} leaf ({per_leaf} B); "
                f"use depth >= "
                f"{min_depth_for_budget(m, k, n, self.budget_bytes, dtype)}"
            )
        if wave_scale != 1.0:
            # Degradation rung: shrink waves below what the budget allows
            # (never below one leaf — single-leaf feasibility was checked
            # above, so this only trades throughput for headroom).
            wave_size = max(1, int(wave_size * wave_scale))

        # Divide/combine chains accumulate (and store) in acc_dtype; blocks
        # round at most once — operands at the staging cast, C at the final
        # cast. One rounding per value instead of one per level is the same
        # discipline as the fused kernel's fp32 MXU accumulation, and what
        # keeps depth>=2 bf16 parity inside 1e-2. Leaf compute and H2D/D2H
        # volume run at ``stage_dtype`` — the accumulation dtype by default
        # (2x the compute-dtype bytes for bf16 inputs), narrowed to the
        # compute dtype via the ``stage_dtype`` knob.
        acc_item = np.dtype(acc_dtype).itemsize
        slot_bytes = max(bam * bak, bak * bbn, bam * bbn) * acc_item
        # Stores built here from a spec are owned (and closed) here;
        # caller-provided BlockStore instances stay open for inspection —
        # and may be shared across runs, so this run's puts are tracked
        # and the failure path deletes only those. Layering, bottom up:
        # base store -> run tracking -> chaos injection (faults must hit
        # the raw bytes) -> recovering wrapper (checksums + lineage
        # recompute sit ABOVE the injector, so injected faults are
        # detected and healed like real ones).
        owned_store = not isinstance(store, BlockStore)
        base = make_store(store, slot_bytes=slot_bytes, root=store_root)
        inner: BlockStore = base
        tracking: Optional[_RunTrackingStore] = None
        if not owned_store:
            tracking = _RunTrackingStore(inner)
            inner = tracking
        chaos_store: Optional[ChaosStore] = None
        if self.chaos is not None and self.chaos.injects_store_faults:
            chaos_store = ChaosStore(
                inner,
                drop=self.chaos.drop,
                corrupt=self.chaos.corrupt,
                seed=self.chaos.seed,
            )
            inner = chaos_store
        recovering: Optional[RecoveringStore] = None
        if self.recovery:

            def lineage_leaf(a_host: np.ndarray, b_host: np.ndarray) -> np.ndarray:
                # Replays one leaf through the same device path the waves
                # use (device_put -> routed leaf matmul -> fenced fetch),
                # so a recomputed leaf product is bit-identical. Runs only
                # while the device is otherwise idle (divide/combine), so
                # one leaf's working set — already <= the budget — is the
                # whole recovery footprint.
                a_dev = jax.device_put(a_host)
                b_dev = jax.device_put(b_host)
                return np.asarray(jax.block_until_ready(self._leaf_matmul(a_dev, b_dev)))

            lineage = Lineage(
                scheme=self.scheme, plan=self.plan, depth=depth, a=a, b=b,
                pm=pm, pk=pk, pn=pn, bam=bam, bak=bak, bbn=bbn,
                acc_dtype=np.dtype(acc_dtype), stage_dtype=stage_dtype,
                leaf_matmul=lineage_leaf,
            )
            recovering = RecoveringStore(inner, lineage)
            inner = recovering
        store = inner
        # The root span opens no profiler annotation: the caller marks the
        # whole product, and the phases below are what a profile reads.
        root_span = tr.begin(
            f"oot.{self.plan.op}", cat="oot", annotate=False, op=self.plan.op,
            m=m, k=k, n=n, depth=depth, scheme=self.scheme.name,
            budget_bytes=self.budget_bytes,
        )
        t_start = root_span.t0
        # Device arrays in flight per wave index — defined out here so the
        # failure path below can release them even when the exception's
        # traceback keeps the frame (and so these references) alive.
        in_flight: dict = {}
        try:

            leaves = rank**depth
            stats = OotStats(
                m=m, k=k, n=n, depth=depth, scheme=self.scheme.name,
                op=self.plan.op,
                leaves=leaves, waves=0, wave_size=wave_size, prefetch=prefetch,
                stage_dtype=stage_dtype.name,
                budget_bytes=self.budget_bytes, per_leaf_bytes=per_leaf,
                peak_device_bytes=0,
            )

            # --- ingest roots (edge/odd dims zero-extend to the padded grain).
            ing_span = tr.begin("oot.ingest", cat="oot")
            a_root = BlockMatrix.from_dense(
                a, (bam, bak), store, self._node_tag(a_name, ()), shape=(pm, pk)
            )
            b_root = BlockMatrix.from_dense(
                b, (bak, bbn), store, self._node_tag(b_name, ()), shape=(pk, pn)
            )
            tr.end(ing_span)
            stats.ingest_s = ing_span.duration

            # --- divide: level-order, all rank^level nodes per level. One
            # span per level, one tag-addressed span per tree node — the
            # exported trace's top lane reads as the recursion tree itself.
            div_span = tr.begin("oot.divide", cat="oot")
            for level in range(depth):
                p_dtype = dtype if level == 0 else acc_dtype
                with tr.span(
                    f"divide.L{level + 1}", cat="oot",
                    level=level + 1, nodes=rank ** (level + 1),
                ):
                    for path in tags.leaf_paths(level, rank):
                        with tr.span(
                            "divide.node", cat="oot",
                            tag=tags.to_string(path), level=level,
                        ):
                            pa = self._node(
                                store, a_name, path, (pm, pk), (bam, bak), p_dtype
                            )
                            pb = self._node(
                                store, b_name, path, (pk, pn), (bak, bbn), p_dtype
                            )
                            for p in range(rank):
                                ca = self._node(
                                    store, a_name, tags.child(path, p, rank),
                                    (pm, pk), (bam, bak), acc_dtype,
                                )
                                cb = self._node(
                                    store, b_name, tags.child(path, p, rank),
                                    (pk, pn), (bak, bbn), acc_dtype,
                                )
                                stats.divide_bytes += self._divide_child(
                                    pa, ca, a_rows[p], acc_dtype
                                ) + self._divide_child(pb, cb, b_rows[p], acc_dtype)
                    stats.host_store_peak_bytes = max(
                        stats.host_store_peak_bytes, store.nbytes()
                    )
                    # Parents are consumed: only the leaf level feeds the
                    # multiply. Freed via the node's own key iteration
                    # (O(blocks-of-node)), not delete_tag's full-store scan.
                    for path in tags.leaf_paths(level, rank):
                        self._node(
                            store, a_name, path, (pm, pk), (bam, bak), p_dtype
                        ).free()
                        self._node(
                            store, b_name, path, (pk, pn), (bak, bbn), p_dtype
                        ).free()
            tr.end(div_span)
            stats.divide_s = div_span.duration
            stats.host_store_peak_bytes = max(stats.host_store_peak_bytes, store.nbytes())

            # --- leaf waves: a 2-deep async pipeline over stage -> dispatch
            # -> fetch. Iteration k issues wave k's leaf multiplies (async
            # JAX dispatch), stages wave k+1's operands (async device_put)
            # while wave k computes, and only THEN drains wave k-1 — so the
            # pipeline's one blocking fence (block_until_ready at D2H)
            # overlaps the in-flight compute instead of serializing behind
            # it. Fetched product buffers are released the moment their
            # bytes land on host (donated into the host-side combine
            # accumulation), keeping the device peak at the budgeted
            # pipelined slot.
            leaf_span = tr.begin(
                "oot.leaf_waves", cat="oot",
                waves=_ceil_div(leaves, wave_size), wave_size=wave_size,
                prefetch=prefetch,
            )
            leaf_list = list(tags.leaf_paths(depth, rank))
            waves: List[List[Tuple[int, ...]]] = [
                leaf_list[i : i + wave_size] for i in range(0, leaves, wave_size)
            ]
            # Per-wave phase spans, recorded on dedicated tracks so the
            # exported trace shows the pipeline's overlap as concurrent
            # lanes: wave k+1's "wave.stage" sits strictly inside wave k's
            # "wave.compute" (dispatch issue -> D2H fence) when prefetch is
            # on. OotStats.wave_events is derived from these spans after
            # the loop — the spans ARE the record, nothing is hand-stamped.
            wave_spans: Dict[int, Dict[str, obs_tracer.Span]] = {}

            def stage(w_idx: int):
                wsp = tr.begin(
                    "wave.stage", cat="oot", track="oot.stage",
                    wave=w_idx, size=len(waves[w_idx]),
                )
                staged = []
                refs = in_flight.setdefault(w_idx, [])
                for path in waves[w_idx]:
                    with tr.span(
                        "leaf.stage", cat="oot", tag=tags.to_string(path),
                        track="oot.stage", wave=w_idx, h2d_bytes=in_bytes,
                    ):
                        na = self._node(
                            store, a_name, path, (pm, pk), (bam, bak), acc_dtype
                        )
                        nb = self._node(
                            store, b_name, path, (pk, pn), (bak, bbn), acc_dtype
                        )
                        # Any rounding to a narrower staging dtype happens
                        # here, at the host->device boundary — never mid-chain.
                        a_dev = jax.device_put(
                            na.to_dense().astype(stage_dtype, copy=False)
                        )
                        b_dev = jax.device_put(
                            nb.to_dense().astype(stage_dtype, copy=False)
                        )
                    refs.extend((a_dev, b_dev))
                    staged.append((path, a_dev, b_dev))
                    stats.h2d_bytes += in_bytes
                tr.end(wsp)
                wave_spans.setdefault(w_idx, {})["stage"] = wsp
                mx.counter("oot.h2d_bytes").inc(len(waves[w_idx]) * in_bytes)
                return staged

            def dispatch(w_idx: int, staged):
                wsp = tr.begin(
                    "wave.dispatch", cat="oot", track="oot.dispatch", wave=w_idx
                )
                refs = in_flight[w_idx]
                outs = []
                for path, a_dev, b_dev in staged:
                    with tr.span(
                        "leaf.mul", cat="oot", tag=tags.to_string(path),
                        track="oot.dispatch", wave=w_idx,
                    ):

                        def call(a_dev=a_dev, b_dev=b_dev):
                            # The chaos shim fails the dispatch the way a
                            # flaky backend would — before issue, so a
                            # retry is a genuinely fresh dispatch.
                            if flaky is not None:
                                flaky.check()
                            return self._leaf_matmul(a_dev, b_dev)

                        out = self._retry_leaf(call, stats, mx)
                    refs.append(out)
                    outs.append((path, out))
                # Multiplies issued: drop this wave's operand refs (XLA
                # keeps the input buffers alive for the in-flight
                # executions) so they free the moment the leaves complete
                # instead of surviving until drain. Only on success —
                # a failing leaf leaves the full ref list for the
                # failure-path release below.
                in_flight[w_idx] = [out for _, out in outs]
                tr.end(wsp)
                wave_spans.setdefault(w_idx, {})["dispatch"] = wsp
                return outs

            def drain(w_idx: int, outs):
                wsp = tr.begin(
                    "wave.fetch", cat="oot", track="oot.fetch", wave=w_idx
                )
                wave_d2h = 0
                for path, out in outs:
                    with tr.span(
                        "leaf.fetch", cat="oot", tag=tags.to_string(path),
                        track="oot.fetch", wave=w_idx,
                    ) as lsp:
                        try:
                            out = jax.block_until_ready(out)  # the only fence
                            host = np.asarray(out)
                        except Exception as fence_exc:
                            from repro.core.backend import is_oom_error

                            if is_oom_error(fence_exc) or not isinstance(
                                fence_exc, FaultError
                            ):
                                # OOM goes to the ladder; unknown errors
                                # propagate (same policy as _retry_leaf).
                                raise
                            # A fault-typed async failure surfaced at the
                            # fence. Drop the dead buffer, then replay this
                            # one leaf synchronously from the host blocks
                            # (still in the store until free() below) —
                            # reaching here already cost one attempt, so it
                            # counts as a retry before the bounded loop.
                            try:
                                out.delete()
                            except Exception:
                                pass
                            stats.leaf_retries += 1
                            mx.counter("fault.retries").inc()
                            mx.counter(f"fault.retries.{self.plan.op}").inc()

                            def redo(path=path):
                                if flaky is not None:
                                    flaky.check()
                                na = self._node(
                                    store, a_name, path,
                                    (pm, pk), (bam, bak), acc_dtype,
                                )
                                nb = self._node(
                                    store, b_name, path,
                                    (pk, pn), (bak, bbn), acc_dtype,
                                )
                                a_dev = jax.device_put(
                                    na.to_dense().astype(stage_dtype, copy=False)
                                )
                                b_dev = jax.device_put(
                                    nb.to_dense().astype(stage_dtype, copy=False)
                                )
                                return np.asarray(
                                    jax.block_until_ready(
                                        self._leaf_matmul(a_dev, b_dev)
                                    )
                                )

                            host = self._retry_leaf(redo, stats, mx)
                        stats.d2h_bytes += host.nbytes
                        wave_d2h += host.nbytes
                        lsp.set(d2h_bytes=host.nbytes)
                        host = host.astype(acc_dtype, copy=False)
                        cn = self._node(
                            store, c_name, path, (pm, pn), (bam, bbn), acc_dtype
                        )
                        for i in range(cn.grid[0]):
                            for j in range(cn.grid[1]):
                                cn.put_block(
                                    i, j,
                                    host[
                                        i * bam : (i + 1) * bam,
                                        j * bbn : (j + 1) * bbn,
                                    ],
                                )
                        self._node(
                            store, a_name, path, (pm, pk), (bam, bak), acc_dtype
                        ).free()
                        self._node(
                            store, b_name, path, (pk, pn), (bak, bbn), acc_dtype
                        ).free()
                # Drop the wave's device references (operands were consumed
                # by the leaf multiplies; products are now on host) so the
                # buffers free without waiting for this host loop or GC.
                in_flight.pop(w_idx, None)
                tr.end(wsp)
                ws = wave_spans.setdefault(w_idx, {})
                ws["fetch"] = wsp
                # In-flight window: multiply issue -> D2H fence completion.
                # Parity lanes keep consecutive (genuinely overlapping)
                # windows from sharing a track, which Chrome renders badly.
                if "dispatch" in ws:
                    tr.add_span(
                        "wave.compute", ws["dispatch"].t1, wsp.t1, cat="oot",
                        track=f"oot.compute/{w_idx % 2}", parent=leaf_span,
                        wave=w_idx, size=len(waves[w_idx]),
                    )
                mx.counter("oot.d2h_bytes").inc(wave_d2h)
                stats.waves += 1
                stats.host_store_peak_bytes = max(
                    stats.host_store_peak_bytes, store.nbytes()
                )

            pending: Optional[Tuple[int, list]] = None
            staged = stage(0) if (prefetch and waves) else None
            for w_idx, wave in enumerate(waves):
                current, staged = staged, None
                if current is None:  # prefetch off: stage synchronously
                    current = stage(w_idx)
                outs = dispatch(w_idx, current)
                current = None
                # Modeled concurrent peak this iteration: wave k's working
                # set + the previous wave's whole working set (un-fetched
                # products, plus operands the in-flight executions may
                # still pin) + the next wave's prefetched operands —
                # matching the wave sizing above, so the budget bounds
                # actual residency.
                device_now = len(wave) * per_leaf
                if pending is not None:
                    device_now += len(pending[1]) * per_leaf
                if prefetch and w_idx + 1 < len(waves):
                    device_now += len(waves[w_idx + 1]) * in_bytes
                stats.peak_device_bytes = max(stats.peak_device_bytes, device_now)
                if prefetch and w_idx + 1 < len(waves):
                    # Stage the next wave's H2D while this wave's multiplies
                    # run behind JAX's async dispatch — the staging calls'
                    # host-side overhead executes on this thread while XLA's
                    # worker pool computes wave k.
                    staged = stage(w_idx + 1)
                if pending is not None:
                    # D2H fence for wave k-1 while wave k is still in flight.
                    drain(*pending)
                    pending = None
                if prefetch:
                    pending = (w_idx, outs)
                else:
                    drain(w_idx, outs)
                outs = None
            if pending is not None:
                drain(*pending)
            tr.end(leaf_span)
            stats.leaf_s = leaf_span.duration
            # Wave telemetry is DERIVED from the recorded spans (public
            # shape unchanged: seconds since run start). finalize_overlap()
            # below then reads these exactly as before the span rewire.
            stats.wave_events = [
                {
                    "wave": i,
                    "size": len(waves[i]),
                    "issue_start": ws["stage"].t0 - t_start,
                    "issue_end": ws["stage"].t1 - t_start,
                    "dispatch_end": ws["dispatch"].t1 - t_start,
                    "fetch_start": ws["fetch"].t0 - t_start,
                    "fetch_end": ws["fetch"].t1 - t_start,
                }
                for i, ws in sorted(wave_spans.items())
            ]
            stats.stage_s = sum(ws["stage"].duration for ws in wave_spans.values())
            stats.fetch_s = sum(ws["fetch"].duration for ws in wave_spans.values())

            # --- combine: level-order bottom-up, freeing children as we go.
            comb_span = tr.begin("oot.combine", cat="oot")
            for level in reversed(range(depth)):
                with tr.span(
                    f"combine.L{level + 1}", cat="oot",
                    level=level + 1, nodes=rank**level,
                ):
                    for path in tags.leaf_paths(level, rank):
                        with tr.span(
                            "combine.node", cat="oot",
                            tag=tags.to_string(path), level=level,
                        ):
                            children = [
                                self._node(
                                    store, c_name, tags.child(path, p, rank),
                                    (pm, pn), (bam, bbn), acc_dtype,
                                )
                                for p in range(rank)
                            ]
                            parent = self._node(
                                store, c_name, path, (pm, pn), (bam, bbn), acc_dtype
                            )
                            self._combine_parent(children, parent, acc_dtype)
                            for child in children:
                                child.free()
                    stats.host_store_peak_bytes = max(
                        stats.host_store_peak_bytes, store.nbytes()
                    )
            tr.end(comb_span)
            stats.combine_s = comb_span.duration

            asm_span = tr.begin("oot.assemble", cat="oot")
            c_root = self._node(store, c_name, (), (pm, pn), (bam, bbn), acc_dtype)
            result = c_root.to_dense()[:m, :n].astype(dtype, copy=False)
            a_root.free()
            b_root.free()
            c_root.free()
            tr.end(asm_span)
            stats.assemble_s = asm_span.duration
        except BaseException:
            # A failing leaf matmul (or store error) mid-pipeline must not
            # leak the run's artifacts. Release the in-flight device
            # buffers eagerly — the raised exception's traceback pins this
            # frame, so dropping the dict alone would keep them alive as
            # long as the caller holds the exception — and, for
            # caller-provided stores the finally below will NOT close,
            # drop exactly the blocks this run put (tracked per key:
            # tags are not run-scoped, and a shared store may hold other
            # runs' blocks under the same "A:"/"B:"/"C:" tag space).
            for refs in in_flight.values():
                for buf in refs:
                    try:
                        buf.delete()
                    except Exception:
                        pass
            in_flight.clear()
            if tracking is not None:
                tracking.drop_created()
            # Close the root span (end() pops any children the unwind left
            # open) so the tracer's per-thread stack stays consistent for
            # whatever the caller runs next.
            tr.end(root_span, failed=True)
            raise
        finally:
            if owned_store:
                base.close()
        # Fault telemetry: what the wrappers detected/healed this attempt
        # (retries were counted in place; injected counts are cumulative
        # for the flaky shim, whose call counter spans ladder rungs).
        if recovering is not None:
            stats.recovered_blocks = recovering.recovered_blocks
            stats.lost_blocks = recovering.lost_blocks
            stats.corrupt_blocks = recovering.corrupt_blocks
            stats.unrecovered_faults = recovering.recompute_mismatches
        if chaos_store is not None:
            stats.injected_faults += (
                chaos_store.injected_drops + chaos_store.injected_corruptions
            )
        if flaky is not None:
            stats.injected_faults += flaky.injected
        stats.total_s = tr.end(root_span).duration
        stats.finalize_overlap()
        root_span.set(
            overlap_efficiency=stats.overlap_efficiency,
            peak_device_bytes=stats.peak_device_bytes,
            h2d_bytes=stats.h2d_bytes,
            d2h_bytes=stats.d2h_bytes,
        )
        return result, stats


# The historical name: Strassen is now simply the first registered plan
# this executor walks. Kept as the public spelling used across the repo.
StrassenScheduler = PlanScheduler


def strassen_oot_matmul(
    a: np.ndarray,
    b: np.ndarray,
    *,
    depth: int,
    budget_bytes: int,
    scheme: Scheme | str = "strassen",
    plan: "BilinearPlan | str | None" = None,
    backend=None,
    block: Optional[int] = None,
    prefetch: bool = True,
    stage_dtype=None,
    store: str | BlockStore = "dict",
    store_root: Optional[str] = None,
    chaos: Optional[ChaosConfig] = None,
    recovery: Optional[bool] = None,
    retries: int = 2,
    retry_backoff_s: float = 0.05,
    degrade: bool = True,
) -> Tuple[np.ndarray, OotStats]:
    """Functional wrapper: one out-of-core Strassen multiply.

    See :class:`StrassenScheduler` for the parameters; this is the entry
    point :mod:`repro.core.backend` (kind='strassen_oot'), the autotuner's
    ``strassen_oot`` candidate family, ``launch/blocks_demo.py``, and
    ``benchmarks/fig8_scaling.py`` share.
    """
    sched = PlanScheduler(
        depth=depth, budget_bytes=budget_bytes, scheme=scheme, plan=plan,
        backend=backend, block=block, prefetch=prefetch, stage_dtype=stage_dtype,
        chaos=chaos, recovery=recovery, retries=retries,
        retry_backoff_s=retry_backoff_s, degrade=degrade,
    )
    return sched.matmul(a, b, store=store, store_root=store_root)
