"""Pluggable matmul backend: routes model-layer matmuls through Strassen.

This is how the paper's technique becomes a first-class framework feature:
every dense projection in :mod:`repro.models` calls :func:`matmul` with the
config's :class:`MatmulBackend`. The backend decides — per call site and
per shape — whether to run the naive XLA matmul (MLLib/Marlin regime), the
batched-BFS Strassen pipeline (Stark regime), or the Pallas-fused variant.

The crossover logic mirrors the paper's empirical finding (§V-C): Strassen
wins only when matrix dims are large relative to the leaf block size; below
``min_dim`` the divide/combine overhead dominates and we fall back to the
naive path (exactly like Stark's ``threshold`` leaf cutoff).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.strassen import strassen_matmul
from repro.obs import tracer as obs_tracer

__all__ = [
    "MatmulBackend",
    "matmul",
    "inverse",
    "solve_triangular",
    "NAIVE_BACKEND",
    "AUTO_BACKEND",
    "resolve_auto",
    "route_for",
    "VALID_KINDS",
    "EAGER_ONLY_KINDS",
    "JIT_SAFE_KINDS",
    "SOLVER_KINDS",
    "SOLVER_EAGER_ONLY_KINDS",
    "SOLVER_JIT_SAFE_KINDS",
    "set_default_matmul_precision",
    "default_matmul_precision",
    "resolve_precision",
    "precision_scope",
]

# The registered routing kinds: every MatmulBackend.kind (and every CLI
# --backend choice) must come from this tuple, so a typo fails shallowly
# with the list of valid names instead of a deep trace-time error.
VALID_KINDS: Tuple[str, ...] = (
    "naive",
    "strassen",
    "winograd",
    "strassen_fused",
    "strassen_oot",
    "auto",
)

# The one kind that can spread a product over a MatmulBackend.mesh, and the
# name the backend.matmul span gives the function that runs it there
# (core/distributed.py).
MESH_KIND = "strassen_fused"
MESH_STRATEGY = "strassen_fused_sharded"

# Kinds that cannot trace under jit (host-resident pipelines). Jitted
# surfaces (train/serve/dryrun CLIs) derive their --backend menus as
# VALID_KINDS minus these.
EAGER_ONLY_KINDS: Tuple[str, ...] = ("strassen_oot",)
JIT_SAFE_KINDS: Tuple[str, ...] = tuple(
    k for k in VALID_KINDS if k not in EAGER_ONLY_KINDS
)

# Routing kinds of the solver ops (:func:`inverse` /
# :func:`solve_triangular`): 'dense' is one device LAPACK-style call,
# 'spin_oot' the SPIN block-recursive pipeline over the tagged block
# runtime, 'auto' picks per shape against ``device_budget``. Error
# messages enumerate these tuples dynamically — new kinds can never
# drift out of the message text.
SOLVER_KINDS: Tuple[str, ...] = ("dense", "spin_oot", "auto")
SOLVER_EAGER_ONLY_KINDS: Tuple[str, ...] = ("spin_oot",)
SOLVER_JIT_SAFE_KINDS: Tuple[str, ...] = tuple(
    k for k in SOLVER_KINDS if k not in SOLVER_EAGER_ONLY_KINDS
)

# Process-default matmul precision, the HomebrewNLP backend.py discipline:
# precision policy is a backend knob set once, not threaded per call site.
# A MatmulBackend with precision=None inherits this default.
_DEFAULT_PRECISION: Optional[str] = None


def set_default_matmul_precision(precision: Optional[str]) -> Optional[str]:
    """Set the process default for backends with ``precision=None``.

    Accepts jax precision names ('default' | 'fastest' | 'high' |
    'highest') or None to clear. Returns the previous default.
    """
    global _DEFAULT_PRECISION
    if precision is not None and precision not in (
        "default", "fastest", "high", "highest", "bfloat16", "float32", "tensorfloat32"
    ):
        raise ValueError(f"unknown matmul precision {precision!r}")
    prev, _DEFAULT_PRECISION = _DEFAULT_PRECISION, precision
    return prev


def default_matmul_precision() -> Optional[str]:
    return _DEFAULT_PRECISION


def resolve_precision(backend: "MatmulBackend") -> Optional[str]:
    """The precision a backend's matmuls run at: its own, else the default."""
    return backend.precision if backend.precision is not None else _DEFAULT_PRECISION


def precision_scope(precision: Optional[str]):
    """Run the jnp ops that take no precision argument (the linalg leaves:
    ``inv``, ``solve_triangular``) at ``precision`` — the same knob the
    matmuls of that backend run at. None leaves the platform default;
    'fastest' is the platform default too (jax's scope has no such name).
    """
    if precision is None or precision == "fastest":
        return contextlib.nullcontext()
    return jax.default_matmul_precision(precision)


def is_oom_error(exc: BaseException) -> bool:
    """Classify a device out-of-memory failure, across jax versions.

    XLA surfaces OOM as ``XlaRuntimeError`` with RESOURCE_EXHAUSTED (the
    type's import path has moved repeatedly, so match by name) or as a
    generic RuntimeError carrying an allocator message. The out-of-core
    scheduler treats OOM differently from transient faults: retrying the
    same dispatch cannot succeed, so it skips straight to the
    degradation ladder (smaller waves, deeper recursion).
    """
    names = {t.__name__ for t in type(exc).__mro__}
    msg = str(exc)
    markers = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory", "OOM")
    if "XlaRuntimeError" in names and any(m in msg for m in markers):
        return True
    if isinstance(exc, MemoryError):
        return True
    return isinstance(exc, RuntimeError) and any(m in msg for m in markers)


@dataclasses.dataclass(frozen=True)
class MatmulBackend:
    """Configuration for routing matmuls.

    Attributes:
      kind: one of :data:`VALID_KINDS`. 'auto' defers the choice to the
        calibrated cost model in :mod:`repro.core.autotune`, resolved per
        (M, K, N, dtype) at trace time and cached — so jitted call sites
        pay the decision once. 'strassen_oot' routes through the
        out-of-core tagged-block runtime (:mod:`repro.blocks`): host
        operands, device bytes capped by ``device_budget`` — eager-only.
      depth: Strassen recursion depth (paper's p - q). Ignored for naive;
        for 'auto' it is the maximum depth the tuner may pick; for
        'strassen_oot' it deepens automatically until the async pipeline's
        wave slot fits the budget (falling back to a bare leaf when no
        depth leaves pipeline headroom).
      min_dim: minimum of (M, K, N) below which the call falls back to the
        naive matmul (the paper's leaf threshold / crossover point).
      precision: jax precision for leaf matmuls ('default' | 'fastest' |
        'highest'...). None inherits the process default set via
        :func:`set_default_matmul_precision` — precision policy is a
        backend knob, not a per-call-site argument.
      tuning_cache: optional path to a persistent autotune JSON cache
        ('auto' only). Decisions found there are reused verbatim — the
        serving engine points this at its warmed startup cache.
      measure: 'auto' only — time the top predicted candidates on device
        instead of trusting the model (slower first trace, exact winner).
      schemes: coefficient schemes 'auto' may choose between.
      device_budget: peak device bytes the out-of-core pipeline may use
        ('strassen_oot', and the gate that lets 'auto' enumerate the
        strassen_oot candidate family). None: 'strassen_oot' budgets one
        single-leaf pipelined wave slot (two leaf working sets plus one
        wave of operand prefetch); 'auto' never picks out-of-core.
      mesh: a device mesh with a 'data' and/or 'model' axis to spread one
        product over ('strassen_fused' only): A and C are split into row
        stripes over those axes, B is gathered to every device, and each
        device runs the fused pipeline on its stripe
        (:func:`repro.core.distributed.strassen_fused_sharded`). None:
        the product runs where its operands are.
    """

    kind: str = "naive"
    depth: int = 1
    min_dim: int = 1024
    precision: Optional[str] = None
    tuning_cache: Optional[str] = None
    measure: bool = False
    schemes: Tuple[str, ...] = ("strassen", "winograd")
    device_budget: Optional[int] = None
    mesh: Optional[jax.sharding.Mesh] = None

    def __post_init__(self):
        if self.kind not in VALID_KINDS:
            raise ValueError(
                f"unknown matmul backend kind {self.kind!r}; "
                f"valid kinds: {', '.join(VALID_KINDS)}"
            )
        if self.mesh is not None and self.kind != MESH_KIND:
            raise ValueError(
                f"backend kind {self.kind!r} cannot take a mesh; only "
                f"{MESH_KIND!r} can"
            )

    @property
    def scheme_name(self) -> str:
        if self.kind == "strassen_oot":
            # The out-of-core runtime executes any scheme; resolve_auto
            # pins the decision's scheme as the single schemes entry.
            return self.schemes[0] if self.schemes else "strassen"
        if self.kind in ("strassen", "strassen_fused"):
            return "strassen"
        if self.kind == "winograd":
            return "winograd"
        raise ValueError(f"no scheme for backend kind {self.kind!r}")

    def effective_depth(self, m: int, k: int, n: int) -> int:
        """Largest usable depth: dims must stay divisible and above min_dim."""
        if self.kind == "naive" or self.depth <= 0:
            return 0
        depth = 0
        while (
            depth < self.depth
            and m % 2 == 0
            and k % 2 == 0
            and n % 2 == 0
            and min(m, k, n) >= self.min_dim
        ):
            m, k, n = m // 2, k // 2, n // 2
            depth += 1
        return depth


NAIVE_BACKEND = MatmulBackend(kind="naive")
AUTO_BACKEND = MatmulBackend(kind="auto", depth=3)


@functools.lru_cache(maxsize=4096)
def resolve_auto(
    m: int,
    k: int,
    n: int,
    dtype_name: str,
    backend: MatmulBackend,
    site: Optional[str] = None,
) -> MatmulBackend:
    """Resolve kind='auto' to a concrete backend for one (M, K, N, dtype).

    Runs at trace time with static shapes, so under jit each call site pays
    the cost-model lookup exactly once per shape; the lru_cache makes every
    later trace (and every other call site with the same shape and site
    tag) free. A persistent ``backend.tuning_cache`` survives process
    restarts. ``site`` keys the decision per call site (e.g. "attn.wq" vs
    "mlp.up"), so equal-shape projections can diverge under measured mode.
    """
    from repro.core import autotune

    cache = autotune.process_cache(backend.tuning_cache)
    decision = autotune.autotune(
        m,
        k,
        n,
        jnp.dtype(dtype_name),
        min_dim=backend.min_dim,
        max_depth=max(backend.depth, 1),
        schemes=backend.schemes,
        cache=cache,
        measure=backend.measure,
        precision=backend.precision,
        site=site,
        oot_budget=backend.device_budget,
    )
    return route_for(decision, backend)


def route_for(choice, backend: MatmulBackend) -> MatmulBackend:
    """The concrete backend that runs ``choice`` in place of ``backend``.

    ``choice`` is an autotune ``Decision`` or ``Candidate``: its kind is a
    routing kind, and its scheme rides along as the single ``schemes``
    entry (the out-of-core runtime executes any scheme; the einsum
    pipelines name theirs by kind). Everything else — min_dim, precision,
    device_budget, tuning_cache — stays the caller's. This is the one
    mapping from a decision to its route: :func:`resolve_auto` routes by it
    and measured autotuning times what it returns.
    """
    return dataclasses.replace(
        backend,
        kind=choice.kind,
        depth=choice.depth,
        schemes=(choice.scheme,),
        measure=False,
    )


def _matmul_oot(x, w, backend: MatmulBackend, lead, m: int, k: int, n: int):
    """Route one matmul through the out-of-core tagged-block runtime.

    Host-resident by construction: the operands are pulled to host, the
    scheduler stages leaf waves through device memory under
    ``backend.device_budget``, and the result returns as a jax array. A
    tracer here means the caller jitted the surrounding computation —
    impossible to honor (the pipeline IS the staging loop), so fail with
    the fix rather than a deep trace error.
    """
    import numpy as np

    from repro.blocks.scheduler import (
        leaf_bytes,
        min_depth_for_budget,
        pipelined_leaf_bytes,
        strassen_oot_matmul,
    )

    if isinstance(x, jax.core.Tracer) or isinstance(w, jax.core.Tracer):
        raise ValueError(
            "kind='strassen_oot' is a host-resident out-of-core pipeline and "
            "cannot run under jit; call it eagerly (launch/blocks_demo.py) or "
            "use kind='auto' without device_budget inside jitted code"
        )
    x_h = np.asarray(x).reshape(m, k)
    w_h = np.asarray(w)
    dtype = np.result_type(x_h.dtype, w_h.dtype)
    depth = max(backend.depth, 1)
    budget = backend.device_budget or pipelined_leaf_bytes(m, k, n, depth, dtype)
    # Deepen until the async pipeline's wave slot fits the budget — a
    # depth that only fits one bare leaf silently degrades the scheduler
    # to synchronous staging, which the autotuner's overlap-discounted
    # prediction did not price. Fall back to the merely-feasible depth
    # when no depth leaves pipeline headroom.
    if pipelined_leaf_bytes(m, k, n, depth, dtype) > budget:
        try:
            depth = min_depth_for_budget(m, k, n, budget, dtype, pipelined=True)
        except ValueError:
            if leaf_bytes(m, k, n, depth, dtype) > budget:
                depth = min_depth_for_budget(m, k, n, budget, dtype)
    leaf_backend = MatmulBackend(
        kind="auto", depth=2, min_dim=backend.min_dim,
        precision=resolve_precision(backend),
    )
    out, _ = strassen_oot_matmul(
        x_h,
        w_h,
        depth=depth,
        budget_bytes=budget,
        scheme=backend.scheme_name,
        backend=leaf_backend,
    )
    return jnp.asarray(out).reshape(*lead, n)


def matmul(
    x: jax.Array,
    w: jax.Array,
    backend: MatmulBackend = NAIVE_BACKEND,
    w_logical=None,
    site: Optional[str] = None,
) -> jax.Array:
    """``x @ w`` routed through the configured backend.

    Args:
      x: (..., K) activations — leading dims are flattened into M.
      w: (K, N) weights.
      backend: routing config.
      w_logical: optional (in_logical, out_logical) names for w's dims
        (e.g. ("fsdp", "d_ff")). When set, the Strassen pipeline pins every
        divide/leaf/combine level to the caller's tensor-parallel layout —
        without this GSPMD loses the sharding at the quadrant reshapes and
        silently replicates the leaf products (hypothesis log, EXPERIMENTS
        §Perf iteration 3).
      site: optional call-site tag ("attn.wq", "mlp.up", ...) for kind=
        'auto': keys the autotune decision (and its persistent cache entry)
        per call site, so same-shape projections can diverge and telemetry
        can attribute decisions.

    Returns:
      (..., N), same dtype as the naive path would produce.
    """
    if w.ndim != 2 or x.shape[-1] != w.shape[0]:
        raise ValueError(f"bad shapes {x.shape} @ {w.shape}")
    *lead, k = x.shape
    n = w.shape[1]
    m = 1
    for d in lead:
        m *= d

    # Disabled-tracer fast path: one attribute read + the shared no-op
    # context manager — this entry sits on every model projection, jitted
    # trace time included. When tracing, eager calls get true wall time;
    # under jit the span covers trace/lowering work (attr traced=True) and
    # the XLA-side timeline comes from the jax.profiler passthrough.
    mesh = backend.mesh
    with obs_tracer.get_tracer().span(
        "backend.matmul", cat="matmul", m=m, k=k, n=n,
        kind=backend.kind, site=site,
        traced=isinstance(x, jax.core.Tracer),
        mesh=None if mesh is None else "x".join(map(str, mesh.devices.shape)),
        strategy=None if mesh is None else MESH_STRATEGY,
    ):
        if mesh is not None:
            return _matmul_mesh(x, w, backend, w_logical, lead, m, k, n)
        return _matmul_routed(x, w, backend, w_logical, site, lead, m, k, n)


def _matmul_mesh(x, w, backend: MatmulBackend, w_logical, lead, m: int, k: int, n: int):
    """One product spread over ``backend.mesh`` by
    :func:`repro.core.distributed.strassen_fused_sharded`.

    The depth is the backend's effective depth for one device's row stripe
    (M over the row shards, rounded up: the strategy pads M to the stripe
    grain). C comes back row-sharded over the mesh.
    """
    from repro.core import distributed

    if w_logical is not None:
        raise ValueError(
            "a MatmulBackend with a mesh lays out its own operands (row "
            "stripes of A and C, B gathered); w_logical applies only to "
            "mesh=None"
        )
    _, rows = distributed.row_shards(backend.mesh)
    stripe = -(-m // rows)
    depth = backend.effective_depth(stripe, k, n)
    if depth == 0:
        raise ValueError(
            f"kind={backend.kind!r} over {rows} row shards needs a per-device "
            f"stripe ({stripe}, {k}) @ ({k}, {n}) that takes a Strassen level "
            f"(even dims, all >= min_dim={backend.min_dim}); use mesh=None "
            f"for smaller products"
        )
    out = distributed.strassen_fused_sharded(
        x.reshape(m, k), w, mesh=backend.mesh, depth=depth,
        scheme=backend.scheme_name, precision=resolve_precision(backend),
    )
    return out.reshape(*lead, n)


def _matmul_routed(x, w, backend, w_logical, site, lead, m, k, n):
    if backend.kind == "auto":
        if backend.device_budget is not None and (
            isinstance(x, jax.core.Tracer) or isinstance(w, jax.core.Tracer)
        ):
            # Under jit the eager-only out-of-core family is infeasible in
            # context: resolve without the budget so the decision (which
            # caches per shape) can never name a plan this call site
            # cannot execute.
            backend = dataclasses.replace(backend, device_budget=None)
        backend = resolve_auto(m, k, n, jnp.result_type(x, w).name, backend, site)

    if backend.kind == "strassen_oot":
        return _matmul_oot(x, w, backend, lead, m, k, n)

    precision = resolve_precision(backend)
    depth = backend.effective_depth(m, k, n) if backend.kind != "naive" else 0
    if depth == 0:
        return jnp.matmul(x, w, precision=precision)

    x2 = x.reshape(m, k)
    if backend.kind == "strassen_fused":
        # Pallas-fused path: divide/combine folded into the leaf kernel.
        from repro.kernels.strassen import ops as strassen_ops

        if w_logical is not None:
            # Pin the kernel's boundary shardings to the caller's
            # tensor-parallel layout — same rationale as the unfused
            # branch's per-level hooks: GSPMD loses sharding at quadrant
            # reshapes. At depth 1 (no outer BFS levels) the boundary
            # fully determines the pallas call's operand layout.
            from repro.models.sharding import constrain

            w_in, w_out = w_logical
            x2 = constrain(x2, "batch", None)
            w = constrain(w, w_in, w_out)
        out = strassen_ops.strassen_matmul_fused_padded(
            x2, w, depth=depth, precision=precision
        )
        if w_logical is not None:
            out = constrain(out, "batch", w_logical[1])
    else:
        from repro.models.sharding import constrain

        c_a = c_b = c_out = None
        if w_logical is not None:
            w_in, w_out = w_logical
            c_a = lambda t: constrain(t, None, "batch", None)
            c_b = lambda t: constrain(t, None, w_in, w_out)
            c_out = lambda t: constrain(t, None, "batch", w_out)
        out = strassen_matmul(
            x2,
            w,
            depth=depth,
            scheme=backend.scheme_name,
            precision=precision,
            constrain_a=c_a,
            constrain_b=c_b,
            constrain_out=c_out,
        )
    return out.reshape(*lead, n)


# --------------------------------------------------------------- solver ops
def _check_solver_kind(kind: str) -> None:
    if kind not in SOLVER_KINDS:
        raise ValueError(
            f"unknown solver kind {kind!r}; "
            f"valid kinds: {', '.join(SOLVER_KINDS)}"
        )


def _solver_jit_guard(op: str, *arrays) -> None:
    if any(isinstance(x, jax.core.Tracer) for x in arrays):
        raise ValueError(
            f"solver kind 'spin_oot' is a host-resident out-of-core "
            f"pipeline and cannot run {op} under jit; jit-safe solver "
            f"kinds: {', '.join(SOLVER_JIT_SAFE_KINDS)}"
        )


def _solver_backend_scheme(backend: MatmulBackend) -> str:
    """Scheme for the solver's nested multiplies (any backend kind)."""
    return backend.schemes[0] if backend.schemes else "strassen"


def _solver_oot_depth(
    op: str, n: int, nrhs: int, dtype, backend: MatmulBackend, budget: int,
    site: Optional[str],
) -> int:
    """Autotuned solver depth (cost-modeled, cached, telemetry-recorded)."""
    from repro.core import autotune

    decision = autotune.autotune_solver(
        op,
        n,
        jnp.dtype(dtype),
        nrhs=nrhs,
        oot_budget=budget,
        max_depth=max(backend.depth, 1) + 8,
        scheme=_solver_backend_scheme(backend),
        cache=autotune.process_cache(backend.tuning_cache),
        site=site,
    )
    return decision.depth


def inverse(
    a: jax.Array,
    backend: MatmulBackend = NAIVE_BACKEND,
    *,
    kind: str = "auto",
    depth: Optional[int] = None,
    site: Optional[str] = None,
) -> jax.Array:
    """Matrix inverse routed through the configured backend.

    ``kind='dense'`` is one device ``jnp.linalg.inv``; ``kind='spin_oot'``
    runs SPIN block-recursive inversion over the tagged block runtime
    (host-resident, device bytes capped by ``backend.device_budget``);
    ``kind='auto'`` picks dense unless the dense op's working set exceeds
    the budget. The recursion's block multiplies route through this
    backend's ``kind='auto'`` dispatcher.
    """
    _check_solver_kind(kind)
    if a.ndim != 2 or a.shape[0] != a.shape[1]:
        raise ValueError(f"inverse needs a square matrix, got {a.shape}")
    n = a.shape[0]
    traced = isinstance(a, jax.core.Tracer)
    if kind == "auto":
        item = jnp.dtype(jnp.result_type(a, jnp.float32)).itemsize
        over = (
            backend.device_budget is not None
            and 2 * n * n * item > backend.device_budget
        )
        kind = "spin_oot" if (over and not traced) else "dense"
    with obs_tracer.get_tracer().span(
        "backend.inverse", cat="matmul", n=n, kind=kind, site=site,
        traced=traced,
    ):
        if kind == "dense":
            with precision_scope(resolve_precision(backend)):
                return jnp.linalg.inv(a)
        _solver_jit_guard("inverse", a)
        import numpy as np

        from repro.blocks.solve import solver_min_depth_for_budget, spin_inverse_oot

        a_h = np.asarray(a)
        budget = backend.device_budget or _leaf_budget_fallback(n, n, a_h.dtype)
        if depth is None:
            depth = max(
                _solver_oot_depth("inverse", n, n, a_h.dtype, backend, budget, site),
                solver_min_depth_for_budget(n, budget, a_h.dtype, leaf_kind="inv"),
            )
        out, _ = spin_inverse_oot(
            a_h,
            depth=depth,
            budget_bytes=budget,
            scheme=_solver_backend_scheme(backend),
            backend=MatmulBackend(
                kind="auto", depth=2, min_dim=backend.min_dim,
                precision=resolve_precision(backend),
            ),
        )
        return jnp.asarray(out)


def solve_triangular(
    l: jax.Array,
    b: jax.Array,
    backend: MatmulBackend = NAIVE_BACKEND,
    *,
    lower: bool = True,
    kind: str = "auto",
    depth: Optional[int] = None,
    site: Optional[str] = None,
) -> jax.Array:
    """Triangular solve ``T @ X = B`` routed through the configured backend.

    Same routing contract as :func:`inverse`: 'dense' is one device
    ``jax.scipy.linalg.solve_triangular``, 'spin_oot' the block-recursive
    forward/backward substitution whose multiplies re-enter the matmul
    scheduler, 'auto' picks against ``backend.device_budget``.
    """
    _check_solver_kind(kind)
    if l.ndim != 2 or l.shape[0] != l.shape[1] or b.ndim != 2:
        raise ValueError(f"bad solve_triangular shapes {l.shape} / {b.shape}")
    if l.shape[1] != b.shape[0]:
        raise ValueError(f"bad solve_triangular shapes {l.shape} @ {b.shape}")
    n, nrhs = l.shape[0], b.shape[1]
    traced = isinstance(l, jax.core.Tracer) or isinstance(b, jax.core.Tracer)
    if kind == "auto":
        item = jnp.dtype(jnp.result_type(l, b, jnp.float32)).itemsize
        over = (
            backend.device_budget is not None
            and (n * n + 2 * n * nrhs) * item > backend.device_budget
        )
        kind = "spin_oot" if (over and not traced) else "dense"
    with obs_tracer.get_tracer().span(
        "backend.solve", cat="matmul", n=n, nrhs=nrhs, kind=kind,
        lower=lower, site=site, traced=traced,
    ):
        if kind == "dense":
            import jax.scipy.linalg as jsl

            with precision_scope(resolve_precision(backend)):
                return jsl.solve_triangular(l, b, lower=lower)
        _solver_jit_guard("solve_triangular", l, b)
        import numpy as np

        from repro.blocks.solve import (
            solver_min_depth_for_budget,
            triangular_solve_oot,
        )

        l_h, b_h = np.asarray(l), np.asarray(b)
        dtype = np.result_type(l_h.dtype, b_h.dtype)
        budget = backend.device_budget or _leaf_budget_fallback(n, nrhs, dtype)
        if depth is None:
            depth = max(
                _solver_oot_depth("solve", n, nrhs, dtype, backend, budget, site),
                solver_min_depth_for_budget(
                    n, budget, dtype, nrhs=nrhs, leaf_kind="trsm_lower"
                ),
            )
        out, _ = triangular_solve_oot(
            l_h,
            b_h,
            lower=lower,
            depth=depth,
            budget_bytes=budget,
            scheme=_solver_backend_scheme(backend),
            backend=MatmulBackend(
                kind="auto", depth=2, min_dim=backend.min_dim,
                precision=resolve_precision(backend),
            ),
        )
        return jnp.asarray(out)


def _leaf_budget_fallback(n: int, nrhs: int, dtype) -> int:
    """Budget when a solver is forced out-of-core without device_budget:
    one depth-1 dense leaf's working set (mirrors _matmul_oot's single
    pipelined-slot default)."""
    import numpy as np

    item = np.dtype(np.result_type(np.dtype(dtype), np.float32)).itemsize
    half = -(-n // 2)
    return max(2 * half * half, half * half + 2 * half * nrhs) * item
