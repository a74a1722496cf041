"""Distributed Strassen on a JAX device mesh.

Two distribution strategies, mirroring the taxonomy in the paper's related
work (§II) and adapted to TPU SPMD:

1. :func:`strassen_bfs_sharded` — Stark's own strategy (and CAPS's
   "unlimited memory" BFS scheme): take ``depth`` BFS steps so the leaf
   batch of 7^depth independent block products is sharded across devices;
   divide/combine levels are einsums whose resharding becomes XLA
   collectives. This is the paper's technique, SPMD-native: where Spark
   shuffles blocks between executors keyed by M-index tags, GSPMD moves
   exactly the blocks whose leaf shard differs — the tag IS the batch
   coordinate.

2. :func:`strassen_2d` — the "Strassen-2D" hybrid of Luo & Drake (paper
   §II-A): run Strassen levels at the top, and execute every leaf product
   as a classic 2D-parallel matmul over the (data, model) mesh. Uses O(1)
   extra memory per device relative to the naive distributed matmul and is
   the right choice when 7^depth is small compared to the device count.

3. :func:`strassen_shardmap` — an explicit-collective shard_map rendition
   of one BFS level over a 7-way mesh axis: every device group owns one
   M_p product; combine is a single weighted psum. This exists to make the
   communication pattern inspectable (tests assert its HLO contains exactly
   one psum) and as the template the Pallas-fused path follows.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.coefficients import Scheme, STRASSEN, get_scheme
from repro.core import strassen as _s
from repro.core.compat import shard_map as _shard_map

__all__ = [
    "strassen_bfs_sharded",
    "strassen_2d",
    "strassen_shardmap",
    "strassen_fused_sharded",
    "row_shards",
    "MESH_STRATEGIES",
    "register_strategy",
    "get_strategy",
    "available_strategies",
]


def _constraint(x: jax.Array, mesh: Mesh, spec: P) -> jax.Array:
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def strassen_bfs_sharded(
    a: jax.Array,
    b: jax.Array,
    *,
    mesh: Mesh,
    depth: int,
    scheme: Scheme | str = STRASSEN,
    batch_axes: Sequence[str] = ("data", "model"),
    leaf_fn=None,
    precision=None,
) -> jax.Array:
    """Stark/CAPS-BFS: shard the 7^depth leaf batch across ``batch_axes``.

    The input/output matrices are row-sharded across the same axes (the
    natural layout for an RDD of block-rows). GSPMD inserts the all-to-all
    style collectives that correspond to Stark's divide/combine shuffles.
    """
    if isinstance(scheme, str):
        scheme = get_scheme(scheme)
    axes = tuple(batch_axes)
    row_spec = P(axes, None)
    # Leaf batch m = 7^depth over the FIRST axis only (uneven shards are
    # padded: 343 over 16 wastes 2.6%); block rows over the second axis.
    # Sharding m over the full 256-device mesh replicates whenever
    # m < devices — measured 33x flops blowup — so rows carry the rest.
    if len(axes) > 1:
        batch_spec = P(axes[0], axes[1:], None)
    else:
        batch_spec = P(axes[0], None, None)

    a = _constraint(a, mesh, row_spec)
    b = _constraint(b, mesh, row_spec)

    a_coef, b_coef, c_coef = scheme.a_coef, scheme.b_coef, scheme.c_coef

    ta, tb = a[None], b[None]
    for _ in range(depth):
        ta = _constraint(_s.divide_level(ta, a_coef), mesh, batch_spec)
        tb = _constraint(_s.divide_level(tb, b_coef), mesh, batch_spec)

    out_dtype = jnp.result_type(a, b)
    acc_dtype = _s.accumulation_dtype(out_dtype)
    if leaf_fn is None:
        prod = jnp.einsum(
            "mij,mjk->mik", ta, tb, precision=precision,
            preferred_element_type=acc_dtype,
        )
    else:
        prod = leaf_fn(ta, tb).astype(acc_dtype)
    prod = _constraint(prod, mesh, batch_spec)

    for _ in range(depth):
        prod = _constraint(_s.combine_level(prod, c_coef), mesh, batch_spec)
    return _constraint(prod[0].astype(out_dtype), mesh, row_spec)


def strassen_2d(
    a: jax.Array,
    b: jax.Array,
    *,
    mesh: Mesh,
    depth: int,
    scheme: Scheme | str = STRASSEN,
    row_axis: str = "data",
    col_axis: str = "model",
    precision=None,
) -> jax.Array:
    """Strassen-2D (Luo & Drake): Strassen on top, 2D-parallel leaves.

    Every one of the 7^depth leaf products is computed as a classic
    2D-sharded matmul: A_leaf row-sharded over ``row_axis``, B_leaf
    col-sharded over ``col_axis``, C_leaf sharded over both. The leaf batch
    stays replicated, so combine levels are communication-free — trading
    leaf-stage bandwidth for a collective-free combine (the reverse of the
    BFS scheme; see EXPERIMENTS.md §Perf for the crossover).
    """
    if isinstance(scheme, str):
        scheme = get_scheme(scheme)

    def leaf(ta: jax.Array, tb: jax.Array) -> jax.Array:
        ta = _constraint(ta, mesh, P(None, row_axis, None))
        tb = _constraint(tb, mesh, P(None, None, col_axis))
        out = jnp.einsum("mij,mjk->mik", ta, tb, precision=precision)
        return _constraint(out, mesh, P(None, row_axis, col_axis))

    out = _s.strassen_matmul(a, b, depth=depth, scheme=scheme, leaf_fn=leaf)
    return _constraint(out, mesh, P(row_axis, col_axis))


def strassen_shardmap_2d(
    a: jax.Array,
    b: jax.Array,
    *,
    mesh: Mesh,
    rows_axis: str = "rows",
    mult_axis: str = "mult",
    scheme: Scheme | str = STRASSEN,
    precision=None,
) -> jax.Array:
    """Explicit one-level Strassen on a (rows x 7) grid — zero GSPMD guessing.

    The paper's processor layout, TPU-native: the 7-way ``mult`` axis owns
    one M_p each (Stark's seven parallel sub-matrix groups), the ``rows``
    axis splits each M_p's row range (Stark's per-executor block rows).
    Inputs replicated (n^2 bf16 fits HBM at n=16384): divide is LOCAL
    arithmetic; the ONLY collective is one psum over ``mult`` that fuses
    Stark's entire combine phase — measured vs the GSPMD variants this is
    the version whose collective term matches the napkin math.
    """
    if isinstance(scheme, str):
        scheme = get_scheme(scheme)
    n = a.shape[0]
    n_rows = mesh.shape[rows_axis]
    assert mesh.shape[mult_axis] == scheme.n_mults
    blk = (n // 2) // n_rows
    a_coef = jnp.asarray(scheme.a_coef)
    b_coef = jnp.asarray(scheme.b_coef)
    c_coef = jnp.asarray(scheme.c_coef)

    def body(a_rep, b_rep):
        r = jax.lax.axis_index(rows_axis)
        p = jax.lax.axis_index(mult_axis)
        aq = _s.split_quadrants(a_rep)  # (4, n/2, n/2) local views
        bq = _s.split_quadrants(b_rep)
        # left operand: only OUR row stripe of the combo (slice THEN add)
        aq_rows = jax.lax.dynamic_slice_in_dim(aq, r * blk, blk, axis=1)
        left = jnp.einsum("q,qij->ij", a_coef[p].astype(a_rep.dtype), aq_rows)
        right = jnp.einsum("q,qij->ij", b_coef[p].astype(b_rep.dtype), bq)
        mp_rows = jnp.matmul(left, right, precision=precision)  # (blk, n/2)
        contrib = c_coef[:, p].astype(mp_rows.dtype)[:, None, None] * mp_rows[None]
        return jax.lax.psum(contrib, mult_axis)  # (4, blk, n/2)

    quads = _shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P()),
        out_specs=P(None, rows_axis, None),
    )(a, b)  # (4, n/2, n/2)
    return _s.merge_quadrants(quads)


def strassen_shardmap_3d(
    a: jax.Array,
    b: jax.Array,
    *,
    mesh: Mesh,
    rb_axis: str = "rb",
    cb_axis: str = "cb",
    mult_axis: str = "mult",
    scheme: Scheme | str = STRASSEN,
    precision=None,
    merge: bool = True,
) -> jax.Array:
    """Explicit one-level Strassen on an (rb x cb x 7) grid.

    merge=False returns C in quadrant-block layout (4, n/2, n/2) — the
    paper's own Block data structure — avoiding the cross-shard interleave
    of merge_quadrants (a pure layout change that costs a full reshard).

    Iteration 3 of the matmul hillclimb: shardmap_2d was memory-bound on
    whole-quadrant right operands. Here each device owns one (row-stripe,
    col-stripe) tile of one M_p: it reads only its stripes of the
    replicated inputs, computes a (blk_r, n/2) x (n/2, blk_c) product, and
    the single psum over ``mult`` both combines Stark's seven products and
    leaves C tile-sharded over (rb, cb) — the 2.5D-Strassen layout of
    CAPS, with the contraction dim kept local.
    """
    if isinstance(scheme, str):
        scheme = get_scheme(scheme)
    n = a.shape[0]
    nrb, ncb = mesh.shape[rb_axis], mesh.shape[cb_axis]
    assert mesh.shape[mult_axis] == scheme.n_mults
    blk_r = (n // 2) // nrb
    blk_c = (n // 2) // ncb
    a_coef = jnp.asarray(scheme.a_coef)
    b_coef = jnp.asarray(scheme.b_coef)
    c_coef = jnp.asarray(scheme.c_coef)

    n2 = n // 2

    def body(a_rep, b_rep):
        r = jax.lax.axis_index(rb_axis)
        c = jax.lax.axis_index(cb_axis)
        p = jax.lax.axis_index(mult_axis)

        # Static +/-1 combos per mult-shard: each branch reads ONLY the
        # quadrant stripes its coefficients touch (avg 12/7 of 4), sliced
        # DIRECTLY from the replicated inputs (split_quadrants' transpose
        # would materialize a full n^2 copy — measured +2.1 GB/device).
        def a_stripe(qi, r_):
            row0 = (qi // 2) * n2 + r_ * blk_r
            col0 = (qi % 2) * n2
            return jax.lax.dynamic_slice(a_rep, (row0, col0), (blk_r, n2))

        def b_stripe(qi, c_):
            row0 = (qi // 2) * n2
            col0 = (qi % 2) * n2 + c_ * blk_c
            return jax.lax.dynamic_slice(b_rep, (row0, col0), (n2, blk_c))

        def make_branch(pi):
            def branch(operands):
                a_, b_, r_, c_ = operands
                left = None
                for qi in range(4):
                    coef = float(scheme.a_coef[pi, qi])
                    if coef == 0.0:
                        continue
                    stripe = a_stripe(qi, r_)
                    term = stripe if coef == 1.0 else coef * stripe
                    left = term if left is None else left + term
                right = None
                for qi in range(4):
                    coef = float(scheme.b_coef[pi, qi])
                    if coef == 0.0:
                        continue
                    stripe = b_stripe(qi, c_)
                    term = stripe if coef == 1.0 else coef * stripe
                    right = term if right is None else right + term
                mp = jnp.matmul(left, right, precision=precision)
                cc = scheme.c_coef[:, pi]
                return jnp.stack(
                    [float(cc[k]) * mp for k in range(4)], axis=0
                )

            return branch

        contrib = jax.lax.switch(
            p, [make_branch(pi) for pi in range(scheme.n_mults)],
            (a_rep, b_rep, r, c),
        )
        return jax.lax.psum(contrib, mult_axis)  # (4, blk_r, blk_c)

    quads = _shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P()),
        out_specs=P(None, rb_axis, cb_axis),
    )(a, b)  # (4, n/2, n/2) tile-sharded
    return _s.merge_quadrants(quads) if merge else quads


def strassen_shardmap(
    a: jax.Array,
    b: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "mult",
    scheme: Scheme | str = STRASSEN,
    precision=None,
) -> jax.Array:
    """One explicit BFS level over a mesh axis of size 7 (rank of the scheme).

    Device p forms its operand combos locally (replicated inputs), computes
    M_p, then the combine is ONE weighted psum:

        C_quadrants = psum_p( c_coef[:, p] outer* M_p )

    i.e. Stark's combine groupByKey collapses to a single all-reduce whose
    payload is 4 * (n/2)^2 — strictly less than shuffling all 7 products.
    """
    if isinstance(scheme, str):
        scheme = get_scheme(scheme)
    if mesh.shape[axis] != scheme.n_mults:
        raise ValueError(
            f"axis {axis!r} must have size {scheme.n_mults}, got {mesh.shape[axis]}"
        )
    a_coef = jnp.asarray(scheme.a_coef)
    b_coef = jnp.asarray(scheme.b_coef)
    c_coef = jnp.asarray(scheme.c_coef)

    def body(a_loc, b_loc):
        p = jax.lax.axis_index(axis)
        aq = _s.split_quadrants(a_loc)  # (4, m/2, k/2)
        bq = _s.split_quadrants(b_loc)
        left = jnp.einsum("q,qij->ij", a_coef[p].astype(a_loc.dtype), aq)
        right = jnp.einsum("q,qij->ij", b_coef[p].astype(b_loc.dtype), bq)
        m_p = jnp.matmul(left, right, precision=precision)
        # Weighted contribution of M_p to all four C quadrants, then one psum.
        contrib = c_coef[:, p].astype(m_p.dtype)[:, None, None] * m_p[None]
        quads = jax.lax.psum(contrib, axis)
        return _s.merge_quadrants(quads)

    fn = _shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P()),
        out_specs=P(),
    )
    return fn(a, b)


def row_shards(mesh: Mesh, rows_axes: Sequence[str] = ("data", "model")):
    """The axes of ``rows_axes`` present in ``mesh``, and how many row
    stripes they split a matrix into."""
    axes = tuple(ax for ax in rows_axes if ax in mesh.shape)
    if not axes:
        raise ValueError(f"none of {tuple(rows_axes)} in mesh axes {tuple(mesh.shape)}")
    n_rows = 1
    for ax in axes:
        n_rows *= mesh.shape[ax]
    return axes, n_rows


def strassen_fused_sharded(
    a: jax.Array,
    b: jax.Array,
    *,
    mesh: Mesh,
    depth: int,
    scheme: Scheme | str = STRASSEN,
    rows_axes: Sequence[str] = ("data", "model"),
    precision=None,
) -> jax.Array:
    """Row-parallel Strassen with the fused Pallas leaf under shard_map.

    Each device owns an M-stripe of A (and of C) with B replicated — the
    communication pattern of the classic row-parallel matmul (one B
    broadcast, no combine collective) — but the per-device product runs
    :func:`repro.kernels.strassen.ops.strassen_matmul_fused`, so the last
    Strassen level (divide + 7 MXU products + combine) never leaves VMEM.
    This is the Huang-et-al. fused-leaf insight lifted to the mesh: the
    7/4x M-term blowup that dominates the BFS strategies' HBM traffic is
    gone, and the only interconnect term is the one-time B replication.

    Rows shard over EVERY ``rows_axes`` axis present in the mesh (data and
    model, for this repo's canonical meshes), so the whole device count
    carries leaf work — which is what :func:`repro.core.autotune
    .predict_seconds` charges it. M is zero-padded up to the stripe grain
    (row shards * 2**depth) and sliced back, so any shape the autotuner
    enumerates (dims divisible by 2**depth) executes.

    On a TPU the kernel compiles; elsewhere it runs in interpret mode
    (bit-faithful, slow). Where Pallas cannot run at all the call fails,
    as the local fused path does.
    """
    from repro.kernels.strassen.ops import strassen_matmul_fused_padded

    if isinstance(scheme, str):
        scheme = get_scheme(scheme)
    axes, n_rows = row_shards(mesh, rows_axes)
    m = a.shape[0]
    grain = n_rows * 2**depth
    mp = -(-m // grain) * grain
    a_p = jnp.pad(a, ((0, mp - m), (0, 0))) if mp != m else a

    def body(a_loc, b_rep):
        return strassen_matmul_fused_padded(
            a_loc, b_rep, depth=depth, scheme_name=scheme.name, precision=precision
        )

    fn = _shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axes, None), P()),
        out_specs=P(axes, None),
    )
    out = fn(a_p, b)
    return out[:m] if mp != m else out


# --------------------------------------------------------------------------
# Strategy registry — the autotuner's enumeration surface.
#
# Each entry maps a stable name to (fn, requires). ``requires(mesh, scheme)``
# answers whether the strategy can run on that mesh at all (e.g. the shardmap
# variants need a mesh axis exactly equal to the scheme rank); the autotuner
# only costs candidates whose requirement holds. Registration is open so
# future PRs (Pallas-fused mesh leaf, 2.5D variants) plug in without touching
# the dispatcher.
# --------------------------------------------------------------------------


def _axes_cover(mesh: Mesh, names: Sequence[str]) -> bool:
    return all(n in mesh.shape for n in names)


def _req_bfs(mesh: Mesh, scheme: Scheme) -> bool:
    return _axes_cover(mesh, ("data", "model"))


def _req_2d(mesh: Mesh, scheme: Scheme) -> bool:
    return _axes_cover(mesh, ("data", "model"))


def _req_shardmap(mesh: Mesh, scheme: Scheme) -> bool:
    return mesh.shape.get("mult") == scheme.n_mults


def _req_shardmap_2d(mesh: Mesh, scheme: Scheme) -> bool:
    return "rows" in mesh.shape and mesh.shape.get("mult") == scheme.n_mults


def _req_shardmap_3d(mesh: Mesh, scheme: Scheme) -> bool:
    return (
        _axes_cover(mesh, ("rb", "cb"))
        and mesh.shape.get("mult") == scheme.n_mults
    )


def _req_fused_sharded(mesh: Mesh, scheme: Scheme) -> bool:
    # Enumerable only where the Pallas leaf actually runs (compiled on TPU,
    # interpret elsewhere).
    from repro.core.compat import pallas_leaf_mode

    return "data" in mesh.shape and pallas_leaf_mode() != "none"


MESH_STRATEGIES: dict = {}


def register_strategy(name: str, fn, requires) -> None:
    """Register a distributed matmul strategy for autotune enumeration."""
    MESH_STRATEGIES[name] = (fn, requires)


def get_strategy(name: str):
    return MESH_STRATEGIES[name][0]


def available_strategies(mesh: Optional[Mesh], scheme: Scheme | str = STRASSEN):
    """Names of registered strategies whose mesh requirement holds."""
    if mesh is None:
        return []
    if isinstance(scheme, str):
        scheme = get_scheme(scheme)
    return [n for n, (_, req) in MESH_STRATEGIES.items() if req(mesh, scheme)]


register_strategy("strassen_bfs_sharded", strassen_bfs_sharded, _req_bfs)
register_strategy("strassen_2d", strassen_2d, _req_2d)
register_strategy("strassen_shardmap", strassen_shardmap, _req_shardmap)
register_strategy("strassen_shardmap_2d", strassen_shardmap_2d, _req_shardmap_2d)
register_strategy("strassen_shardmap_3d", strassen_shardmap_3d, _req_shardmap_3d)
register_strategy("strassen_fused_sharded", strassen_fused_sharded, _req_fused_sharded)
