"""Distributed Strassen on a JAX device mesh: the mesh route of the backend.

One product spread over a mesh, the way Stark spreads one product's block
rows over a cluster's executors: A and C are split into row stripes over
the mesh's :data:`ROW_AXES`, B is gathered to every device, and each device
runs the fused Strassen pipeline on its stripe
(:func:`strassen_fused_sharded`). ``backend.matmul`` reaches it through a
``MatmulBackend(kind="strassen_fused", mesh=...)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.coefficients import Scheme, STRASSEN, get_scheme
from repro.core.compat import shard_map as _shard_map

__all__ = [
    "ROW_AXES",
    "strassen_fused_sharded",
    "row_shards",
]

# The mesh axes a product's rows shard over, where the mesh has them: data
# and model, for this repo's canonical meshes.
ROW_AXES = ("data", "model")


def row_shards(mesh: Mesh):
    """The axes of :data:`ROW_AXES` present in ``mesh``, and how many row
    stripes they split a matrix into."""
    axes = tuple(ax for ax in ROW_AXES if ax in mesh.shape)
    if not axes:
        raise ValueError(f"none of {ROW_AXES} in mesh axes {tuple(mesh.shape)}")
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
    precision=None,
) -> jax.Array:
    """Row-parallel Strassen with the fused Pallas leaf under shard_map.

    Each device owns an M-stripe of A (and of C) with B replicated — the
    communication pattern of the classic row-parallel matmul (one B
    broadcast, no combine collective) — but the per-device product runs
    :func:`repro.kernels.strassen.ops.strassen_matmul_fused`, so the last
    Strassen level (divide + 7 MXU products + combine) never leaves VMEM.
    This is the Huang-et-al. fused-leaf insight lifted to the mesh: the
    7/4x M-term blowup of a materialized last level never touches HBM,
    and the only interconnect term is the one-time B replication.

    Rows shard over EVERY :data:`ROW_AXES` axis present in the mesh, so
    the whole device count carries leaf work. M is zero-padded up to the
    stripe grain (row shards * 2**depth) and sliced back, so any M
    executes.

    On a TPU the kernel compiles; elsewhere it runs in interpret mode
    (bit-faithful, slow). Where Pallas cannot run at all the call fails,
    as the local fused path does.
    """
    from repro.kernels.strassen.ops import strassen_matmul_fused_padded

    if isinstance(scheme, str):
        scheme = get_scheme(scheme)
    axes, n_rows = row_shards(mesh)
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
