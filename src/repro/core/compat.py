"""Thin wrappers over the JAX surfaces this repo pins a behaviour on.

The repo targets the installed JAX (0.9): ``jax.shard_map``, typed meshes
(``jax.sharding.AxisType``) and dict-returning ``Compiled.cost_analysis``.
Mesh construction, replication-unchecked shard_map, the cost-analysis
flattening and the Pallas-leaf probe go through this module so each
choice is made in exactly one place.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Sequence

import jax
from jax.sharding import AxisType

__all__ = [
    "make_mesh",
    "shard_map",
    "compiled_cost_analysis",
    "pallas_leaf_mode",
]


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], **kwargs):
    """``jax.make_mesh`` with Auto axis types (what every sharding rule here
    assumes: GSPMD propagates, nothing is explicit-typed)."""
    kwargs.setdefault("axis_types", (AxisType.Auto,) * len(tuple(axis_names)))
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names), **kwargs)


def shard_map(f, *, mesh, in_specs, out_specs):
    """Replication-unchecked ``jax.shard_map``.

    The mesh route's body is a Pallas call whose output shape carries no
    varying-mesh-axes annotation, so the ``check_vma`` verifier must be off.
    """
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


# The probe runs the fused leaf at its real tile size: a probe smaller than
# the tiles production calls use would pass where they fail.
_PROBE_TILE = 256


@functools.lru_cache(None)
def pallas_leaf_mode() -> str:
    """How the fused Strassen Pallas leaf runs on this host.

    Returns one of:
      'compiled'  — a TPU backend is present; the kernel compiles via Mosaic.
      'interpret' — no TPU, but interpret-mode ``pallas_call`` works (CPU
                    hosts, including host-platform multi-device test meshes).
      'none'      — no TPU and pallas is unavailable or broken in this jax
                    build; callers must use the jnp reference path.

    The probe executes one fused kernel at the production tile size rather
    than sniffing versions: autotune enumeration gates ``strassen_fused``
    candidates on this answer. On a TPU a failed probe raises — a Mosaic
    compile error there is a bug to surface, never a reason to route
    around the kernel. Cached per process (the backend is fixed after jax
    init).
    """
    import jax.numpy as jnp

    on_tpu = jax.default_backend() == "tpu"

    def probe():
        from repro.kernels.strassen.strassen import strassen1_matmul_pallas

        t = _PROBE_TILE
        x = jnp.ones((1, 4, t, t), jnp.float32)
        jax.block_until_ready(
            strassen1_matmul_pallas(
                x, x, block_m=t, block_n=t, block_k=t, interpret=not on_tpu
            )
        )

    if on_tpu:
        probe()
        return "compiled"
    try:
        probe()
    except Exception:
        return "none"
    return "interpret"


def compiled_cost_analysis(compiled: Any) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` as a flat dict ({} where the backend
    reports none)."""
    return dict(compiled.cost_analysis() or {})
