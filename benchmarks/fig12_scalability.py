"""Paper Fig. 12: scalability with executor count.

Wall-clock on forced host devices cannot show real speedup, so this
measures what IS measurable from the compiled artifact — the per-device
work division — by lowering the distributed Strassen under meshes of 1..8
CPU host devices in a SUBPROCESS (device count is locked at jax init) and
reporting per-device HLO FLOPs. Ideal scaling halves
per-device FLOPs per doubling; the derived column reports the achieved
parallel efficiency vs T(1)/n, exactly the paper's ideal-line comparison.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit

_CHILD = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={sys.argv[1]}"
import functools, jax, jax.numpy as jnp
from repro.core.compat import compiled_cost_analysis, make_mesh
from repro.core.distributed import strassen_bfs_sharded
from repro.runtime.elastic import plan_mesh
n_dev = int(sys.argv[1])
n = 1024
shape, axes = ((n_dev,), ("data",)) if n_dev > 1 else ((1,), ("data",))
mesh = make_mesh(shape, axes)
a = jax.ShapeDtypeStruct((n, n), jnp.float32)
fn = jax.jit(functools.partial(
    strassen_bfs_sharded, mesh=mesh, depth=2, batch_axes=("data",)))
compiled = fn.lower(a, a).compile()
cost = compiled_cost_analysis(compiled)
print(json.dumps({"devices": n_dev, "flops": cost.get("flops", 0.0)}))
"""


def run():
    rows = []
    base = None
    for n_dev in (1, 2, 4, 8):
        env = dict(os.environ)
        env["PYTHONPATH"] = "src"
        # The children only count HLO FLOPs on forced host devices; pinned to
        # the CPU they never contend for an accelerator the parent holds.
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, "-c", _CHILD, str(n_dev)],
            capture_output=True, text=True, env=env, cwd=os.path.dirname(__file__) + "/..",
        )
        line = out.stdout.strip().splitlines()[-1]
        data = json.loads(line)
        if base is None:
            base = data["flops"]
        eff = base / (data["flops"] * n_dev) if data["flops"] else 0.0
        rows.append(
            emit(
                f"fig12/per_device_flops/dev{n_dev}",
                data["flops"] * 1e-6,  # report as 'us' column = MFLOP count
                f"parallel_efficiency={eff:.2f}",
            )
        )
    return rows
