"""Distributed Strassen on a multi-device mesh (the paper's cluster demo).

One product spread over a (data, model) mesh of the devices present, the
way Stark spreads one product's block rows over a cluster's executors:
``backend.matmul`` with ``MatmulBackend(kind="strassen_fused", mesh=...)``
splits A and C into row stripes over the mesh, gathers B to every device,
and runs the fused Strassen pipeline on each device's stripe.

On a TPU host it uses the chips; on a CPU host, force host devices first
(the Pallas leaf then runs in interpret mode):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/strassen_distributed.py
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core import backend

devices = jax.devices()
print(f"devices: {len(devices)} x {devices[0].device_kind}")
rng = np.random.default_rng(1)
a = jnp.asarray(rng.standard_normal((512, 512)), jnp.float32)
b = jnp.asarray(rng.standard_normal((512, 512)), jnp.float32)
want = jnp.matmul(a, b, precision="highest")

# (data, model) over every device: two model columns when the count allows.
cols = 2 if len(devices) % 2 == 0 else 1
mesh = Mesh(
    np.asarray(devices).reshape(len(devices) // cols, cols),
    ("data", "model"),
    axis_types=(AxisType.Auto, AxisType.Auto),
)
rows = NamedSharding(mesh, P(("data", "model"), None))
print(f"mesh: {dict(mesh.shape)}")
a, b = jax.device_put(a, rows), jax.device_put(b, rows)

for depth in (1, 2):
    be = backend.MatmulBackend(
        kind="strassen_fused", depth=depth, min_dim=32, precision="highest", mesh=mesh
    )
    fn = jax.jit(lambda x, y: backend.matmul(x, y, be), out_shardings=rows)
    got = fn(a, b)
    shards = {s.device for s in got.addressable_shards}
    print(
        f"strassen_fused depth={depth} over {len(shards)} devices "
        f"max|err| = {float(jnp.max(jnp.abs(got - want))):.3e}"
    )

# the collective footprint: B gathered to every device, nothing else
txt = fn.lower(a, b).compile().as_text()
ops = re.findall(r" = (\S+) ([a-z\-]+)\(", txt)
print(
    "collectives (mesh route, depth=2):",
    [f"{op} -> {shape}" for shape, op in ops
     if op.startswith(("all-", "reduce-scatter", "collective-"))],
)
