"""Distributed Strassen on a multi-device mesh (the paper's cluster demo).

Builds its meshes from the devices present and runs the distribution
strategies that fit them:
  * strassen_bfs_sharded — Stark/CAPS BFS leaf-batch sharding
  * strassen_2d          — Luo & Drake Strassen-2D (2D-parallel leaves)
  * strassen_shardmap    — explicit-collective 7-way level (needs >= 7
                           devices: one per Strassen product)

On a TPU host it uses the chips; on a CPU host, force host devices first:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/strassen_distributed.py
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh

from repro.core.distributed import strassen_2d, strassen_bfs_sharded, strassen_shardmap
from repro.launch.roofline import collective_bytes

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
print(f"mesh: {dict(mesh.shape)}")

bfs = jax.jit(functools.partial(strassen_bfs_sharded, mesh=mesh, depth=2, precision="highest"))
got = bfs(a, b)
print(f"bfs_sharded   max|err| = {float(jnp.max(jnp.abs(got - want))):.3e}")

s2d = jax.jit(functools.partial(strassen_2d, mesh=mesh, depth=1, precision="highest"))
got = s2d(a, b)
print(f"strassen_2d   max|err| = {float(jnp.max(jnp.abs(got - want))):.3e}")

if len(devices) >= 7:
    mesh7 = Mesh(np.asarray(devices[:7]), ("mult",), axis_types=(AxisType.Auto,))
    smap = jax.jit(functools.partial(strassen_shardmap, mesh=mesh7, precision="highest"))
    got = smap(a, b)
    print(f"shardmap(7)   max|err| = {float(jnp.max(jnp.abs(got - want))):.3e}")
else:
    print(f"shardmap(7)   skipped: needs 7 devices, have {len(devices)}")

# show the collective footprint of the BFS pipeline
txt = bfs.lower(a, b).compile().as_text()
print("collective bytes (bfs, depth=2):", collective_bytes(txt))
