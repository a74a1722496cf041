"""One product spread over a mesh of chips through ``repro.core.backend.matmul``.

The configuration names the mesh (its shape and axis names); the route
names the backend kind and depth, and the backend gets the mesh. A, B and C
are row stripes over every mesh axis, so each timed product gathers B to
every chip and each chip runs the route's pipeline on its stripe of A. The
product is one jitted program with row-striped output, compiled and run
once per operand pair in set-up.
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class Entry:
    def __init__(self, config: dict, route: dict, devices):
        from repro.core.backend import MatmulBackend

        self.config, self.route = config, route
        shape, axes = tuple(config["mesh"]), tuple(config["mesh_axes"])
        self.devices = list(devices)[: math.prod(shape)]
        self.mesh = Mesh(np.asarray(self.devices).reshape(shape), axes)
        self.rows = NamedSharding(self.mesh, P(axes, None))
        # Built before any operand, so a program without mesh support
        # refuses the cell at once.
        self.backend = MatmulBackend(
            kind=route["kind"], depth=route["depth"], min_dim=route.get("min_dim", 1024),
            schemes=(route.get("scheme", "strassen"),), mesh=self.mesh,
        )
        self.stats: list = []

    def make_operands(self, seed: int, kinds, integer_max: int) -> None:
        from bench.operands import operand_pairs

        c = self.config
        self.pairs = operand_pairs(
            seed, kinds, (c["m"], c["k"]), (c["k"], c["n"]), c["dtype"], self.rows, integer_max,
        )

    def prepare(self) -> None:
        from repro.core import backend

        be = self.backend
        a, b = self.pairs[0]
        fn = jax.jit(lambda x, y: backend.matmul(x, y, be), out_shardings=self.rows)
        self.fn = fn.lower(a, b).compile()
        for a, b in self.pairs:
            jax.block_until_ready(self.fn(a, b))

    def product(self, i: int):
        a, b = self.pairs[i]
        return jax.block_until_ready(self.fn(a, b))

    def reference_operands(self, i: int):
        return self.pairs[i]

    def release(self) -> None:
        self.fn = None
