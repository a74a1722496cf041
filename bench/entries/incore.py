"""In-core products on one chip through ``repro.core.backend.matmul``.

The route names the backend kind and depth (``strassen_fused``: the outer
levels materialized and the last level in the Pallas leaf; ``strassen``:
every level materialized and XLA dot leaves). The product is one jitted
program, compiled and run once per operand pair in set-up.
"""
from __future__ import annotations

import jax


class Entry:
    def __init__(self, config: dict, route: dict, devices):
        from repro.core.backend import MatmulBackend

        self.config, self.route = config, route
        self.devices = list(devices)[:1]
        self.backend = MatmulBackend(
            kind=route["kind"], depth=route["depth"], min_dim=route.get("min_dim", 1024),
            schemes=(route.get("scheme", "strassen"),),
        )
        self.stats: list = []

    def make_operands(self, seed: int, kinds, integer_max: int) -> None:
        from bench.operands import operand_pairs

        c = self.config
        self.pairs = operand_pairs(
            seed, kinds, (c["m"], c["k"]), (c["k"], c["n"]), c["dtype"],
            jax.sharding.SingleDeviceSharding(self.devices[0]), integer_max,
        )

    def prepare(self) -> None:
        from repro.core import backend

        be = self.backend
        a, b = self.pairs[0]
        self.fn = jax.jit(lambda x, y: backend.matmul(x, y, be)).lower(a, b).compile()
        for a, b in self.pairs:
            jax.block_until_ready(self.fn(a, b))

    def product(self, i: int):
        a, b = self.pairs[i]
        return jax.block_until_ready(self.fn(a, b))

    def reference_operands(self, i: int):
        return self.pairs[i]

    def release(self) -> None:
        self.fn = None
