"""Out-of-core products: host operands streamed through a device budget.

The window drives ``repro.blocks.scheduler.strassen_oot_matmul`` with the
route's depth and a leaf backend pinned to the route's ``leaf`` kind, so no
per-process calibration decides anything. Operands are made on the device
from the seed and held on the host, as a deployment's would be. Set-up runs
one product one level shallower on the top-left quarter of the first pair:
its leaves have the timed product's shapes, so every program the window
runs is compiled before it opens, at a fraction of a whole product's host
work.
"""
from __future__ import annotations

import jax
import numpy as np


class Entry:
    def __init__(self, config: dict, route: dict, devices):
        from repro.core.backend import MatmulBackend

        self.config, self.route = config, route
        self.devices = list(devices)[:1]
        self.leaf = MatmulBackend(kind=route["leaf"])
        self.stats: list = []

    def make_operands(self, seed: int, kinds, integer_max: int) -> None:
        from bench.operands import operand_pairs

        c = self.config
        dev = operand_pairs(
            seed, kinds, (c["m"], c["k"]), (c["k"], c["n"]), c["dtype"],
            jax.sharding.SingleDeviceSharding(self.devices[0]), integer_max,
        )
        self.pairs = [(np.asarray(a), np.asarray(b)) for a, b in dev]
        for a, b in dev:
            a.delete()
            b.delete()

    def _run(self, i: int, depth=None):
        from repro.blocks import scheduler

        a, b = self.pairs[i]
        depth = self.route["depth"] if depth is None else depth
        if depth < self.route["depth"]:
            cut = self.route["depth"] - depth
            a = a[: a.shape[0] >> cut, : a.shape[1] >> cut]
            b = b[: b.shape[0] >> cut, : b.shape[1] >> cut]
        out, stats = scheduler.strassen_oot_matmul(
            a, b,
            depth=depth,
            budget_bytes=self.config["device_budget_bytes"],
            scheme=self.route.get("scheme", "strassen"),
            backend=self.leaf,
        )
        return out, stats

    def prepare(self) -> None:
        self._run(0, depth=max(self.route["depth"] - 1, 1))

    def product(self, i: int):
        out, stats = self._run(i)
        self.stats.append(stats.to_dict())
        return out

    def reference_operands(self, i: int):
        a, b = self.pairs[i]
        dev = self.devices[0]
        return jax.device_put(a, dev), jax.device_put(b, dev)

    def release(self) -> None:
        pass

    def phase_labeler(self, summary):
        """Split a device-idle gap among the program's phases on the host.

        Each traced product's phases follow from its ``OotStats``: divide,
        the leaf waves and combine, laid end to end from the product's
        start (the operands' ingest before divide is left to the caller).
        """
        spans = []
        for (p0, _p1), st in zip(summary.products, self.stats[-len(summary.products):]):
            t = p0
            for name, sec in (("oot.divide", st["divide_s"]), ("oot.leaf_waves", st["leaf_s"]),
                              ("oot.combine", st["combine_s"])):
                spans.append((name, t, t + sec * 1e9))
                t += sec * 1e9

        def split(g0, g1):
            parts = {}
            for name, s, e in spans:
                ov = min(g1, e) - max(g0, s)
                if ov > 0:
                    parts[name] = parts.get(name, 0.0) + ov
            return parts

        return split if spans else None
