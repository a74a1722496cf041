"""The one traffic generator: a closed loop read from a mix's data file.

A mix (``traffic/<mix>.json``) gives:

* ``loop``: ``"closed"``, the only kind so far: one caller sends the next
  product when the last one has returned;
* ``clients``: callers in the loop (1);
* ``operand_pairs``: the kind of each seeded (A, B) pair the products
  cycle through (``bench/operands.py``), so that no product repeats its
  predecessor's inputs; ``integer_max`` bounds the entries of
  ``"integers"`` pairs;
* ``compare``: ``"all"`` products of the window, or how many of each
  operand pair's products the correctness check samples (drawn from the
  seed), so that every pair is always compared;
* ``route``: what the entry runs (kind, depth, scheme, leaf).

No product starts after the window's ``seconds`` have passed, but the
window always holds one product of each pair, so each pair is compared; it
runs from the first product's start to the last one's end.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np


@dataclasses.dataclass
class LoopResult:
    latencies: List[float]
    window_s: float
    kept: List[Tuple[int, int, object]]  # (product index, operand pair, output)

    @property
    def count(self) -> int:
        return len(self.latencies)


class Sampler:
    """Keeps every output (``"all"``) or, per operand pair, a seeded
    reservoir sample of ``k`` of that pair's outputs."""

    def __init__(self, compare, seed: int):
        self.all = compare == "all"
        self.k = None if self.all else int(compare)
        self.rng = np.random.default_rng([seed, 0x5A3])
        self.by_pair: Dict[int, List[Tuple[int, int, object]]] = {}
        self.seen: Dict[int, int] = {}

    def offer(self, i: int, pair: int, out) -> None:
        kept = self.by_pair.setdefault(pair, [])
        seen = self.seen[pair] = self.seen.get(pair, 0) + 1
        if self.all or len(kept) < self.k:
            kept.append((i, pair, out))
            return
        j = int(self.rng.integers(0, seen))
        if j < self.k:
            kept[j] = (i, pair, out)

    @property
    def kept(self) -> List[Tuple[int, int, object]]:
        return sorted(x for v in self.by_pair.values() for x in v)


def run(
    traffic: dict,
    product: Callable[[int], object],
    seconds: float,
    seed: int,
    around: Optional[Callable[[], object]] = None,
) -> LoopResult:
    """Drive ``product(pair)`` in the mix's loop for ``seconds``.

    ``around()``, where given, returns a context manager entered around
    each product (the traced run's ``bench.product`` mark).
    """
    if traffic.get("loop") != "closed" or traffic.get("clients", 1) != 1:
        raise ValueError(f"unsupported traffic loop {traffic.get('loop')!r} "
                         f"with {traffic.get('clients')} clients")
    pairs = len(traffic["operand_pairs"])
    sampler = Sampler(traffic.get("compare", "all"), seed)
    latencies: List[float] = []
    start = time.perf_counter()
    deadline = start + seconds
    end = start
    i = 0
    while i < pairs or time.perf_counter() < deadline:
        t0 = time.perf_counter()
        if around is None:
            out = product(i % pairs)
        else:
            with around():
                out = product(i % pairs)
        end = time.perf_counter()
        latencies.append(end - t0)
        sampler.offer(i, i % pairs, out)
        out = None
        i += 1
    return LoopResult(latencies=latencies, window_s=end - start, kept=sampler.kept)
