"""CPU tests of the out-of-core runtime's readers on hand-built readings.

    python -m pytest bench/tests -q

Each reader is checked against a number worked out by hand, and against a
reading that lacks its input (a program without the counter or span, no
trace, no ``oot.leaf_waves`` event), which must give None.
"""
from __future__ import annotations

import pytest

from bench import harness, trace

NEW = ("oot_ingest_s", "oot_assemble_s", "oot_divide_gbps", "oot_waves_exposed")


@pytest.fixture(scope="module")
def cell():
    return harness.resolve(harness.load_spec(), "oot8k-d2")


def _reading(cell, stats=(), summary=None):
    return harness.Reading(cell=cell, setup_s=1.0, latencies=[1.5, 1.5], window_s=3.0,
                           stats=list(stats), trace=summary, device_kind="TPU v5 lite",
                           devices=1)


# Two products' OotStats as the program now writes them (the keys the
# readers use, and one the parent also had).
STATS = [
    {"ingest_s": 0.03, "divide_s": 0.8, "divide_bytes": 6_207_569_920,
     "leaf_s": 0.4, "combine_s": 0.23, "assemble_s": 0.05},
    {"ingest_s": 0.05, "divide_s": 0.9, "divide_bytes": 6_207_569_920,
     "leaf_s": 0.4, "combine_s": 0.24, "assemble_s": 0.07},
]


def _summary(host_events, busy, window=(0.0, 100.0)):
    dev = trace.DeviceTime(plane="/device:TPU:0", busy_ns=sum(e - s for s, e in busy),
                           busy=busy, by_op={}, by_class={})
    return trace.TraceSummary(window=window, products=[window], devices=[dev],
                              host_events=host_events)


def test_the_new_readers_are_cell_metrics(cell):
    names = {m["name"]: m for m in cell.per_layer}
    for name in NEW:
        m = names[name]
        assert m["layer"] == "out-of-core runtime" and m["moves"] == "oot_product_s"
        assert m["workloads"] == ["oot8k-d2"]


def test_phase_seconds_are_means_over_products(cell):
    r = _reading(cell, STATS)
    assert harness.metric_reader("oot_ingest_s")(r) == pytest.approx(0.04)
    assert harness.metric_reader("oot_assemble_s")(r) == pytest.approx(0.06)


def test_divide_rate_is_bytes_over_seconds(cell):
    r = _reading(cell, STATS)
    assert harness.metric_reader("oot_divide_gbps")(r) == pytest.approx(
        2 * 6_207_569_920 / 1.7 / 1e9)


def test_waves_exposed_by_hand(cell):
    # Waves at [5, 45] and [48, 60] ns, and one that starts before the window
    # (clipped to [0, 4]); the device runs [1, 3], [10, 20], [40, 50] and
    # [58, 70]. Inside the waves it is busy 2 + 10 + (5 + 2) + 2 = 21 ns of
    # 4 + 40 + 12 = 56 ns.
    events = [("oot.divide", 0.0, 5.0), ("oot.leaf_waves", 5.0, 45.0),
              ("oot.leaf_waves", 48.0, 60.0), ("oot.leaf_waves", -10.0, 4.0),
              ("oot.combine", 60.0, 70.0)]
    busy = [(1.0, 3.0), (10.0, 20.0), (40.0, 50.0), (58.0, 70.0)]
    r = _reading(cell, summary=_summary(events, busy))
    assert harness.metric_reader("oot_waves_exposed")(r) == pytest.approx(
        100.0 * (1 - 21.0 / 56.0))


def test_waves_exposed_reads_a_share(cell):
    idle = _summary([("oot.leaf_waves", 10.0, 20.0)], [(30.0, 40.0)])
    full = _summary([("oot.leaf_waves", 10.0, 20.0)], [(0.0, 100.0)])
    assert harness.metric_reader("oot_waves_exposed")(_reading(cell, summary=idle)) == 100.0
    assert harness.metric_reader("oot_waves_exposed")(_reading(cell, summary=full)) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_reader_without_its_input_reads_nothing(cell, name):
    read = harness.metric_reader(name)
    assert read(_reading(cell)) is None
    # A program that lacks the spans and counter: its stats have no such
    # keys, and its trace has no oot.leaf_waves event.
    parent = [{k: v for k, v in s.items() if k in ("divide_s", "leaf_s", "combine_s")}
              for s in STATS]
    no_waves = _summary([("oot.divide", 0.0, 50.0)], [(10.0, 20.0)])
    assert read(_reading(cell, parent, no_waves)) is None
