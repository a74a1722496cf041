"""CPU tests of the mesh cell's collective reader.

    python -m pytest bench/tests -q

``collective_ms.mesh`` is checked on a four-device trace built by hand from
``XLA Ops`` events in the form a TPU records them (each op's HLO text), with
a number worked out by hand; on a recorded one-chip trace, which holds no
collective; and on a run without a trace, which must give None.
"""
from __future__ import annotations

import os
from types import SimpleNamespace

import pytest

from bench import harness, trace

READER = "collective_ms.mesh"
MS = 1_000_000  # ns


def _plane(name, lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=line, events=[
            SimpleNamespace(name=text, start_ns=float(s), duration_ns=float(d))
            for text, s, d in events])
        for line, events in lines.items()])


def _gather(i):
    return (f"%all-gather-start.{i} = (bf16[8192,32768]{{1,0}}, bf16[32768,32768]{{1,0}}) "
            f"all-gather-start(bf16[8192,32768]{{1,0}} %p), dimensions={{0}}")


def _done(i):
    return (f"%all-gather-done.{i} = bf16[32768,32768]{{1,0}} "
            f"all-gather-done((bf16[8192,32768]{{1,0}}, bf16[32768,32768]{{1,0}}) "
            f"%all-gather-start.{i})")


LEAF = ('%strassen_fused_leaf.1 = f32[7,4,2048,8192]{3,2,1,0} custom-call(bf16[7,4,2048,8192] '
        '%a, bf16[7,4,8192,8192] %b), custom_call_target="tpu_custom_call"')
SUM = "%fusion.30 = bf16[8192,32768]{1,0} fusion(f32[7,4,2048,8192] %x), kind=kLoop"


def _four_device_trace():
    """Two products of 100 ms; on device d each gathers B for (10 + d) ms in
    a -start and a -done half, then runs the leaf and one combine sum."""
    host = _plane("/host:CPU", {"python": [
        ("bench.window", 0, 200 * MS), ("bench.product", 0, 100 * MS),
        ("bench.product", 100 * MS, 100 * MS)]})
    devices = []
    for d in range(4):
        events = []
        for p in range(2):
            t = p * 100 * MS
            g = (10 + d) * MS
            events += [(_gather(p), t, g // 2), (_done(p), t + g // 2, g // 2),
                       (LEAF, t + g, 70 * MS), (SUM, t + g + 70 * MS, 5 * MS)]
        devices.append(_plane(f"/device:TPU:{d}", {"XLA Ops": events}))
    return trace.reduce_profile(SimpleNamespace(planes=[host] + devices))


@pytest.fixture(scope="module")
def cell():
    return harness.resolve(harness.load_spec(), "mesh4-32k-fused-d2")


def _reading(cell, summary, devices):
    return harness.Reading(cell=cell, setup_s=1.0, latencies=[0.1, 0.1], window_s=0.2,
                           stats=[], trace=summary, device_kind="TPU v5 lite",
                           devices=devices)


def test_reads_collective_device_ms_per_product(cell):
    summary = _four_device_trace()
    assert len(summary.devices) == 4 and len(summary.products) == 2
    # Per product, the devices gather for 10, 11, 12 and 13 ms: 11.5 on average.
    got = harness.metric_reader(READER)(_reading(cell, summary, 4))
    assert got == pytest.approx(11.5)
    # The leaf and the combine sum are not collective time.
    assert harness.metric_reader("divide_combine_ms")(_reading(cell, summary, 4)) == \
        pytest.approx(5.0)


def test_one_chip_trace_reads_no_collective_time(cell):
    summary = trace.load(os.path.join(harness.ROOT, "bench", "fixtures",
                                      "fused_d2_16384.xplane.pb"))
    assert harness.metric_reader(READER)(_reading(cell, summary, 1)) == 0.0


def test_without_a_trace_reads_nothing(cell):
    assert harness.metric_reader(READER)(_reading(cell, None, 4)) is None


def test_applies_to_the_mesh_cell_alone():
    spec = harness.load_spec()
    (metric,) = [m for m in spec["per_layer"] if m["name"] == READER]
    assert metric["workloads"] == ["mesh4-32k-fused-d2"]
    cells = {w["name"]: w for w in spec["workloads"]}
    assert cells["mesh4-32k-fused-d2"]["chips"] == 4
    cell = harness.resolve(spec, "mesh4-32k-fused-d2")
    assert READER in {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"product_s", "product_p95_s", "setup_s"}
