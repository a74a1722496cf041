"""CPU tests of the benchmark: its file layout, its yardstick and its faults.

    python -m pytest bench/tests -q

The trace reduction is checked on traces recorded on a TPU v5e
(``bench/fixtures``: three 16384^2 products each, strassen_fused d2 and
strassen d2, with the embedded binary stats and host paths stripped); the
harness is driven end to end at small sizes with the chip check skipped,
once sound, once with each control in the program's place, and once for
each fault a cell can have, which must read incorrect.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness, loop, operands, reference, trace, work

ROOT = harness.ROOT
FIXTURES = os.path.join(ROOT, "bench", "fixtures")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec()


# ------------------------------------------------------------ trace reduction
@pytest.fixture(scope="module")
def fused_trace():
    return trace.load(os.path.join(FIXTURES, "fused_d2_16384.xplane.pb"))


@pytest.fixture(scope="module")
def bfs_trace():
    return trace.load(os.path.join(FIXTURES, "bfs_d2_16384.xplane.pb"))


def test_trace_finds_window_products_and_device(fused_trace):
    assert len(fused_trace.devices) == 1
    assert fused_trace.devices[0].plane == "/device:TPU:0"
    assert len(fused_trace.products) == 3
    assert 0.0 < fused_trace.busy_s() <= fused_trace.window_s
    assert 0.0 <= fused_trace.idle_share() < 0.05


def test_trace_names_the_pallas_leaf_kernel(fused_trace):
    dev = fused_trace.devices[0]
    kernels = [k for k in dev.by_op if k.endswith("custom-call")]
    assert kernels == ["strassen_matmul_fused.1 custom-call"]
    leaf = dev.by_class[trace.LEAF_KERNEL]
    assert leaf == dev.by_op[kernels[0]]
    # Three products of about 52 ms of leaf kernel each (recorded on a v5e).
    assert 0.14e9 < leaf < 0.17e9
    assert trace.MATMUL not in dev.by_class and trace.COLLECTIVE not in dev.by_class


def test_trace_names_the_xla_dot_leaf(bfs_trace):
    dev = bfs_trace.devices[0]
    assert trace.LEAF_KERNEL not in dev.by_class
    assert dev.by_class[trace.MATMUL] == dev.by_op["fusion.2 fusion"]
    assert sum(dev.by_class.values()) == pytest.approx(sum(dev.by_op.values()))


def test_busy_is_the_union_inside_the_window(fused_trace):
    dev = fused_trace.devices[0]
    t0, t1 = fused_trace.window
    assert all(t0 <= s < e <= t1 for s, e in dev.busy)
    assert all(a[1] < b[0] for a, b in zip(dev.busy, dev.busy[1:]))
    assert dev.busy_ns <= sum(dev.by_class.values()) + 1


def test_idle_gaps_sum_to_the_idle_time(fused_trace):
    gaps = fused_trace.idle_gaps(10)
    total = sum(s for _, s in gaps)
    assert total == pytest.approx(fused_trace.window_s - fused_trace.busy_s(), rel=1e-6)
    ops = fused_trace.top_ops(3)
    assert ops[0][0] == "strassen_matmul_fused.1 custom-call"


@pytest.mark.parametrize(
    "text, want",
    [
        ("%fusion.2 = f32[49,4]{1,0} fusion(bf16[49,4]{1,0} %x), kind=kOutput, calls=%f",
         ("fusion.2", "fusion", trace.MATMUL)),
        ("%copy.4 = f32[7,2]{1,0} copy(f32[7,2]{0,1} %bitcast.12)", ("copy.4", "copy", trace.OTHER)),
        ('%k.1 = f32[7]{0} custom-call(bf16[7]{0} %a), custom_call_target="tpu_custom_call"',
         ("k.1", "custom-call", trace.LEAF_KERNEL)),
        ("%ag = (bf16[8]{0}, bf16[32]{0}) all-gather-start(bf16[8]{0} %p), dimensions={0}",
         ("ag", "all-gather-start", trace.COLLECTIVE)),
        ("%all-gather.5 = bf16[32]{0} all-gather(bf16[8]{0} %p)", ("all-gather.5", "all-gather",
                                                                    trace.COLLECTIVE)),
        ("dot_general.1", ("dot_general.1", "dot_general.1", trace.OTHER)),
    ],
)
def test_parse_and_classify(text, want):
    assert trace.parse_hlo_op(text) == want[:2]
    assert trace.classify(text) == want[2]


# ------------------------------------------------------------ work and peaks
def test_fused_d2_16384_work_by_hand():
    w = work.fused_leaf_kernel(16384, 16384, 16384, 2, "bfloat16")
    assert w.flops == 49 * 2 * 4096**3 == 6_734_508_720_128
    # (7, 4, 4096, 4096) bf16 operands twice, (7, 4, 4096, 4096) f32 result.
    assert w.bytes == 7 * 4 * 4096 * 4096 * (2 + 2 + 4)
    peaks = work.load_peaks("TPU v5 lite")
    assert work.least_seconds(w, peaks) == pytest.approx(w.flops / 197e12)


def test_mesh_device_share_work_by_hand():
    # 32768^2 rows split over 4 devices: (8192, 32768) @ (32768, 32768) each.
    w = work.fused_leaf_kernel(32768 // 4, 32768, 32768, 2, "bfloat16")
    assert w.flops == 49 * 2 * 2048 * 8192 * 8192
    assert w.bytes == 7 * 4 * (2048 * 8192 * 2 + 8192 * 8192 * 2 + 2048 * 8192 * 4)


def test_fused_depth_one_result_in_input_dtype():
    w = work.fused_leaf_kernel(512, 512, 512, 1, "bfloat16")
    assert w.flops == 7 * 2 * 256**3
    assert w.bytes == 4 * 256 * 256 * (2 + 2 + 2)


def test_strassen_leaves_count_and_shape_check():
    assert work.strassen_leaves(8192, 8192, 8192, 2).flops == 49 * 2 * 2048**3
    with pytest.raises(ValueError):
        work.strassen_leaves(100, 100, 100, 3)
    assert work.dense_product(16384, 16384, 16384) == 2 * 16384**3


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        work.load_peaks("TPU v9 imaginary")


def test_roofline_of_fixture_is_a_share(fused_trace, spec):
    cell = harness.resolve(spec, "dense16k-fused-d2")
    r = harness.Reading(cell=cell, setup_s=1.0, latencies=[0.1] * 3, window_s=0.3, stats=[],
                        trace=fused_trace, device_kind="TPU v5 lite", devices=1)
    share = harness.metric_reader("leaf_roofline.fused")(r)
    assert 50.0 < share < 100.0
    idle = harness.metric_reader("device_idle.dense")(r)
    assert 0.0 <= idle < 5.0
    dc = harness.metric_reader("divide_combine_ms")(r)
    assert 40.0 < dc < 70.0


def test_readers_without_a_trace_read_nothing(spec):
    for name in ("device_idle.dense", "leaf_roofline.fused", "divide_combine_ms",
                 "device_idle.oot", "oot_divide_s", "oot_combine_s"):
        cell = harness.resolve(spec, spec["workloads"][0]["name"])
        r = harness.Reading(cell=cell, setup_s=1.0, latencies=[1.0], window_s=1.0, stats=[],
                            trace=None, device_kind="TPU v5 lite", devices=1)
        assert harness.metric_reader(name)(r) is None


# ------------------------------------------------------------ BENCHMARK.json
def test_benchmark_json_shape(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "bench/run.py"] and spec["paths"] == ["bench"]
    assert 1 <= spec["run_seconds"] <= 51
    # A full check of 24 cells fits its 43200 s.
    assert (2 + 14 * 24) * (spec["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in spec[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in spec[k]}) == len(spec[k])
    metrics = spec["end_to_end"] + spec["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and os.path.exists(os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and NAME.match(w["traffic"])
    pairs = {(w["config"], w["traffic"]) for w in spec["workloads"]}
    assert len(pairs) == len(spec["workloads"])
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 2)
    for text in [x["why"] for k in ("configs", "workloads") for x in spec[k]] + [
        c["source"] for c in spec["configs"]
    ] + [m["layer"] for m in spec["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"


def test_every_cell_resolves_by_name(spec):
    for w in spec["workloads"]:
        cell = harness.resolve(spec, w["name"])
        assert cell.chips == w["chips"]
        assert harness.entry_class(cell.config["entry"])
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.metric_reader(m["name"]))
        assert set(cell.limits) == {"rel_fro", "int_mismatches"}
        assert cell.limits["int_mismatches"]["limit"] == 0
        assert {"loop", "clients", "operand_pairs", "route"} <= set(cell.traffic)
        # Every number compared has a pair of its kind in the mix.
        assert set(cell.traffic["operand_pairs"]) == {"normal", "integers"}


def test_every_cell_reports_what_its_layer_metrics_move(spec):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    cells = [w["name"] for w in spec["workloads"]]
    for m in spec["per_layer"]:
        moved = e2e[m["moves"]]
        for c in m.get("workloads", cells):
            assert c in cells
            assert "workloads" not in moved or c in moved["workloads"], (m["name"], c)
    for c in cells:
        names = {m["name"] for m in spec["end_to_end"] if harness._applies(m, c)}
        assert "setup_s" in names and len(names) >= 2
        assert any(harness._applies(m, c) for m in spec["per_layer"])


def test_files_under_paths_are_named_from_name_characters():
    for path in glob.glob(os.path.join(ROOT, "bench", "**", "*"), recursive=True):
        rel = os.path.relpath(path, ROOT)
        if "__pycache__" in rel or os.path.isdir(path):
            continue
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dense16k-fused-d2", "--seed",
         "4000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_run_refuses_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "repro" in p.stderr


# ------------------------------------------------------------ generator, seeds
def test_seeds_wider_than_32_bits_give_distinct_keys():
    a = operands.key_words(2**33 + 1, 4)
    b = operands.key_words(2**33 + 2, 4)
    assert a.shape == (4, 2) and not (a == b).all()
    assert (a == operands.key_words(2**33 + 1, 4)).all()
    with pytest.raises(ValueError):
        operands.key_words(-1, 1)


def test_operands_repeat_for_a_seed():
    import numpy as np

    kinds = ["normal", "normal", "integers"]
    x = operands.operand_pairs(2**35 + 7, kinds, (64, 32), (32, 16), "bfloat16")
    y = operands.operand_pairs(2**35 + 7, kinds, (64, 32), (32, 16), "bfloat16")
    assert x[0][0].shape == (64, 32) and x[1][1].shape == (32, 16)
    for (a, b), (c, d) in zip(x, y):
        assert np.array_equal(np.asarray(a), np.asarray(c))
        assert np.array_equal(np.asarray(b), np.asarray(d))
    assert not np.array_equal(np.asarray(x[0][0]), np.asarray(x[1][0]))
    ints = np.asarray(x[2][0]).astype(np.float32)
    assert np.array_equal(ints, np.round(ints)) and ints.min() == -8 and ints.max() == 8
    with pytest.raises(ValueError):
        operands.operand_pairs(1, ["uniform"], (8, 8), (8, 8), "bfloat16")


def test_exact_reference_counts_each_wrong_entry():
    import jax.numpy as jnp
    import numpy as np

    (a, b), = operands.operand_pairs(2**34 + 3, ["integers"], (256, 512), (512, 128),
                                     "bfloat16")
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    out = jnp.asarray(exact.astype(np.float32)).astype(jnp.bfloat16)
    assert reference.exact_numbers(a, b, out) == {"int_mismatches": 0}
    assert reference.exact_numbers(a, b, out.at[3, :5].add(1))["int_mismatches"] > 0
    assert reference.exact_numbers(a, b, out[:, :64])["int_mismatches"] == float("inf")


def test_closed_loop_cycles_pairs_and_samples_from_the_seed():
    seen = []
    res = loop.run({"loop": "closed", "clients": 1, "operand_pairs": ["normal"] * 2,
                    "compare": 2},
                   lambda p: seen.append(p) or p, 0.05, seed=9)
    assert seen[:4] == [0, 1, 0, 1] and res.count == len(seen)
    assert sorted(k[1] for k in res.kept) == [0, 0, 1, 1] and res.window_s > 0
    again = loop.Sampler(2, 9)
    for i, p in enumerate(seen):
        again.offer(i, p, p)
    assert [k[0] for k in again.kept] == [k[0] for k in res.kept]
    with pytest.raises(ValueError):
        loop.run({"loop": "open", "operand_pairs": ["normal"]}, lambda p: p, 0.01, seed=1)


# ------------------------------------------------------------ end to end, small
# Every cell of BENCHMARK.json, run at 256^2 (its budget scaled to match).
SMALL = {w["name"]: 256 for w in harness.load_spec()["workloads"]}


def small_cell(spec, name):
    cell = harness.resolve(spec, name)
    n = SMALL[name]
    cfg = dict(cell.config, m=n, k=n, n=n)
    if "device_budget_bytes" in cfg:
        cfg["device_budget_bytes"] = 2 * (3 * n * n * 2) // 3  # 2/3 of A+B+C
    route = dict(cell.traffic["route"], min_dim=64)
    return dataclasses.replace(cell, config=cfg, traffic=dict(cell.traffic, route=route))


def run_small(cell, seed=2**33 + 11):
    return harness.run_cell(cell, seed, 0.2, False, process_start=time.perf_counter(),
                            require_tpu=False, log=lambda m: None)


@pytest.mark.parametrize("name", sorted(SMALL))
def test_small_cell_runs_correct(spec, name):
    r = run_small(small_cell(spec, name))
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0 and r["compiles_in_window"] == 0
    assert list(r)[-1] == "checks"
    cell = harness.resolve(spec, name)
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert r["device"]["count"] == cell.chips


def _patch_product(monkeypatch, cell, fn):
    """Put ``fn(entry, i, out)`` in the place of the entry's product."""
    cls = harness.entry_class(cell.config["entry"])
    monkeypatch.setattr(harness, "entry_class", lambda name, root=ROOT: cls)
    orig = cls.product
    monkeypatch.setattr(cls, "product", lambda self, i: fn(self, i, orig(self, i)))


@pytest.mark.parametrize("name", sorted(SMALL))
def test_control_reads_incorrect(spec, monkeypatch, name):
    """The reference in float8 in the program's place fails the limits."""
    cell = small_cell(spec, name)

    def control(entry, i, out):
        a, b = entry.reference_operands(i)
        return reference.control_product(a, b, cell.config["dtype"])

    _patch_product(monkeypatch, cell, control)
    r = run_small(cell)
    assert not r["correct"]
    assert r["checks"]["rel_fro"]["value"] > r["checks"]["rel_fro"]["limit"]


@pytest.mark.parametrize("name", sorted(SMALL))
def test_bf16_accumulation_control_reads_incorrect(spec, monkeypatch, name):
    """The reference with bf16-held partial sums in the program's place
    fails the exact comparison on whole-number operands."""
    cell = small_cell(spec, name)

    def control(entry, i, out):
        a, b = entry.reference_operands(i)
        return reference.control_bf16_accumulation(a, b, 2 ** cell.traffic["route"]["depth"])

    _patch_product(monkeypatch, cell, control)
    r = run_small(cell)
    assert not r["correct"]
    assert r["checks"]["int_mismatches"]["value"] > 0


@pytest.mark.parametrize("name", ["dense16k-fused-d2", "dense16k-bfs-d2"])
def test_program_with_bf16_accumulation_reads_incorrect(spec, name):
    from bench import calibrate

    cell = small_cell(spec, name)
    with calibrate.program_bf16_accumulation():
        r = run_small(cell)
    assert not r["correct"]
    assert r["checks"]["int_mismatches"]["value"] > 0


def _altered(out):
    """One 64 x 64 tile of the product changed where it is produced."""
    import numpy as np

    if isinstance(out, np.ndarray):
        out = out.copy()
        out[:64, :64] += 8
        return out
    return out.at[:64, :64].add(8)


def _half_rows(out):
    """The second half of the rows left out."""
    import numpy as np

    if isinstance(out, np.ndarray):
        out = out.copy()
        out[out.shape[0] // 2:] = 0
        return out
    return out.at[out.shape[0] // 2:].set(0)


@pytest.mark.parametrize("name", sorted(SMALL))
@pytest.mark.parametrize("fault", ["altered", "half_rows", "unchanged"])
def test_faults_read_incorrect(spec, monkeypatch, name, fault):
    cell = small_cell(spec, name)
    first = {}

    def broken(entry, i, out):
        if fault == "altered":
            return _altered(out)
        if fault == "half_rows":
            return _half_rows(out)
        # A product that hands back its first result whatever it is given.
        return first.setdefault("out", out)

    _patch_product(monkeypatch, cell, broken)
    r = run_small(cell)
    assert not r["correct"], (fault, r["checks"])
