"""Distributed Strassen + model sharding under multi-device host platform.

Device count is locked at jax init, so the tests that need another count
run in SUBPROCESSES with XLA_FLAGS=--xla_force_host_platform_device_count=N.
The mesh route of ``backend.matmul`` runs in this process, on a 2x2 mesh of
four of the conftest's eight host devices.
"""
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(n_devices: int, code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_sharded_train_step_runs_and_matches_single_device():
    """Numerical parity: mesh-sharded train step == single-device step."""
    out = _run(8, """
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.launch.train import build
        from repro.optim.adamw import AdamWConfig
        from repro.launch.mesh import make_mesh_for

        cfg = get_smoke_config("phi4_mini_3_8b")
        opt = AdamWConfig(lr=1e-2, warmup_steps=2, total_steps=10)
        mesh = make_mesh_for(8, model_parallel=2)

        s1, data, f1 = build(cfg, opt, batch=8, seq=32, accum=1, mesh=None, seed=3)
        s2, _, f2 = build(cfg, opt, batch=8, seq=32, accum=1, mesh=mesh, seed=3)
        b = data(0)
        s1n, m1 = f1(s1, b)
        s2n, m2 = f2(s2, b)
        d = jax.tree.map(lambda a, c: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - c.astype(jnp.float32)))), s1n.params, s2n.params)
        worst = max(jax.tree.leaves(d))
        assert worst < 5e-3, worst
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3
        print("OK", worst)
    """)
    assert "OK" in out


def test_grad_accum_parity_under_mesh():
    out = _run(4, """
        import jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.launch.train import build
        from repro.optim.adamw import AdamWConfig
        from repro.launch.mesh import make_mesh_for
        cfg = get_smoke_config("gemma_7b")
        opt = AdamWConfig(lr=1e-2, warmup_steps=2, total_steps=10)
        mesh = make_mesh_for(4, model_parallel=2)
        s1, data, f1 = build(cfg, opt, batch=8, seq=16, accum=1, mesh=mesh, seed=5)
        s2, _, f4 = build(cfg, opt, batch=8, seq=16, accum=4, mesh=mesh, seed=5)
        b = data(0)
        s1n, _ = f1(s1, b)
        s2n, _ = f4(s2, b)
        d = jax.tree.map(lambda a, c: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - c.astype(jnp.float32)))), s1n.params, s2n.params)
        worst = max(jax.tree.leaves(d))
        assert worst < 5e-3, worst
        print("OK", worst)
    """)
    assert "OK" in out


# ------------------------------------------- backend.matmul over a 2x2 mesh
def _mesh():
    return Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("data", "model"))


def _mesh_backend(mesh, **kw):
    from repro.core.backend import MatmulBackend

    kw = {"depth": 2, "min_dim": 64, **kw}
    return MatmulBackend(kind="strassen_fused", mesh=mesh, **kw)


def _operands(kind, m, k, n, seed):
    rng = np.random.default_rng(seed)
    if kind == "integers":
        a, b = rng.integers(-8, 9, (m, k)), rng.integers(-8, 9, (k, n))
    else:
        a, b = rng.standard_normal((m, k)), rng.standard_normal((k, n))
    return jnp.asarray(a, jnp.bfloat16), jnp.asarray(b, jnp.bfloat16)


def _reference(a, b):
    """A @ B in float32 at the highest precision (the plain reference)."""
    with jax.default_matmul_precision("highest"):
        return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32))


def _mesh_matmul(a, b, be):
    from repro.core import backend

    return jax.jit(lambda x, y: backend.matmul(x, y, be))(a, b)


# (m, k, n): a square product; a product whose 4 row stripes are far from
# square; an M that the stripe grain (4 stripes x 2**depth) does not divide.
MESH_SHAPES = [(512, 512, 512), (512, 1024, 768), (1022, 512, 256)]


@pytest.mark.parametrize("shape", MESH_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_mesh_route_is_exact_on_whole_numbers(shape):
    """Whole numbers in [-8, 8] keep every divide sum, leaf product and
    combine sum a whole number below 2**24, so a program that accumulates in
    float32 equals the exact product rounded once to bf16. One that held its
    partial sums in bf16 (exact only up to 256) would not: see
    test_mesh_route_with_bf16_accumulation_is_caught."""
    a, b = _operands("integers", *shape, seed=sum(shape))
    got = _mesh_matmul(a, b, _mesh_backend(_mesh()))
    want = _reference(a, b).astype(jnp.bfloat16)
    assert got.shape == want.shape and got.dtype == jnp.bfloat16
    assert int(jnp.sum(got != want)) == 0


@pytest.mark.parametrize("shape", MESH_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_mesh_route_matches_reference_on_normal_operands(shape):
    a, b = _operands("normal", *shape, seed=7 + sum(shape))
    got = _mesh_matmul(a, b, _mesh_backend(_mesh())).astype(jnp.float32)
    want = _reference(a, b)
    rel_fro = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel_fro <= 0.025, rel_fro  # the mesh4-32k-fused-d2 cell's limit


def test_mesh_route_with_bf16_accumulation_is_caught(monkeypatch):
    """The whole-number check fails once the leaf products and combine
    sums are held in bf16 instead of float32."""
    from repro.kernels.strassen import ops

    monkeypatch.setattr(ops, "accumulation_dtype", lambda dtype: jnp.dtype(dtype))
    a, b = _operands("integers", 512, 640, 512, seed=3)  # a shape no other test traces
    got = _mesh_matmul(a, b, _mesh_backend(_mesh()))
    want = _reference(a, b).astype(jnp.bfloat16)
    assert int(jnp.sum(got != want)) > 0


def test_mesh_route_returns_row_stripes_on_four_devices():
    mesh = _mesh()
    rows = NamedSharding(mesh, P(("data", "model"), None))
    a, b = _operands("normal", 512, 512, 512, seed=11)
    a, b = jax.device_put(a, rows), jax.device_put(b, rows)
    got = _mesh_matmul(a, b, _mesh_backend(mesh))
    assert got.sharding.is_equivalent_to(rows, 2)
    shards = got.addressable_shards
    assert len({s.device for s in shards}) == 4
    assert {s.data.shape for s in shards} == {(128, 512)}


def test_mesh_program_gathers_b_once_and_nothing_else():
    """Row-sharded A and B: the only collective is one all-gather of B
    (what the cell's collective_ms.mesh reads from the device trace)."""
    from repro.core import backend

    mesh = _mesh()
    rows = NamedSharding(mesh, P(("data", "model"), None))
    x = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16, sharding=rows)
    be = _mesh_backend(mesh)
    text = jax.jit(lambda a, b: backend.matmul(a, b, be), out_shardings=rows).lower(
        x, x).compile().as_text()
    ops = re.findall(r" = \S+ ([a-z\-]+)\(", text)
    collectives = [op for op in ops if op.split("-start")[0].split("-done")[0] in (
        "all-gather", "all-reduce", "reduce-scatter", "collective-permute", "all-to-all",
        "collective-broadcast", "ragged-all-to-all")]
    assert [op for op in collectives if not op.endswith("-done")] in (
        ["all-gather"], ["all-gather-start"]), collectives
    # All of B (the CPU compiler gathers it as f32, a TPU as bf16).
    gathered = re.search(r" = \w+\[(\d+),(\d+)\]\S* all-gather(?:-start)?\(", text)
    assert gathered and gathered.groups() == ("512", "512")


def test_mesh_route_refuses_what_it_cannot_run():
    from repro.core import backend
    from repro.core.backend import MatmulBackend

    mesh = _mesh()
    for kind in ("naive", "strassen", "winograd", "strassen_oot", "auto"):
        with pytest.raises(ValueError, match="strassen_fused"):
            MatmulBackend(kind=kind, mesh=mesh)
    a, b = _operands("normal", 512, 512, 512, seed=1)
    with pytest.raises(ValueError, match="w_logical"):
        backend.matmul(a, b, _mesh_backend(mesh), w_logical=("fsdp", "d_ff"))
    # A stripe of 64 rows takes no level above min_dim=128: no silent
    # single-device product in its place.
    with pytest.raises(ValueError, match="min_dim"):
        backend.matmul(a[:256], b, _mesh_backend(mesh, min_dim=128))


def test_mesh_route_fails_loudly_without_the_pallas_leaf(monkeypatch):
    """Where the fused leaf cannot run, the mesh call raises; it never
    runs another pipeline in its place."""
    from repro.kernels.strassen import ops

    def broken(*args, **kwargs):
        raise RuntimeError("no Pallas leaf here")

    monkeypatch.setattr(ops, "strassen1_matmul_pallas", broken)
    a, b = _operands("normal", 384, 256, 256, seed=2)  # a shape no other test traces
    with pytest.raises(RuntimeError, match="no Pallas leaf"):
        _mesh_matmul(a, b, _mesh_backend(_mesh()))


def test_local_route_never_reaches_a_mesh_strategy(monkeypatch):
    """mesh=None leaves the local strassen_fused route as it was; a mesh
    routes through strassen_fused_sharded."""
    from repro.core import distributed
    from repro.core.backend import MatmulBackend

    asked = []
    real = distributed.strassen_fused_sharded

    def spy(*a, **k):
        asked.append("strassen_fused_sharded")
        return real(*a, **k)

    monkeypatch.setattr(distributed, "strassen_fused_sharded", spy)
    a, b = _operands("integers", 256, 256, 256, seed=5)
    local = MatmulBackend(kind="strassen_fused", depth=2, min_dim=64)
    want = _reference(a, b).astype(jnp.bfloat16)
    assert int(jnp.sum(_mesh_matmul(a, b, local) != want)) == 0
    assert asked == []
    got = _mesh_matmul(a, b, _mesh_backend(_mesh(), depth=1))
    assert asked == ["strassen_fused_sharded"]
    assert int(jnp.sum(got != want)) == 0


def test_matmul_span_names_the_mesh_and_strategy():
    from repro import obs

    a, b = _operands("normal", 256, 256, 256, seed=6)
    obs.reset_tracing()
    obs.configure(enabled=True)
    try:
        _mesh_matmul(a, b, _mesh_backend(_mesh(), depth=1))
        _mesh_matmul(a, b, _mesh_backend(None, depth=1))
        spans = obs.get_tracer().find("backend.matmul")
    finally:
        obs.configure(enabled=False)
        obs.reset_tracing()
    assert [(s.attrs["mesh"], s.attrs["strategy"]) for s in spans] == [
        ("2x2", "strassen_fused_sharded"), (None, None)]
