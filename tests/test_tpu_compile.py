"""Compile rehearsals of the Strassen kernels for a described TPU v5e chip.

Nothing here runs on a TPU: the TPU compiler that ships with jax compiles
for a ``v5e:2x2`` topology that is described, not attached, and refuses
what the chip would refuse (unaligned blocks, VMEM overruns, programs that
do not fit HBM) — failures interpret mode cannot see. Every kernel is
forced to ``interpret=False``: the default backend here is the CPU, where
the kernels would otherwise choose interpret mode.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file. The persistent compilation cache is off
around these compiles (a cache entry for a device that is absent cannot
be read back).
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import backend
from repro.core.coefficients import get_scheme
from repro.core.compat import compiled_cost_analysis
from repro.core.strassen import strassen_matmul
from repro.kernels.strassen.ops import (
    strassen_matmul_fused,
    strassen_matmul_fused_padded,
)
from repro.kernels.strassen.strassen import (
    combine_pallas,
    divide_pallas,
    strassen1_matmul_pallas,
)

STRASSEN = get_scheme("strassen")


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.fixture(scope="module")
def four_chips(v5e_2x2):
    """The host's four chips as the (data, model) mesh of the mesh cell."""
    return Mesh(np.asarray(v5e_2x2.devices).reshape(2, 2), ("data", "model"))


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_strassen1_leaf_compiles_at_256_tiles(one_chip, dtype):
    aq = _spec((2, 4, 512, 512), dtype, one_chip)
    fn = jax.jit(
        lambda a, b: strassen1_matmul_pallas(
            a, b, block_m=256, block_n=256, block_k=256, interpret=False
        )
    )
    _assert_kernel(fn.lower(aq, aq).compile())


@pytest.mark.parametrize(
    "a_shape,b_shape",
    [
        ((7, 4, 4096, 4096), (7, 4, 4096, 4096)),  # dense16k-fused-d2
        ((7, 4, 2048, 8192), (7, 4, 8192, 8192)),  # mesh4-32k-fused-d2, per chip
    ],
)
def test_strassen1_leaf_compiles_at_the_cells_wide_tiles(one_chip, a_shape, b_shape):
    """The cells' depth-2 leaf calls take 1024x512 output tiles, which need
    more than the compiler's default scoped VMEM: the limit the rule sets
    lets them compile."""
    from repro.kernels.strassen import strassen as kernel

    m2, k2, n2 = a_shape[2], a_shape[3], b_shape[3]
    assert kernel._leaf_blocks(m2, k2, n2, interpret=False) == (1024, 512, 256)
    fn = jax.jit(
        lambda a, b: strassen1_matmul_pallas(
            a, b, out_dtype=jnp.float32, interpret=False
        )
    )
    a = _spec(a_shape, jnp.bfloat16, one_chip)
    b = _spec(b_shape, jnp.bfloat16, one_chip)
    _assert_kernel(fn.lower(a, b).compile())


@pytest.mark.parametrize(
    "a_shape,b_shape,out_dtype",
    [
        ((7, 4, 768, 1280), (7, 4, 1280, 768), jnp.float32),
        ((1, 4, 16, 1536), (1, 4, 1536, 4096), jnp.bfloat16),
    ],
)
def test_strassen1_leaf_outside_the_rule_lowers_as_at_256_tiles(
    one_chip, a_shape, b_shape, out_dtype
):
    """Quadrants the wide rule does not take get the kernel explicit 256^3
    blocks give: the same Mosaic module, with no VMEM limit of its own."""

    def lowered(**blocks):
        fn = jax.jit(
            lambda a, b: strassen1_matmul_pallas(
                a, b, out_dtype=out_dtype, interpret=False, **blocks
            )
        )
        a = _spec(a_shape, jnp.bfloat16, one_chip)
        b = _spec(b_shape, jnp.bfloat16, one_chip)
        return fn.lower(a, b).as_text()

    # One call site for both: the kernel's text carries its caller's location.
    rule, narrow = (lowered(**b) for b in ({}, dict(block_m=256, block_n=256, block_k=256)))
    assert "scoped_memory_configs" not in rule
    assert rule == narrow


def _entry_opcodes(compiled):
    """Opcode of each instruction in the compiled program's entry computation."""
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    entry = entry[1:entry.index("\n}")].splitlines()[1:]
    found = (re.search(r" = .*?[\]})] ([a-z][a-z0-9-]*)\(", line) for line in entry)
    return [m.group(1) for m in found if m]


# Instructions that only move data into another layout. The async
# copy-start/copy-done pairs (memory-space prefetches) are other opcodes.
_RELAYOUT = {"copy", "transpose", "reshape"}


def test_fused_depth2_8192_bf16_compiles(one_chip):
    x = _spec((8192, 8192), jnp.bfloat16, one_chip)
    compiled = strassen_matmul_fused.lower(x, x, depth=2, interpret=False).compile()
    _assert_kernel(compiled)
    # The outer divide writes the leaf's quadrant layout and the combine
    # reads it in place: no relayout between the levels and the kernel,
    # and far fewer bytes than the 8.13 GiB the reshaped quadrants moved.
    assert not _RELAYOUT & set(_entry_opcodes(compiled))
    assert compiled_cost_analysis(compiled)["bytes accessed"] <= 4.5 * 2**30
    # A, B, C and the depth-2 intermediates fit the chip's 16 GB of HBM.
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9


def test_strassen_depth2_8192_bf16_compiles(one_chip):
    """The all-XLA pipeline addresses quadrants by slices too. What relayout
    is left is XLA's: two copies of the stacked level-2 operands into the
    batch-major layout the dot reads (13.7 GiB moved with seven copies and
    seventeen reshapes when quadrants were reshaped)."""
    x = _spec((8192, 8192), jnp.bfloat16, one_chip)
    compiled = jax.jit(lambda a, b: strassen_matmul(a, b, depth=2)).lower(x, x).compile()
    ops = _entry_opcodes(compiled)
    assert not {"transpose", "reshape"} & set(ops)
    assert ops.count("copy") <= 2
    assert compiled_cost_analysis(compiled)["bytes accessed"] <= 11 * 2**30
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9


@pytest.mark.parametrize("stage", ["divide", "combine"])
def test_divide_combine_2048_bf16_compile(one_chip, stage):
    if stage == "divide":
        x = _spec((1, 4, 2048, 2048), jnp.bfloat16, one_chip)
        fn = jax.jit(lambda t: divide_pallas(t, STRASSEN.a_coef, interpret=False))
    else:
        x = _spec((1, 7, 2048, 2048), jnp.bfloat16, one_chip)
        fn = jax.jit(lambda t: combine_pallas(t, STRASSEN.c_coef, interpret=False))
    _assert_kernel(fn.lower(x).compile())


def test_fused_padded_unaligned_1000_compiles(one_chip):
    """1000 has no 128-aligned divisor: the padded pipeline must hand the
    leaf aligned quadrants, or Mosaic refuses the block shape."""
    x = _spec((1000, 1000), jnp.bfloat16, one_chip)
    compiled = strassen_matmul_fused_padded.lower(
        x, x, depth=1, interpret=False
    ).compile()
    _assert_kernel(compiled)


@pytest.fixture
def compiled_leaf(monkeypatch):
    """The backend's routes pick the kernel's mode from the default backend
    (the CPU here): make them compile it for the chip."""
    import repro.kernels.strassen.strassen as kernel

    monkeypatch.setattr(kernel, "default_interpret", lambda: False)


def _fused_d2(mesh=None):
    be = backend.MatmulBackend(kind="strassen_fused", depth=2, mesh=mesh)
    return lambda a, b: backend.matmul(a, b, be)


def test_mesh_route_32768_bf16_fits_each_of_four_chips(four_chips, compiled_leaf):
    """The mesh cell's program: row-sharded 32768^2 bf16 operands, B
    gathered, the fused depth-2 pipeline on each chip's 8192-row stripe.
    Its arguments, output and temporaries fit each chip's 16 GB."""
    rows = NamedSharding(four_chips, P(("data", "model"), None))
    x = _spec((32768, 32768), jnp.bfloat16, rows)
    compiled = jax.jit(_fused_d2(four_chips), out_shardings=rows).lower(x, x).compile()
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == 2 * 8192 * 32768 * 2
    per_chip = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert per_chip < 16e9


def test_local_route_32768_bf16_does_not_fit_one_chip(one_chip, compiled_leaf):
    """Why the mesh cell is 32768^2: one chip cannot hold that program."""
    x = _spec((32768, 32768), jnp.bfloat16, one_chip)
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        jax.jit(_fused_d2()).lower(x, x).compile()


def test_oot_subtree_program_fits_its_modelled_budget(one_chip):
    """The out-of-core cell's device program: one level-1 subtree of an
    8192^2 depth-2 product, a 2x2 grid of 2048^2 f32 blocks per operand.
    Its arguments, output and temporaries stay within W(1), the bytes the
    level rule charges against the cell's 256 MiB budget."""
    from repro.blocks.scheduler import PlanScheduler, subtree_bytes, subtree_level

    budget = 268_435_456
    assert subtree_level(8192, 8192, 8192, 2, budget, jnp.bfloat16) == 1
    sched = PlanScheduler(
        depth=2, budget_bytes=budget, backend=backend.MatmulBackend(kind="naive")
    )
    program = sched._subtree_program(1, np.dtype(np.float32), np.dtype(np.float32))
    grid = [[_spec((2048, 2048), jnp.float32, one_chip)] * 2] * 2
    mem = program.lower(grid, grid).compile().memory_analysis()
    assert mem.argument_size_in_bytes == 2 * 4096 * 4096 * 4
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert total <= subtree_bytes(8192, 8192, 8192, 2, 1, jnp.bfloat16) == 251_658_240
