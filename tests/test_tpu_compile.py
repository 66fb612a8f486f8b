"""Compile-only checks for a described TPU v5e (no chip needed).

Interpret mode cannot see what the chip's compiler refuses: fast-memory
(SMEM/VMEM) budgets, tile alignment, partitioning.  These tests compile
the main path's programs for a ``v5e:2x2`` topology described by the
installed TPU compiler: the smm stack kernel at DBCSR's block sizes at a
30,000-entry stack, the fused scan executor, one Cannon and one SUMMA
``shard_map`` program on the 2x2 mesh, and the benchmark's 2x2 dense
product, whose scheduled program must overlap the skew's second panel
with the first quarter's dot; both benchmark cells' programs take the
scale factor of their product in an op they run anyway.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.  The persistent compilation cache is off around these compiles
(a cache entry written for a described chip cannot be read back).
"""
import collections
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import AxisType, SingleDeviceSharding

from repro.core import multiply, schedule
from repro.core.blocking import GridSpec
from repro.core.cannon import cannon_matmul
from repro.core.densify import blocked_local_matmul, densified_local_matmul
from repro.core.summa import summa_matmul, summa_n_panels
from repro.kernels.smm import ops as smm_ops

STACK = 30000  # the executor's default stack tile


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # code that asks the backend takes its CPU branch (interpret
        # mode); compile the kernel itself, and drop traces made for CPU
        mp.setattr(smm_ops, "_on_cpu", lambda: False)
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        jax.clear_caches()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()
            jax.clear_caches()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh2x2(topo):
    return Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("align", [False, True])
@pytest.mark.parametrize("block", [22, 23, 64])
def test_smm_kernel_compiles_at_full_stack(one_chip, block, align):
    """A 30,000-entry stack fits the kernel's scalar-prefetch in SMEM."""
    blocks = _sds((64, block, block), jnp.float32, one_chip)
    triples = _sds((STACK, 4), jnp.int32, one_chip)

    def run(a, b, c, t):
        return smm_ops.smm_process_stack(a, b, c, t, align=align,
                                         interpret=False)

    compiled = jax.jit(run).lower(blocks, blocks, blocks, triples).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_executor_scan_compiles(one_chip):
    """Several 30,000-entry stacks scanned through the smm kernel."""
    n = 22 * 40
    f = blocked_local_matmul(n, n, n, block_m=22, block_k=22, block_n=22,
                             stack_size=STACK, align=False, kernel="smm")
    assert f.executor_plan.n_stacks > 1
    x = _sds((n, n), jnp.float32, one_chip)
    compiled = jax.jit(f).lower(x, x).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "while" in text


@pytest.mark.parametrize("densify", [True, False])
@pytest.mark.parametrize("algorithm", ["cannon", "summa"])
def test_distributed_multiply_compiles_on_2x2(mesh2x2, algorithm, densify):
    """One Cannon and one SUMMA program over the four chips."""
    n, bs = 22 * 32, 22
    grid = GridSpec()
    pr, pc = grid.grid_shape(mesh2x2)
    lm = None
    if not densify:
        kl = n // pc if algorithm == "cannon" else n // summa_n_panels(pr, pc)
        lm = blocked_local_matmul(n // pr, kl, n // pc, block_m=bs,
                                  block_k=bs, block_n=bs, stack_size=STACK,
                                  align=False, kernel="smm")
    matmul = cannon_matmul if algorithm == "cannon" else summa_matmul
    x = _sds((n, n), jnp.float32,
             NamedSharding(mesh2x2, P("data", "model")))

    def run(a, b):
        return matmul(a, b, mesh=mesh2x2, grid=grid, local_matmul=lm)

    compiled = jax.jit(run).lower(x, x).compile()
    text = compiled.as_text()
    assert ("collective-permute" in text if algorithm == "cannon"
            else "all-reduce" in text)
    assert ("tpu_custom_call" in text) == (not densify)
    # each chip holds a quarter of A and B (plus tile padding), not all
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < n * n * 4


CELL_N = 33792   # the benchmark's dense-n33792-2x2 cell
ONE_CHIP_N = 11264   # the benchmark's dense-n11264-1chip cell
HLO_OP = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.+?) ([a-z][a-z0-9-]*)\((.*)$")


def _cell_program(mesh):
    """The cell's cached densified program, compiled for the described
    chips, and its panel count."""
    x = _sds((CELL_N, CELL_N), jnp.float32,
             NamedSharding(mesh, P("data", "model")))
    program, info, _ = multiply._densified_program(
        "cannon", x, x, mesh=mesh, grid=GridSpec(), depth=2,
        precision=jax.lax.Precision.DEFAULT, local_kernel=None,
        local_shape=(CELL_N // 2,) * 3, kw={})
    alpha = jax.ShapeDtypeStruct((), jnp.float32)
    return program.lower(x, x, alpha).compile(), info["skew_panels"]


def _entry_ops(text):
    """The entry computation's ops in schedule order: (name, result
    shape, opcode, the rest of the line)."""
    entry = text[text.index("\nENTRY"):]
    return [m.groups() for m in map(HLO_OP.match, entry.splitlines()[1:])
            if m]


def _elements(shape):
    dims = re.search(r"\[([\d,]*)\]", shape).group(1)
    return int(np.prod([int(d) for d in dims.split(",")]))


def test_cell_skew_panels_overlap_the_first_dot(mesh2x2, monkeypatch):
    """At N = 33,792, float32 at DEFAULT: the skew runs in two row/column
    panels, a dot runs while the second panel's transfers are in flight
    (the one-panel program's first dot waits for all of the skew), the
    panels together move what the one-panel skew moves, and the program
    holds under 1 GB more."""
    monkeypatch.setattr(multiply, "_programs", collections.OrderedDict())
    compiled, panels = _cell_program(mesh2x2)
    assert panels == 2
    ops = _entry_ops(compiled.as_text())
    order = {name: i for i, (name, *_) in enumerate(ops)}
    skew = [(name, shape) for name, shape, opcode, rest in ops
            if opcode == "collective-permute-start" and "dbcsr.skew" in rest]
    assert len(skew) == 4
    done = {re.match(r"%(\S+?)[,)]", rest).group(1): name
            for name, _, opcode, rest in ops
            if opcode == "collective-permute-done"}
    dots = [order[name] for name, _, opcode, rest in ops
            if opcode == "fusion" and "dbcsr.local_dot" in rest]
    assert len(dots) == 8   # four quarters a step, two steps
    # panel 1's A and B move under at least the first quarter's dot
    hidden = [start for start, _ in skew
              if any(order[start] < d < order[done[start]] for d in dots)]
    assert len(hidden) >= 2
    half = CELL_N // 2
    assert sum(_elements(shape) for _, shape in skew) == 2 * half * half

    monkeypatch.setattr(multiply, "_programs", collections.OrderedDict())
    monkeypatch.setattr(schedule, "SKEW_PANEL_MIN", CELL_N)  # one panel
    one, one_panels = _cell_program(mesh2x2)
    assert one_panels == 1
    one_ops = _entry_ops(one.as_text())
    one_order = {name: i for i, (name, *_) in enumerate(one_ops)}
    one_done = {re.match(r"%(\S+?)[,)]", rest).group(1): name
                for name, _, opcode, rest in one_ops
                if opcode == "collective-permute-done"}
    one_dots = [one_order[name] for name, _, opcode, rest in one_ops
                if opcode == "fusion" and "dbcsr.local_dot" in rest]
    assert not any(
        one_order[name] < d < one_order[one_done[name]]
        for name, _, opcode, rest in one_ops
        if opcode == "collective-permute-start" and "dbcsr.skew" in rest
        for d in one_dots)
    grown = (compiled.memory_analysis().temp_size_in_bytes
             - one.memory_analysis().temp_size_in_bytes)
    assert grown < 1 << 30


@pytest.mark.parametrize("side", [1, 2], ids=["one_chip", "2x2"])
def test_cell_scale_factor_adds_no_pass_over_c(topo, side, monkeypatch):
    """The factor ``dbcsr.multiply`` folds into each cell's densified
    program lands in an op the program runs anyway, the dot's output on
    one chip and the assembly of C's quarters on 2x2: the program runs
    fusions of the same shapes as the schedule without the factor, one
    of them takes the factor, and no op multiplies on its own."""
    n = ONE_CHIP_N if side == 1 else CELL_N
    mesh = Mesh(np.array(topo.devices[:side * side]).reshape(side, side),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    x = _sds((n, n), jnp.float32, NamedSharding(mesh, P("data", "model")))
    monkeypatch.setattr(multiply, "_programs", collections.OrderedDict())
    program, _, _ = multiply._densified_program(
        "cannon", x, x, mesh=mesh, grid=GridSpec(), depth=2,
        precision=jax.lax.Precision.DEFAULT, local_kernel=None,
        local_shape=(n // side,) * 3, kw={})
    alpha = jax.ShapeDtypeStruct((), jnp.float32)
    scaled = _entry_ops(program.lower(x, x, alpha).compile().as_text())
    plain = _entry_ops(jax.jit(lambda a, b: multiply._schedule_matmul(
        "cannon", a, b, mesh=mesh, grid=GridSpec(),
        local_matmul=densified_local_matmul(),
        precision=jax.lax.Precision.DEFAULT, pipeline_depth=2)).lower(
            x, x).compile().as_text())

    def fusions(ops):
        return collections.Counter(shape for _, shape, opcode, _ in ops
                                   if opcode == "fusion")

    assert fusions(scaled) == fusions(plain)
    assert [name for name, _, opcode, _ in scaled
            if opcode == "fusion" and "multiply" in name] != []
    assert [name for name, _, opcode, _ in plain
            if opcode == "fusion" and "multiply" in name] == []
    assert not [op for op in scaled if op[2] == "multiply"]
