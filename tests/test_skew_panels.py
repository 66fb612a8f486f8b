"""Cannon's prologue on row/column panels at pipeline depth 2.

Where the skew moves bytes and the local multiply is the densified dot,
``execute_schedule`` runs the schedule on two panels, A's upper rows
with B's left columns and A's lower rows with B's right columns, four
quarter products a step (``skew_panels``): each element of C is the
same sum as in the one-panel program, so the result is allclose to
depth 1 and to float64 (bitwise only where the backend's dot blocks a
quarter as it blocks the whole), depth 1 and the blocked path keep the
legacy loops' bits, a one-chip grid keeps the one-panel program, and
the dispatch span says how many panels ran.

Each battery runs in one subprocess on its own virtual CPU mesh: Cannon
on 2x2 (and 1x1) with four devices, Cannon-2.5D on 2x2x2 with eight.
The batteries lower ``SKEW_PANEL_MIN`` to the lane width, so that small
blocks take the panelled program: local blocks of 308 rows of A split
at 128 and 180, of 220 columns of B at 128 and 92.
"""
import json

import pytest

from conftest import run_subprocess_devices

M, N, K = 616, 440, 352   # 28 x 20 x 16 blocks of 22
M_SMALL = 176              # local 88 rows: under the lowered threshold

CANNON = r"""
import json, sys
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.compat import make_mesh
from repro.core import dbcsr, multiply, schedule
from repro.core.blocking import GridSpec
from repro.core.cannon import (_shift_perm, _skew_perm,
                               build_cannon_schedule, cannon_matmul)
from repro.core.densify import blocked_local_matmul, densified_local_matmul

M, N, K, M_SMALL = (int(x) for x in sys.argv[1:5])
out = {}
rng = np.random.RandomState(0)
grid = GridSpec()
sched = build_cannon_schedule(2, row_axis="data", col_axis="model")
dense = densified_local_matmul()
# the rule at its own threshold, before the battery lowers it
floor = schedule.SKEW_PANEL_MIN
out["rule"] = [schedule.skew_panels(sched, dense, floor, floor, 2),
               schedule.skew_panels(sched, dense, floor - 1, floor, 2),
               schedule.skew_panels(sched, dense, floor, floor - 1, 2),
               schedule.skew_panels(sched, dense, floor, floor, 1),
               schedule.skew_panels(sched, dense, floor, floor, 0),
               schedule.skew_panels(sched.replace(empty_steps=frozenset({1})),
                                    dense, floor, floor, 2)]
schedule.SKEW_PANEL_MIN = 128


def legacy(a, b, mesh, pg, lm):
    # the serial Cannon loop the schedule engine replaced: skew, then
    # multiply and shift, accumulating in float32 in step order
    def body(a_blk, b_blk):
        axes = (grid.row_axis, grid.col_axis)
        a_blk = jax.lax.ppermute(a_blk, axes, _skew_perm(pg, "a"))
        b_blk = jax.lax.ppermute(b_blk, axes, _skew_perm(pg, "b"))
        c = jnp.zeros(a_blk.shape[:-1] + b_blk.shape[-1:], jnp.float32)
        for t in range(pg):
            c = c + lm(a_blk, b_blk).astype(jnp.float32)
            if t + 1 < pg:
                a_blk = jax.lax.ppermute(a_blk, grid.col_axis,
                                         _shift_perm(pg))
                b_blk = jax.lax.ppermute(b_blk, grid.row_axis,
                                         _shift_perm(pg))
        return c.astype(jnp.float32)

    spec = P(grid.row_axis, grid.col_axis)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                                 out_specs=spec, check_vma=False))(a, b)


def operands(mesh, m):
    a = rng.randn(m, K).astype(np.float32)
    b = rng.randn(K, N).astype(np.float32)
    sh = NamedSharding(mesh, P(grid.row_axis, grid.col_axis))
    return a, b, jax.device_put(a, sh), jax.device_put(b, sh)


def cannon(a, b, mesh, depth, lm):
    return np.asarray(jax.jit(lambda x, y: cannon_matmul(
        x, y, mesh=mesh, grid=grid, local_matmul=lm,
        pipeline_depth=depth))(a, b))


def permutes(f, *args):
    text = jax.jit(f).lower(*args).compile().as_text()
    return sum(1 for line in text.splitlines()
               if " collective-permute(" in line
               or " collective-permute-start(" in line)


mesh = make_mesh((2, 2), ("data", "model"))
a, b, ad, bd = operands(mesh, M)
ref = a.astype(np.float64) @ b.astype(np.float64)
out["split"] = [schedule._split(M // 2), schedule._split(N // 2)]
out["panels"] = schedule.skew_panels(sched, dense, M // 2, N // 2, 2)
c2 = cannon(ad, bd, mesh, 2, dense)
c1 = cannon(ad, bd, mesh, 1, dense)
scale = np.abs(a).astype(np.float64) @ np.abs(b).astype(np.float64)
out["depth2_err"] = float(np.max(np.abs(c2 - ref) / scale))
out["depth2_vs_depth1"] = float(np.max(np.abs(c2 - c1) / scale))
out["depth1_bitwise_legacy"] = bool(np.array_equal(
    c1, np.asarray(legacy(ad, bd, mesh, 2, dense))))

blocked = blocked_local_matmul(M // 2, K // 2, N // 2, block_m=22,
                               block_k=22, block_n=22, kernel="ref")
out["blocked_panels"] = schedule.skew_panels(sched, blocked, M // 2,
                                             N // 2, 2)
lb = np.asarray(legacy(ad, bd, mesh, 2, blocked))
out["blocked_bitwise_legacy"] = {
    str(d): bool(np.array_equal(cannon(ad, bd, mesh, d, blocked), lb))
    for d in (1, 2)}

# permutes in the compiled program: a panelled program sends the skew's
# and the shift's two once per panel; a program the rule leaves in one
# panel sends the legacy loop's four
out["permutes_panelled"] = permutes(
    lambda x, y: cannon_matmul(x, y, mesh=mesh, grid=grid,
                               local_matmul=dense), ad, bd)
_, _, sa, sb = operands(mesh, M_SMALL)
out["permutes_small"] = permutes(
    lambda x, y: cannon_matmul(x, y, mesh=mesh, grid=grid,
                               local_matmul=dense), sa, sb)
out["permutes_small_legacy"] = permutes(
    lambda x, y: legacy(x, y, mesh, 2, dense), sa, sb)


def dispatch(mesh, m, **kw):
    a, b, _, _ = operands(mesh, m)
    am = dbcsr.create(a, mesh=mesh, block_size=22)
    bm = dbcsr.create(b, mesh=mesh, block_size=22)
    obs.enable()
    c = dbcsr.multiply(am, bm, mesh=mesh, algorithm="cannon", **kw)
    c = np.asarray(jax.block_until_ready(c.data))
    (span,) = [s for s in obs.last_trace() if s.name == "dispatch"]
    obs.disable()
    err = np.max(np.abs(c - a.astype(np.float64) @ b.astype(np.float64))
                 / (np.abs(a).astype(np.float64) @ np.abs(b)))
    return {"skew_panels": span.attrs.get("skew_panels"), "err": float(err)}


out["dispatch"] = dispatch(mesh, M, densify=True)
out["dispatch_warm"] = dispatch(mesh, M, densify=True)
out["dispatch_small"] = dispatch(mesh, M_SMALL, densify=True)
out["dispatch_depth1"] = dispatch(mesh, M, densify=True, pipeline_depth=1)
out["dispatch_blocked"] = dispatch(mesh, M_SMALL, densify=False)
# telemetry off: the panelled dispatch leaves the registry empty
obs.clear_metrics()
a, b, _, _ = operands(mesh, M)
jax.block_until_ready(dbcsr.multiply(
    dbcsr.create(a, mesh=mesh, block_size=22),
    dbcsr.create(b, mesh=mesh, block_size=22), mesh=mesh,
    algorithm="cannon", densify=True).data)
out["registry_when_off"] = len(obs.registry())

# a one-chip grid: the skew is a permutation onto itself
mesh1 = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
             ("data", "model"), axis_types=(AxisType.Auto,) * 2)
_, _, oa, ob = operands(mesh1, M)
sched1 = build_cannon_schedule(1, row_axis="data", col_axis="model")
out["one_chip_panels"] = schedule.skew_panels(sched1, dense, M, N, 2)
out["one_chip_permutes"] = permutes(
    lambda x, y: cannon_matmul(x, y, mesh=mesh1, grid=grid,
                               local_matmul=dense), oa, ob)
out["one_chip_permutes_legacy"] = permutes(
    lambda x, y: legacy(x, y, mesh1, 1, dense), oa, ob)
out["one_chip_dispatch"] = dispatch(mesh1, M, densify=True)

# the benchmark's 2x2 cell, counted from shapes alone, at the rule's
# own threshold
schedule.SKEW_PANEL_MIN = floor
n = 33792
cell = jax.ShapeDtypeStruct(
    (n, n), jnp.float32,
    sharding=NamedSharding(mesh, P(grid.row_axis, grid.col_axis)))
multiply._programs.clear()
_, info, _ = multiply._densified_program(
    "cannon", cell, cell, mesh=mesh, grid=grid, depth=2,
    precision=jax.lax.Precision.DEFAULT, local_kernel=None,
    local_shape=(n // 2,) * 3, kw={})
out["cell_info"] = info
out["cell_comm"] = multiply._comm_count(
    "cannon", cell, cell, mesh=mesh, grid=grid, local_shape=(n // 2,) * 3,
    kw={})
out["cell_split"] = schedule._split(n // 2)
print(json.dumps(out))
"""

CANNON25D = r"""
import json, sys
import numpy as np, jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import make_mesh
from repro.core import schedule
from repro.core.blocking import GridSpec
from repro.core.cannon25d import build_cannon25d_schedule, cannon25d_matmul
from repro.core.densify import densified_local_matmul

M, N, K = (int(x) for x in sys.argv[1:4])
schedule.SKEW_PANEL_MIN = 128
rng = np.random.RandomState(1)
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
grid = GridSpec("data", "model", stack_axis="pod")
dense = densified_local_matmul()
a = rng.randn(M, K).astype(np.float32)
b = rng.randn(K, N).astype(np.float32)
sh = NamedSharding(mesh, P("data", "model"))
ad, bd = jax.device_put(a, sh), jax.device_put(b, sh)


def run(depth, reduce):
    return np.asarray(jax.jit(lambda x, y: cannon25d_matmul(
        x, y, mesh=mesh, grid=grid, local_matmul=dense,
        pipeline_depth=depth, reduce=reduce))(ad, bd))


ref = a.astype(np.float64) @ b.astype(np.float64)
scale = np.abs(a).astype(np.float64) @ np.abs(b)
sched = build_cannon25d_schedule(2, 2, row_axis="data", col_axis="model",
                                 stack_axis="pod")
out = {"panels": schedule.skew_panels(sched, dense, M // 2, N // 2, 2)}
for reduce in ("all_reduce", "reduce_scatter"):
    c2, c1 = run(2, reduce), run(1, reduce)
    out[reduce] = {"depth2_err": float(np.max(np.abs(c2 - ref) / scale)),
                   "depth2_vs_depth1": float(np.max(np.abs(c2 - c1)
                                                / scale))}
print(json.dumps(out))
"""

# float32 sums of 352 terms: the per-element error over |A||B| stays far
# below this on CPU, where the dot runs in float32
F32_TOL = 1e-5


@pytest.fixture(scope="module")
def cannon():
    code = (f"import sys; sys.argv = ['battery', '{M}', '{N}', '{K}', "
            f"'{M_SMALL}']\n" + CANNON)
    return json.loads(run_subprocess_devices(code, n_devices=4)
                      .strip().splitlines()[-1])


@pytest.fixture(scope="module")
def cannon25d():
    code = f"import sys; sys.argv = ['battery', '{M}', '{N}', '{K}']\n" \
        + CANNON25D
    return json.loads(run_subprocess_devices(code, n_devices=8)
                      .strip().splitlines()[-1])


def test_rule_engages_at_its_threshold_and_depth_2_only(cannon):
    # smaller blocks, depth 1 or 0, or a schedule with an empty step
    # keep one panel
    assert cannon["rule"] == [2, 1, 1, 1, 1, 1]


def test_rule_splits_halves_on_blocks_that_do_not_divide(cannon):
    # 308 / 2 = 154 and 220 / 2 = 110 both round to one lane of 128
    assert cannon["split"] == [128, 128]
    assert cannon["panels"] == 2


def test_panelled_depth2_is_allclose_to_float64_and_depth1(cannon):
    assert cannon["depth2_err"] < F32_TOL
    assert cannon["depth2_vs_depth1"] < F32_TOL


def test_depth1_keeps_the_legacy_bits(cannon):
    assert cannon["depth1_bitwise_legacy"]


def test_blocked_path_is_never_panelled_and_keeps_its_bits(cannon):
    assert cannon["blocked_panels"] == 1
    assert cannon["blocked_bitwise_legacy"] == {"1": True, "2": True}


def test_panelled_program_sends_skew_and_shift_per_panel(cannon):
    assert cannon["permutes_panelled"] == 8


def test_one_panel_program_has_the_legacy_permutes(cannon):
    assert cannon["permutes_small"] == cannon["permutes_small_legacy"]
    assert cannon["one_chip_permutes"] <= cannon["one_chip_permutes_legacy"]


@pytest.mark.parametrize("call", ["dispatch", "dispatch_warm"])
def test_dispatch_span_reads_the_engaged_count(cannon, call):
    got = cannon[call]
    assert got["skew_panels"] == 2
    assert got["err"] < F32_TOL


@pytest.mark.parametrize("call", ["dispatch_small", "dispatch_depth1",
                                  "dispatch_blocked", "one_chip_dispatch"])
def test_dispatch_span_reads_one_where_the_rule_keeps_one(cannon, call):
    got = cannon[call]
    assert got["skew_panels"] == 1
    assert got["err"] < F32_TOL


def test_one_chip_grid_keeps_one_panel(cannon):
    assert cannon["one_chip_panels"] == 1


def test_counter_stays_out_of_the_registry_when_off(cannon):
    assert cannon["registry_when_off"] == 0


def test_cell_accounting_is_unchanged(cannon):
    counts = {"comm_steps": 2, "comm_bytes": 4_567_597_056}
    assert cannon["cell_comm"] == counts
    assert cannon["cell_info"] == {**counts, "skew_panels": 2}
    assert cannon["cell_split"] == 8448


@pytest.mark.parametrize("reduce", ["all_reduce", "reduce_scatter"])
def test_cannon25d_panelled_is_allclose(cannon25d, reduce):
    assert cannon25d["panels"] == 2
    assert cannon25d[reduce]["depth2_err"] < F32_TOL
    assert cannon25d[reduce]["depth2_vs_depth1"] < F32_TOL


@pytest.mark.parametrize("n, depth, grown", [(33792, 2, True),
                                             (33792, 1, False),
                                             (4400, 2, False)])
def test_planner_prices_the_assembled_c(n, depth, grown):
    """The memory gate charges the panelled program's copy of C (local
    blocks of 16,896 take it; depth 1 and blocks of 2,200 do not)."""
    from repro.planner.cost_model import DEFAULT_HARDWARE, Problem, \
        candidate_cost

    prob = Problem(m=n, k=n, n=n, block_m=22, block_k=22, block_n=22,
                   occupancy=1.0, itemsize=4, pr=2, pc=2)
    cost = candidate_cost(DEFAULT_HARDWARE, prob, "cannon", True,
                          pipeline_depth=depth)
    local = n // 2
    base = 4 * 3 * local * local
    assert cost.mem_bytes == base + (4 * local * local if grown else 0)
