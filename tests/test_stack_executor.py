"""Fused stack executor (core/engine.py): equivalence vs the jnp oracle
across the paper's block sizes, the single-compile property (one smm
trace per block geometry, not per stack), plan memoization, autotune
default resolution, and the blocked-path local-geometry regression
(blocked vs densified on 1x1 and 2x2 meshes)."""
import json
import types

import numpy as np
import pytest

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp

from conftest import run_subprocess_devices
from repro.core import engine
from repro.core.blocking import BlockLayout, GridSpec
from repro.core.densify import blocked_local_matmul, from_blocks, to_blocks
from repro.core.multiply import distributed_matmul
from repro.core.stacks import build_stacks, pad_plans, stack_statistics
from repro.kernels.smm.ref import smm_process_stack_ref


# ---------------------------------------------------------------------------
# executor vs oracle equivalence (paper block sizes, ragged final stack)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kernel", ["ref", "smm"])
@pytest.mark.parametrize("block", [4, 22, 64])
def test_executor_matches_oracle(block, kernel, rng):
    nb = 3
    m = k = n = block * nb
    a = jnp.asarray(rng.randn(m, k).astype(np.float32))
    b = jnp.asarray(rng.randn(k, n).astype(np.float32))

    # stack_size = 2 k-runs; nb*nb = 9 runs total -> 5 stacks, ragged tail
    f = blocked_local_matmul(m, k, n, block_m=block, block_k=block,
                             block_n=block, stack_size=2 * nb, kernel=kernel)
    plan = f.executor_plan
    assert plan.n_stacks > 1, "test must exercise the multi-stack scan"
    assert plan.n_padding > 0, "test must exercise the ragged final stack"

    c = np.asarray(f(a, b))

    # oracle 1: one un-padded mega-stack through the jnp reference
    triples = jnp.asarray(np.concatenate([p.triples for p in f.plans]))
    c0 = jnp.zeros((nb * nb, block, block), jnp.float32)
    oracle = np.asarray(from_blocks(
        smm_process_stack_ref(to_blocks(a, block, block),
                              to_blocks(b, block, block), c0, triples),
        nb, nb))
    # oracle 2: the dense product itself
    dense = np.asarray(a) @ np.asarray(b)

    tol = 1e-4 * block
    np.testing.assert_allclose(c, oracle, rtol=0, atol=tol)
    np.testing.assert_allclose(c, dense, rtol=0, atol=tol)


def test_executor_rectangular_blocks(rng):
    """Non-uniform (bm, bk, bn) geometry through the fused path."""
    bm, bk, bn = 8, 16, 4
    m, k, n = 4 * bm, 3 * bk, 5 * bn
    a = jnp.asarray(rng.randn(m, k).astype(np.float32))
    b = jnp.asarray(rng.randn(k, n).astype(np.float32))
    f = blocked_local_matmul(m, k, n, block_m=bm, block_k=bk, block_n=bn,
                             stack_size=5, kernel="ref")
    np.testing.assert_allclose(np.asarray(f(a, b)),
                               np.asarray(a) @ np.asarray(b),
                               rtol=0, atol=1e-4)


# ---------------------------------------------------------------------------
# single-compile property: scan traces the smm kernel once per geometry
# ---------------------------------------------------------------------------


def _count_named_calls(jaxpr, name) -> int:
    """Call-site equations (pjit etc.) named ``name``, recursing into
    every sub-jaxpr (scan bodies, nested calls)."""
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.params.get("name") == name:
            count += 1
        for v in eqn.params.values():
            subs = v if isinstance(v, (list, tuple)) else [v]
            for s in subs:
                if isinstance(s, jex_core.ClosedJaxpr):
                    s = s.jaxpr
                if isinstance(s, jex_core.Jaxpr):
                    count += _count_named_calls(s, name)
    return count


def test_fused_executor_traces_smm_once():
    block, nb = 8, 4
    m = k = n = block * nb
    a = jnp.zeros((m, k), jnp.float32)
    b = jnp.zeros((k, n), jnp.float32)

    f = blocked_local_matmul(m, k, n, block_m=block, block_k=block,
                             block_n=block, stack_size=2 * nb, align=False,
                             kernel="smm")
    n_stacks = f.executor_plan.n_stacks
    assert n_stacks > 1

    fused = jax.make_jaxpr(f)(a, b).jaxpr
    assert _count_named_calls(fused, "smm_process_stack") == 1, \
        "fused executor must embed exactly one smm call (inside the scan)"

    # the legacy per-plan loop embeds one call per stack
    plan = f.executor_plan

    def looped(a, b):
        ab = to_blocks(a, block, block)
        bb = to_blocks(b, block, block)
        c0 = jnp.zeros((plan.nbr * plan.nbc, block, block), jnp.float32)
        c = engine.execute_plans_looped(list(plan.plans), ab, bb, c0,
                                        kernel="smm", align=False)
        return from_blocks(c, plan.nbr, plan.nbc)

    looped_jaxpr = jax.make_jaxpr(looped)(a, b).jaxpr
    assert _count_named_calls(looped_jaxpr, "smm_process_stack") == n_stacks


# ---------------------------------------------------------------------------
# host-side plan construction: padding contract + memoization
# ---------------------------------------------------------------------------


def test_pad_plans_mask_and_sentinel():
    a = BlockLayout(64, 96, 16, 16)
    b = BlockLayout(96, 80, 16, 16)
    plans = build_stacks(a, b, stack_size=13)  # runs of 6 -> ragged stacks
    padded = pad_plans(plans)
    tile = max(p.size for p in plans)
    assert padded.shape == (len(plans), tile, 4)
    n_c = plans[0].n_c_blocks
    total = sum(p.size for p in plans)
    assert int(padded[:, :, 3].sum()) == total
    valid = padded[:, :, 3].astype(bool)
    # padding rows: zeroed a/b, sentinel c one past the real C blocks
    assert (padded[~valid][:, 2] == n_c).all()
    assert (padded[~valid][:, :2] == 0).all()
    # real rows reproduce the original triples, in order
    flat = padded[valid][:, :3]
    np.testing.assert_array_equal(flat, np.concatenate(
        [p.triples for p in plans]))
    # stats surface the padding
    stats = stack_statistics(plans, stack_tile=tile)
    assert stats["n_padding"] == len(plans) * tile - total
    assert 0 < stats["fill"] <= 1


def test_executor_plan_memoized():
    p1 = engine.build_executor_plan(64, 64, 64, 16, 16, 16, 1000)
    p2 = engine.build_executor_plan(64, 64, 64, 16, 16, 16, 1000)
    assert p1 is p2, "plan construction must be memoized per geometry"
    p3 = engine.build_executor_plan(64, 64, 64, 16, 16, 16, 999)
    assert p3 is not p1


def test_autotune_defaults_resolved():
    from repro.kernels.smm.autotune import best_params_for
    f = blocked_local_matmul(64, 64, 64, block_m=16, block_k=16, block_n=16)
    assert (f.align, f.stack_size) == best_params_for(16, 16, 16)
    # explicit overrides win over the winners table
    g = blocked_local_matmul(64, 64, 64, block_m=16, block_k=16, block_n=16,
                             stack_size=7, align=False)
    assert (g.align, g.stack_size) == (False, 7)


def test_best_params_reads_winners_table(tmp_path):
    from repro.kernels.smm.autotune import best_params
    cache = tmp_path / "smm_autotune.json"
    cache.write_text(json.dumps(
        {"22": {"best": {"align": True, "stack_tile": 4096}}}))
    assert best_params(22, str(cache)) == (True, 4096)
    assert best_params(99, str(cache)) == (True, 30000)  # heuristic fallback


# ---------------------------------------------------------------------------
# blocked-path local-geometry regression (multiply.py)
# ---------------------------------------------------------------------------


def test_blocked_nonsquare_grid_raises():
    """The cannon blocked path must refuse non-square grids loudly
    instead of silently building wrong StackPlan geometry.  (Summa
    no longer rejects non-square grids: its blocked path builds
    per-panel plans — covered by the geometry battery below; it still
    rejects shapes whose panels don't block-divide.)"""
    mesh = types.SimpleNamespace(shape={"data": 2, "model": 4})
    a = jnp.zeros((64, 96), jnp.float32)
    b = jnp.zeros((96, 80), jnp.float32)
    with pytest.raises(ValueError):
        distributed_matmul(a, b, mesh=mesh, grid=GridSpec("data", "model"),
                           algorithm="cannon", densify=False,
                           block_m=8, block_k=8, block_n=8)
    # 2x4 grid: N/pc = 20 does not divide into 8-blocks -> loud error
    with pytest.raises(ValueError, match="divisible"):
        distributed_matmul(a, b, mesh=mesh, grid=GridSpec("data", "model"),
                           algorithm="summa", densify=False,
                           block_m=8, block_k=8, block_n=8)


GEOMETRY_BATTERY = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import make_mesh
from repro.core.blocking import GridSpec
from repro.core.multiply import distributed_matmul

rng = np.random.RandomState(0)
out = {}
M, K, N = 64, 96, 80
A = rng.randn(M, K).astype(np.float32)
B = rng.randn(K, N).astype(np.float32)
ref = A @ B
for pg in (1, 2):
    mesh = make_mesh((pg, pg), ("data", "model"))
    grid = GridSpec("data", "model")
    sh = NamedSharding(mesh, P("data", "model"))
    Ad, Bd = jax.device_put(A, sh), jax.device_put(B, sh)
    C = distributed_matmul(Ad, Bd, mesh=mesh, grid=grid,
                           algorithm="cannon", densify=False,
                           block_m=8, block_k=8, block_n=8,
                           local_kernel="ref")
    Cd = distributed_matmul(Ad, Bd, mesh=mesh, grid=grid,
                            algorithm="cannon", densify=True)
    out[f"blocked_vs_dense_{pg}x{pg}"] = float(
        np.max(np.abs(np.asarray(C) - ref)))
    out[f"blocked_vs_densified_{pg}x{pg}"] = float(
        np.max(np.abs(np.asarray(C) - np.asarray(Cd))))
    # summa blocked, both broadcast variants (gather's local multiply
    # sees the full K extent — a distinct stack-plan geometry)
    for bcast in ("psum", "gather"):
        Cs = distributed_matmul(Ad, Bd, mesh=mesh, grid=grid,
                                algorithm="summa", densify=False,
                                block_m=8, block_k=8, block_n=8,
                                local_kernel="ref", bcast=bcast)
        out[f"summa_{bcast}_blocked_{pg}x{pg}"] = float(
            np.max(np.abs(np.asarray(Cs) - ref)))

# non-square grids: summa's blocked path builds per-panel plans (panel
# K-extent k/lcm(pr,pc) != the local K extent), no longer a ValueError
for pr, pc in ((1, 2), (2, 1)):
    mesh = make_mesh((pr, pc), ("data", "model"))
    grid = GridSpec("data", "model")
    sh = NamedSharding(mesh, P("data", "model"))
    Ad, Bd = jax.device_put(A, sh), jax.device_put(B, sh)
    for bcast in ("psum", "gather"):
        Cs = distributed_matmul(Ad, Bd, mesh=mesh, grid=grid,
                                algorithm="summa", densify=False,
                                block_m=8, block_k=8, block_n=8,
                                local_kernel="ref", bcast=bcast)
        out[f"summa_{bcast}_blocked_{pr}x{pc}"] = float(
            np.max(np.abs(np.asarray(Cs) - ref)))
print("JSON" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def geometry_results():
    stdout = run_subprocess_devices(GEOMETRY_BATTERY, n_devices=4,
                                    timeout=600)
    line = [l for l in stdout.splitlines() if l.startswith("JSON")][-1]
    return json.loads(line[4:])


@pytest.mark.parametrize("key", [
    "blocked_vs_dense_1x1", "blocked_vs_densified_1x1",
    "blocked_vs_dense_2x2", "blocked_vs_densified_2x2",
    "summa_psum_blocked_1x1", "summa_gather_blocked_1x1",
    "summa_psum_blocked_2x2", "summa_gather_blocked_2x2",
    "summa_psum_blocked_1x2", "summa_gather_blocked_1x2",
    "summa_psum_blocked_2x1", "summa_gather_blocked_2x1",
])
def test_blocked_local_geometry(geometry_results, key):
    assert geometry_results[key] < 2e-4, (key, geometry_results[key])


def test_executor_rejects_mismatched_operands(rng):
    """Shapes that divide into the blocks but disagree with the plan's
    geometry must fail loudly, not execute with clamped block indices."""
    f = blocked_local_matmul(32, 32, 32, block_m=8, block_k=8, block_n=8,
                             kernel="ref")
    with pytest.raises(ValueError, match="stack executor built for"):
        f(jnp.zeros((16, 64), jnp.float32), jnp.zeros((64, 32), jnp.float32))
