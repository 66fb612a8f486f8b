"""Schedule-engine acceptance battery (ISSUE 4).

Three layers:

1. **Oracle battery** (multi-device subprocess): the unified pipelined
   driver must be *bit-identical* at ``pipeline_depth=1`` to the
   pre-refactor per-algorithm step loops — which are preserved verbatim
   inside the subprocess as the oracle — for every algorithm x fill
   {dense, 50%, 5%} x mesh {1x1, 2x2, 4x1}, on both local paths
   (densified and blocked/stepwise).  ``pipeline_depth=2`` must agree
   numerically (allclose) with depth 1.
2. **Mask-slice property tests** (host-side): the per-step union mask
   slices of each schedule's step map (``union_view`` of
   ``cannon_step_map`` / ``summa_step_map`` / ``ts_step_map``) must
   match a brute-force enumeration of every rank's present triples at
   every step, and Cannon's map must name the K chunk its skew and
   shifts deliver to each rank.
3. **Ragged executor bins** (host-side): the size-binned stack executor
   must be bit-identical to the legacy looped dispatch, collapse to a
   single legacy-layout bin for uniform (dense) plans, and report the
   padding-FLOP savings.
"""
import json

import numpy as np
import pytest

from conftest import run_subprocess_devices

from repro.core.steps import union_view

# ---------------------------------------------------------------------------
# 1. oracle battery: schedule engine vs the pre-refactor loops
# ---------------------------------------------------------------------------

# The subprocess embeds the PRE-REFACTOR step loops verbatim (from
# core/cannon.py, core/summa.py, core/tall_skinny.py before the schedule
# engine landed) as the bitwise oracle.  ``lm`` objects are shared
# between oracle and engine so both paths dispatch the identical local
# multiplies.
BATTERY = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import make_mesh
from repro.core.blocking import GridSpec
from repro.core.cannon import (_default_local_matmul, _shift_perm,
                               _skew_perm, cannon_matmul, cannon_step_map)
from repro.core.cannon25d import _skew25d_perm, cannon25d_matmul
from repro.core.summa import summa_matmul, summa_n_panels, summa_step_map
from repro.core.tall_skinny import tall_skinny_matmul
from repro.core.multiply import _blocked_steps_lm, distributed_matmul
from repro.core.stacks import normalize_block_masks
from repro.core.steps import union_view


# ---- pre-refactor loops, preserved verbatim as the oracle -------------

def legacy_cannon_local_steps(a_blk, b_blk, *, pg, row_axis, col_axis,
                              local_matmul, out_dtype, skew=True,
                              double_buffer=True, steps=None, step_offset=0):
    if skew:
        a_blk = jax.lax.ppermute(a_blk, (row_axis, col_axis), _skew_perm(pg, "a"))
        b_blk = jax.lax.ppermute(b_blk, (row_axis, col_axis), _skew_perm(pg, "b"))
    if step_offset:
        shift_a = [(j, (j - step_offset) % pg) for j in range(pg)]
        shift_b = [(i, (i - step_offset) % pg) for i in range(pg)]
        a_blk = jax.lax.ppermute(a_blk, col_axis, shift_a)
        b_blk = jax.lax.ppermute(b_blk, row_axis, shift_b)
    n_steps = pg if steps is None else steps
    c_blk = jnp.zeros((a_blk.shape[0], b_blk.shape[1]), dtype=out_dtype)
    shift_a = _shift_perm(pg)
    shift_b = _shift_perm(pg)
    stepwise = bool(getattr(local_matmul, "stepwise", False))
    if double_buffer or stepwise:
        for t in range(n_steps):
            if t < n_steps - 1:
                a_nxt = jax.lax.ppermute(a_blk, col_axis, shift_a)
                b_nxt = jax.lax.ppermute(b_blk, row_axis, shift_b)
            part = (local_matmul(a_blk, b_blk, step=t) if stepwise
                    else local_matmul(a_blk, b_blk))
            if part is not None:
                c_blk = c_blk + part.astype(out_dtype)
            if t < n_steps - 1:
                a_blk, b_blk = a_nxt, b_nxt
    else:
        def body(_, carry):
            a_c, b_c, c_c = carry
            c_c = c_c + local_matmul(a_c, b_c).astype(out_dtype)
            a_c = jax.lax.ppermute(a_c, col_axis, shift_a)
            b_c = jax.lax.ppermute(b_c, row_axis, shift_b)
            return a_c, b_c, c_c
        _, _, c_blk = jax.lax.fori_loop(0, n_steps, body, (a_blk, b_blk, c_blk))
    return c_blk


def legacy_cannon(a, b, *, mesh, grid, local_matmul, out_dtype=None,
                  double_buffer=True):
    pg = grid.validate_square(mesh)
    if out_dtype is None:
        out_dtype = jnp.promote_types(a.dtype, b.dtype)
    def body(a_blk, b_blk):
        c = legacy_cannon_local_steps(
            a_blk, b_blk, pg=pg, row_axis=grid.row_axis,
            col_axis=grid.col_axis, local_matmul=local_matmul,
            out_dtype=jnp.float32, double_buffer=double_buffer)
        return c.astype(out_dtype)
    spec = P(grid.row_axis, grid.col_axis)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                         out_specs=spec, check_vma=False)(a, b)


def legacy_cannon25d(a, b, *, mesh, grid, local_matmul, out_dtype=None):
    pg = grid.validate_square(mesh)
    c_repl = grid.stack_size(mesh)
    spr = pg // c_repl
    if out_dtype is None:
        out_dtype = jnp.promote_types(a.dtype, b.dtype)
    axes3 = (grid.stack_axis, grid.row_axis, grid.col_axis)
    def body(a_blk, b_blk):
        a_blk = jax.lax.ppermute(a_blk, axes3, _skew25d_perm(pg, c_repl, spr, "a"))
        b_blk = jax.lax.ppermute(b_blk, axes3, _skew25d_perm(pg, c_repl, spr, "b"))
        c_partial = legacy_cannon_local_steps(
            a_blk, b_blk, pg=pg, row_axis=grid.row_axis,
            col_axis=grid.col_axis, local_matmul=local_matmul,
            out_dtype=jnp.float32, skew=False, steps=spr)
        return jax.lax.psum(c_partial, grid.stack_axis).astype(out_dtype)
    spec2d = P(grid.row_axis, grid.col_axis)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec2d, spec2d),
                         out_specs=spec2d, check_vma=False)(a, b)


def legacy_summa(a, b, *, mesh, grid, local_matmul, out_dtype=None):
    pr, pc = grid.grid_shape(mesh)
    if out_dtype is None:
        out_dtype = jnp.promote_types(a.dtype, b.dtype)
    row_ax, col_ax = grid.row_axis, grid.col_axis
    n_panels = summa_n_panels(pr, pc)
    stepwise = bool(getattr(local_matmul, "stepwise", False))
    empty_steps = getattr(local_matmul, "empty_steps", frozenset())
    def body(a_blk, b_blk):
        my_col = jax.lax.axis_index(col_ax)
        my_row = jax.lax.axis_index(row_ax)
        kl_a = a_blk.shape[1] * pc // n_panels
        kl_b = b_blk.shape[0] * pr // n_panels
        c = jnp.zeros((a_blk.shape[0], b_blk.shape[1]), jnp.float32)
        for p in range(n_panels):
            if p in empty_steps:
                continue
            col_owner = p * pc // n_panels
            row_owner = p * pr // n_panels
            a_off = (p % (n_panels // pc)) * kl_a if n_panels != pc else 0
            b_off = (p % (n_panels // pr)) * kl_b if n_panels != pr else 0
            a_panel = jax.lax.dynamic_slice_in_dim(a_blk, a_off, kl_a, axis=1)
            b_panel = jax.lax.dynamic_slice_in_dim(b_blk, b_off, kl_b, axis=0)
            a_panel = jnp.where(my_col == col_owner, a_panel, 0)
            a_panel = jax.lax.psum(a_panel, col_ax)
            b_panel = jnp.where(my_row == row_owner, b_panel, 0)
            b_panel = jax.lax.psum(b_panel, row_ax)
            part = (local_matmul(a_panel, b_panel, step=p) if stepwise
                    else local_matmul(a_panel, b_panel))
            if part is not None:
                c = c + part.astype(jnp.float32)
        return c.astype(out_dtype)
    spec = P(row_ax, col_ax)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                         out_specs=spec, check_vma=False)(a, b)


def legacy_ts_k(a, b, *, mesh, grid, local_matmul, out_dtype=None,
                reduce="all_reduce"):
    axes = (grid.row_axis, grid.col_axis)
    if out_dtype is None:
        out_dtype = jnp.promote_types(a.dtype, b.dtype)
    def body_k(a_blk, b_blk):
        partial = local_matmul(a_blk, b_blk).astype(jnp.float32)
        if reduce == "all_reduce":
            c = jax.lax.psum(partial, axes)
        else:
            c = jax.lax.psum_scatter(partial, axes, scatter_dimension=0,
                                     tiled=True)
        return c.astype(out_dtype)
    out_spec = P(None, None) if reduce == "all_reduce" else P(axes, None)
    return jax.shard_map(body_k, mesh=mesh,
                         in_specs=(P(None, axes), P(axes, None)),
                         out_specs=out_spec, check_vma=False)(a, b)


# ---- battery ----------------------------------------------------------

BLOCK = 8
out = {}
rng = np.random.RandomState(0)


def masked_operands(m, k, n, fill):
    A = rng.randn(m, k).astype(np.float32)
    B = rng.randn(k, n).astype(np.float32)
    if fill >= 1.0:
        return A, B, None, None
    am = rng.rand(m // BLOCK, k // BLOCK) < fill
    bm = rng.rand(k // BLOCK, n // BLOCK) < fill
    am[0, 0] = bm[0, 0] = True
    A = A * np.repeat(np.repeat(am, BLOCK, 0), BLOCK, 1)
    B = B * np.repeat(np.repeat(bm, BLOCK, 0), BLOCK, 1)
    return A, B, am, bm


def blocked_lm_for(algo, mesh, grid, m, k, n, am, bm):
    # the stepwise/blocked local multiply the dispatcher would build,
    # shared verbatim between oracle and engine
    pr, pc = grid.grid_shape(mesh)
    amn, bmn = normalize_block_masks(m // BLOCK, k // BLOCK, n // BLOCK,
                                     am, bm)
    kw = dict(block_m=BLOCK, block_k=BLOCK, block_n=BLOCK, kernel="ref")
    if algo in ("cannon", "cannon25d"):
        pg = pr
        c_repl = grid.stack_size(mesh) if algo == "cannon25d" else 1
        smap = cannon_step_map(*amn.shape, bmn.shape[1], pg, c_repl)
        steps = [[u] for u in union_view(smap, amn, bmn)]
        return _blocked_steps_lm(m // pg, k // pg, n // pg, steps, **kw)
    assert algo == "summa"
    n_panels = summa_n_panels(pr, pc)
    smap = summa_step_map(*amn.shape, bmn.shape[1], pr, pc, n_panels)
    steps = [[u] for u in union_view(smap, amn, bmn)]
    return _blocked_steps_lm(m // pr, k // n_panels, n // pc, steps, **kw)


def run_case(tag, legacy_fn, engine_fn, depth2_fn, ref):
    c_legacy = np.asarray(legacy_fn())
    c_d1 = np.asarray(engine_fn())
    c_d2 = np.asarray(depth2_fn())
    out[tag + "/bitwise_d1"] = bool(np.array_equal(c_legacy, c_d1))
    out[tag + "/allclose_d2"] = bool(np.allclose(c_d1, c_d2, atol=1e-4))
    out[tag + "/err"] = float(np.max(np.abs(c_d1 - ref)))


MESHES = {
    "1x1": ((1, 1), ("data", "model")),
    "2x2": ((2, 2), ("data", "model")),
    "4x1": ((4, 1), ("data", "model")),
}

for mesh_name, (shape, axes) in MESHES.items():
    mesh = make_mesh(shape, axes)
    grid = GridSpec("data", "model")
    pr, pc = shape
    sh = NamedSharding(mesh, P("data", "model"))
    m = k = n = 64
    for fill in (1.0, 0.5, 0.05):
        A, B, am, bm = masked_operands(m, k, n, fill)
        Ad, Bd = jax.device_put(A, sh), jax.device_put(B, sh)
        ref = A @ B
        algos = ["summa"] + (["cannon"] if pr == pc else [])
        for algo in algos:
            legacy = legacy_cannon if algo == "cannon" else legacy_summa
            engine = cannon_matmul if algo == "cannon" else summa_matmul
            # densified path
            lm = _default_local_matmul(jax.lax.Precision.DEFAULT)
            run_case(
                f"{mesh_name}/{algo}/dens/{fill:g}",
                lambda: legacy(Ad, Bd, mesh=mesh, grid=grid, local_matmul=lm),
                lambda: engine(Ad, Bd, mesh=mesh, grid=grid, local_matmul=lm,
                               pipeline_depth=1),
                lambda: engine(Ad, Bd, mesh=mesh, grid=grid, local_matmul=lm,
                               pipeline_depth=2),
                ref)
            # blocked (stepwise when masked) path
            if am is None:
                blm = blocked_lm_for(algo, mesh, grid, m, k, n, None, None)
            else:
                blm = blocked_lm_for(algo, mesh, grid, m, k, n, am, bm)
            run_case(
                f"{mesh_name}/{algo}/blk/{fill:g}",
                lambda: legacy(Ad, Bd, mesh=mesh, grid=grid, local_matmul=blm),
                lambda: engine(Ad, Bd, mesh=mesh, grid=grid, local_matmul=blm,
                               pipeline_depth=1),
                lambda: engine(Ad, Bd, mesh=mesh, grid=grid, local_matmul=blm,
                               pipeline_depth=2),
                ref)

        # tall-skinny: K sharded over every device
        p_all = pr * pc
        Kbig = 64 * p_all
        A2, B2, _, _ = masked_operands(16, Kbig, 16, 1.0)
        A2d = jax.device_put(A2, NamedSharding(mesh, P(None, ("data", "model"))))
        B2d = jax.device_put(B2, NamedSharding(mesh, P(("data", "model"), None)))
        lm = _default_local_matmul(jax.lax.Precision.DEFAULT)
        ref2 = A2 @ B2
        run_case(
            f"{mesh_name}/ts_k/dens/{fill:g}",
            lambda: legacy_ts_k(A2d, B2d, mesh=mesh, grid=grid, local_matmul=lm),
            lambda: tall_skinny_matmul(A2d, B2d, mesh=mesh, grid=grid,
                                       mode="ts_k", reduce="all_reduce",
                                       local_matmul=lm, pipeline_depth=1),
            lambda: tall_skinny_matmul(A2d, B2d, mesh=mesh, grid=grid,
                                       mode="ts_k", reduce="all_reduce",
                                       local_matmul=lm, pipeline_depth=2),
            ref2)

# 2.5D on a (2, 2, 2) pod mesh
mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
grid3 = GridSpec("data", "model", stack_axis="pod")
sh3 = NamedSharding(mesh3, P("data", "model"))
m = k = n = 64
for fill in (1.0, 0.5, 0.05):
    A, B, am, bm = masked_operands(m, k, n, fill)
    Ad, Bd = jax.device_put(A, sh3), jax.device_put(B, sh3)
    ref = A @ B
    lm = _default_local_matmul(jax.lax.Precision.DEFAULT)
    run_case(
        f"2x2x2/cannon25d/dens/{fill:g}",
        lambda: legacy_cannon25d(Ad, Bd, mesh=mesh3, grid=grid3, local_matmul=lm),
        lambda: cannon25d_matmul(Ad, Bd, mesh=mesh3, grid=grid3,
                                 local_matmul=lm, pipeline_depth=1),
        lambda: cannon25d_matmul(Ad, Bd, mesh=mesh3, grid=grid3,
                                 local_matmul=lm, pipeline_depth=2),
        ref)
    blm = blocked_lm_for("cannon25d", mesh3, grid3, m, k, n, am, bm)
    run_case(
        f"2x2x2/cannon25d/blk/{fill:g}",
        lambda: legacy_cannon25d(Ad, Bd, mesh=mesh3, grid=grid3, local_matmul=blm),
        lambda: cannon25d_matmul(Ad, Bd, mesh=mesh3, grid=grid3,
                                 local_matmul=blm, pipeline_depth=1),
        lambda: cannon25d_matmul(Ad, Bd, mesh=mesh3, grid=grid3,
                                 local_matmul=blm, pipeline_depth=2),
        ref)

# rolled ablation (depth 0) must match the legacy double_buffer=False loop
mesh = make_mesh((2, 2), ("data", "model"))
grid = GridSpec("data", "model")
sh = NamedSharding(mesh, P("data", "model"))
A, B, _, _ = masked_operands(64, 64, 64, 1.0)
Ad, Bd = jax.device_put(A, sh), jax.device_put(B, sh)
lm = _default_local_matmul(jax.lax.Precision.DEFAULT)
c_legacy = np.asarray(legacy_cannon(Ad, Bd, mesh=mesh, grid=grid,
                                    local_matmul=lm, double_buffer=False))
c_rolled = np.asarray(cannon_matmul(Ad, Bd, mesh=mesh, grid=grid,
                                    local_matmul=lm, pipeline_depth=0))
out["rolled/bitwise"] = bool(np.array_equal(c_legacy, c_rolled))

# auto dispatch carries the plan's depth and schedule stats
C, plan = distributed_matmul(Ad, Bd, mesh=mesh, grid=grid, return_plan=True)
out["plan/depth_valid"] = plan.pipeline_depth in (1, 2)
out["plan/schedule_stats"] = bool(plan.schedule_stats
                                  and plan.schedule_stats["n_steps"] >= 1)

print("JSON" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def battery():
    stdout = run_subprocess_devices(BATTERY, n_devices=8, timeout=900)
    line = [l for l in stdout.splitlines() if l.startswith("JSON")][-1]
    return json.loads(line[4:])


def test_depth1_bit_identical_to_legacy_loops(battery):
    bad = {k: v for k, v in battery.items()
           if k.endswith("/bitwise_d1") and v is not True}
    assert not bad, f"schedule engine diverged bitwise from legacy: {bad}"


def test_depth2_numerically_equivalent(battery):
    bad = {k: v for k, v in battery.items()
           if k.endswith("/allclose_d2") and v is not True}
    assert not bad, f"pipelined depth-2 diverged from depth-1: {bad}"


def test_engine_correct_vs_numpy(battery):
    bad = {k: v for k, v in battery.items()
           if k.endswith("/err") and v > 2e-4}
    assert not bad, f"schedule engine wrong vs numpy reference: {bad}"


def test_rolled_ablation_bit_identical(battery):
    assert battery["rolled/bitwise"] is True


def test_auto_plan_carries_schedule(battery):
    assert battery["plan/depth_valid"] and battery["plan/schedule_stats"]


# ---------------------------------------------------------------------------
# 2. mask-slice property tests: step maps vs brute-force rank enumeration
# ---------------------------------------------------------------------------


def _random_masks(rng, nbr, nbk, nbc, fill):
    am = rng.rand(nbr, nbk) < fill
    bm = rng.rand(nbk, nbc) < fill
    return am, bm


def _union_masks(smap, am, bm):
    """The union view's masks per step: the pair tensor, or the factored
    (a_mask, b_mask) pair."""
    return [s["pair_mask"] if "pair_mask" in s else (s["a_mask"], s["b_mask"])
            for s in union_view(smap, am, bm)]


@pytest.mark.parametrize("pg,c_repl", [(2, 1), (4, 1), (4, 2), (3, 1)])
@pytest.mark.parametrize("fill", [1.0, 0.4, 0.1])
def test_cannon_union_view_matches_per_rank_enumeration(pg, c_repl, fill):
    from repro.core.cannon import cannon_step_map

    rng = np.random.RandomState(pg * 10 + int(fill * 10))
    lr, lk, lc = 2, 3, 2
    nbr, nbk, nbc = pg * lr, pg * lk, pg * lc
    am, bm = _random_masks(rng, nbr, nbk, nbc, fill)
    got = _union_masks(cannon_step_map(nbr, nbk, nbc, pg, c_repl), am, bm)
    spr = pg // c_repl
    assert len(got) == spr

    want = [np.zeros((lr, lk, lc), dtype=bool) for _ in range(spr)]
    # brute force: every (replica p, rank (i, j), step t) holds A chunk
    # (i, q) and B chunk (q, j) with q = (i + j + p*spr + t) % pg; its
    # present local triples are the chunk-mask product
    for p in range(c_repl):
        for i in range(pg):
            for j in range(pg):
                for t in range(spr):
                    q = (i + j + p * spr + t) % pg
                    ac = am[i * lr:(i + 1) * lr, q * lk:(q + 1) * lk]
                    bc = bm[q * lk:(q + 1) * lk, j * lc:(j + 1) * lc]
                    want[t] |= ac[:, :, None] & bc[None, :, :]
    for t in range(spr):
        np.testing.assert_array_equal(got[t], want[t])


@pytest.mark.parametrize("pg", [2, 4])
@pytest.mark.parametrize("c_repl", [1, 2])
def test_cannon_step_map_matches_skew_and_shifts(pg, c_repl):
    """The host's step map and the device's permutations state one fact:
    at step t, rank r holds the A and B chunks that the skew
    (``_skew_perm``, or ``_skew25d_perm`` for 2.5D) and then t
    applications of ``_shift_perm`` deliver to it."""
    from repro.core.cannon import _shift_perm, _skew_perm, cannon_step_map
    from repro.core.cannon25d import _skew25d_perm

    spr = pg // c_repl
    lr, lk, lc = 2, 3, 1
    smap = cannon_step_map(pg * lr, pg * lk, pg * lc, pg, c_repl)

    def flat(p, i, j):
        return (p * pg + i) * pg + j

    ranks = [(p, i, j) for p in range(c_repl) for i in range(pg)
             for j in range(pg)]
    # every rank enters with its own chunks, replicated over replicas:
    # A chunk (row i, K j) and B chunk (K i, column j)
    a_held = [(i, j) for _, i, j in ranks]
    b_held = [(i, j) for _, i, j in ranks]

    def permute(held, pairs):
        out = list(held)
        for src, dst in pairs:
            out[dst] = held[src]
        return out

    if c_repl == 1:
        a_held = permute(a_held, _skew_perm(pg, "a"))
        b_held = permute(b_held, _skew_perm(pg, "b"))
    else:
        a_held = permute(a_held, _skew25d_perm(pg, c_repl, spr, "a"))
        b_held = permute(b_held, _skew25d_perm(pg, c_repl, spr, "b"))
    # A shifts along the column axis, B along the row axis
    a_shift = [(flat(p, i, s), flat(p, i, d)) for p in range(c_repl)
               for i in range(pg) for s, d in _shift_perm(pg)]
    b_shift = [(flat(p, s, j), flat(p, d, j)) for p in range(c_repl)
               for j in range(pg) for s, d in _shift_perm(pg)]
    assert len(smap.index) == spr
    for t in range(spr):
        for r, ((rows, ks), (ks_b, cols)) in enumerate(smap.index[t]):
            assert ks == ks_b
            assert a_held[r] == (rows.start // lr, ks.start // lk), (t, r)
            assert b_held[r] == (ks.start // lk, cols.start // lc), (t, r)
        a_held = permute(a_held, a_shift)
        b_held = permute(b_held, b_shift)


@pytest.mark.parametrize("pr,pc", [(2, 2), (4, 1), (2, 4), (3, 2)])
@pytest.mark.parametrize("fill", [1.0, 0.4, 0.1])
def test_summa_union_view_matches_per_rank_enumeration(pr, pc, fill):
    from repro.core.summa import summa_n_panels, summa_step_map

    rng = np.random.RandomState(pr * 10 + pc + int(fill * 10))
    n_panels = summa_n_panels(pr, pc)
    lr, lc, lkp = 2, 2, 2
    nbr, nbc, nbk = pr * lr, pc * lc, n_panels * lkp
    am, bm = _random_masks(rng, nbr, nbk, nbc, fill)
    got = _union_masks(summa_step_map(nbr, nbk, nbc, pr, pc, n_panels),
                       am, bm)
    assert len(got) == n_panels
    for p in range(n_panels):
        ksl = slice(p * lkp, (p + 1) * lkp)
        # brute force over every (row rank, col rank) pair: the rank's
        # panel-p triples are its A row chunk x B col chunk product
        want = np.zeros((lr, lkp, lc), dtype=bool)
        for i in range(pr):
            for j in range(pc):
                ac = am[i * lr:(i + 1) * lr, ksl]
                bc = bm[ksl, j * lc:(j + 1) * lc]
                want |= ac[:, :, None] & bc[None, :, :]
        ua, ub = got[p]
        have = ua[:, :, None] & ub[None, :, :]
        # the factored union is SPMD-sound (covers every rank's triples)
        assert (want & ~have).sum() == 0
        # and row/col independence makes it exactly tight
        np.testing.assert_array_equal(have, want)


@pytest.mark.parametrize("mode", ["ts_k", "ts_m", "ts_n"])
@pytest.mark.parametrize("fill", [1.0, 0.3])
def test_ts_union_view_matches_per_rank_enumeration(mode, fill):
    from repro.core.tall_skinny import ts_step_map

    rng = np.random.RandomState(
        {"ts_k": 11, "ts_m": 22, "ts_n": 33}[mode] + int(fill * 10))
    p_all = 4
    nbr, nbk, nbc = 4 * (p_all if mode == "ts_m" else 1), \
        4 * (p_all if mode == "ts_k" else 1), \
        4 * (p_all if mode == "ts_n" else 1)
    am, bm = _random_masks(rng, nbr, nbk, nbc, fill)
    got = union_view(ts_step_map(mode, nbr, nbk, nbc, p_all), am, bm)[0]
    if mode == "ts_k":
        lk = nbk // p_all
        want = np.zeros((nbr, lk, nbc), dtype=bool)
        for d in range(p_all):
            ac = am[:, d * lk:(d + 1) * lk]
            bc = bm[d * lk:(d + 1) * lk, :]
            want |= ac[:, :, None] & bc[None, :, :]
        np.testing.assert_array_equal(got["pair_mask"], want)
    elif mode == "ts_m":
        lr = nbr // p_all
        want = np.zeros((lr, nbk), dtype=bool)
        for d in range(p_all):
            want |= am[d * lr:(d + 1) * lr]
        np.testing.assert_array_equal(got["a_mask"], want)
        np.testing.assert_array_equal(got["b_mask"], bm)
    else:
        lc = nbc // p_all
        want = np.zeros((nbk, lc), dtype=bool)
        for d in range(p_all):
            want |= bm[:, d * lc:(d + 1) * lc]
        np.testing.assert_array_equal(got["a_mask"], am)
        np.testing.assert_array_equal(got["b_mask"], want)


# ---------------------------------------------------------------------------
# 3. ragged-aware (size-binned) stack executor
# ---------------------------------------------------------------------------


def test_dense_plan_single_bin_legacy_layout():
    from repro.core.engine import build_executor_plan

    plan = build_executor_plan(64, 64, 64, 8, 8, 8, 32)
    assert plan.n_bins == 1
    assert plan.n_padding == plan.n_padding_unbinned
    # legacy single-tensor view is the bin itself
    assert plan.triples is plan.bin_triples[0]


def test_ragged_plan_bins_cut_padding():
    import jax.numpy as jnp

    from repro.core.densify import from_blocks, to_blocks
    from repro.core.engine import (build_executor_plan, execute_plan,
                                   execute_plans_looped)

    rng = np.random.RandomState(3)
    block, nb = 8, 16
    dim = block * nb
    # row 0 of A dense (k-runs of nb per C block), the rest one k each:
    # with stack_size 8 the long runs become oversized single-run
    # stacks (size nb) while short runs pack 8 per stack — padding to
    # the longest would waste > 25% of the rows, so binning engages
    am = np.zeros((nb, nb), dtype=bool)
    am[0, :] = True
    am[1:, 0] = True
    bm = np.ones((nb, nb), dtype=bool)
    plan = build_executor_plan(dim, dim, dim, block, block, block, 8,
                               a_mask=am, b_mask=bm)
    assert 2 <= plan.n_bins <= 4
    assert plan.n_padding < plan.n_padding_unbinned
    assert plan.stats()["padding_triples_saved"] > 0
    stats = plan.stats()
    assert stats["padding_triples_saved"] == \
        plan.n_padding_unbinned - plan.n_padding
    assert stats["padding_flops_saved"] == \
        stats["padding_triples_saved"] * 2 * block ** 3

    a = rng.randn(dim, dim).astype(np.float32)
    b = rng.randn(dim, dim).astype(np.float32)
    af = a * np.repeat(np.repeat(am, block, 0), block, 1)
    bf = b * np.repeat(np.repeat(bm, block, 0), block, 1)
    ab = to_blocks(jnp.asarray(af), block, block)
    bb = to_blocks(jnp.asarray(bf), block, block)
    c0 = jnp.zeros((nb * nb, block, block), jnp.float32)
    c_binned = execute_plan(plan, ab, bb, c0, kernel="ref")
    c_looped = execute_plans_looped(list(plan.plans), ab, bb, c0,
                                    kernel="ref")
    # binned execution is bit-identical to the legacy looped dispatch
    assert np.array_equal(np.asarray(c_binned), np.asarray(c_looped))
    got = np.asarray(from_blocks(c_binned, nb, nb))
    np.testing.assert_allclose(got, af @ bf, atol=1e-4)


def test_resolve_pipeline_depth_semantics():
    from repro.core.schedule import resolve_pipeline_depth

    assert resolve_pipeline_depth(None) == 2
    assert resolve_pipeline_depth(None, True) == 2
    assert resolve_pipeline_depth(None, False) == 0
    assert resolve_pipeline_depth(1, False) == 1  # explicit depth wins
    assert resolve_pipeline_depth(0) == 0
    assert resolve_pipeline_depth(7) == 2  # clamped
    with pytest.raises(ValueError):
        resolve_pipeline_depth(-1)
